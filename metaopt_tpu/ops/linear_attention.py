"""The gated delta rule: linear attention whose state forgets and corrects.

For one head, keys ``k_t`` (d_k), values ``v_t`` (d_v), a decay
``alpha_t = exp(g_t)`` in (0, 1] and a step ``beta_t`` in [0, 2]:

    S_0 = 0 (d_k x d_v)
    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

(Gated DeltaNet, arXiv:2412.06464). :func:`gated_delta_rule` computes it in
**chunks** of ``CHUNK`` tokens. Inside a chunk the rank-one corrections
compose (the WY form): with ``gamma`` the chunk's running sum of ``g``,
``D_ij = exp(gamma_i - gamma_j)`` for ``j <= i`` and ``S`` the state that
enters the chunk,

    A  = strictly_lower(beta_i D_ij k_i.k_j)       T = (I + A)^-1
    U  = T (beta V) - T (beta exp(gamma) K) S      the corrected values
    O  = exp(gamma) Q S + lower(D * Q K^T) U
    S' = exp(gamma_C) S + (exp(gamma_C - gamma) K)^T U

which is the recurrence itself, not an approximation of it. The state, the
running log decays, their exponentials and the triangular inverse are
float32; the other products take bfloat16 operands (the operands' own type:
float32 or float64 operands are multiplied as they are, which is what the
tests compare with) and accumulate in float32. The inverse is made by block
elimination, blocks of 1, 2, 4 ... rows (two products a level): exact for
a unit triangular matrix and as stable as forward substitution.

The backward pass walks the chunks in reverse with the state's cotangent
and needs the state that entered each chunk: the forward writes those out
(``d_k d_v`` float32 a chunk and head). The chunk's algebra is written once
(``_local``, ``_carry``, ``_carry_back``, ``_local_back``) and runs on two
routes, which :func:`linear_attention_route` names from what a call can see
(the one rule; no option overrides it):

- ``"pallas"``, on the TPU: two kernels, ``linear_scan_fwd`` and
  ``linear_scan_bwd``, a program a (row, head, chunk), the chunks in order
  with the state in on-chip memory;
- ``"xla"``, elsewhere: the plain chunked twin, the chunks' own parts as
  batched products and the state's walk as a ``lax.scan``.

Both sit under the scope ``linear_attention.core``, forward and backward.
A length that is no multiple of ``CHUNK`` is padded (a padded token has
``g`` 0, ``beta`` 0: it leaves the state alone). The output and the chunks'
entering states carry names (``REMAT_KEEPS``) by which a rematerialised
block keeps them and does not walk the chunks forward again. A second door,
ops/delta_hand_over.gated_delta_rule_heads_first, takes q, k, v heads first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from metaopt_tpu.utils import trace

#: tokens a chunk (the kernels' own affair; PERF.md has what was measured)
CHUNK = 128
#: What a rematerialised block keeps of the scan besides its input (beside
#: ``ops/attention.REMAT_KEEPS``, for a model with linear layers): the
#: output (2 H d_v bytes a token) and the chunks' entering states (4 d_k d_v
#: a chunk and head), which cost the whole forward walk to make again.
REMAT_KEEPS = ("linear_attention.out", "linear_attention.states")
_HI = jax.lax.Precision.HIGHEST
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def linear_attention_route() -> dict:
    """The route a call of :func:`gated_delta_rule` takes, and its chunk:
    the Pallas kernels on the TPU, the plain chunked twin elsewhere. No
    extent decides: a length pads, a width is a whole block."""
    on_tpu = jax.default_backend() == "tpu"
    return {"route": "pallas" if on_tpu else "xla", "chunk": CHUNK}


# ---------------------------------------------------------------------------
# a chunk's algebra, on 2-D arrays: what the kernels run on a block in
# on-chip memory and what the plain twin maps over (row, head, chunk)


def _mm(a, b, dims, mx):
    """a . b contracted over ``dims`` with operands of type ``mx``,
    accumulated in float32 (or wider): bfloat16 operands are one pass of
    the MXU, float32 ones are multiplied in full."""
    return jax.lax.dot_general(
        a.astype(mx), b.astype(mx), (dims, ((), ())),
        precision=None if mx == jnp.bfloat16 else _HI,
        preferred_element_type=jnp.promote_types(mx, jnp.float32))


def _grid(c: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0),
            jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _as_column(x_row, row, col):
    """(1, C) -> (C, 1) without a transpose: the diagonal of the row laid
    over a square."""
    return jnp.sum(jnp.where(row == col, x_row, 0.0), axis=1, keepdims=True)


def _as_row(x_col, row, col):
    return jnp.sum(jnp.where(row == col, x_col, 0.0), axis=0, keepdims=True)


def _unit_lower_inverse(a, row, col):
    """(I + a)^-1 for a strictly lower triangular ``a`` (C, C), C a power
    of two, by block elimination in ``a``'s own float type: with the
    inverse of the diagonal blocks of ``b`` rows ``t``, that of ``2 b`` rows
    is ``t - t a_off t``, ``a_off`` the blocks under the diagonal ones."""
    t = jnp.where(row == col, 1.0, 0.0).astype(a.dtype)
    level = 0
    while (1 << level) < a.shape[0]:
        off = jnp.where(
            (jnp.right_shift(row, level + 1) == jnp.right_shift(col, level + 1))
            & (jnp.right_shift(row, level) != jnp.right_shift(col, level)),
            a, 0.0)
        t = t - _mm(_mm(t, off, _NN, a.dtype), t, _NN, a.dtype)
        level += 1
    return t


def _local(q, k, v, gr, br, mx):
    """What a chunk makes without the state, from q, k (C, d_k), v (C,
    d_v) and the rows (1, C) of its running log decay and of its beta."""
    c = q.shape[0]
    f = gr.dtype
    row, col = _grid(c)
    low = row >= col
    gc, bc = _as_column(gr, row, col), _as_column(br, row, col)
    decay = jnp.where(low, jnp.exp(jnp.where(low, gc - gr, 0.0)), 0.0)
    kk = _mm(k, k, _NT, mx)
    a = jnp.where(row > col, bc * decay * kk, 0.0)
    t = _unit_lower_inverse(a, row, col)
    gam = jnp.exp(gc)
    kf, vf = k.astype(f), v.astype(f)
    kg, vb = (bc * gam) * kf, bc * vf
    last = jnp.sum(jnp.where(col[0:1] == c - 1, gr, 0.0), axis=1,
                   keepdims=True)                       # gamma_C, (1, 1)
    e = jnp.exp(last - gc)
    return {
        "bc": bc, "decay": decay, "kk": kk, "a": a, "t": t, "gam": gam,
        "kf": kf, "vf": vf, "kg": kg, "vb": vb, "w": _mm(t, kg, _NN, mx),
        "ut": _mm(t, vb, _NN, mx), "p": decay * _mm(q, k, _NT, mx),
        "qg": gam * q.astype(f), "e": e, "kd": e * kf,
        "gam_last": jnp.exp(last)}


def _carry(loc, s, mx):
    """(the chunk's output (C, d_v), the state that leaves it, the
    corrected values) from the state ``s`` (d_k, d_v) that enters."""
    u = loc["ut"] - _mm(loc["w"], s, _NN, mx) if "w" in loc else loc["ut"]
    o = _mm(loc["qg"], s, _NN, mx) + _mm(loc["p"], u, _NN, mx)
    return o, loc["gam_last"] * s + _mm(loc["kd"], u, _TN, mx), u


def _carry_back(loc, do, ds_out, mx):
    """(the corrected values' cotangent, the entering state's) from the
    output's and the leaving state's."""
    du = _mm(loc["p"], do, _TN, mx) + _mm(loc["kd"], ds_out, _NN, mx)
    ds = _mm(loc["qg"], do, _TN, mx) + loc["gam_last"] * ds_out
    corrected = _mm(loc["w"], du, _TN, mx) if "w" in loc else 0.0
    return du, ds - corrected


def _local_back(q, k, loc, s, u, do, du, ds_out, mx):
    """(dq, dk, dv, dgamma (1, C), dbeta (1, C)) of a chunk, given what
    :func:`_carry_back` made."""
    bc, gam, kf, t = (loc[n] for n in ("bc", "gam", "kf", "t"))
    f = gam.dtype
    row, col = _grid(q.shape[0])
    rowsum = lambda x: jnp.sum(x, axis=1, keepdims=True)  # noqa: E731
    total = lambda x: jnp.sum(rowsum(x), axis=0, keepdims=True)  # noqa: E731
    dp = jnp.where(row >= col, _mm(do, u, _NT, mx), 0.0)
    dqs = _mm(do, s, _NT, mx)                             # d(exp(gamma) q)
    dkd = _mm(u, ds_out, _NT, mx)
    dw = -_mm(du, s, _NT, mx)
    dvb, dkg = _mm(t, du, _TN, mx), _mm(t, dw, _TN, mx)
    dt = _mm(du, loc["vb"], _NT, mx) + _mm(dw, loc["kg"], _NT, mx)
    da = jnp.where(row > col, -_mm(_mm(t, dt, _TN, f), t, _NT, f), 0.0)
    dpd, dga = dp * loc["decay"], da * bc * loc["decay"]
    kdkg = rowsum(dkg * kf)
    dq = gam * dqs + _mm(dpd, k, _NN, mx)
    dk = _mm(dpd, q, _TN, mx) + _mm(dga, k, _NN, mx) + _mm(dga, k, _TN, mx) \
        + (bc * gam) * dkg + loc["e"] * dkd
    dv = bc * dvb
    # beta reaches a, beta v and beta exp(gamma) k
    dbeta = rowsum(da * loc["decay"] * loc["kk"]) + rowsum(dvb * loc["vf"]) \
        + gam * kdkg
    # gamma reaches the decays of a and p (a row's less a column's),
    # exp(gamma) on q and on beta k, exp(gamma_C - gamma) on k and, at the
    # chunk's last token, exp(gamma_C) on the state and on every k
    ek = loc["e"] * rowsum(dkd * kf)
    through = da * loc["a"] + dp * loc["p"]
    at_last = loc["gam_last"] * total(ds_out * s) + total(ek)
    dgamma = _as_row(rowsum(through) - ek
                     + (rowsum(dqs * q.astype(f)) + bc * kdkg) * gam,
                     row, col) \
        - jnp.sum(through, axis=0, keepdims=True) \
        + jnp.where(col[0:1] == q.shape[0] - 1, at_last, 0.0)
    return dq, dk, dv, dgamma, _as_row(dbeta, row, col)


# ---------------------------------------------------------------------------
# the Pallas route: a program a (row, head, chunk), the chunks in order


def _fwd_kernel(q_ref, k_ref, v_ref, gb_ref, o_ref, states_ref, s_scr):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    s = s_scr[...]
    states_ref[...] = s
    loc = _local(q_ref[...], k_ref[...], v_ref[...], gb_ref[0:1, :],
                 gb_ref[1:2, :], q_ref.dtype)
    o, s_scr[...], _ = _carry(loc, s, q_ref.dtype)
    o_ref[...] = o.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, gb_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dgb_ref, ds_scr):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    mx = q_ref.dtype
    q, k, s, do, ds_out = (q_ref[...], k_ref[...], states_ref[...],
                           do_ref[...], ds_scr[...])
    loc = _local(q, k, v_ref[...], gb_ref[0:1, :], gb_ref[1:2, :], mx)
    u = loc["ut"] - _mm(loc["w"], s, _NN, mx)
    du, ds_scr[...] = _carry_back(loc, do, ds_out, mx)
    dq, dk, dv, dgb_ref[0:1, :], dgb_ref[1:2, :] = _local_back(
        q, k, loc, s, u, do, du, ds_out, mx)
    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _specs(c, dk, dv, n, backward: bool):
    at = (lambda b, h, i: (b, h, n - 1 - i, 0)) if backward \
        else (lambda b, h, i: (b, h, i, 0))
    at5 = lambda b, h, i: at(b, h, i) + (0,)  # noqa: E731
    return {"qk": pl.BlockSpec((None, None, c, dk), at),
            "v": pl.BlockSpec((None, None, c, dv), at),
            "gb": pl.BlockSpec((None, None, None, 2, c), at5),
            "state": pl.BlockSpec((None, None, None, dk, dv), at5)}


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


# jitted, so that the layers of one shape share one trace and one lowering
@functools.partial(jax.jit, static_argnames=("interpret",))
def _fwd_pallas(q, k, v, gb, interpret: bool = False):
    b, h, t, dk = q.shape
    dv, n, c = v.shape[-1], gb.shape[2], gb.shape[4]
    sp = _specs(c, dk, dv, n, False)
    return pl.pallas_call(
        _fwd_kernel, name="linear_scan_fwd", grid=(b, h, n),
        in_specs=[sp["qk"], sp["qk"], sp["v"], sp["gb"]],
        out_specs=[sp["v"], sp["state"]],
        out_shape=[jax.ShapeDtypeStruct((b, h, t, dv), v.dtype),
                   jax.ShapeDtypeStruct((b, h, n, dk, dv), gb.dtype)],
        scratch_shapes=[pltpu.VMEM((dk, dv), gb.dtype)],
        compiler_params=_PARAMS, interpret=interpret)(q, k, v, gb)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bwd_pallas(q, k, v, gb, states, do, interpret: bool = False):
    b, h, t, dk = q.shape
    dv, n, c = v.shape[-1], gb.shape[2], gb.shape[4]
    sp = _specs(c, dk, dv, n, True)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    return pl.pallas_call(
        _bwd_kernel, name="linear_scan_bwd", grid=(b, h, n),
        in_specs=[sp["qk"], sp["qk"], sp["v"], sp["gb"], sp["state"],
                  sp["v"]],
        out_specs=[sp["qk"], sp["qk"], sp["v"], sp["gb"]],
        out_shape=[like(q), like(k), like(v), like(gb)],
        scratch_shapes=[pltpu.VMEM((dk, dv), gb.dtype)],
        compiler_params=_PARAMS, interpret=interpret)(q, k, v, gb, states, do)


# ---------------------------------------------------------------------------
# the plain route: the chunks' own parts batched, the state's walk a scan


def _over_chunks(fn, n_batched: int):
    """``fn`` of 2-D arrays mapped over (row, head, chunk)."""
    for _ in range(n_batched):
        fn = jax.vmap(fn)
    return fn


def _chunked(x, c):
    """(B, H, T, d) -> (B, H, N, C, d)."""
    return x.reshape(*x.shape[:2], x.shape[2] // c, c, x.shape[3])


def _walk(step, init, xs, reverse=False):
    """``lax.scan`` over the chunk axis (2) of every array of ``xs``."""
    xs = jax.tree.map(lambda x: jnp.moveaxis(x, 2, 0), xs)
    last, ys = jax.lax.scan(step, init, xs, reverse=reverse)
    return last, jax.tree.map(lambda y: jnp.moveaxis(y, 0, 2), ys)


_SCANNED = ("ut", "w", "qg", "p", "kd", "gam_last")


def _local_xla(qc, kc, vc, gb, mx):
    return _over_chunks(functools.partial(_local, mx=mx), 3)(
        qc, kc, vc, gb[..., 0:1, :], gb[..., 1:2, :])


def _fwd_xla(q, k, v, gb):
    c, mx = gb.shape[-1], q.dtype
    qc, kc, vc = _chunked(q, c), _chunked(k, c), _chunked(v, c)
    loc = _local_xla(qc, kc, vc, gb, mx)
    carry = _over_chunks(functools.partial(_carry, mx=mx), 2)

    def step(s, part):
        o, s_new, _ = carry(part, s)
        return s_new, (o, s)

    init = jnp.zeros(q.shape[:2] + (q.shape[-1], v.shape[-1]), gb.dtype)
    _, (o, states) = _walk(step, init, {n: loc[n] for n in _SCANNED})
    return o.reshape(v.shape).astype(v.dtype), states


def _bwd_xla(q, k, v, gb, states, do):
    c, mx = gb.shape[-1], q.dtype
    qc, kc, vc, doc = (_chunked(x, c) for x in (q, k, v, do))
    loc = _local_xla(qc, kc, vc, gb, mx)
    back = _over_chunks(functools.partial(_carry_back, mx=mx), 2)

    def step(ds_out, part):
        du, ds = back(part["loc"], part["do"], ds_out)
        return ds, (du, ds_out)

    _, (du, ds_out) = _walk(
        step, jnp.zeros_like(states[:, :, 0]),
        {"loc": {n: loc[n] for n in _SCANNED}, "do": doc}, reverse=True)
    u = loc["ut"] - _over_chunks(
        lambda w, s: _mm(w, s, _NN, mx), 3)(loc["w"], states)
    dq, dk, dv, dgamma, dbeta = _over_chunks(
        functools.partial(_local_back, mx=mx), 3)(
            qc, kc, loc, states, u, doc, du, ds_out)
    return (dq.reshape(q.shape).astype(q.dtype),
            dk.reshape(k.shape).astype(k.dtype),
            dv.reshape(v.shape).astype(v.dtype),
            jnp.concatenate([dgamma, dbeta], axis=3))


# ---------------------------------------------------------------------------
# the door


def _heads_first(x):
    """(B, T, H, ...) -> (B, H, T', ...), T' the next whole chunk."""
    return jnp.pad(jnp.moveaxis(x, 1, 2),
                   ((0, 0), (0, 0), (0, -x.shape[1] % CHUNK))
                   + ((0, 0),) * (x.ndim - 3))


def _tokens_first(x, like):
    """The inverse, for an array like ``like`` (B, T, H, ...)."""
    return jnp.moveaxis(x, 2, 1)[:, :like.shape[1]].astype(like.dtype)


def _operands(q, k, v, g, beta):
    """Heads first, the length padded to whole chunks, and ``gb`` (B, H,
    N, 2, C): a chunk's running log decay and its beta, float32."""
    f = jnp.promote_types(g.dtype, jnp.float32)
    split = lambda x: _heads_first(x).astype(f).reshape(  # noqa: E731
        x.shape[0], x.shape[2], -1, CHUNK)
    gb = jnp.stack([jnp.cumsum(split(g), axis=-1), split(beta)], axis=3)
    return _heads_first(q), _heads_first(k), _heads_first(v), gb


def _by_kernels(interpret) -> bool:
    return interpret is not None \
        or linear_attention_route()["route"] == "pallas"


def _forward(q, k, v, g, beta, interpret=None):
    with trace.scope("linear_attention.core"):
        qh, kh, vh, gb = _operands(q, k, v, g, beta)
        if _by_kernels(interpret):
            o, states = _fwd_pallas(qh, kh, vh, gb, interpret=bool(interpret))
        else:
            o, states = _fwd_xla(qh, kh, vh, gb)
        return _tokens_first(o, v), states


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, interpret):
    return _forward(q, k, v, g, beta, interpret)[0]


def _rule_fwd(q, k, v, g, beta, interpret):
    o, states = _forward(q, k, v, g, beta, interpret)
    # named here, inside the rule: a name on the caller's value would keep
    # the output and still walk the chunks again for the states
    o, states = (checkpoint_name(x, name)
                 for x, name in zip((o, states), REMAT_KEEPS))
    return o, (q, k, v, g, beta, states)


def _rule_bwd(interpret, kept, do):
    q, k, v, g, beta, states = kept
    with trace.scope("linear_attention.core"):
        qh, kh, vh, gb = _operands(q, k, v, g, beta)
        doh = _heads_first(do).astype(v.dtype)
        if _by_kernels(interpret):
            dq, dk, dv, dgb = _bwd_pallas(qh, kh, vh, gb, states, doh,
                                          interpret=bool(interpret))
        else:
            dq, dk, dv, dgb = _bwd_xla(qh, kh, vh, gb, states, doh)
        # gamma is the chunk's running sum of g: g_i collects gamma_i..C
        dg = jnp.flip(jnp.cumsum(jnp.flip(dgb[:, :, :, 0], -1), -1), -1)
        whole = lambda x: x.reshape(*x.shape[:2], -1)  # noqa: E731
        return (_tokens_first(dq, q), _tokens_first(dk, k),
                _tokens_first(dv, v), _tokens_first(whole(dg), g),
                _tokens_first(whole(dgb[:, :, :, 1]), beta))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q, k, v, g, beta, *, interpret=None):
    """``o`` (B, T, H, d_v) of the recurrence in the module's docstring from
    ``q``, ``k`` (B, T, H, d_k), ``v`` (B, T, H, d_v), the log decays ``g``
    <= 0 and the steps ``beta`` (B, T, H), a state a (row, head) that
    starts at zero. ``interpret`` (tests): run the kernels whatever the
    backend, interpreted or not."""
    return _rule(q, k, v, g, beta, interpret)


# ---------------------------------------------------------------------------
# the scalar decay rule: the same walk without corrections, q and k shared
# by a group of heads


#: what a rematerialised block keeps of :func:`scalar_decay_rule`, as
#: ``REMAT_KEEPS`` is of the delta rule: the output and the chunks' entering
#: states (4 d_k d_v bytes a chunk and head)
SCALAR_DECAY_KEEPS = ("ssd.out", "ssd.states")


def _local_decay(q, k, qk, v, gr):
    """:func:`_local` of the rule without corrections (A = 0, T = I, U = V:
    a chunk's parts without ``"w"``), for one head of a group: from the
    group's q, k (C, d_k) and their product ``qk`` (C, C), made once a
    group, the head's v (C, d_v) and the row (1, C) of its running log
    decay."""
    c = q.shape[0]
    f = gr.dtype
    row, col = _grid(c)
    low = row >= col
    gc = _as_column(gr, row, col)
    decay = jnp.where(low, jnp.exp(jnp.where(low, gc - gr, 0.0)), 0.0)
    gam = jnp.exp(gc)
    last = jnp.sum(jnp.where(col[0:1] == c - 1, gr, 0.0), axis=1,
                   keepdims=True)
    e = jnp.exp(last - gc)
    kf = k.astype(f)
    return {"decay": decay, "gam": gam, "kf": kf, "e": e, "ut": v.astype(f),
            "p": decay * qk, "qg": gam * q.astype(f), "kd": e * kf,
            "gam_last": jnp.exp(last)}


def _head_fwd(q, k, qk, v, gr, s, mx):
    """(a head's output (C, d_v), the state that leaves the chunk)."""
    return _carry(_local_decay(q, k, qk, v, gr), s, mx)[:2]


def _head_bwd(q, k, qk, v, gr, s, do, ds_out, mx):
    """A head's part of a chunk's backward pass: (dq's and dk's terms
    through the states (C, d_k), the masked scores' cotangent (C, C), which
    the group sums before it meets k and q, dv, dgamma (1, C), the
    entering state's cotangent)."""
    loc = _local_decay(q, k, qk, v, gr)
    gam, e, f = loc["gam"], loc["e"], loc["gam"].dtype
    row, col = _grid(q.shape[0])
    rowsum = lambda x: jnp.sum(x, axis=1, keepdims=True)  # noqa: E731
    total = lambda x: jnp.sum(rowsum(x), axis=0, keepdims=True)  # noqa: E731
    dv, ds = _carry_back(loc, do, ds_out, mx)
    dp = jnp.where(row >= col, _mm(do, v, _NT, mx), 0.0)
    dqs = _mm(do, s, _NT, mx)
    dkd = _mm(v, ds_out, _NT, mx)
    # gamma reaches the decay of p (a row's less a column's), exp(gamma) on
    # q, exp(gamma_C - gamma) on k and, at the chunk's last token,
    # exp(gamma_C) on the state and on every k
    ek = e * rowsum(dkd * loc["kf"])
    through = dp * loc["p"]
    at_last = loc["gam_last"] * total(ds_out * s) + total(ek)
    dgamma = _as_row(rowsum(through) - ek + rowsum(dqs * q.astype(f)) * gam,
                     row, col) \
        - jnp.sum(through, axis=0, keepdims=True) \
        + jnp.where(col[0:1] == q.shape[0] - 1, at_last, 0.0)
    return gam * dqs, e * dkd, dp * loc["decay"], dv, dgamma, ds


def _group_fwd(q, k, v, g, s, mx):
    """A chunk of a group: q, k (C, d_k), v (Hg, C, d_v), g (Hg, C) the
    heads' running log decays, s (Hg, d_k, d_v) -> (o like v, s')."""
    qk = _mm(q, k, _NT, mx)
    o, s_new = zip(*(_head_fwd(q, k, qk, v[h], g[h:h + 1], s[h], mx)
                     for h in range(v.shape[0])))
    return jnp.stack(o), jnp.stack(s_new)


def _group_bwd(q, k, v, g, s, do, ds_out, mx):
    """(dq, dk (C, d_k), dv like v, dgamma like g, ds like s) of a chunk of
    a group."""
    qk = _mm(q, k, _NT, mx)
    dq, dk, dpd, dv, dg, ds = zip(*(
        _head_bwd(q, k, qk, v[h], g[h:h + 1], s[h], do[h], ds_out[h], mx)
        for h in range(v.shape[0])))
    dpd = sum(dpd)
    return (sum(dq) + _mm(dpd, k, _NN, mx), sum(dk) + _mm(dpd, q, _TN, mx),
            jnp.stack(dv), jnp.concatenate(dg, axis=0), jnp.stack(ds))


def _decay_fwd_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, states_ref, s_scr):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    mx = q_ref.dtype
    q, k = q_ref[...], k_ref[...]
    qk = _mm(q, k, _NT, mx)
    for h in range(v_ref.shape[0]):
        s = s_scr[h]
        states_ref[h] = s
        o, s_scr[h] = _head_fwd(q, k, qk, v_ref[h], g_ref[h:h + 1, :], s, mx)
        o_ref[h] = o.astype(o_ref.dtype)


def _decay_bwd_kernel(q_ref, k_ref, v_ref, g_ref, states_ref, do_ref,
                      dq_ref, dk_ref, dv_ref, dg_ref, ds_scr):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    mx = q_ref.dtype
    q, k = q_ref[...], k_ref[...]
    qk = _mm(q, k, _NT, mx)
    dq = dk = dpd = 0.0
    for h in range(v_ref.shape[0]):
        dq_h, dk_h, dpd_h, dv, dg_ref[h:h + 1, :], ds_scr[h] = _head_bwd(
            q, k, qk, v_ref[h], g_ref[h:h + 1, :], states_ref[h], do_ref[h],
            ds_scr[h], mx)
        dv_ref[h] = dv.astype(dv_ref.dtype)
        dq, dk, dpd = dq + dq_h, dk + dk_h, dpd + dpd_h
    dq_ref[...] = (dq + _mm(dpd, k, _NN, mx)).astype(dq_ref.dtype)
    dk_ref[...] = (dk + _mm(dpd, q, _TN, mx)).astype(dk_ref.dtype)


def _decay_specs(c, dk, dv, hg, n, backward: bool):
    at = (lambda i: n - 1 - i) if backward else (lambda i: i)
    return {"qk": pl.BlockSpec((None, None, c, dk),
                               lambda b, g, i: (b, g, at(i), 0)),
            "v": pl.BlockSpec((None, None, hg, c, dv),
                              lambda b, g, i: (b, g, 0, at(i), 0)),
            "g": pl.BlockSpec((None, None, None, hg, c),
                              lambda b, g, i: (b, g, at(i), 0, 0)),
            "state": pl.BlockSpec((None, None, None, hg, dk, dv),
                                  lambda b, g, i: (b, g, at(i), 0, 0, 0))}


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decay_fwd_pallas(q, k, v, gam, interpret: bool = False):
    b, grp, t, dk = q.shape
    hg, dv, n, c = v.shape[2], v.shape[-1], gam.shape[2], gam.shape[-1]
    sp = _decay_specs(c, dk, dv, hg, n, False)
    return pl.pallas_call(
        _decay_fwd_kernel, name="ssd_scan_fwd", grid=(b, grp, n),
        in_specs=[sp["qk"], sp["qk"], sp["v"], sp["g"]],
        out_specs=[sp["v"], sp["state"]],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, grp, n, hg, dk, dv), gam.dtype)],
        scratch_shapes=[pltpu.VMEM((hg, dk, dv), gam.dtype)],
        compiler_params=_PARAMS, interpret=interpret)(q, k, v, gam)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decay_bwd_pallas(q, k, v, gam, states, do, interpret: bool = False):
    b, grp, t, dk = q.shape
    hg, dv, n, c = v.shape[2], v.shape[-1], gam.shape[2], gam.shape[-1]
    sp = _decay_specs(c, dk, dv, hg, n, True)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    return pl.pallas_call(
        _decay_bwd_kernel, name="ssd_scan_bwd", grid=(b, grp, n),
        in_specs=[sp["qk"], sp["qk"], sp["v"], sp["g"], sp["state"],
                  sp["v"]],
        out_specs=[sp["qk"], sp["qk"], sp["v"], sp["g"]],
        out_shape=[like(q), like(k), like(v), like(gam)],
        scratch_shapes=[pltpu.VMEM((hg, dk, dv), gam.dtype)],
        compiler_params=_PARAMS, interpret=interpret)(
            q, k, v, gam, states, do)


def _group_chunks(q, k, v, n):
    """q, k (B, G, T, d_k) -> (B, G, N, C, d_k); v (B, G, Hg, T, d_v) ->
    (B, G, N, Hg, C, d_v): the chunk axis where :func:`_walk` takes it."""
    cut = lambda x: x.reshape(  # noqa: E731
        *x.shape[:-2], n, x.shape[-2] // n, x.shape[-1])
    return cut(q), cut(k), jnp.moveaxis(cut(v), 3, 2)


def _decay_fwd_xla(q, k, v, gam):
    qc, kc, vc = _group_chunks(q, k, v, gam.shape[2])
    fwd = _over_chunks(functools.partial(_group_fwd, mx=q.dtype), 2)

    def step(s, part):
        o, s_new = fwd(part["q"], part["k"], part["v"], part["g"], s)
        return s_new, (o, s)

    init = jnp.zeros(v.shape[:3] + (q.shape[-1], v.shape[-1]), gam.dtype)
    _, (o, states) = _walk(step, init,
                           {"q": qc, "k": kc, "v": vc, "g": gam})
    return jnp.moveaxis(o, 2, 3).reshape(v.shape).astype(v.dtype), states


def _decay_bwd_xla(q, k, v, gam, states, do):
    qc, kc, vc = _group_chunks(q, k, v, gam.shape[2])
    doc = _group_chunks(q, k, do, gam.shape[2])[2]
    bwd = _over_chunks(functools.partial(_group_bwd, mx=q.dtype), 2)

    def step(ds_out, part):
        dq, dk, dv, dg, ds = bwd(part["q"], part["k"], part["v"], part["g"],
                                 part["s"], part["do"], ds_out)
        return ds, (dq, dk, dv, dg)

    _, (dq, dk, dv, dg) = _walk(
        step, jnp.zeros_like(states[:, :, 0]),
        {"q": qc, "k": kc, "v": vc, "g": gam, "s": states, "do": doc},
        reverse=True)
    return (dq.reshape(q.shape).astype(q.dtype),
            dk.reshape(k.shape).astype(k.dtype),
            jnp.moveaxis(dv, 2, 3).reshape(v.shape).astype(v.dtype), dg)


def _decay_operands(q, k, v, g):
    """Groups first, the length padded to whole chunks: q, k (B, G, T',
    d_k), v (B, G, Hg, T', d_v) and ``gam`` (B, G, N, Hg, C), a chunk's
    running log decay a head, float32."""
    f = jnp.promote_types(g.dtype, jnp.float32)
    b, grp = q.shape[0], q.shape[2]
    vh = _heads_first(v)
    gam = jnp.cumsum(_heads_first(g).astype(f).reshape(
        b, grp, g.shape[2] // grp, -1, CHUNK), axis=-1)
    return (_heads_first(q), _heads_first(k),
            vh.reshape(b, grp, -1, *vh.shape[2:]), jnp.moveaxis(gam, 2, 3))


def _decay_forward(q, k, v, g, interpret=None):
    with trace.scope("ssd.core"):
        qh, kh, vh, gam = _decay_operands(q, k, v, g)
        if _by_kernels(interpret):
            o, states = _decay_fwd_pallas(qh, kh, vh, gam,
                                          interpret=bool(interpret))
        else:
            o, states = _decay_fwd_xla(qh, kh, vh, gam)
        return _tokens_first(o.reshape(o.shape[0], -1, *o.shape[3:]),
                             v), states


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _decay_rule(q, k, v, g, interpret):
    return _decay_forward(q, k, v, g, interpret)[0]


def _decay_rule_fwd(q, k, v, g, interpret):
    o, states = _decay_forward(q, k, v, g, interpret)
    o, states = (checkpoint_name(x, name)
                 for x, name in zip((o, states), SCALAR_DECAY_KEEPS))
    return o, (q, k, v, g, states)


def _decay_rule_bwd(interpret, kept, do):
    q, k, v, g, states = kept
    with trace.scope("ssd.core"):
        qh, kh, vh, gam = _decay_operands(q, k, v, g)
        doh = _heads_first(do).astype(v.dtype).reshape(vh.shape)
        if _by_kernels(interpret):
            dq, dk, dv, dgam = _decay_bwd_pallas(
                qh, kh, vh, gam, states, doh, interpret=bool(interpret))
        else:
            dq, dk, dv, dgam = _decay_bwd_xla(qh, kh, vh, gam, states, doh)
        # gamma is the chunk's running sum of g: g_i collects gamma_i..C
        dg = jnp.flip(jnp.cumsum(jnp.flip(dgam, -1), -1), -1)
        heads = lambda x: x.reshape(  # noqa: E731  (B, G, Hg, ...) -> (B, H, ...)
            x.shape[0], -1, *x.shape[3:])
        dg = jnp.moveaxis(dg, 2, 3)                       # (B, G, Hg, N, C)
        return (_tokens_first(dq, q), _tokens_first(dk, k),
                _tokens_first(heads(dv), v),
                _tokens_first(heads(dg.reshape(*dg.shape[:3], -1)), g))


_decay_rule.defvjp(_decay_rule_fwd, _decay_rule_bwd)


def scalar_decay_rule(q, k, v, g, *, interpret=None):
    """``o`` (B, T, H, d_v) of S_t = exp(g_t) S_{t-1} + k_t v_t^T, o_t =
    S_t^T q_t, a state (d_k, d_v) a (row, head) that starts at zero
    (Mamba-2's recurrence in its matmul form, arXiv:2405.21060: q = C, k =
    B, v = dt x, g = dt A): the chunks' walk of :func:`gated_delta_rule`
    without corrections. ``q``, ``k`` (B, T, G, d_k) are shared by groups
    of H / G heads (head h reads group h // (H / G)); ``v`` (B, T, H,
    d_v); the log decays ``g`` <= 0 (B, T, H), a scalar a head and token.
    On the Pallas route a program is a (row, GROUP, chunk): it makes q k^T
    once and walks the group's heads under a mask each. The route is
    :func:`linear_attention_route`'s; ``interpret`` as there."""
    return _decay_rule(q, k, v, g, interpret)
