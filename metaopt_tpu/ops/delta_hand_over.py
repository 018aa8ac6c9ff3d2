"""A gated-delta mixer's hand-over to the scan and back.

models/lm_layers.LinearAttention makes its q, k, v and g products as four
projections leave them, bfloat16 and tokens first, and the scan of
ops/linear_attention.py reads bfloat16 operands HEADS first, padded to whole
chunks, and writes its output so. In front of the scan lie three causal
convolutions, three SiLUs, two L2 norms over a head, q's scaling and one
rounding each; behind it an RMS norm over a head's values, its scale, the
gate ``silu(g)`` and a rounding: float32 arithmetic that XLA runs as passes
of their own through HBM at four bytes a number, forward, again under remat
and, transposed, backward, with the turns to heads first and back as copies
beside them. Here they are ONE Pallas call a side and direction, after the
pattern of ops/ssd_hand_over.py, whose helpers (the halo, the window, the
convolution in ``short_conv``'s order, the call) these kernels share:

- :func:`delta_operands` (a ``jax.custom_vjp``), in front: forward the call
  ``delta_operands``, a program a (row and head, tile of the sequence): the
  head's ``(tile, width)`` of each product and the ``conv - 1`` rows before
  the tile from a second block over the same array (the halo; zero at the
  row's start), float32 in registers only, q, k and v out as
  ``linear_scan_fwd`` reads them. Backward the call ``delta_operands_bwd``:
  the scan's three cotangents in, heads first as ``linear_scan_bwd`` writes
  them, the products' cotangents out, bfloat16, each number rounded once
  where it enters the projection's backward matmul; the convolution's
  transpose reads the rows AFTER a tile; the taps' gradients are a tile's
  sums, added up outside.
- :func:`delta_gated_norm` (a ``jax.custom_vjp``), behind: forward the call
  ``delta_gated_norm``, a program likewise: the scan's output heads first
  and the g product's head in, ``rmsnorm(o) scale silu(g)`` out, bfloat16.
  Backward the call ``delta_gated_norm_bwd``: o's cotangent (heads first,
  to the scan) and g's out, the scale's gradient a tile's sums.
- :func:`gated_delta_rule_heads_first`, the scan's second door, between
  them: the kernels, residual names and ``gb`` of
  ``ops/linear_attention.gated_delta_rule`` on operands that are heads first
  already, the output left so.

A head's 96 or 192 columns are no whole lanes, so a program's block is a
head of a product turned heads first, ``(tile, width)`` with the width the
array's own; the turn is a ``jnp.moveaxis`` on the projection's output (and
on the cotangent into its backward) that XLA is free to make the matmul's
own layout. The residuals are the products, which is what a rematerialised
block keeps (``LinearSpec.KEPT``), and the scan's output and states
(``REMAT_KEEPS``), so under remat the two forward calls are what is made
again. :func:`hand_over` is the one rule for whether a mixer takes this
form.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from metaopt_tpu.ops import linear_attention as la
from metaopt_tpu.ops.ssd_hand_over import (_HALO, _conv, _fill, _plan,
                                           _silu_slope, _valid)
from metaopt_tpu.utils import trace

#: a program's tile of the sequence where the length is longer
_TILE = 1024
#: rows of a tile the arithmetic covers at a time: a head's (128, 96) or
#: (128, 192) float32 (64, as ops/ssd_hand_over.py walks its 512 columns,
#: takes 8-15 % longer a call here and 32 a fifth more: PERF.md section 6)
_ROWS = 128
#: the VMEM a kernel may take without asking (Mosaic's scoped default on a
#: v5e)
_ALLOWED = 16 << 20
#: the L2 norms' epsilon (Gated DeltaNet's published code: x / sqrt(|x|^2 +
#: 1e-6)); the gated norm's is the model's
_L2_EPS = 1e-6


class Sizes(NamedTuple):
    """A mixer's sizes as the rule needs them (``LinearSpec``'s)."""

    heads: int
    key_dim: int
    value_dim: int
    conv: int


def hand_over(route: str, mesh, sz: Sizes) -> str:
    """How a gated-delta mixer's operands reach the scan and its output the
    output projection on ``route`` (``linear_attention_route``'s): ``"one
    pass"`` (this module) on the Pallas route of one device where a head's
    widths are whole sublane tiles of bfloat16 (multiples of 16) and the
    taps reach no further back than a halo's aligned rows (8), else
    ``"passes"``, XLA's: the routes that do not run the kernels, a mesh of
    several devices, whose shards these calls do not know, and a
    rehearsal's widths (a key width of 8: Mosaic lowers such a block, but
    fifteen lanes of sixteen in every register and every block of it are
    padding, where XLA's passes see all the heads' columns side by side;
    no cell has such heads and neither form was timed there)."""
    one_device = mesh is None or mesh.size == 1
    blocks = sz.key_dim % 16 == 0 and sz.value_dim % 16 == 0 \
        and 1 <= sz.conv <= 9
    return "one pass" if route == "pallas" and one_device and blocks \
        else "passes"


# ---------------------------------------------------------------------------
# what the calls share


def _rows_first(x, length: int):
    """A tokens-first (B, T, H, d) as the calls cut it: (B H, T', d), heads
    first, T' = ``length`` (the rows past T zero)."""
    b, t, h, d = x.shape
    x = jnp.moveaxis(x, 1, 2)
    if length != t:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, length - t), (0, 0)))
    return x.reshape(b * h, length, d)


def _tokens_first(x, like):
    """The inverse, for an array like ``like`` (B, T, H, d)."""
    b, t, h, d = like.shape
    return jnp.moveaxis(x.reshape(b, h, -1, d)[:, :, :t], 2, 1)


def _whole_chunks(t: int) -> int:
    return t + -t % la.CHUNK


def _taps_a_head(taps):
    """(conv, H, d) -> (H, conv + 1, d) float32: a head's taps, then the
    bias ``_conv`` adds, which is zero here."""
    taps = jnp.moveaxis(taps.astype(jnp.float32), 0, 1)
    return jnp.pad(taps, ((0, 0), (0, 1), (0, 0)))


def _specs(plan, heads: int, taps: int, width: int):
    """(a tile's block of a (B H, T', width) array, the halo's before it,
    the halo's after it, a head's taps', a tile's sums')."""
    own = lambda j: 0  # noqa: E731
    return (plan.rows(width, own), plan.before(width, own),
            plan.after(width, own),
            pl.BlockSpec((None, taps + 1, width),
                         lambda b, i, j: (b % heads, 0, 0)),
            plan.sums(taps + 1, width, own))


def _over_rows(block: int, body, init=None):
    """``body(at, rows, carry)`` over a tile ``block`` rows long, ``_ROWS``
    at a time where they divide it."""
    if block % _ROWS or block == _ROWS:
        return body(0, block, init)
    return jax.lax.fori_loop(
        0, block // _ROWS,
        lambda i, c: body(pl.multiple_of(i * _ROWS, _ROWS), _ROWS, c), init)


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
          operands, interpret, **static):
    """``pallas_call`` over (row and head, tile, 1), all parallel, the
    scratch float32. No VMEM is asked for beyond the compiler's own
    allowance while the double-buffered blocks and the scratch fit it
    twice over (the cell's largest, ``delta_operands_bwd``: 14.5 MB of 16);
    ops/ssd_hand_over.py's ``_call`` asks for 32 MB at least, which XLA
    takes from what it may hold on chip around a call: 0.5 ms a step of
    this cell's mixers (PERF.md section 6, PR 52)."""
    size = lambda shape, dtype: jnp.dtype(dtype).itemsize * math.prod(  # noqa: E731
        n or 1 for n in shape)
    need = 2 * sum(size(sp.block_shape, x.dtype) for sp, x in zip(
        [*in_specs, *out_specs], [*operands, *out_shape]))
    need += sum(size(shape, jnp.float32) for shape in scratch)
    limit = {} if 2 * need <= _ALLOWED else {
        "vmem_limit_bytes": min(2 * need, 100 << 20)}
    return pl.pallas_call(
        functools.partial(kernel, **static), out_shape=out_shape, grid=grid,
        in_specs=in_specs, out_specs=out_specs, name=name,
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3, **limit),
        interpret=interpret)(*operands)


def _unit(y):
    """(y over its L2 norm along the lanes, that norm's inverse)."""
    r = jax.lax.rsqrt(jnp.sum(jnp.square(y), axis=-1, keepdims=True)
                      + _L2_EPS)
    return y * r, r


# ---------------------------------------------------------------------------
# in front of the scan


def _operands_kernel(qb_ref, q_ref, kb_ref, k_ref, vb_ref, v_ref, tq_ref,
                     tk_ref, tv_ref, qo_ref, ko_ref, vo_ref, wq, wk, wv, *,
                     length: int, padded: bool, alone: bool, scale: float):
    """One (row and head, tile) program. Shapes in VMEM: a product's tile
    (Bs, d) and the halo before it (halo, d); its taps (taps + 1, d); out q,
    k (Bs, d_k) and v (Bs, d_v)."""
    block = q_ref.shape[0]
    start = pl.program_id(1) * block
    first = alone or pl.program_id(1) == 0
    for before, tile, win in ((qb_ref, q_ref, wq), (kb_ref, k_ref, wk),
                              (vb_ref, v_ref, wv)):
        _fill(win, before, tile, first)

    def body(at, rows, _):
        for win, tb, out, normed, times in (
                (wq, tq_ref, qo_ref, True, scale),
                (wk, tk_ref, ko_ref, True, None),
                (wv, tv_ref, vo_ref, False, None)):
            y = jax.nn.silu(_conv(win, at, rows, tb)[0])
            if normed:
                y = _unit(y)[0]
            if times is not None:
                y = y * times
            if padded:
                y = jnp.where(_valid(start + at, rows, length), y, 0.0)
            out[pl.ds(at, rows), :] = y.astype(out.dtype)

    _over_rows(block, body)


def _operands_bwd_kernel(
        qb_ref, q_ref, qa_ref, kb_ref, k_ref, ka_ref, vb_ref, v_ref, va_ref,
        tq_ref, tk_ref, tv_ref, dq_ref, dqa_ref, dk_ref, dka_ref, dv_ref,
        dva_ref, pq_ref, pk_ref, pv_ref, sq_ref, sk_ref, sv_ref,
        win_k, dy_k, win_v, dy_v, *, length: int, alone: bool, scale: float):
    """The transpose of ``_operands_kernel``, a program likewise.
    ``*a_ref``: the halo rows after the tile, of a product and of the
    scan's cotangent. Out: the products' cotangents (Bs, d) and the taps'
    sums over the tile (taps + 1, d; the last row the bias's, unread)."""
    block = q_ref.shape[0]
    start = pl.program_id(1) * block
    first = alone or pl.program_id(1) == 0
    f32 = jnp.float32

    def transposed(before, tile, after, tb_ref, d_ref, da_ref, dp_ref,
                   sum_ref, win, dy_scr, normed: bool, times):
        taps, width = tb_ref.shape[0] - 1, tile.shape[1]
        _fill(win, before, tile, first, after, start, length)

        def slope(at, rows, later: bool):
            """(the convolution's output's cotangent for ``rows`` rows from
            the tile's row ``at``, or with ``later`` the halo's after it,
            the shifted rows)."""
            u, shifts = _conv(win, at, rows, tb_ref)
            s = jax.nn.sigmoid(u)
            d = (da_ref if later else d_ref)[
                pl.ds(0 if later else at, rows), :].astype(f32)
            if times is not None:
                d = d * times
            if normed:
                # out = y r, r = (sum(y^2) + eps)^-1/2:
                # dy = r (d - out sum(d out))
                unit, r = _unit(u * s)
                d = r * (d - unit * jnp.sum(d * unit, axis=-1, keepdims=True))
            du = jnp.where(_valid(start + at, rows, length),
                           d * _silu_slope(u, s), 0.0)
            return du, shifts

        def down(at, rows, sums):
            du, shifts = slope(at, rows, False)
            dy_scr[pl.ds(at, rows), :] = du
            return sums + jnp.concatenate(
                [jnp.sum(du * x, axis=0, keepdims=True) for x in shifts]
                + [jnp.sum(du, axis=0, keepdims=True)], axis=0)

        sum_ref[...] = _over_rows(
            block, down, jnp.zeros((taps + 1, width), f32))
        # a row of one tile: nothing after it
        dy_scr[block:block + 8] = jnp.zeros((8, width), f32) if alone \
            else slope(block, 8, True)[0]

        def up(at, rows, _):
            # the transpose of the taps' sum: row r collects from the rows
            # r .. r + taps - 1 of the convolution's output
            w = dy_scr[pl.ds(at, rows + 8), :]
            acc = 0.0
            for k in range(taps):
                ahead = taps - 1 - k
                acc = acc + tb_ref[k:k + 1, :] * (
                    pltpu.roll(w, rows + 8 - ahead, 0) if ahead else w)[:rows]
            dp_ref[pl.ds(at, rows), :] = acc.astype(dp_ref.dtype)

        _over_rows(block, up)

    transposed(qb_ref, q_ref, qa_ref, tq_ref, dq_ref, dqa_ref, pq_ref,
               sq_ref, win_k, dy_k, True, scale)
    transposed(kb_ref, k_ref, ka_ref, tk_ref, dk_ref, dka_ref, pk_ref,
               sk_ref, win_k, dy_k, True, None)
    transposed(vb_ref, v_ref, va_ref, tv_ref, dv_ref, dva_ref, pv_ref,
               sv_ref, win_v, dy_v, False, None)


_jit = functools.partial(jax.jit, static_argnames=(
    "heads", "length", "tile", "interpret"))


@_jit
def _operands_forward(pq, pk, pv, tq, tk, tv, heads, length, tile, interpret):
    """q, k, v (B H, T', d) from the products cut likewise."""
    plan = _plan(pq.shape[1], tile or _TILE)
    taps, dk, dv = tq.shape[1] - 1, pq.shape[2], pv.shape[2]
    rk, bk, _, tk_spec, _ = _specs(plan, heads, taps, dk)
    rv, bv, _, tv_spec, _ = _specs(plan, heads, taps, dv)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16)  # noqa: E731
    return _call(
        _operands_kernel, "delta_operands", (pq.shape[0], plan.tiles, 1),
        [bk, rk, bk, rk, bv, rv, tk_spec, tk_spec, tv_spec],
        [rk, rk, rv], [like(pq), like(pk), like(pv)],
        [(_HALO + plan.tile, dk)] * 2 + [(_HALO + plan.tile, dv)],
        [pq, pq, pk, pk, pv, pv, tq, tk, tv], interpret,
        length=length, padded=length != pq.shape[1], alone=plan.alone,
        scale=dk ** -0.5)


@_jit
def _operands_backward(pq, pk, pv, tq, tk, tv, dq, dk, dv, heads, length,
                       tile, interpret):
    """(the three products' cotangents (B H, T', d) bfloat16, the three
    taps' sums (B H, tiles, taps + 1, d))."""
    plan = _plan(pq.shape[1], tile or _TILE)
    taps, wk, wv = tq.shape[1] - 1, pq.shape[2], pv.shape[2]
    rk, bk, ak, tk_spec, sk = _specs(plan, heads, taps, wk)
    rv, bv, av, tv_spec, sv = _specs(plan, heads, taps, wv)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    sums = lambda w: jax.ShapeDtypeStruct(  # noqa: E731
        (pq.shape[0], plan.tiles, taps + 1, w), jnp.float32)
    return _call(
        _operands_bwd_kernel, "delta_operands_bwd",
        (pq.shape[0], plan.tiles, 1),
        [bk, rk, ak, bk, rk, ak, bv, rv, av, tk_spec, tk_spec, tv_spec,
         rk, ak, rk, ak, rv, av],
        [rk, rk, rv, sk, sk, sv],
        [like(pq), like(pk), like(pv), sums(wk), sums(wk), sums(wv)],
        [(2 * _HALO + plan.tile, wk), (plan.tile + _HALO, wk),
         (2 * _HALO + plan.tile, wv), (plan.tile + _HALO, wv)],
        [pq, pq, pq, pk, pk, pk, pv, pv, pv, tq, tk, tv,
         dq, dq, dk, dk, dv, dv], interpret,
        length=length, alone=plan.alone, scale=wk ** -0.5)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def delta_operands(q, k, v, taps_q, taps_k, taps_v, tile=None,
                   interpret: bool = False):
    """The scan's operands from a mixer's products: ``q``, ``k`` (B, T, H,
    d_k) and ``v`` (B, T, H, d_v), the projections' outputs, bfloat16, and
    their convolutions' taps (conv, H, d). Returns q, k (B, H, T', d_k) and
    v (B, H, T', d_v), bfloat16, heads first and T' the next whole chunk
    (the rows past T zero), as :func:`gated_delta_rule_heads_first` takes
    them: the causal convolution, SiLU, for q and k the L2 norm over a
    head, q over sqrt(d_k), one rounding. ``tile`` (tests): the rows a
    program."""
    return _operands_fwd(q, k, v, taps_q, taps_k, taps_v, tile, interpret)[0]


def _operands_fwd(q, k, v, taps_q, taps_k, taps_v, tile, interpret):
    b, t, h, _ = q.shape
    whole = _whole_chunks(t)
    out = _operands_forward(
        *(_rows_first(p, whole) for p in (q, k, v)),
        *(_taps_a_head(w) for w in (taps_q, taps_k, taps_v)),
        h, t, tile, interpret)
    return tuple(o.reshape(b, h, whole, -1) for o in out), (
        q, k, v, taps_q, taps_k, taps_v)


@trace.scope("linear_attention")  # a backward rule has no forward name stack
def _operands_bwd(tile, interpret, residuals, cotangents):
    products, taps = residuals[:3], residuals[3:]
    b, t, h, _ = products[0].shape
    whole = _whole_chunks(t)
    out = _operands_backward(
        *(_rows_first(p, whole) for p in products),
        *(_taps_a_head(w) for w in taps),
        *(d.reshape(b * h, whole, -1) for d in cotangents),
        h, t, tile, interpret)
    # (B H, tiles, taps + 1, d) -> (taps, H, d)
    summed = lambda s, w: jnp.moveaxis(  # noqa: E731
        s.reshape(b, h, *s.shape[1:]).sum(axis=(0, 2))[:, :-1], 0, 1
    ).astype(w.dtype)
    return (*(_tokens_first(d, p) for d, p in zip(out[:3], products)),
            *(summed(s, w) for s, w in zip(out[3:], taps)))


delta_operands.defvjp(_operands_fwd, _operands_bwd)


# ---------------------------------------------------------------------------
# behind the scan


def _normed(o_ref, g_ref, w_ref, at, rows, eps):
    """A head's rows from ``at``: (o over its root mean square, that
    root's inverse, the same times the scale, g, sigmoid(g)), float32, in
    the mixer's order."""
    o = o_ref[pl.ds(at, rows), :].astype(jnp.float32)
    g = g_ref[pl.ds(at, rows), :].astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + eps)
    unit = o * r
    return unit, r, unit * w_ref[...], g, jax.nn.sigmoid(g)


def _gated_norm_kernel(o_ref, g_ref, w_ref, y_ref, *, eps: float):
    """One (row and head, tile) program. Shapes in VMEM: the scan's
    output, the g product's head and out (Bs, d_v); the scale (1, d_v)."""
    def body(at, rows, _):
        _, _, scaled, g, sg = _normed(o_ref, g_ref, w_ref, at, rows, eps)
        y_ref[pl.ds(at, rows), :] = (scaled * (g * sg)).astype(y_ref.dtype)

    _over_rows(o_ref.shape[0], body)


def _gated_norm_bwd_kernel(dn_ref, o_ref, g_ref, w_ref, do_ref, dg_ref,
                           sum_ref, *, eps: float):
    """The transpose of ``_gated_norm_kernel``, a program likewise. Out:
    o's and g's cotangents (Bs, d_v) and the tile's sum (1, d_v): the
    scale's gradient."""
    def body(at, rows, total):
        unit, r, scaled, g, sg = _normed(o_ref, g_ref, w_ref, at, rows, eps)
        dn = dn_ref[pl.ds(at, rows), :].astype(jnp.float32)
        gate = g * sg
        dg_ref[pl.ds(at, rows), :] = (
            dn * scaled * _silu_slope(g, sg)).astype(dg_ref.dtype)
        # unit = o r, r = (mean(o^2) + eps)^-1/2:
        # do = r (u - unit mean(u unit)) with u = dout gate scale
        u = dn * gate * w_ref[...]
        do_ref[pl.ds(at, rows), :] = (r * (u - unit * jnp.mean(
            u * unit, axis=-1, keepdims=True))).astype(do_ref.dtype)
        return total + jnp.sum(dn * gate * unit, axis=0, keepdims=True)

    sum_ref[...] = _over_rows(o_ref.shape[0], body, jnp.zeros(
        (1, o_ref.shape[1]), jnp.float32))


_jit_eps = functools.partial(jax.jit, static_argnames=(
    "eps", "tile", "interpret"))


def _back(o, tile):
    plan = _plan(o.shape[1], tile or _TILE)
    own = lambda j: 0  # noqa: E731
    return plan, plan.rows(o.shape[2], own), pl.BlockSpec(
        (1, o.shape[2]), lambda b, i, j: (0, 0))


@_jit_eps
def _gated_norm_forward(o, g, w, eps, tile, interpret):
    plan, rows, scale = _back(o, tile)
    return _call(
        _gated_norm_kernel, "delta_gated_norm", (o.shape[0], plan.tiles, 1),
        [rows, rows, scale], [rows],
        [jax.ShapeDtypeStruct(o.shape, jnp.bfloat16)], [], [o, g, w],
        interpret, eps=eps)[0]


@_jit_eps
def _gated_norm_backward(dn, o, g, w, eps, tile, interpret):
    """(o's cotangent, g's, the scale's sums (B H, tiles, 1, d_v))."""
    plan, rows, scale = _back(o, tile)
    own = lambda j: 0  # noqa: E731
    return _call(
        _gated_norm_bwd_kernel, "delta_gated_norm_bwd",
        (o.shape[0], plan.tiles, 1), [rows, rows, rows, scale],
        [rows, rows, plan.sums(1, o.shape[2], own)],
        [jax.ShapeDtypeStruct(o.shape, o.dtype),
         jax.ShapeDtypeStruct(g.shape, g.dtype),
         jax.ShapeDtypeStruct((o.shape[0], plan.tiles, 1, o.shape[2]),
                              jnp.float32)],
        [], [dn, o, g, w], interpret, eps=eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def delta_gated_norm(o, g, scale, eps: float, tile=None,
                     interpret: bool = False):
    """The output projection's operand (B, T, H, d_v) bfloat16 from the
    scan's output ``o`` (B, H, T', d_v), heads first as
    :func:`gated_delta_rule_heads_first` leaves it, the g product (B, T,
    H, d_v) and the norm's ``scale`` (d_v,): ``rmsnorm(o; eps) scale
    silu(g)``, the mean square over a head's values."""
    return _gated_norm_fwd(o, g, scale, eps, tile, interpret)[0]


def _gated_norm_fwd(o, g, scale, eps, tile, interpret):
    b, h, whole, d = o.shape
    y = _gated_norm_forward(
        o.reshape(b * h, whole, d), _rows_first(g, whole),
        scale.astype(jnp.float32)[None], eps, tile, interpret)
    return _tokens_first(y, g), (o, g, scale)


@trace.scope("linear_attention")  # a backward rule has no forward name stack
def _gated_norm_bwd(eps, tile, interpret, residuals, dn):
    o, g, scale = residuals
    b, h, whole, d = o.shape
    do, dg, sums = _gated_norm_backward(
        _rows_first(dn, whole), o.reshape(b * h, whole, d),
        _rows_first(g, whole), scale.astype(jnp.float32)[None], eps, tile,
        interpret)
    return (do.reshape(o.shape), _tokens_first(dg, g),
            sums.sum(axis=(0, 1, 2)).astype(scale.dtype))


delta_gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)


# ---------------------------------------------------------------------------
# the scan's second door: operands that are heads first already


def _gb(g, beta):
    """``gb`` (B, H, N, 2, C) as ``ops/linear_attention._operands`` makes
    it: a chunk's running log decay and its beta, float32."""
    f = jnp.promote_types(g.dtype, jnp.float32)
    split = lambda x: la._heads_first(x).astype(f).reshape(  # noqa: E731
        x.shape[0], x.shape[2], -1, la.CHUNK)
    return jnp.stack([jnp.cumsum(split(g), axis=-1), split(beta)], axis=3)


def _scan(q, k, v, g, beta, interpret):
    with trace.scope("linear_attention.core"):
        return la._fwd_pallas(q, k, v, _gb(g, beta),
                              interpret=bool(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, interpret):
    return _scan(q, k, v, g, beta, interpret)[0]


def _rule_fwd(q, k, v, g, beta, interpret):
    o, states = (checkpoint_name(x, name) for x, name in zip(
        _scan(q, k, v, g, beta, interpret), la.REMAT_KEEPS))
    return o, (q, k, v, g, beta, states)


def _rule_bwd(interpret, kept, do):
    q, k, v, g, beta, states = kept
    with trace.scope("linear_attention.core"):
        dq, dk, dv, dgb = la._bwd_pallas(q, k, v, _gb(g, beta), states, do,
                                         interpret=bool(interpret))
        # gamma is the chunk's running sum of g: g_i collects gamma_i..C
        dg = jnp.flip(jnp.cumsum(jnp.flip(dgb[:, :, :, 0], -1), -1), -1)
        whole = lambda x: x.reshape(*x.shape[:2], -1)  # noqa: E731
        return (dq, dk, dv, la._tokens_first(whole(dg), g),
                la._tokens_first(whole(dgb[:, :, :, 1]), beta))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule_heads_first(q, k, v, g, beta, *, interpret=None):
    """``ops/linear_attention.gated_delta_rule`` on operands that are heads
    first and whole chunks long already: ``q``, ``k`` (B, H, T', d_k) and
    ``v`` (B, H, T', d_v) as :func:`delta_operands` writes them, the log
    decays ``g`` and the steps ``beta`` (B, T, H) tokens first as the mixer
    makes them (``gb``, their chunks' form, stays XLA's). Returns ``o`` (B,
    H, T', d_v), heads first as ``linear_scan_fwd`` writes it. The same
    kernels and residual names, and the kernels alone: the rule sends no
    mixer here off the Pallas route (``interpret``, tests: interpreted)."""
    return _rule(q, k, v, g, beta, interpret)
