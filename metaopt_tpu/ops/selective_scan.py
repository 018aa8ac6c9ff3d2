"""The selective scan: a state-space recurrence whose step is the input's.

For one channel ``d`` with ``N`` states, an input ``x_t``, a step
``delta_t > 0`` (both the channel's own) and the token's ``B_t``, ``C_t``
(N each, shared by all channels), with ``A[d, n] < 0``:

    h_0 = 0 (N)
    h_t[n] = exp(delta_t A[d, n]) h_{t-1}[n] + delta_t x_t B_t[n]
    y_t    = sum_n h_t[n] C_t[n]

(Mamba, arXiv:2312.00752: its first form). The decay differs by channel
AND by state, so there is no scalar decay a head and no attention-like
product form of a chunk: the recurrence is walked token by token, on the
vector unit. :func:`selective_scan` walks it in **chunks** of tokens: the
forward writes the state that enters each chunk (``N`` float32 a channel
and chunk); the backward walks the chunks in reverse with the state's
cotangent, rebuilds the chunk's states from the one that entered it and
walks its tokens back. Everything is float32: the state, the steps, the
decays and the running sums.

A token's algebra is written once, a state at a time (``_forward_token``,
``_backward_token``), and runs on two routes, which
:func:`selective_scan_route` names from what a call can see (the one rule;
no option overrides it):

- ``"pallas"``, on one TPU device: two kernels, ``selective_scan_fwd`` and
  ``selective_scan_bwd``, a program a (row, tile of ``TILE`` channels,
  chunk), the chunks in order. A tile's channels fill whole registers (8
  sublanes x 128 lanes), a state of the tile is one register a ``n``, and
  a token's ``B_t[n]``, ``C_t[n]`` are scalars read from SMEM: nothing is
  broadcast along lanes and the forward reduces nothing across them;
- ``"xla"``, elsewhere (the CPU, a mesh of several devices): the plain
  chunked twin, two nested ``lax.scan``.

Both sit under the scope ``ssm.core``, forward and backward. The chunk is
the largest power of two up to ``CHUNK`` that divides the row; a row that
is no whole number of the chunks a caller names is refused. The output and
the chunks' entering states carry names (``REMAT_KEEPS``) by which a
rematerialised block keeps them, so that its backward pass does not walk
the row forward a second time.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from metaopt_tpu.utils import trace

#: tokens a chunk at most (the backward keeps a chunk's states on chip:
#: (CHUNK + 1) N TILE float32, 8.5 MB at 128, 16 and 1024)
CHUNK = 128
#: channels a program: 8 sublanes x 128 lanes, one register a state
TILE = 1024
_ROWS, _LANES = 8, 128
#: What a rematerialised block keeps of the scan besides its input: the
#: output (4 bytes a token and channel) and the chunks' entering states
#: (4 N a chunk and channel), which cost the whole forward walk to make
#: again.
REMAT_KEEPS = ("selective_scan.out", "selective_scan.states")


def selective_scan_route(tokens: int, mesh=None) -> dict:
    """The route a call of :func:`selective_scan` over rows of ``tokens``
    takes under ``mesh``, and its chunk: the Pallas kernels on one TPU
    device, the plain chunked twin elsewhere."""
    on_one_tpu = jax.default_backend() == "tpu" and (
        mesh is None or mesh.size == 1)
    return {"route": "pallas" if on_one_tpu else "xla",
            "chunk": math.gcd(tokens, CHUNK), "state": "float32"}


# ---------------------------------------------------------------------------
# a token's algebra for one state n: what the kernels run on a register
# (a tile's channels) and the plain twin on (rows, channels) arrays


def _forward_token(h, a, dt, dtx, b):
    """h_t[n] from h_{t-1}[n]: ``a`` = A[., n], ``dtx`` = delta_t x_t,
    ``b`` = B_t[n]."""
    return jnp.exp(dt * a) * h + dtx * b


def _backward_token(dh, h, h_prev, a, dt, x, dy, b, c):
    """One state's part of a token's backward: from ``dh`` (the cotangent
    of h_t[n] that came from the tokens after), h_t[n], h_{t-1}[n] and the
    token's own values, (the cotangent of h_{t-1}[n], this state's terms of
    d delta_t, d x_t and d A[., n], and the channels' terms of d B_t[n] and
    d C_t[n], not yet summed over channels)."""
    dh = dh + dy * c
    decay = jnp.exp(dt * a)
    kept = dh * decay * h_prev
    return (dh * decay, kept * a + dh * (x * b), dh * (dt * b), kept * dt,
            dh * (dt * x), dy * h)


# ---------------------------------------------------------------------------
# the Pallas route: a program a (row, channel tile, chunk)


def _fwd_kernel(bc_ref, x_ref, dt_ref, a_ref, y_ref, states_ref, h_scr):
    """x, dt, y (C, 8, 128); a, states, the scratch (N, 8, 128); bc (C *
    2 N,) in SMEM: a token's B then its C."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    n = a_ref.shape[0]
    states_ref[...] = h_scr[...]
    a = [a_ref[i] for i in range(n)]

    def token(t, h):
        dt = dt_ref[t]
        dtx = dt * x_ref[t]
        h = [_forward_token(h[i], a[i], dt, dtx, bc_ref[t * 2 * n + i])
             for i in range(n)]
        y = h[0] * bc_ref[t * 2 * n + n]
        for i in range(1, n):
            y = y + h[i] * bc_ref[t * 2 * n + n + i]
        y_ref[t] = y
        return h

    h = jax.lax.fori_loop(0, x_ref.shape[0], token,
                          [h_scr[i] for i in range(n)])
    for i in range(n):
        h_scr[i] = h[i]


def _bwd_kernel(bc_ref, x_ref, dt_ref, a_ref, states_ref, dy_ref,
                dx_ref, ddt_ref, da_ref, dbc_ref, dh_scr, da_scr, h_scr,
                part_scr):
    """The chunks in reverse. ``h_scr`` (C + 1, N, 8, 128): the state that
    entered the chunk and the one after each of its tokens; ``part_scr`` (C,
    2 N, 128): the channels' terms of a token's dB and dC summed over
    sublanes, summed over lanes once a chunk into ``dbc`` (C, 2 N)."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        da_scr[...] = jnp.zeros_like(da_scr)

    n = a_ref.shape[0]
    c = x_ref.shape[0]
    a = [a_ref[i] for i in range(n)]
    h_scr[0] = states_ref[...]

    def again(t, h):
        dt = dt_ref[t]
        dtx = dt * x_ref[t]
        h = [_forward_token(h[i], a[i], dt, dtx, bc_ref[t * 2 * n + i])
             for i in range(n)]
        for i in range(n):
            h_scr[t + 1, i] = h[i]
        return h

    jax.lax.fori_loop(0, c, again, [states_ref[i] for i in range(n)])

    def back(j, carry):
        dh, da = carry
        t = c - 1 - j
        dt, x, dy = dt_ref[t], x_ref[t], dy_ref[t]
        ddt = dx = jnp.zeros_like(dt)
        dh_new, da_new = [], []
        for i in range(n):
            dh_i, ddt_i, dx_i, da_i, db_i, dc_i = _backward_token(
                dh[i], h_scr[t + 1, i], h_scr[t, i], a[i], dt, x, dy,
                bc_ref[t * 2 * n + i], bc_ref[t * 2 * n + n + i])
            dh_new.append(dh_i)
            da_new.append(da[i] + da_i)
            ddt, dx = ddt + ddt_i, dx + dx_i
            part_scr[t, i:i + 1] = jnp.sum(db_i, axis=0, keepdims=True)
            part_scr[t, n + i:n + i + 1] = jnp.sum(dc_i, axis=0,
                                                   keepdims=True)
        ddt_ref[t] = ddt
        dx_ref[t] = dx
        return dh_new, da_new

    dh, da = jax.lax.fori_loop(
        0, c, back, ([dh_scr[i] for i in range(n)],
                     [da_scr[i] for i in range(n)]))
    for i in range(n):
        dh_scr[i] = dh[i]
        da_scr[i] = da[i]
    dbc_ref[...] = jnp.sum(part_scr[...], axis=-1)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        da_ref[...] = da_scr[...]


def _specs(c: int, n: int, chunks: int, backward: bool):
    at = (lambda r, j, i: chunks - 1 - i) if backward else (lambda r, j, i: i)
    return {
        "bc": pl.BlockSpec((None, c * 2 * n), lambda r, j, i: (r, at(r, j, i)),
                           memory_space=pltpu.SMEM),
        "tokens": pl.BlockSpec((None, c, _ROWS, _LANES),
                               lambda r, j, i: (r, at(r, j, i), j, 0)),
        "a": pl.BlockSpec((n, _ROWS, _LANES), lambda r, j, i: (0, j, 0)),
        "state": pl.BlockSpec((None, None, n, _ROWS, _LANES),
                              lambda r, j, i: (r, at(r, j, i), 0, j, 0)),
        "da": pl.BlockSpec((None, n, _ROWS, _LANES),
                           lambda r, j, i: (r, 0, j, 0)),
        "dbc": pl.BlockSpec((None, None, c, 2 * n),
                            lambda r, j, i: (r, j, at(r, j, i), 0)),
    }


def _params(vmem_bytes: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=vmem_bytes)


# jitted, so that the layers of one shape share one trace and one lowering
_jit = functools.partial(jax.jit, static_argnames=("chunk", "interpret"))


@_jit
def _fwd_pallas(x, dt, a, bc, chunk: int, interpret: bool = False):
    """x, dt (R, T, D / 128, 128); a (N, D / 128, 128); bc (R, T * 2 N)."""
    r, t, rows, _ = x.shape
    n, chunks = a.shape[0], t // chunk
    sp = _specs(chunk, n, chunks, False)
    return pl.pallas_call(
        _fwd_kernel, name="selective_scan_fwd",
        grid=(r, rows // _ROWS, chunks),
        in_specs=[sp["bc"], sp["tokens"], sp["tokens"], sp["a"]],
        out_specs=[sp["tokens"], sp["state"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((r, chunks, n, rows, _LANES),
                                        x.dtype)],
        scratch_shapes=[pltpu.VMEM((n, _ROWS, _LANES), x.dtype)],
        compiler_params=_params(32 << 20), interpret=interpret)(bc, x, dt, a)


@_jit
def _bwd_pallas(x, dt, a, bc, states, dy, chunk: int,
                interpret: bool = False):
    r, t, rows, _ = x.shape
    n, chunks, tiles = a.shape[0], t // chunk, rows // _ROWS
    sp = _specs(chunk, n, chunks, True)
    like = lambda y: jax.ShapeDtypeStruct(y.shape, y.dtype)  # noqa: E731
    state = pltpu.VMEM((n, _ROWS, _LANES), x.dtype)
    tile_bytes = 4 * n * _ROWS * _LANES
    return pl.pallas_call(
        _bwd_kernel, name="selective_scan_bwd", grid=(r, tiles, chunks),
        in_specs=[sp["bc"], sp["tokens"], sp["tokens"], sp["a"], sp["state"],
                  sp["tokens"]],
        out_specs=[sp["tokens"], sp["tokens"], sp["da"], sp["dbc"]],
        out_shape=[like(x), like(dt),
                   jax.ShapeDtypeStruct((r,) + a.shape, a.dtype),
                   jax.ShapeDtypeStruct((r, tiles, t, 2 * n), x.dtype)],
        scratch_shapes=[state, state,
                        pltpu.VMEM((chunk + 1, n, _ROWS, _LANES), x.dtype),
                        pltpu.VMEM((chunk, 2 * n, _LANES), x.dtype)],
        compiler_params=_params((chunk + 1) * tile_bytes + (32 << 20)),
        interpret=interpret)(bc, x, dt, a, states, dy)


# ---------------------------------------------------------------------------
# the plain route: the chunks a scan, a chunk's tokens a scan inside it


def _chunked(x, c: int):
    """(R, T, ...) -> (T / C, C, R, ...): the scans' axes first."""
    x = x.reshape(x.shape[0], x.shape[1] // c, c, *x.shape[2:])
    return jnp.moveaxis(x, 0, 2)


def _whole(x):
    """The inverse of :func:`_chunked`."""
    x = jnp.moveaxis(x, 2, 0)
    return x.reshape(x.shape[0], -1, *x.shape[3:])


def _token_xla(a, h, xs):
    """h (N, R, D) after a token, from ``xs`` = (x, dt (R, D), bc (R, 2 N))."""
    x, dt, bc = xs
    n = a.shape[0]
    dtx = dt * x
    return [_forward_token(h[i], a[i], dt, dtx, bc[:, i, None])
            for i in range(n)]


def _fwd_xla(x, dt, a, bc, chunk: int):
    """x, dt (R, T, D); a (N, D); bc (R, T, 2 N) -> y (R, T, D) and the
    chunks' entering states (R, T / C, N, D)."""
    n = a.shape[0]

    def token(h, xs):
        h = _token_xla(a, h, xs)
        return h, sum(h[i] * xs[2][:, n + i, None] for i in range(n))

    def a_chunk(h, xs):
        last, y = jax.lax.scan(token, h, xs)
        return last, (y, jnp.stack(h))

    init = [jnp.zeros(x.shape[::2], x.dtype)] * n
    _, (y, states) = jax.lax.scan(
        a_chunk, init, tuple(_chunked(v, chunk) for v in (x, dt, bc)))
    return _whole(y), jnp.moveaxis(states, 2, 0)


def _bwd_xla(x, dt, a, bc, states, dy, chunk: int):
    n = a.shape[0]

    def again(h, xs):
        return _token_xla(a, h, xs), jnp.stack(h)

    def back(carry, xs):
        dh, da = carry
        x_t, dt_t, bc_t, dy_t, h_t, h_prev = xs
        parts = [_backward_token(
            dh[i], h_t[i], h_prev[i], a[i], dt_t, x_t, dy_t,
            bc_t[:, i, None], bc_t[:, n + i, None]) for i in range(n)]
        dh, ddt, dx, da_t, db, dc = map(list, zip(*parts))
        dbc = jnp.stack([p.sum(axis=-1) for p in db + dc], axis=-1)
        return (dh, [u + v for u, v in zip(da, da_t)]), \
            (sum(dx), sum(ddt), dbc)

    def a_chunk(carry, xs):
        x_c, dt_c, bc_c, dy_c, entering = xs
        last, before = jax.lax.scan(again, list(entering),
                                    (x_c, dt_c, bc_c))
        after = jnp.concatenate([before[1:], jnp.stack(last)[None]])
        return jax.lax.scan(back, carry,
                            (x_c, dt_c, bc_c, dy_c, after, before),
                            reverse=True)

    zeros = [jnp.zeros(x.shape[::2], x.dtype)] * n
    (_, da), (dx, ddt, dbc) = jax.lax.scan(
        a_chunk, (zeros, zeros),
        tuple(_chunked(v, chunk) for v in (x, dt, bc, dy))
        + (jnp.moveaxis(states, 0, 2),), reverse=True)
    return _whole(dx), _whole(ddt), jnp.stack(da).sum(axis=1), _whole(dbc)


# ---------------------------------------------------------------------------
# the door


def _tiled(x):
    """(..., D) -> (..., D' / 128, 128), D' the next whole tile of
    channels: a padded channel has x, delta and A of 0 and its state stays
    0."""
    x = jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, -x.shape[-1] % TILE),))
    return x.reshape(*x.shape[:-1], -1, _LANES)


def _untiled(x, d: int):
    return x.reshape(*x.shape[:-2], -1)[..., :d]


def _by_kernels(tokens: int, interpret) -> bool:
    from metaopt_tpu.parallel.mesh import active_mesh

    return interpret is not None or selective_scan_route(
        tokens, active_mesh())["route"] == "pallas"


def _forward(x, dt, a, b, c, chunk, interpret):
    with trace.scope("ssm.core"):
        bc = jnp.concatenate([b, c], axis=-1)
        if _by_kernels(x.shape[1], interpret):
            y, states = _fwd_pallas(
                _tiled(x), _tiled(dt), _tiled(a.T),
                bc.reshape(bc.shape[0], -1), chunk=chunk,
                interpret=bool(interpret))
            return _untiled(y, x.shape[-1]), states
        return _fwd_xla(x, dt, a.T, bc, chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(x, dt, a, b, c, chunk, interpret):
    return _forward(x, dt, a, b, c, chunk, interpret)[0]


def _scan_fwd(x, dt, a, b, c, chunk, interpret):
    y, states = _forward(x, dt, a, b, c, chunk, interpret)
    # named here, inside the rule: a name on the caller's value would keep
    # the output and still walk the row again for the states
    y, states = (checkpoint_name(v, name)
                 for v, name in zip((y, states), REMAT_KEEPS))
    return y, (x, dt, a, b, c, states)


def _scan_bwd(chunk, interpret, kept, dy):
    x, dt, a, b, c, states = kept
    n, d = b.shape[-1], x.shape[-1]
    with trace.scope("ssm.core"):
        bc = jnp.concatenate([b, c], axis=-1)
        if _by_kernels(x.shape[1], interpret):
            dx, ddt, da, dbc = _bwd_pallas(
                _tiled(x), _tiled(dt), _tiled(a.T),
                bc.reshape(bc.shape[0], -1), states, _tiled(dy),
                chunk=chunk, interpret=bool(interpret))
            dx, ddt = _untiled(dx, d), _untiled(ddt, d)
            # a row's and a tile's shares, summed
            da, dbc = _untiled(da.sum(axis=0), d), dbc.sum(axis=1)
        else:
            dx, ddt, da, dbc = _bwd_xla(x, dt, a.T, bc, states, dy, chunk)
        return dx, ddt, da.T, dbc[..., :n], dbc[..., n:]


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x, dt, a, b, c, *, chunk=None, interpret=None):
    """``y`` (R, T, D) of the recurrence in the module's docstring from
    ``x`` and the steps ``dt`` > 0 (R, T, D), ``a`` < 0 (D, N) and ``b``,
    ``c`` (R, T, N), float32 all, a state a (row, channel) that starts at
    zero. ``chunk``: the tokens a chunk, by default the route's; a row that
    is no whole number of them is refused. ``interpret`` (tests): run the
    kernels whatever the backend, interpreted or not."""
    tokens = x.shape[1]
    if chunk is None:
        chunk = selective_scan_route(tokens)["chunk"]
    if tokens % chunk:
        raise ValueError(f"a row of {tokens} tokens is no whole number of "
                         f"chunks of {chunk}")
    f32 = lambda v: v.astype(jnp.float32)  # noqa: E731
    return _scan(f32(x), f32(dt), f32(a), f32(b), f32(c), int(chunk),
                 interpret)
