"""A gated short convolution's element-wise core, one Pallas pass a direction.

models/lm_layers.ShortConvMixer makes ``[B | C | X]`` as one projection
leaves it (tokens, 3 D), bfloat16, and its output projection reads a
bfloat16 (tokens, D). Between the two lies

    y_t = C_t * sum_i taps[i] (B X)_{t - (K - 1) + i}     (B X) = 0 before
                                                          the row's start

two gates and a causal depthwise convolution of K taps, no activation, no
bias: seven multiply-adds a token and channel at K = 3, float32. Left to
XLA they are passes of their own through HBM over float32 copies of the
thirds, forward, again under remat and, transposed, backward (what PRs 50
and 52 found around two other mixers' convolutions). Here they are ONE call
a direction, on the product where the projection left it:

- forward the call ``short_conv_fwd``, a program a (row, tile of the
  sequence): the tile's (rows, 3 D) of the product and the rows before it
  from a second block over the same array (the halo; zero at the row's
  start), ``B X`` float32 in a window in VMEM, y out, bfloat16;
- backward the call ``short_conv_bwd``: the product's tile with a halo on
  both sides and y's cotangent with the halo after it in, the product's
  whole cotangent (``dB | dC | dX``, bfloat16, each number rounded once) and
  the taps' gradient as a tile's float32 sums out, added up outside. The
  convolution's transpose walks the rows AFTER a tile: ``d(B X)_t = sum_i
  taps[i] (dy C)_{t + (K - 1) - i}``.

No slice, transpose or float32 copy of a (tokens, 3 D) array stands in HBM
in either direction. The residuals are the product and the taps: what a
rematerialised block keeps (``ShortConvSpec.KEPT``) or makes again.
:func:`gated_short_conv_plain` is the same arithmetic in ``jax.numpy``
(models/lm_layers.short_conv's sum between two products): the form off the
TPU and on a mesh of several devices, and the tests' oracle;
:func:`short_conv_route` is the one rule for which of the two a mixer
takes. Both directions sit under the scope ``short_conv.core``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from metaopt_tpu.ops.ssd_hand_over import (_HALO, _over_rows, _plan,
                                           _shifted, _valid)
from metaopt_tpu.utils import trace

#: a program's tile of the sequence, forward and backward (the backward
#: holds the product's tile twice, in and out): what the double-buffered
#: blocks and the windows leave inside Mosaic's own 16 MB at D = 2048
_TILE = (256, 128)
#: columns the arithmetic covers at a time, ``_over_rows``' 64 rows of them:
#: (64, 512) float32 and what is made of it
_COLUMNS = 512
#: the VMEM a kernel may take without asking (Mosaic's scoped default on a
#: v5e)
_ALLOWED = 16 << 20


def short_conv_route(mesh, channels: int, tokens: int) -> str:
    """The route a gated short convolution's core takes over rows of
    ``tokens`` and ``channels`` channels under ``mesh``: ``"pallas"`` (this
    module's calls) on one TPU device where a third of the product is whole
    lanes (a multiple of 128) and a row whole sublane tiles of bfloat16 (a
    multiple of 16), else ``"plain"``: off the TPU, on a mesh of several
    devices, whose shards the calls do not know, and at a rehearsal's
    widths."""
    on_one_tpu = jax.default_backend() == "tpu" and (
        mesh is None or mesh.size == 1)
    whole = channels % 128 == 0 and tokens % 16 == 0
    return "pallas" if on_one_tpu and whole else "plain"


def gated_short_conv_plain(bcx, taps):
    """y (B, T, D) bfloat16 of the product ``bcx`` (B, T, 3 D), its thirds B
    | C | X, and ``taps`` (K, D): C * conv(B * X) in float32, the sum over
    the taps in models/lm_layers.short_conv's order, one rounding."""
    b, c, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    k, t = taps.shape[0], bcx.shape[1]
    u = jnp.pad(b * x, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(taps[i].astype(jnp.float32) * u[:, i:i + t] for i in range(k))
    return (c * conv).astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# what the two kernels share


def _thirds(d: int):
    """[(B's, C's, X's and the output's columns) a block of ``_COLUMNS``
    channels] of a product 3 ``d`` wide."""
    w = min(d, _COLUMNS)
    return [tuple(slice(third * d + col, third * d + col + w)
                  for third in range(3)) + (slice(col, col + w),)
            for col in range(0, d, w)]


def _gated(ref, rows, cb, cx):
    """(B X) float32 of the rows ``rows`` of a block of the product."""
    return ref[rows, cb].astype(jnp.float32) * ref[rows, cx].astype(
        jnp.float32)


def _fill_halo(win, before_ref, first, cb, cx, own):
    """The window's rows before the tile: B X of the halo block, zero in a
    row's first tile (``first`` True: a row of one tile, whose halo block
    is not read)."""
    win[0:_HALO, own] = jnp.zeros((_HALO, own.stop - own.start),
                                  jnp.float32) if first is True \
        else jnp.where(first, 0.0, _gated(before_ref, slice(None), cb, cx))


def _conv(win, at, rows: int, own, taps_ref):
    """(the convolution's output (rows, columns) float32 for the tile's rows
    from ``at``, which the window ``win`` holds ``_HALO`` rows down; the
    rows shifted a tap each): ``short_conv``'s sum in its order."""
    taps = taps_ref.shape[0]
    w = win[pl.ds(at + (_HALO - 8), rows + 8), own]
    shifts = [_shifted(w, taps - 1 - i) for i in range(taps)]
    acc = taps_ref[0:1, own] * shifts[0]
    for i in range(1, taps):
        acc = acc + taps_ref[i:i + 1, own] * shifts[i]
    return acc, shifts


def _pallas(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
            operands, interpret, **static):
    """``pallas_call`` over (row, tile, 1), all parallel, the scratch
    float32; VMEM is asked for only where the double-buffered blocks and
    the scratch do not fit the compiler's own allowance."""
    size = lambda shape, dtype: jnp.dtype(dtype).itemsize * math.prod(  # noqa: E731
        n or 1 for n in shape)
    need = 2 * sum(size(sp.block_shape, x.dtype) for sp, x in zip(
        [*in_specs, *out_specs], [*operands, *out_shape]))
    need += sum(size(shape, jnp.float32) for shape in scratch)
    limit = {} if need + (2 << 20) <= _ALLOWED else {
        "vmem_limit_bytes": min(need + (8 << 20), 100 << 20)}
    return pl.pallas_call(
        functools.partial(kernel, **static), out_shape=out_shape, grid=grid,
        in_specs=in_specs, out_specs=out_specs, name=name,
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3, **limit),
        interpret=interpret)(*operands)


# ---------------------------------------------------------------------------
# the two directions


def _fwd_kernel(before_ref, tile_ref, taps_ref, y_ref, win, *, alone: bool):
    """One (row, tile) program. Shapes in VMEM: the product's tile (Bs, 3 D)
    and the halo before it (halo, 3 D); the taps (K, D); out y (Bs, D);
    ``win`` (halo + Bs, D): B X, float32."""
    block, d = y_ref.shape
    first = alone or pl.program_id(1) == 0
    for cb, cc, cx, own in _thirds(d):
        _fill_halo(win, before_ref, first, cb, cx, own)

        def fill(at, rows, _, cb=cb, cx=cx, own=own):
            win[pl.ds(_HALO + at, rows), own] = _gated(
                tile_ref, pl.ds(at, rows), cb, cx)

        def conv(at, rows, _, cc=cc, own=own):
            y_ref[pl.ds(at, rows), own] = (
                tile_ref[pl.ds(at, rows), cc].astype(jnp.float32)
                * _conv(win, at, rows, own, taps_ref)[0]).astype(y_ref.dtype)

        _over_rows(block, fill)
        _over_rows(block, conv)


def _bwd_kernel(before_ref, tile_ref, after_ref, dy_ref, dya_ref, taps_ref,
                dp_ref, sums_ref, win, dc, *, length: int, alone: bool):
    """The transpose of ``_fwd_kernel``, a program likewise. ``after_ref``,
    ``dya_ref``: the halo rows after the tile, of the product and of y's
    cotangent. Out: the product's cotangent (Bs, 3 D) and the taps' sums
    over the tile (K, D). ``dc`` (Bs + 8, D): the convolution's output's
    cotangent dy C, float32, the rows past the sequence's ``length``
    zero."""
    block, d = dy_ref.shape
    taps = taps_ref.shape[0]
    start = pl.program_id(1) * block
    first = alone or pl.program_id(1) == 0
    f32 = jnp.float32
    for cb, cc, cx, own in _thirds(d):
        width = own.stop - own.start
        _fill_halo(win, before_ref, first, cb, cx, own)

        def fill(at, rows, _, cb=cb, cx=cx, own=own):
            win[pl.ds(_HALO + at, rows), own] = jnp.where(
                _valid(start + at, rows, length),
                _gated(tile_ref, pl.ds(at, rows), cb, cx), 0.0)

        def down(at, rows, sums, cc=cc, own=own):
            here = pl.ds(at, rows)
            conv, shifts = _conv(win, at, rows, own, taps_ref)
            dy = dy_ref[here, own].astype(f32)
            dp_ref[here, cc] = (dy * conv).astype(dp_ref.dtype)
            d_conv = jnp.where(_valid(start + at, rows, length),
                               dy * tile_ref[here, cc].astype(f32), 0.0)
            dc[here, own] = d_conv
            return sums + jnp.concatenate(
                [jnp.sum(d_conv * u, axis=0, keepdims=True) for u in shifts],
                axis=0)

        _over_rows(block, fill)
        sums_ref[:, own] = _over_rows(block, down,
                                      jnp.zeros((taps, width), f32))
        # a row of one tile: nothing after it
        dc[block:block + 8, own] = jnp.zeros((8, width), f32) if alone \
            else jnp.where(_valid(start + block, 8, length),
                           dya_ref[0:8, own].astype(f32)
                           * after_ref[0:8, cc].astype(f32), 0.0)

        def up(at, rows, _, cb=cb, cx=cx, own=own):
            # the transpose of the taps' sum: row r collects from the rows
            # r .. r + taps - 1 of the convolution's output's cotangent
            here = pl.ds(at, rows)
            w = dc[pl.ds(at, rows + 8), own]
            du = 0.0
            for k in range(taps):
                ahead = taps - 1 - k
                du = du + taps_ref[k:k + 1, own] * (
                    pltpu.roll(w, rows + 8 - ahead, 0) if ahead else w)[:rows]
            dp_ref[here, cb] = (du * tile_ref[here, cx].astype(f32)).astype(
                dp_ref.dtype)
            dp_ref[here, cx] = (du * tile_ref[here, cb].astype(f32)).astype(
                dp_ref.dtype)

        _over_rows(block, up)


_jit = functools.partial(jax.jit, static_argnames=("tile", "interpret"))


def _own(j):
    return 0


@_jit
def _forward(bcx, taps, tile, interpret):
    d = taps.shape[1]
    plan = _plan(bcx.shape[1], tile or _TILE[0])
    return _pallas(
        _fwd_kernel, "short_conv_fwd", (bcx.shape[0], plan.tiles, 1),
        [plan.before(3 * d, _own), plan.rows(3 * d, _own),
         pl.BlockSpec(taps.shape, lambda b, i, j: (0, 0))],
        [plan.rows(d, _own)],
        [jax.ShapeDtypeStruct(bcx.shape[:2] + (d,), jnp.bfloat16)],
        [(_HALO + plan.tile, d)], [bcx, bcx, taps.astype(jnp.float32)],
        interpret, alone=plan.alone)[0]


@_jit
def _backward(bcx, taps, dy, tile, interpret):
    """(the product's cotangent (B, T, 3 D) bfloat16, the taps' (K, D)
    float32)."""
    d = taps.shape[1]
    plan = _plan(bcx.shape[1], tile or _TILE[1])
    d_bcx, sums = _pallas(
        _bwd_kernel, "short_conv_bwd", (bcx.shape[0], plan.tiles, 1),
        [plan.before(3 * d, _own), plan.rows(3 * d, _own),
         plan.after(3 * d, _own), plan.rows(d, _own), plan.after(d, _own),
         pl.BlockSpec(taps.shape, lambda b, i, j: (0, 0))],
        [plan.rows(3 * d, _own), plan.sums(taps.shape[0], d, _own)],
        [jax.ShapeDtypeStruct(bcx.shape, jnp.bfloat16),
         jax.ShapeDtypeStruct((bcx.shape[0], plan.tiles) + taps.shape,
                              jnp.float32)],
        [(_HALO + plan.tile, d), (plan.tile + 8, d)],
        [bcx, bcx, bcx, dy, dy, taps.astype(jnp.float32)], interpret,
        length=bcx.shape[1], alone=plan.alone)
    return d_bcx, jnp.sum(sums, axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _core(bcx, taps, tile, interpret):
    return _core_fwd(bcx, taps, tile, interpret)[0]


def _core_fwd(bcx, taps, tile, interpret):
    with trace.scope("short_conv.core"):
        return _forward(bcx, taps, tile and tile[0], interpret), (bcx, taps)


def _core_bwd(tile, interpret, kept, dy):
    bcx, taps = kept
    with trace.scope("short_conv.core"):
        d_bcx, d_taps = _backward(bcx, taps, dy.astype(jnp.bfloat16),
                                  tile and tile[1], interpret)
    return d_bcx, d_taps.astype(taps.dtype)


_core.defvjp(_core_fwd, _core_bwd)


def gated_short_conv(bcx, taps, *, tile=None, interpret: bool = False):
    """y (B, T, D) bfloat16 = C * conv(B * X) of the product ``bcx`` (B, T,
    3 D) bfloat16, its thirds B | C | X, and ``taps`` (K, D), K <= 9, by the
    two Pallas calls (module docstring); differentiable in both. ``tile``:
    (forward's, backward's) tiles of the sequence, multiples of 16, for the
    tests; ``interpret``: the calls interpreted, off the TPU."""
    if bcx.shape[-1] != 3 * taps.shape[1] or not 1 <= taps.shape[0] <= 9:
        raise ValueError(f"a product {bcx.shape} is no three thirds of the "
                         f"taps' {taps.shape} (at most 9 taps)")
    return _core(bcx.astype(jnp.bfloat16), taps, tile and tuple(tile),
                 interpret)
