"""A Mamba-2 mixer's hand-over to the scalar decay rule and back.

models/lm_layers.ScalarDecayMixer makes ``[z | x B C]`` as one projection
leaves it, bfloat16, and the scan of ops/linear_attention.py reads bfloat16
operands and writes a bfloat16 output. In front of the scan lie the causal
convolution with its bias, SiLU, the split, softplus, the product with the
step and one rounding each; behind it the skip ``D x``, the gate ``silu(z)``
and the RMS norm over a group's channels: float32 arithmetic that XLA runs
as passes of their own through HBM, each over ``(B, T, d_inner)`` at four
bytes a number, forward, again under remat and, transposed, backward. Here
they are ONE Pallas call a side and direction:

- :func:`ssd_operands` (a ``jax.custom_vjp``), in front: forward the call
  ``ssd_operands``, a program a (row, tile of the sequence, block of the
  product's columns): the block's ``(tile, columns)`` of x B C read where
  the projection left them, the ``conv - 1`` rows before the tile from a
  second block over the same array (the halo; zero at the row's start),
  float32 in registers only, C, B, ``dt x`` (bfloat16) and ``dt a``
  (float32) out, tokens first as :func:`scalar_decay_rule` takes them.
  Backward the call ``ssd_operands_bwd``: the scan's four cotangents in,
  the product's cotangent (all its columns, bfloat16) and the step's
  (float32) out; the convolution's transpose reads the rows AFTER a tile (a
  halo on that side of the product and of the cotangents); the taps', the
  bias's, ``dt_bias``'s and ``A_log``'s gradients are a tile's sums, added
  up outside.
- :func:`ssd_gated_norm` (a ``jax.custom_vjp``), behind: forward the call
  ``ssd_gated_norm``, a program a (row, tile, group of the norm): y, z and
  x's columns of the product in, x made AGAIN from them with the same taps
  in ``short_conv``'s order (the float32 number the front call used, never
  written to HBM), ``rmsnorm((y + D x) silu(z))`` over the group out,
  bfloat16. Backward the call ``ssd_gated_norm_bwd``: y's cotangent (to the
  scan), z's and the skip's share of x's out, ``D``'s and the norm
  weight's gradients a tile's sums.
- the two are halves of one hand-over and share the product: the front call
  returns two HANDLES beside the operands, ``z`` and ``x`` (zeros that no
  kernel reads; XLA drops them), through which the back call's backward
  hands z's cotangent and x's share to the front call's, which then writes
  the product's whole cotangent once, every column rounded once where it
  enters the projection's backward matmul. A caller passes the handles on
  and nothing else; the back call's product is not differentiated.

The residuals are the product and the step's, which is what a
rematerialised block keeps (``ScalarDecaySpec.KEPT``), and the scan's
output (``SCALAR_DECAY_KEEPS``), so under remat the two forward calls are
what is made again. :func:`hand_over` is the one rule for whether a mixer
takes this form.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from metaopt_tpu.utils import trace

#: rows of a halo block: a whole tile of bfloat16 sublanes, more than the
#: ``conv - 1`` rows the convolution reaches
_HALO = 16
#: rows of a tile the arithmetic covers at a time: (64, 512) float32 and
#: what is made of it
_ROWS = 64
#: a program's tile of the sequence where the length is longer
_TILE = 512
#: the widest block of the product's columns a front program takes
_COLUMNS = 512


class Sizes(NamedTuple):
    """A mixer's sizes as the calls need them (``ScalarDecaySpec``'s)."""

    heads: int
    head_dim: int
    groups: int
    state: int

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def bc(self) -> int:
        return self.groups * self.state


def hand_over(route: str, mesh, sz: Sizes) -> str:
    """How a Mamba-2 mixer's operands reach the scan and its output the
    output projection on ``route`` (``linear_attention_route``'s):
    ``"one pass"`` (this module) on the Pallas route of one device where a
    program's block of columns is whole lanes (a norm group's channels
    and a common divisor of x's and B's widths: multiples of 128), else
    ``"passes"``, XLA's: the routes that do not run the kernels, a mesh of
    several devices, whose shards these calls do not know, and widths
    that Mosaic takes no block of (a rehearsal's)."""
    one_device = mesh is None or mesh.size == 1
    lanes = (sz.inner // sz.groups) % 128 == 0 and _columns(sz) % 128 == 0
    return "one pass" if route == "pallas" and one_device and lanes \
        else "passes"


# ---------------------------------------------------------------------------
# what the kernels share


def _softplus(x):
    """``jax.nn.softplus``'s own expression (``logaddexp(x, 0)``)."""
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _silu_slope(u, s):
    """d silu(u) / du with s = sigmoid(u)."""
    return s * (1.0 + u * (1.0 - s))


def _shifted(w, by: int):
    """w (8 + rows, columns) -> (rows, columns): row r is w's row 8 + r -
    ``by``, 0 <= ``by`` <= 8: a rotation down the sublanes and an aligned
    slice."""
    return (pltpu.roll(w, by, 0) if by else w)[8:]


def _conv(win, at, rows: int, tb_ref):
    """(the convolution's output plus its bias (rows, columns) float32 for
    the tile's rows from ``at``, which the window ``win`` holds ``_HALO``
    rows down; the rows shifted a tap each): ``short_conv``'s sum in its
    order. ``tb_ref`` (taps + 1, columns): the taps, then the bias."""
    taps = tb_ref.shape[0] - 1
    w = win[pl.ds(at + (_HALO - 8), rows + 8), :]
    shifts = [_shifted(w, taps - 1 - i) for i in range(taps)]
    acc = tb_ref[0:1, :] * shifts[0]
    for i in range(1, taps):
        acc = acc + tb_ref[i:i + 1, :] * shifts[i]
    return acc + tb_ref[taps:taps + 1, :], shifts


def _spread(heads: int, columns: int, first, head_dim: int, mx):
    """(heads, columns) of 0 and 1: 1 where channel ``first`` + c is head
    h's."""
    h = jax.lax.broadcasted_iota(jnp.int32, (heads, columns), 0) * head_dim
    c = jax.lax.broadcasted_iota(jnp.int32, (heads, columns), 1) + first
    return ((c >= h) & (c < h + head_dim)).astype(mx)


def _thirds(x, mx):
    """Float32 x as three numbers of bfloat16's width whose sum is x, so
    that a product with 0 and 1 on the MXU is exact."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    rest = x - hi
    mid = rest.astype(jnp.bfloat16).astype(jnp.float32)
    return [part.astype(mx) for part in (hi, mid, rest - mid)]


def _a_head(x, spread):
    """x (rows, heads) float32 -> (rows, columns): each channel its head's
    number, bit for bit."""
    return sum(jnp.dot(part, spread, preferred_element_type=jnp.float32)
               for part in _thirds(x, spread.dtype))


def _by_head(x, spread):
    """x (rows, columns) float32 -> (rows, heads): the sums over each
    head's channels, float32 to a rounding of the sum's."""
    return sum(jax.lax.dot_general(
        part, spread, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
               for part in _thirds(x, spread.dtype))


def _over_rows(block: int, body, init=None):
    """``body(at, rows, carry)`` over a tile ``block`` rows long, ``_ROWS``
    at a time where they divide it."""
    if block % _ROWS or block == _ROWS:
        return body(0, block, init)
    return jax.lax.fori_loop(
        0, block // _ROWS,
        lambda i, c: body(pl.multiple_of(i * _ROWS, _ROWS), _ROWS, c), init)


def _valid(at, rows: int, length: int):
    """(rows, 1): which rows from the row ``at`` of the whole sequence lie
    inside its ``length``."""
    return jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) + at < length


def _fill(win, before_ref, tile_ref, first_tile, after_ref=None, start=None,
          length=None):
    """The window ``win`` float32: the halo before the tile (zero in a
    row's first tile; ``first_tile`` True: a row of one tile, whose halo
    blocks are not read), the tile and, with ``after_ref``, the halo after
    it with the rows past the sequence's ``length`` zero, the tile's too."""
    block = tile_ref.shape[0]
    zeros = jnp.zeros((_HALO, win.shape[1]), jnp.float32)
    win[0:_HALO] = zeros if first_tile is True else jnp.where(
        first_tile, 0.0, before_ref[...].astype(jnp.float32))
    tile = tile_ref[...].astype(jnp.float32)
    if after_ref is None:
        win[_HALO:] = tile
        return
    win[_HALO:_HALO + block] = jnp.where(_valid(start, block, length), tile,
                                         0.0)
    win[_HALO + block:] = zeros if first_tile is True else jnp.where(
        _valid(start + block, _HALO, length),
        after_ref[...].astype(jnp.float32), 0.0)


class _Plan(NamedTuple):
    """How a call cuts (B, T, ...): ``tile`` rows a program, ``tiles`` of
    them a row of ``length``."""

    length: int
    tile: int
    tiles: int

    @property
    def halo(self) -> int:
        """Rows of a halo block; a row of one tile reads none of them."""
        return min(_HALO, self.length)

    @property
    def alone(self) -> bool:
        """Is a tile the whole row?"""
        return self.tiles == 1

    def rows(self, width: int, column):
        """A tile's block of a (B, T, ...) array, ``width`` columns at the
        block ``column(j)``."""
        return pl.BlockSpec((None, self.tile, width),
                            lambda b, i, j: (b, i, column(j)))

    def before(self, width: int, column):
        per = self.tile // _HALO
        return pl.BlockSpec(
            (None, self.halo, width),
            lambda b, i, j: (b, jnp.maximum(i * per - 1, 0), column(j)))

    def after(self, width: int, column):
        per, last = self.tile // _HALO, (self.length - 1) // _HALO
        return pl.BlockSpec(
            (None, self.halo, width),
            lambda b, i, j: (b, jnp.minimum((i + 1) * per, last), column(j)))

    def sums(self, rows: int, width: int, column):
        """A tile's sums: a block of a (B, tiles, rows, ...) array."""
        return pl.BlockSpec((None, None, rows, width),
                            lambda b, i, j: (b, i, 0, column(j)))


def _plan(length: int, tile) -> _Plan:
    tile = tile or _TILE
    assert tile % _HALO == 0, tile
    if length <= tile:
        return _Plan(length, length, 1)
    return _Plan(length, tile, pl.cdiv(length, tile))


def _columns(sz: Sizes) -> int:
    """The width of a front program's block of columns: the widest that
    divides x's and B's, of whole lanes where one does."""
    common = math.gcd(sz.inner, sz.bc)
    fits = [w for w in range(min(common, _COLUMNS), 0, -1) if common % w == 0]
    return next((w for w in fits if w % 128 == 0), fits[0])


def _clamp(j, low: int, n: int):
    """Block ``j - low`` of ``n``, the nearest where ``j`` is outside."""
    return jnp.clip(j - low, 0, n - 1)


def _taps_and_bias(taps, bias):
    return jnp.concatenate([taps.astype(jnp.float32),
                            bias.astype(jnp.float32)[None]], axis=0)


def _call(kernel, name, grid, last: str, in_specs, out_specs, out_shape,
          scratch, operands, interpret, **static):
    """``pallas_call`` over (row, tile, a third axis that is ``last``), the
    scratch float32, with the VMEM its double-buffered blocks, its scratch
    and as much again for the rows in flight need."""
    size = lambda shape, dtype: jnp.dtype(dtype).itemsize * math.prod(  # noqa: E731
        n or 1 for n in shape)
    need = 2 * sum(size(sp.block_shape, x.dtype) for sp, x in zip(
        [*in_specs, *out_specs], [*operands, *out_shape]))
    need += sum(size(shape, jnp.float32) for shape in scratch)
    return pl.pallas_call(
        functools.partial(kernel, **static), out_shape=out_shape, grid=grid,
        in_specs=in_specs, out_specs=out_specs, name=name,
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", last),
            vmem_limit_bytes=min(max(2 * need, 32 << 20), 100 << 20)),
        interpret=interpret)(*operands)


def _mx(interpret: bool):
    # this CPU's dot takes no pair of bfloat16 inside a loop
    return jnp.float32 if interpret else jnp.bfloat16


# ---------------------------------------------------------------------------
# in front of the scan


def _operands_kernel(before_ref, tile_ref, tb_ref, dt_ref, dtb_ref, a_ref,
                     v_ref, b_ref, c_ref, g_ref, win, dt_scr, *,
                     nx: int, nb: int, head_dim: int, alone: bool, mx):
    """One (row, tile, block of columns) program; the blocks of x first,
    then B's, then C's, in order (the grid's last axis is "arbitrary").
    Shapes in VMEM: the product's tile (Bs, W) and the halo before it
    (halo, W); taps and bias (taps + 1, W); the step before the softplus
    (Bs, H), ``dt_bias`` and a (1, H); out v, B, C (Bs, W) and g (Bs, H)."""
    i, j = pl.program_id(1), pl.program_id(2)
    block, width = tile_ref.shape

    @pl.when(j == 0)
    def _():
        dt = _softplus(dt_ref[...] + dtb_ref[...])
        dt_scr[...] = dt
        g_ref[...] = dt * a_ref[...]

    _fill(win, before_ref, tile_ref, alone or i == 0)

    def activated(at, rows):
        return jax.nn.silu(_conv(win, at, rows, tb_ref)[0])

    @pl.when(j < nx)
    def _():
        spread = _spread(dt_ref.shape[1], width, j * width, head_dim, mx)

        def body(at, rows, _):
            dt = _a_head(dt_scr[pl.ds(at, rows), :], spread)
            v_ref[pl.ds(at, rows), :] = (dt * activated(at, rows)).astype(
                v_ref.dtype)

        _over_rows(block, body)

    for ref, low in ((b_ref, nx), (c_ref, nx + nb)):
        @pl.when((j >= low) & (j < low + nb))
        def _(ref=ref):
            def body(at, rows, _):
                ref[pl.ds(at, rows), :] = activated(at, rows).astype(
                    ref.dtype)

            _over_rows(block, body)


def _operands_bwd_kernel(
        before_ref, tile_ref, after_ref, tb_ref, dt_ref, dt_after_ref,
        dtb_ref, a_ref, dv_ref, dv_after_ref, dxs_ref, dxs_after_ref,
        db_ref, db_after_ref, dc_ref, dc_after_ref, dg_ref, dz_ref,
        dp_ref, ddt_ref, dtb_sum_ref, dh_sum_ref, win, dy_scr, dt_scr, *,
        nx: int, nb: int, head_dim: int, length: int, alone: bool, mx):
    """The transpose of ``_operands_kernel``, a program a (row, tile, block
    of ALL the product's columns): z's ``nx`` blocks first (their cotangent
    is handed through), then x's, B's and C's. ``*_after_ref``: the halo rows
    after the tile. Out: the product's cotangent (Bs, W); the step's (Bs,
    H), summed over x's blocks and finished at the last of them; the taps'
    and the bias's sums over the tile (taps + 1, W); ``dt_bias``'s and a's
    (2, H)."""
    i, j = pl.program_id(1), pl.program_id(2)
    block, width = tile_ref.shape
    taps = tb_ref.shape[0] - 1
    start = i * block
    nz, f32 = nx, jnp.float32

    @pl.when(j == 0)
    def _():
        dt_scr[0:block] = _softplus(dt_ref[...] + dtb_ref[...])
        if not alone:
            dt_scr[block:] = _softplus(dt_after_ref[...] + dtb_ref[...])
        ddt_ref[...] = jnp.zeros_like(ddt_ref)

    @pl.when(j < nz)
    def _():
        dp_ref[...] = dz_ref[...]

    def transposed(cotangent):
        """The block's share of the product's cotangent and of the taps'
        sums, given ``cotangent(after, at, rows, act)``: the activation's
        cotangent (rows, W) float32 for the rows from ``at`` of the tile or,
        with ``after``, of the halo after it, ``act`` the activation."""
        _fill(win, before_ref, tile_ref, alone or i == 0, after_ref, start,
              length)

        def slope(at, rows, after: bool):
            """(the convolution's output's cotangent for ``rows`` rows from
            the tile's row ``at``, the shifted rows)."""
            u, shifts = _conv(win, at, rows, tb_ref)
            s = jax.nn.sigmoid(u)
            dact = cotangent(after, 0 if after else at, rows, u * s)
            dy = jnp.where(_valid(start + at, rows, length),
                           dact * _silu_slope(u, s), 0.0)
            return dy, shifts

        def down(at, rows, sums):
            dy, shifts = slope(at, rows, False)
            dy_scr[pl.ds(at, rows), :] = dy
            return sums + jnp.concatenate(
                [jnp.sum(dy * x, axis=0, keepdims=True) for x in shifts]
                + [jnp.sum(dy, axis=0, keepdims=True)], axis=0)

        dtb_sum_ref[...] = _over_rows(
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

    @pl.when((j >= nz) & (j < nz + nx))
    def _():
        spread = _spread(dt_ref.shape[1], width, (j - nz) * width, head_dim,
                         mx)

        def cotangent(after, at, rows, act):
            dv = (dv_after_ref if after else dv_ref)[pl.ds(at, rows), :]
            dxs = (dxs_after_ref if after else dxs_ref)[pl.ds(at, rows), :]
            dv = dv.astype(f32)
            if not after:
                ddt_ref[pl.ds(at, rows), :] += _by_head(dv * act, spread)
            dt = dt_scr[pl.ds(block + at if after else at, rows), :]
            return dv * _a_head(dt, spread) + dxs

        transposed(cotangent)

    for ref, ref_after, low in ((db_ref, db_after_ref, nz + nx),
                                (dc_ref, dc_after_ref, nz + nx + nb)):
        @pl.when((j >= low) & (j < low + nb))
        def _(ref=ref, ref_after=ref_after):
            transposed(lambda after, at, rows, act: (
                ref_after if after else ref)[pl.ds(at, rows), :].astype(f32))

    @pl.when(j == nz + nx - 1)
    def _():
        inside = _valid(start, block, length)
        dt, dg = dt_scr[0:block], dg_ref[...]
        # g = dt a, dt = softplus(.): its slope is the sigmoid
        ddt = jnp.where(inside, (ddt_ref[...] + dg * a_ref[...])
                        * jax.nn.sigmoid(dt_ref[...] + dtb_ref[...]), 0.0)
        ddt_ref[...] = ddt
        dh_sum_ref[0:1, :] = jnp.sum(ddt, axis=0, keepdims=True)
        dh_sum_ref[1:2, :] = jnp.sum(jnp.where(inside, dg * dt, 0.0),
                                     axis=0, keepdims=True)


_jit = functools.partial(jax.jit, static_argnames=(
    "sz", "tile", "interpret"))


def _front(zxbc, sz: Sizes, tile):
    """(the plan, the width of a block of columns, the blocks of x (z's as
    many) and of B (C's as many))."""
    plan = _plan(zxbc.shape[1], tile)
    width = _columns(sz)
    return plan, width, sz.inner // width, sz.bc // width


@_jit
def _operands_forward(zxbc, dt, tb, dt_bias, a, sz, tile, interpret):
    plan, width, nx, nb = _front(zxbc, sz, tile)
    b, t, _ = zxbc.shape
    heads = sz.heads
    product = lambda j: nx + j  # noqa: E731  (z's blocks lie before x's)
    whole = pl.BlockSpec((None, plan.tile, heads), lambda b, i, j: (b, i, 0))
    head = pl.BlockSpec((1, heads), lambda b, i, j: (0, 0))
    out = lambda n, low: plan.rows(  # noqa: E731
        width, lambda j: _clamp(j, low, n))
    return _call(
        _operands_kernel, "ssd_operands", (b, plan.tiles, nx + 2 * nb),
        "arbitrary",
        [plan.before(width, product), plan.rows(width, product),
         pl.BlockSpec((tb.shape[0], width), lambda b, i, j: (0, j)),
         whole, head, head],
        [out(nx, 0), out(nb, nx), out(nb, nx + nb), whole],
        [jax.ShapeDtypeStruct((b, t, sz.inner), jnp.bfloat16),
         jax.ShapeDtypeStruct((b, t, sz.bc), jnp.bfloat16),
         jax.ShapeDtypeStruct((b, t, sz.bc), jnp.bfloat16),
         jax.ShapeDtypeStruct((b, t, heads), jnp.float32)],
        [(_HALO + plan.tile, width), (plan.tile, heads)],
        [zxbc, zxbc, tb, dt, dt_bias[None], a[None]], interpret,
        nx=nx, nb=nb, head_dim=sz.head_dim, alone=plan.alone,
        mx=_mx(interpret))


@_jit
def _operands_backward(zxbc, dt, tb, dt_bias, a, dv, dxs, db, dc, dg, dz,
                       sz, tile, interpret):
    """(the product's cotangent, the step's, the taps' and bias's sums
    (taps + 1, x B C's width), ``dt_bias``'s and a's (2, H))."""
    plan, width, nx, nb = _front(zxbc, sz, tile)
    nz = nx                     # z's blocks, as many as x's and before them
    b, t, _ = zxbc.shape
    heads, rows = sz.heads, tb.shape[0]
    product = lambda j: jnp.maximum(j, nz)  # noqa: E731
    x_block = lambda j: _clamp(j, nz, nx)  # noqa: E731
    b_block = lambda j: _clamp(j, nz + nx, nb)  # noqa: E731
    c_block = lambda j: _clamp(j, nz + nx + nb, nb)  # noqa: E731
    whole = lambda j: 0  # noqa: E731
    both = lambda w, column: [plan.rows(w, column),  # noqa: E731
                              plan.after(w, column)]
    head = pl.BlockSpec((1, heads), lambda b, i, j: (0, 0))
    n = nz + nx + 2 * nb
    dp, ddt, dtb, dh = _call(
        _operands_bwd_kernel, "ssd_operands_bwd", (b, plan.tiles, n),
        "arbitrary",
        [plan.before(width, product), *both(width, product),
         pl.BlockSpec((rows, width), lambda b, i, j: (0, _clamp(j, nz, n))),
         *both(heads, whole), head, head,
         *both(width, x_block), *both(width, x_block),
         *both(width, b_block), *both(width, c_block),
         plan.rows(heads, whole),
         plan.rows(width, lambda j: _clamp(j, 0, nz))],
        [plan.rows(width, lambda j: j), plan.rows(heads, whole),
         plan.sums(rows, width, lambda j: _clamp(j, nz, n)),
         plan.sums(2, heads, whole)],
        [jax.ShapeDtypeStruct(zxbc.shape, zxbc.dtype),
         jax.ShapeDtypeStruct((b, t, heads), jnp.float32),
         jax.ShapeDtypeStruct((b, plan.tiles, rows, tb.shape[1]),
                              jnp.float32),
         jax.ShapeDtypeStruct((b, plan.tiles, 2, heads), jnp.float32)],
        [(2 * _HALO + plan.tile, width), (plan.tile + _HALO, width),
         (plan.tile + _HALO, heads)],
        [zxbc, zxbc, zxbc, tb, dt, dt, dt_bias[None], a[None], dv, dv,
         dxs, dxs, db, db, dc, dc, dg, dz], interpret,
        nx=nx, nb=nb, head_dim=sz.head_dim, length=t,
        alone=plan.alone, mx=_mx(interpret))
    return dp, ddt, dtb.sum(axis=(0, 1)), dh.sum(axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def ssd_operands(zxbc, dt, taps, bias, dt_bias, a_log, sz: Sizes,
                 tile=None, interpret: bool = False):
    """The scan's operands from a mixer's products: ``zxbc`` (B, T, 2
    d_inner + 2 groups state), the input projection's ``[z | x B C]``,
    bfloat16; ``dt`` (B, T, H) float32, the step's columns before the bias
    and the softplus; ``taps`` (conv, d_inner + 2 groups state) and
    ``bias``, the convolution's; ``dt_bias`` and ``a_log`` (H,). Returns
    ``(C, B, dt x, dt a, z, x)``: C and B (B, T, groups, state) and ``dt
    x`` (B, T, H, head_dim) bfloat16, ``dt a`` (B, T, H) float32, which
    ``scalar_decay_rule`` takes as q, k, v and g, and the two handles that
    :func:`ssd_gated_norm` takes (the module's docstring). ``tile``
    (tests): the rows a program."""
    return _operands_fwd(zxbc, dt, taps, bias, dt_bias, a_log, sz, tile,
                         interpret)[0]


def _operands_fwd(zxbc, dt, taps, bias, dt_bias, a_log, sz, tile, interpret):
    b, t, _ = zxbc.shape
    a = -jnp.exp(a_log.astype(jnp.float32))
    v, bm, cm, g = _operands_forward(
        zxbc, dt, _taps_and_bias(taps, bias), dt_bias.astype(jnp.float32),
        a, sz, tile, interpret)
    grouped = lambda y: y.reshape(b, t, sz.groups, sz.state)  # noqa: E731
    out = (grouped(cm), grouped(bm), v.reshape(b, t, sz.heads, sz.head_dim),
           g, jnp.zeros((b, t, sz.inner), zxbc.dtype),
           jnp.zeros((b, t, sz.inner), jnp.float32))
    return out, (zxbc, dt, taps, bias, dt_bias, a_log)


@trace.scope("ssd")  # a backward rule has no forward name stack
def _operands_bwd(sz, tile, interpret, residuals, cotangents):
    zxbc, dt, taps, bias, dt_bias, a_log = residuals
    dc, db, dv, dg, dz, dxs = cotangents
    b, t, _ = zxbc.shape
    a = -jnp.exp(a_log.astype(jnp.float32))
    flat = lambda y: y.reshape(b, t, -1)  # noqa: E731
    dp, ddt, dtb, dh = _operands_backward(
        zxbc, dt, _taps_and_bias(taps, bias), dt_bias.astype(jnp.float32),
        a, flat(dv), dxs, flat(db), flat(dc), dg.astype(jnp.float32), dz,
        sz, tile, interpret)
    # a = -exp(A_log): its slope is a itself
    return (dp, ddt.astype(dt.dtype), dtb[:-1].astype(taps.dtype),
            dtb[-1].astype(bias.dtype), dh[0].astype(dt_bias.dtype),
            (dh[1] * a).astype(a_log.dtype))


ssd_operands.defvjp(_operands_fwd, _operands_bwd)


# ---------------------------------------------------------------------------
# behind the scan


def _gated(win, at, rows, tb_ref, dw_ref, y_ref, z_ref, eps):
    """A group's rows from ``at``: (x made again, y + D x, sigmoid(z),
    silu(z), the gated rows over their root mean square, its inverse),
    float32, in the mixer's order."""
    x = jax.nn.silu(_conv(win, at, rows, tb_ref)[0])
    y = y_ref[pl.ds(at, rows), :].astype(jnp.float32) + dw_ref[0:1, :] * x
    z = z_ref[pl.ds(at, rows), :].astype(jnp.float32)
    sg = jax.nn.sigmoid(z)
    gated = y * (z * sg)
    r = jax.lax.rsqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
                      + eps)
    return x, y, z, sg, gated * r, r


def _gated_norm_kernel(y_ref, z_ref, before_ref, tile_ref, tb_ref, dw_ref,
                       o_ref, win, *, eps: float, alone: bool):
    """One (row, tile, group of the norm) program. Shapes in VMEM: y, z,
    x's columns of the product and out (Bs, W), W a group's channels; the
    halo before the tile (halo, W); taps and bias (taps + 1, W); ``D`` a
    channel and the norm's weight (2, W)."""
    block = tile_ref.shape[0]
    _fill(win, before_ref, tile_ref, alone or pl.program_id(1) == 0)

    def body(at, rows, _):
        unit = _gated(win, at, rows, tb_ref, dw_ref, y_ref, z_ref,
                      eps)[4]
        o_ref[pl.ds(at, rows), :] = (unit * dw_ref[1:2, :]).astype(
            o_ref.dtype)

    _over_rows(block, body)


def _gated_norm_bwd_kernel(dn_ref, y_ref, z_ref, before_ref, tile_ref,
                           tb_ref, dw_ref, dy_ref, dz_ref, dxs_ref, sum_ref,
                           win, *, eps: float, length: int, alone: bool):
    """The transpose of ``_gated_norm_kernel``, a program likewise. Out: y's
    and z's cotangents (Bs, W) as y and z, the skip's share of x's (Bs, W)
    float32, and the tile's sums (2, W): ``D``'s gradient a channel and the
    weight's."""
    block = tile_ref.shape[0]
    start = pl.program_id(1) * block
    _fill(win, before_ref, tile_ref, alone or pl.program_id(1) == 0)

    def body(at, rows, sums):
        x, y, z, sg, unit, r = _gated(win, at, rows, tb_ref, dw_ref,
                                      y_ref, z_ref, eps)
        inside = _valid(start + at, rows, length)
        dn = jnp.where(inside, dn_ref[pl.ds(at, rows), :].astype(
            jnp.float32), 0.0)
        # out = unit w, unit = gated r, r = (mean(gated^2) + eps)^-1/2:
        # dgated = r (u - unit mean(u unit)) with u = dout w
        u = dn * dw_ref[1:2, :]
        dgated = r * (u - unit * jnp.mean(u * unit, axis=-1, keepdims=True))
        dy = dgated * (z * sg)
        dy_ref[pl.ds(at, rows), :] = dy.astype(dy_ref.dtype)
        dz_ref[pl.ds(at, rows), :] = (
            dgated * y * _silu_slope(z, sg)).astype(dz_ref.dtype)
        dxs_ref[pl.ds(at, rows), :] = dw_ref[0:1, :] * dy
        return sums + jnp.concatenate(
            [jnp.sum(jnp.where(inside, term, 0.0), axis=0, keepdims=True)
             for term in (dy * x, dn * unit)], axis=0)

    sum_ref[...] = _over_rows(
        block, body, jnp.zeros((2, tile_ref.shape[1]), jnp.float32))


def _back(zxbc, tb, sz: Sizes, tile):
    """(the plan, a group's rows' spec, the specs of y's, z's and the
    product's x blocks with the halo, of the taps and of ``D`` and the
    weight)."""
    plan = _plan(zxbc.shape[1], tile)
    width, groups = sz.inner // sz.groups, sz.groups
    rows = plan.rows(width, lambda j: j)
    x_block = lambda j: groups + j  # noqa: E731
    return plan, rows, [
        rows, rows, plan.before(width, x_block), plan.rows(width, x_block),
        pl.BlockSpec((tb.shape[0], width), lambda b, i, j: (0, j)),
        pl.BlockSpec((2, width), lambda b, i, j: (0, j))]


def _skip_and_weight(d, weight, sz: Sizes):
    return jnp.stack([jnp.repeat(d.astype(jnp.float32), sz.head_dim),
                      weight.astype(jnp.float32)])


_jit_eps = functools.partial(jax.jit, static_argnames=(
    "sz", "eps", "tile", "interpret"))


@_jit_eps
def _gated_norm_forward(y, zxbc, tb, dw, sz, eps, tile, interpret):
    plan, rows, specs = _back(zxbc, tb, sz, tile)
    return _call(
        _gated_norm_kernel, "ssd_gated_norm",
        (zxbc.shape[0], plan.tiles, sz.groups), "parallel", specs,
        [rows], [jax.ShapeDtypeStruct(y.shape, jnp.bfloat16)],
        [(_HALO + plan.tile, rows.block_shape[-1])],
        [y, zxbc, zxbc, zxbc, tb, dw], interpret, eps=eps,
        alone=plan.alone)[0]


@_jit_eps
def _gated_norm_backward(dn, y, zxbc, tb, dw, sz, eps, tile, interpret):
    """(y's cotangent, z's, the skip's share of x's, the sums (2, d_inner):
    ``D``'s a channel and the weight's)."""
    plan, rows, specs = _back(zxbc, tb, sz, tile)
    b, width = zxbc.shape[0], rows.block_shape[-1]
    dy, dz, dxs, sums = _call(
        _gated_norm_bwd_kernel, "ssd_gated_norm_bwd",
        (b, plan.tiles, sz.groups), "parallel", [rows, *specs],
        [rows, rows, rows, plan.sums(2, width, lambda j: j)],
        [jax.ShapeDtypeStruct(y.shape, y.dtype),
         jax.ShapeDtypeStruct(y.shape, zxbc.dtype),
         jax.ShapeDtypeStruct(y.shape, jnp.float32),
         jax.ShapeDtypeStruct((b, plan.tiles, 2, sz.inner), jnp.float32)],
        [(_HALO + plan.tile, width)],
        [dn, y, zxbc, zxbc, zxbc, tb, dw], interpret, eps=eps,
        length=zxbc.shape[1], alone=plan.alone)
    return dy, dz, dxs, sums.sum(axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def ssd_gated_norm(y, z, x, zxbc, taps, bias, d, weight, sz: Sizes,
                   eps: float, tile=None, interpret: bool = False):
    """The output projection's operand (B, T, d_inner) bfloat16 from the
    scan's output ``y`` (B, T, H, head_dim): ``rmsnorm((y + d x) silu(z);
    weight)``, the mean square over each of ``sz.groups`` groups of
    channels, the gate BEFORE the norm. z and x are read from ``zxbc``, the
    product :func:`ssd_operands` took (x made again from it with ``taps``
    and ``bias``); the arguments ``z`` and ``x`` are that call's handles,
    through which their cotangents return to it: ``zxbc``, ``taps`` and
    ``bias`` are not differentiated here."""
    return _gated_norm_fwd(y, z, x, zxbc, taps, bias, d, weight, sz, eps,
                           tile, interpret)[0]


def _gated_norm_fwd(y, z, x, zxbc, taps, bias, d, weight, sz, eps, tile,
                    interpret):
    out = _gated_norm_forward(
        y.reshape(z.shape), zxbc, _taps_and_bias(taps, bias)[:, :sz.inner],
        _skip_and_weight(d, weight, sz), sz, eps, tile, interpret)
    return out, (y, zxbc, taps, bias, d, weight)


@trace.scope("ssd")  # a backward rule has no forward name stack
def _gated_norm_bwd(sz, eps, tile, interpret, residuals, dn):
    y, zxbc, taps, bias, d, weight = residuals
    dy, dz, dxs, sums = _gated_norm_backward(
        dn, y.reshape(dn.shape), zxbc,
        _taps_and_bias(taps, bias)[:, :sz.inner],
        _skip_and_weight(d, weight, sz), sz, eps, tile, interpret)
    dd = sums[0].reshape(sz.heads, sz.head_dim).sum(axis=1)
    return (dy.reshape(y.shape), dz, dxs, None, None, None,
            dd.astype(d.dtype), sums[1].astype(weight.dtype))


ssd_gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)
