"""A grouped layer's hand-over of q and k to the attention kernels.

models/lm_layers.GroupedAttention makes q and k as its projections leave
them, bfloat16, and the kernels of ops/attention.py read bfloat16 operands.
Between the two lie an RMS norm over a head's width, the rotary turn, for q
the score scale, and one rounding: float32 arithmetic that XLA runs as
passes of their own through HBM, each over ``(B, S, H, D)`` at four bytes a
number, forward, again under remat and, transposed, backward. Here they are
ONE Pallas call a direction:

- :func:`operand` (a ``jax.custom_vjp``): forward the call ``grouped_qk``,
  a program a (row, tile of the sequence, head): the head's ``(D, tile)``
  of the product in, float32 in registers only, the kernels' operand out.
  Backward the call ``grouped_qk_bwd``: the operand's cotangent and the
  product in, the product's cotangent out and the norm scale's gradient
  summed over the heads in VMEM; the rotary's transpose is the same turn
  with the angle negated. Its one residual is the product itself, which
  is what a rematerialised block keeps (models/lm_remat.py), so under
  remat the forward call is what is made again.
- the calls read and write FEATURE-MAJOR ``(B, H * D, S)``, the kernels'
  own layout (ops/attention.py), so a head's channels lie on the sublanes:
  the norm's sum runs down them, elementwise between registers, the
  rotary pairs (j, j + turned / 2) are aligned row slices, the channels
  past ``turned`` pass beside them in the same tile, and no value is
  transposed in VMEM. The ``(B, S, H, D)`` the callers speak is that
  array with two axes swapped, a bitcast where XLA lays the product out
  for the kernels, as it did for the passes.
- :func:`tables`: cos and sin ``(turned / 2, S)`` float32, the rule's
  ``factor`` on both; made from positions alone, so the layers of one rule
  share one pair a step.

:func:`hand_over` is the one rule for whether a layer takes this form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from metaopt_tpu.ops.attention import (_call, _feature_major, _heads_last,
                                       _tile)
from metaopt_tpu.utils import trace

#: positions of a tile the arithmetic covers at a time: a head's (128, 256)
#: float32 and what is made of it stay in registers (128 at a time reads
#: 0.97 ms for 0.70 backward at 64 heads x 8192, 512 the same: PERF.md
#: section 6, PR 47)
_LANES = 256
#: a program's tile of the sequence, the largest that divides the length:
#: a megabyte in and a megabyte out a program at 4096 x 128 (4-6 % under
#: tiles of 2048, 20 % under 1024)
_TILES = (4096, 2048, 1024, 512, 256, 128)


def hand_over(route: str, mesh, qk_norm, positions: bool) -> str:
    """How a grouped layer's q and k reach attention on ``route``:
    ``"one pass"`` (this module) on the Pallas route of one device where
    there is float32 work between the product and the operand that a head
    can do alone: a norm over a head's width (``qk_norm`` ``"head"``) or,
    without a norm, rotary ``positions``; else ``"passes"``, XLA's: a norm
    over the heads' whole width, the routes that shard or do not run the
    kernels, and a layer with neither norm nor positions, whose q is the
    product times the scale."""
    one_device = mesh is None or mesh.size == 1
    alone = qk_norm == "head" or (qk_norm is None and positions)
    return "one pass" if route == "pallas" and one_device and alone \
        else "passes"


def tables(frequencies, factor: float, s: int):
    """(cos, sin), each (len(frequencies), S) float32: pair j of the turned
    channels at position p turns by p * ``frequencies``[j]; both times
    ``factor`` where it is not 1. The numbers ``lm_layers.rope`` makes, with
    the sequence last."""
    angle = frequencies[:, None] * jnp.arange(s, dtype=jnp.float32)[None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    return cos, sin


def _turned(x, cos_ref, sin_ref, at, back: bool):
    """x (D, lanes) float32 with its first 2 * half rows turned by the
    tables' angles at lanes ``at``, the angle negated with ``back``."""
    half = cos_ref.shape[0]
    a, b = x[:half], x[half:2 * half]
    cos, sin = cos_ref[:, at], sin_ref[:, at]
    if back:
        sin = -sin
    rest = [x[2 * half:]] if 2 * half < x.shape[0] else []
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, *rest], axis=0)


def _over_lanes(block: int, body, init=None):
    """``body(at, carry)`` over a tile ``block`` long, ``_LANES`` positions
    at a time where they divide it."""
    if block % _LANES or block == _LANES:
        return body(slice(None), init)
    return jax.lax.fori_loop(
        0, block // _LANES, lambda i, c: body(_tile(i, _LANES), c), init)


def _unit(x, eps: float):
    """(x / rms(x), 1 / rms(x)) down the rows of x (D, lanes) float32."""
    r = jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=0, keepdims=True) + eps)
    return x * r, r


def _fwd_kernel(*refs, normed: bool, turns: bool, eps: float,
                multiplier: float):
    """One (row, tile, head) program. Shapes in VMEM: x, o (1, D, Bs); the
    norm's scale (D, 1) float32 if ``normed``; cos, sin (turned / 2, Bs)
    float32 if ``turns``. The arithmetic in ``RMSNorm``'s, ``rope``'s and
    the layer's order."""
    refs = list(refs)
    x_ref, o_ref = refs.pop(0), refs.pop()
    w_ref = refs.pop(0) if normed else None

    def body(at, _):
        x = x_ref[0, :, at].astype(jnp.float32)
        if normed:
            x = _unit(x, eps)[0] * w_ref[...]
        if turns:
            x = _turned(x, *refs, at, False)
        if multiplier != 1.0:
            x = x * multiplier
        o_ref[0, :, at] = x.astype(o_ref.dtype)

    _over_lanes(x_ref.shape[2], body)


def _bwd_kernel(*refs, normed: bool, turns: bool, eps: float,
                multiplier: float):
    """The transpose of ``_fwd_kernel``, a program likewise. g, dx (1, D,
    Bs); with ``normed`` also x (1, D, Bs), the scale (D, 1) and dw (1, 1,
    D, 1) float32, the scale's gradient of this (row, tile), summed over
    the heads, which run in order (the grid's last axis is "arbitrary")."""
    refs = list(refs)
    g_ref = refs.pop(0)
    x_ref, w_ref, dw_ref = (refs.pop(0), refs.pop(0), refs.pop()) \
        if normed else (None, None, None)
    dx_ref = refs.pop()

    def body(at, dw):
        g = g_ref[0, :, at].astype(jnp.float32)
        if multiplier != 1.0:
            g = g * multiplier
        if turns:
            g = _turned(g, *refs, at, True)
        if normed:
            # y = x r w, r = (mean(x^2) + eps)^-1/2:
            # dx = r (u - xh mean(u xh)) with u = dy w, xh = x r
            xh, r = _unit(x_ref[0, :, at].astype(jnp.float32), eps)
            dw = dw + jnp.sum(g * xh, axis=1, keepdims=True)
            u = g * w_ref[...]
            g = r * (u - xh * jnp.mean(u * xh, axis=0, keepdims=True))
        dx_ref[0, :, at] = g.astype(dx_ref.dtype)
        return dw

    dw = _over_lanes(g_ref.shape[2], body,
                     jnp.zeros((g_ref.shape[1], 1), jnp.float32))
    if normed:
        @pl.when(pl.program_id(2) == 0)
        def _():
            dw_ref[...] = jnp.zeros_like(dw_ref)

        dw_ref[0, 0] += dw


_jit = functools.partial(jax.jit, static_argnames=(
    "heads", "eps", "multiplier", "interpret"))


def _specs(xt, w, cos, heads: int):
    """(the grid, a head's block, the specs of the scale and of the
    tables' pair) for xt (B, H * D, S)."""
    b, rows, s = xt.shape
    d = rows // heads
    block = next((t for t in _TILES if s % t == 0), s)
    head = pl.BlockSpec((1, d, block), lambda i, j, hh: (i, hh, j))
    scale = [] if w is None else [
        pl.BlockSpec((d, 1), lambda i, j, hh: (0, 0))]
    pair = [] if cos is None else 2 * [
        pl.BlockSpec((cos.shape[0], block), lambda i, j, hh: (0, j))]
    return (b, s // block, heads), head, scale, pair


def _column(w):
    return [] if w is None else [w.astype(jnp.float32)[:, None]]


@_jit
def _forward(xt, w, cos, sin, heads, eps, multiplier, interpret):
    grid, head, scale, pair = _specs(xt, w, cos, heads)
    return _call(
        _fwd_kernel, "grouped_qk", grid, ("parallel",) * 3,
        [head, *scale, *pair], [head],
        [jax.ShapeDtypeStruct(xt.shape, jnp.bfloat16)], [],
        [xt, *_column(w), *(() if cos is None else (cos, sin))], interpret,
        normed=w is not None, turns=cos is not None, eps=eps,
        multiplier=multiplier)[0]


@_jit
def _backward(xt, w, cos, sin, gt, heads, eps, multiplier, interpret):
    """(dxt as gt, dw (D,) float32); without a norm xt and w are None and
    so is dw."""
    grid, head, scale, pair = _specs(gt, w, cos, heads)
    d = gt.shape[1] // heads
    normed = w is not None
    read, sums, summed = [], [], []
    if normed:
        read = [head]
        sums = [pl.BlockSpec((1, 1, d, 1), lambda i, j, hh: (i, j, 0, 0))]
        summed = [jax.ShapeDtypeStruct((*grid[:2], d, 1), jnp.float32)]
    dxt, *dw = _call(
        _bwd_kernel, "grouped_qk_bwd", grid,
        ("parallel", "parallel", "arbitrary" if normed else "parallel"),
        [head, *read, *scale, *pair], [head, *sums],
        [jax.ShapeDtypeStruct(gt.shape, gt.dtype), *summed], [],
        [gt, *([xt] if normed else []), *_column(w),
         *(() if cos is None else (cos, sin))], interpret,
        normed=normed, turns=cos is not None, eps=eps,
        multiplier=multiplier)
    return dxt, dw[0].sum(axis=(0, 1, 3)) if normed else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def operand(x, scale, cos, sin, eps: float, multiplier: float,
            interpret: bool = False):
    """x (B, S, H, D), a projection's product -> the kernels' q or k,
    bfloat16, shaped alike: an RMS norm over D with ``scale`` (D,) and
    ``eps`` (``scale`` None: no norm), then the first ``2 * len(cos)``
    channels of every head turned by :func:`tables`' ``cos`` and ``sin``
    (None: no positions), the pairs the halves of the turned channels,
    then times ``multiplier`` (q's score scale; 1 for k), float32
    throughout and rounded once."""
    return _operand_fwd(x, scale, cos, sin, eps, multiplier, interpret)[0]


def _operand_fwd(x, scale, cos, sin, eps, multiplier, interpret):
    heads = x.shape[2]
    out = _forward(_feature_major(x), scale, cos, sin, heads, eps,
                   multiplier, interpret)
    return _heads_last(out, heads), (x, scale, cos, sin)


@trace.scope("attention")  # a backward rule has no forward name stack
def _operand_bwd(eps, multiplier, interpret, residuals, g):
    x, scale, cos, sin = residuals
    heads = x.shape[2]
    dxt, dw = _backward(None if scale is None else _feature_major(x), scale,
                        cos, sin, _feature_major(g), heads, eps, multiplier,
                        interpret)
    return (_heads_last(dxt, heads).astype(x.dtype),
            None if scale is None else dw.astype(scale.dtype), None, None)


operand.defvjp(_operand_fwd, _operand_bwd)
