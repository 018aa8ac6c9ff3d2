"""Pallas kernels for a mask stated by a selection (attention.SelectedMask).

The causal kernels of ops/attention.py grown by one operand: the same
feature-major operands, transposed score tile, program per (batch row,
query head, tile) and grouped K/V heads through the block index; the walk
over the resident sequence stops at the causal limit, as theirs does. What
differs: a key set of its own for every query row cannot be skipped by
position, so every tile up to the causal limit is walked and masked, by
bits and not by iotas. A program's slab of the packed selection
(``SelectedMask.bits``) is (S / 32, Bq) words in the forward kernel and
(Bk / 32, S) in the backward one, 1 MiB each at S 16 384; a tile's (Bk / 32,
Bq) words become its (Bk, Bq) mask by repeating them down the sublanes and
shifting row ``r`` by ``r // (Bk / 32)``: the packing is made for that.
The mask is the same for all heads and is unpacked once a head all the
same (a program is one head): what a masked-dense walk costs over the
selected pairs is what ``sparse_fwd_roofline`` / ``sparse_bwd_roofline``
say. Tiles none of whose keys a row selected are still walked: skipping
them is for a selection that concentrates (ROADMAP R1).

Loaded by ``attention.flash_attention`` when a ``SelectedMask`` arrives,
and by nothing else: a model without such layers never imports it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from metaopt_tpu.ops.attention import (_NEG_BIG, _NT, SelectedMask, _call,
                                       _cdiv, _dot, _feature_major,
                                       _heads_last, _kept, _narrowest, _tile)
from metaopt_tpu.utils import trace


def selected_block(size: int) -> tuple:
    """(block, padded_size) of a sequence under a ``SelectedMask``: a tile
    holds a whole sublane tile of words (8 x 32 keys) at the least, so 512
    where it divides the length and 256 otherwise."""
    block = 512 if size % 512 == 0 else 256
    return block, -(-size // block) * block


def _row_shift(block: int, bq: int):
    """Which bit of its word each row of a (block, Bq) tile reads."""
    per_tile = block // 32
    return jax.lax.broadcasted_iota(jnp.int32, (block, bq), 0) // per_tile


def _unpack(words, shift):
    """(Bk / 32, Bq) words -> the (Bk, Bq) tile's selected pairs."""
    return ((pltpu.repeat(words, 32, axis=0) >> shift) & 1) != 0


def _selected_fwd_kernel(q_ref, k_ref, v_ref, bits_ref, o_ref, lse_ref, *,
                         block_k: int):
    """One (batch row, query head, q tile) program.

    Shapes in VMEM: q, o (1, D, Bq); k, v (1, D, S), the head's K/V head;
    bits (1, S / 32, Bq) int32; lse (1, 1, 1, Bq) float32.
    """
    d, bq = q_ref.shape[1], q_ref.shape[2]
    per_tile = block_k // 32
    q0 = pl.program_id(2) * bq
    shift = _row_shift(block_k, bq)
    q = q_ref[0]

    def fold(i, carry):
        m, l, acc = carry
        ks = _tile(i, block_k)
        st = _dot(k_ref[0, :, ks].T, q)                     # (Bk, Bq)
        st = jnp.where(_unpack(bits_ref[0, _tile(i, per_tile), :], shift),
                       st, _NEG_BIG)
        m_new = jnp.maximum(
            jnp.maximum(m, jnp.max(st, axis=0, keepdims=True)),
            0.5 * _NEG_BIG)
        alpha = jnp.exp(m - m_new)
        pt = jnp.exp(st - m_new)
        l_new = alpha * l + jnp.sum(pt, axis=0, keepdims=True)
        vt = v_ref[0, :, ks]                                # (D, Bk)
        return m_new, l_new, alpha * acc + _dot(vt, pt.astype(vt.dtype))

    hi = jnp.minimum(k_ref.shape[2] // block_k, _cdiv(q0 + bq, block_k))
    m, l, acc = jax.lax.fori_loop(
        0, hi, fold, (jnp.full((1, bq), -jnp.inf, jnp.float32),
                      jnp.zeros((1, bq), jnp.float32),
                      jnp.zeros((d, bq), jnp.float32)))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)),
                              jnp.inf)


def _selected_bwd_kernel(q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref,
                         bits_ref, dq_ref, dk_ref, dv_ref, acc_ref, *,
                         block_q: int):
    """One (batch row, query head, k tile) program: the head's share of
    the tile's dK and dV and the tile's share of the head's dQ, as
    ``attention._causal_bwd_kernel`` has them.

    Shapes in VMEM: k, v (1, D, Bk); dk, dv (1, D, Bk) float32; q, dO, dq
    (1, D, S); lse, delta (1, 1, 1, S); bits (1, Bk / 32, S) int32; dq
    scratch (D, S) float32.
    """
    d, bk = k_ref.shape[1], k_ref.shape[2]
    k0 = pl.program_id(2) * bk
    shift = _row_shift(bk, block_q)

    @pl.when(pl.program_id(2) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kt = k_ref[0]                                           # (D, Bk)
    kb = kt.T
    vb = v_ref[0].T

    def body(i, carry):
        dk, dv = carry
        qs = _tile(i, block_q)
        qt = q_ref[0, :, qs]                                # (D, Bq)
        gt = g_ref[0, :, qs]
        st = jnp.where(_unpack(bits_ref[0, :, qs], shift), _dot(kb, qt),
                       _NEG_BIG)                            # (Bk, Bq)
        pt = jnp.exp(st - lse_ref[0, 0, :, qs])
        dst = (pt * (_dot(vb, gt) - delta_ref[0, 0, :, qs])).astype(qt.dtype)
        acc_ref[:, qs] += _dot(kt, dst)                     # dQ.T (D, Bq)
        return (dk + _dot(qt, dst, _NT),                    # dK.T (D, Bk)
                dv + _dot(gt, pt.astype(gt.dtype), _NT))

    dk, dv = jax.lax.fori_loop(
        jax.lax.div(k0, block_q), q_ref.shape[2] // block_q, body,
        (jnp.zeros((d, bk), jnp.float32), jnp.zeros((d, bk), jnp.float32)))
    dk_ref[0] = dk
    dv_ref[0] = dv

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


_jit = functools.partial(jax.jit, static_argnames=("block", "interpret"))


@_jit
def _forward(q, k, v, bits, block, interpret):
    """(out, lse). q (B, S, H, D); k, v (B, S, Hkv, D); bits (B, S / 32, S);
    S a multiple of ``block``."""
    b, s, h, d = q.shape
    group = h // k.shape[2]
    q_spec = pl.BlockSpec((1, d, block), lambda i, hh, j: (i, hh, j))
    kv_spec = pl.BlockSpec((1, d, s), lambda i, hh, j: (i, hh // group, 0))
    out, lse = _call(
        _selected_fwd_kernel, "sparse_fwd", (b, h, s // block),
        ("parallel", "parallel", "parallel"),
        [q_spec, kv_spec, kv_spec,
         pl.BlockSpec((1, s // 32, block), lambda i, hh, j: (i, 0, j))],
        [q_spec, pl.BlockSpec((1, 1, 1, block),
                              lambda i, hh, j: (i, hh, 0, j))],
        [jax.ShapeDtypeStruct((b, h * d, s), q.dtype),
         jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32)],
        [], [_feature_major(q), _feature_major(k), _feature_major(v), bits],
        interpret, block_k=block)
    return _heads_last(out, h), lse


@_jit
def _backward(q, k, v, bits, out, lse, g, block, interpret):
    """(dq, dk, dv). Shapes as ``_forward``."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)[:, :, None]  # (B, H, 1, S)
    whole = pl.BlockSpec((1, d, s), lambda i, hh, j: (i, hh, 0))
    kv_spec = pl.BlockSpec((1, d, block),
                           lambda i, hh, j: (i, hh // group, j))
    stat = pl.BlockSpec((1, 1, 1, s), lambda i, hh, j: (i, hh, 0, 0))
    tile = pl.BlockSpec((1, d, block), lambda i, hh, j: (i, hh, j))
    dq, dk, dv = _call(
        _selected_bwd_kernel, "sparse_bwd", (b, h, s // block),
        ("parallel", "parallel", "arbitrary"),
        [whole, whole, kv_spec, kv_spec, stat, stat,
         pl.BlockSpec((1, block // 32, s), lambda i, hh, j: (i, j, 0))],
        [whole, tile, tile],
        [jax.ShapeDtypeStruct((b, h * d, s), q.dtype),
         jax.ShapeDtypeStruct((b, h * d, s), jnp.float32),
         jax.ShapeDtypeStruct((b, h * d, s), jnp.float32)],
        [(d, s)],
        [_feature_major(q), _feature_major(g), _feature_major(k),
         _feature_major(v), lse, delta, bits],
        interpret, block_q=block)

    def group_sum(x, like):
        """A K/V head's gradient: the sum over the query heads reading it."""
        x = x.reshape(b, hkv, group, d, s).sum(axis=2).astype(like.dtype)
        return _heads_last(x.reshape(b, hkv * d, s), hkv)

    return _heads_last(dq, h), group_sum(dk, k), group_sum(dv, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_selected(q, k, v, bits, block, interpret):
    return _flash_selected_fwd(q, k, v, bits, block, interpret)[0]


@trace.scope("attention.core")
def _flash_selected_fwd(q, k, v, bits, block, interpret):
    out, lse = _kept(*_forward(q, k, v, bits, block, interpret))
    return out, (q, k, v, bits, out, lse)


@trace.scope("attention.core")
def _flash_selected_bwd(block, interpret, residuals, g):
    q, k, v, bits, out, lse = residuals
    return (*_backward(q, k, v, bits, out, lse, g, block, interpret), None)


_flash_selected.defvjp(_flash_selected_fwd, _flash_selected_bwd)


def flash_selected(q, k, v, mask: SelectedMask, interpret: bool = False):
    """``attention.flash_attention`` under a ``SelectedMask``: self
    attention, q pre-scaled, the length padded to the mask's own (a padded
    key or query has no bit set)."""
    b, s, h, d = q.shape
    s_p = mask.bits.shape[2]
    if k.shape[1] != s or s_p % mask.block or not 0 <= s_p - s < mask.block:
        raise ValueError(f"a selection packed for {s_p} positions in tiles "
                         f"of {mask.block} does not fit {s} queries and "
                         f"{k.shape[1]} keys")
    narrow = _narrowest(q, k, v)
    pad = lambda x: jnp.pad(  # noqa: E731
        x.astype(narrow), ((0, 0), (0, s_p - s), (0, 0), (0, 0)))
    out = _flash_selected(pad(q), pad(k), pad(v), mask.bits, mask.block,
                          interpret)
    return out[:, :s].astype(q.dtype)
