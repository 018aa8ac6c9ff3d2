"""Blocked softmax cross-entropy against a (tied) readout table.

The flagship Transformer's loss was the MFU ceiling at short sequence: the
readout einsum materializes an f32 ``(B, T, V)`` logits tensor (2.1 GB at
batch 64 × seq 256 × vocab 32k) and ``optax.softmax_cross_entropy...``
makes several more full passes over it — all HBM traffic, no MXU work.
(ref: the lineage has no equivalent; SURVEY.md §6 MFU north star.)

This op never materializes the logits. Forward is a ``lax.scan`` over
vocab blocks: each block's logits tile ``y @ embᵀ[block]`` feeds an online
logsumexp (the flash-attention trick applied to the softmax denominator)
and the label logit is gathered blockwise; live memory is O(B·T·block_v).
Backward recomputes each block's probabilities from the saved (lse,
label_logit) and accumulates dY and dEmb per block — two more MXU matmuls
per block instead of a (B, T, V) round-trip through HBM.

FLOP cost: 2·N·D·V forward + 6·N·D·V backward (one logits recompute, dY,
dEmb) vs 2+4 for the materializing path — 33% more readout FLOPs traded
for never touching a (N, V) f32 tensor in HBM. On bandwidth-bound shapes
that trade wins by construction; where it starts to pay is
``models/transformer.blocked_xent_enabled``'s question.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from metaopt_tpu.utils import trace


def pick_block_v(vocab: int, target: int = 4096) -> int:
    """Largest divisor of ``vocab`` ≤ target (the scan's tile width).

    Falls back to the whole vocab when no useful divisor exists (e.g. a
    prime vocab) — one big "block" degrades to the materializing path for
    that call, which is correct, just not faster.
    """
    best = vocab
    for cand in range(min(target, vocab), 0, -1):
        if vocab % cand == 0:
            best = cand
            break
    # a block much narrower than asked (worst case 1, for a prime vocab)
    # would make the scan absurdly long — degrade to one whole-vocab block
    return best if best >= max(1, target // 8) else vocab


def _block_logits(y, emb_block):
    """(N, bv) f32 logits tile for one vocab block; bf16 in, f32 accum."""
    return jax.lax.dot_general(
        y, emb_block, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _fwd_scan(y, emb, labels, n_blocks, block_v):
    """(lse, label_logit) via online logsumexp over vocab blocks."""
    n = y.shape[0]

    def body(carry, i):
        m, l, lab = carry
        eb = jax.lax.dynamic_slice_in_dim(emb, i * block_v, block_v, axis=0)
        s = _block_logits(y, eb)                          # (N, bv)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        l_new = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(s - m_new[:, None]), axis=-1
        )
        # gather this block's label logits where the label falls inside it
        loc = labels - i * block_v
        inside = (loc >= 0) & (loc < block_v)
        picked = jnp.take_along_axis(
            s, jnp.clip(loc, 0, block_v - 1)[:, None], axis=1
        )[:, 0]
        lab = jnp.where(inside, picked, lab)
        return (m_new, l_new, lab), None

    m0 = jnp.full((n,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((n,), jnp.float32)
    lab0 = jnp.zeros((n,), jnp.float32)
    (m, l, lab), _ = jax.lax.scan(body, (m0, l0, lab0), jnp.arange(n_blocks))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return lse, lab


# mtpu: hotpath
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def blocked_softmax_xent(y, emb, labels, block_v: int = 2048):
    """Per-token ``lse(y·embᵀ) - (y·embᵀ)[label]`` without (N, V) logits.

    y: (N, D) features (bf16 recommended); emb: (V, D) readout/embedding
    table; labels: (N,) int32 in [0, V). ``block_v`` must divide V — use
    :func:`pick_block_v` to choose one (padding the table instead would
    add spurious exp(y·pad) mass to every denominator). Returns (N,) f32
    losses. Differentiable in y and emb.
    """
    loss, _ = _xent_fwd_impl(y, emb, labels, block_v)
    return loss


@trace.scope("readout_xent")
def _xent_fwd_impl(y, emb, labels, block_v):
    v = emb.shape[0]
    assert v % block_v == 0, (v, block_v)
    lse, lab = _fwd_scan(y, emb, labels, v // block_v, block_v)
    return lse - lab, (lse, lab)


def _xent_fwd(y, emb, labels, block_v):
    # custom_vjp fwd keeps the primal signature; only bwd gets the
    # nondiff argnums hoisted to the front
    loss, (lse, _) = _xent_fwd_impl(y, emb, labels, block_v)
    return loss, (y, emb, labels, lse)


@trace.scope("readout_xent")  # a backward rule has no forward name stack
def _xent_bwd(block_v, res, g):
    """dY, dEmb from recomputed per-block probabilities.

    d loss / d logits = softmax(logits) − onehot(label); chain with g (N,).
    """
    y, emb, labels, lse = res
    v, _ = emb.shape
    n_blocks = v // block_v
    gf = g.astype(jnp.float32)

    def body(dy, i):
        eb = jax.lax.dynamic_slice_in_dim(emb, i * block_v, block_v, axis=0)
        s = _block_logits(y, eb)                          # (N, bv)
        p = jnp.exp(s - lse[:, None])                     # softmax tile
        loc = labels - i * block_v
        inside = (loc >= 0) & (loc < block_v)
        onehot = (
            (jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
             == jnp.clip(loc, 0, block_v - 1)[:, None])
            & inside[:, None]
        )
        ds = (p - onehot.astype(jnp.float32)) * gf[:, None]
        dy = dy + jax.lax.dot_general(                    # ds·emb (N, D)
            ds, eb.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        demb_b = jax.lax.dot_general(                     # dsᵀ·y (bv, D)
            ds, y.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dy, demb_b

    dy0 = jnp.zeros((y.shape[0], y.shape[1]), jnp.float32)
    dy, demb_blocks = jax.lax.scan(body, dy0, jnp.arange(n_blocks))
    demb = demb_blocks.reshape(v, y.shape[1])
    return dy.astype(y.dtype), demb.astype(emb.dtype), None


blocked_softmax_xent.defvjp(_xent_fwd, _xent_bwd)
