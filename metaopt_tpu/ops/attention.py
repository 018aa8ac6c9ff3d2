"""Flash attention for the demo-zoo Transformer: Pallas + chunked-XLA twins.

The demo-zoo Transformer (BASELINE config 4) is the framework's flagship
trial workload; its attention is the one genuinely hot op we own end-to-end.
The plain XLA path materializes the (B, H, Sq, Sk) logits tensor in HBM —
O(S²) memory traffic, the classic attention bottleneck. Two memory-efficient
implementations share one custom-VJP wrapper:

- ``impl="pallas"`` — a Pallas TPU kernel: blocked **online-softmax**
  attention that keeps Q·Kᵀ tiles in VMEM, carries running (max,
  denominator, accumulator) statistics across K blocks, and never writes the
  quadratic logits to HBM. MXU does the two matmuls per tile; the VPU
  handles the rescaling. Compiles via Mosaic on the TPU; tests off the
  chip pass ``interpret=True``.
- ``impl="chunked"`` — the same blocked online-softmax as a ``lax.scan``
  over K blocks in plain XLA. Live tiles are O(Sq·block_k), never
  O(Sq·Sk). This twin compiles on ANY backend and supports
  attention-probability dropout, reproduced bit-exactly in the backward
  from the same ``fold_in`` counter stream.

Backward is blockwise recompute from the saved (q, k, v, mask, lse) — the
forward emits the per-row logsumexp for exactly this — so peak memory
stays O(Sq·block_k) per step and the forward's HBM saving is preserved
through training. Two formulations: ``impl="pallas"`` (dropout-free)
runs the two-pass Pallas kernels (``_flash_bwd_dkv_kernel`` parallel
over K blocks + ``_flash_bwd_dq_kernel`` parallel over Q blocks — TPU
has no cross-program atomics, so each pass owns its outputs exclusively);
everything else uses the chunked ``lax.scan`` formulation, which also
replays dropout bit-exactly from the same ``fold_in`` counter stream.

Irregular sequence lengths are padded up to block multiples with masked
tails (``_block_and_pad``); block sizes never exceed the requested
block_q/block_k.

``MHA`` in metaopt_tpu.models.transformer routes here by default on TPU
backends (chunked twin; see :func:`attention_impl` for the selection table
and why the Pallas kernel stays opt-in), and wraps
the call in ``shard_map`` over the trial mesh (batch on "dp", heads on
"tp") via :func:`sharded_flash_attention` — attention is embarrassingly
parallel over (batch, head), so each shard runs the kernel locally and the
Megatron head split survives instead of GSPMD all-gathering q/k/v.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from metaopt_tpu.utils import trace

_NEG_BIG = -1e30
_SUBLANE = 8  # pad granularity for sequences shorter than a block


# ---------------------------------------------------------------------------
# blocking / padding


def _block_and_pad(size: int, target: int) -> tuple:
    """(block, padded_size): block ≤ target, padded_size % block == 0."""
    if size % target == 0:
        return target, size
    if size < target:
        p = -(-size // _SUBLANE) * _SUBLANE
        return p, p
    return target, -(-size // target) * target


# ---------------------------------------------------------------------------
# Pallas forward kernel


def _flash_fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                      *, block_k: int):
    """One (batch·head, q-block) program: online softmax over K blocks.

    Shapes in VMEM: q (1, Bq, D); k/v (1, Sk, D); mask (1, Bq, Sk) int8 or
    None; o (1, Bq, D); lse (1, Bq, 1) — the trailing singleton keeps the
    block's last two dims (Bq, 1) legal under Mosaic's (÷8, ÷128-or-equal)
    tiling rule; a (1, Bq) block over a (B·H, Sq) array is rejected.
    """
    q = q_ref[0].astype(jnp.float32)                      # (Bq, D)
    bq, d = q.shape
    sk = k_ref.shape[1]
    n_blocks = sk // block_k

    m0 = jnp.full((bq, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)

    def body(i, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(                           # (Bq, Bk) on MXU
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if mask_ref is not None:
            # int8 (not i1): Mosaic's sub-byte bool tiling is a pitfall
            mb = mask_ref[0, :, pl.ds(i * block_k, block_k)]
            s = jnp.where(mb != 0, s, _NEG_BIG)
        # floor the running max above the mask fill: a fully-masked block
        # would otherwise get exp(s - m) = exp(0) = 1 (uniform attention)
        m_new = jnp.maximum(
            jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True)), 0.5 * _NEG_BIG
        )
        alpha = jnp.exp(m - m_new)                         # rescale old stats
        p = jnp.exp(s - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = alpha * acc + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
    # fully-masked rows have l == 0; emit zeros rather than NaNs, and an
    # lse of +inf so the blockwise backward recomputes p == 0 for them
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[0] = jnp.where(
        l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), jnp.inf
    )


def _pallas_forward(q, k, v, mask, block_q, block_k, interpret):
    """(out, lse) via the Pallas kernel. Shapes pre-padded to block multiples."""
    b, sq, h, d = q.shape
    sk = k.shape[1]

    # head-major flattening: one grid row per (batch, head)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)

    grid = (b * h, sq // block_q)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
        pl.BlockSpec((1, sk, d), lambda bh, qi: (bh, 0, 0)),
        pl.BlockSpec((1, sk, d), lambda bh, qi: (bh, 0, 0)),
    ]
    operands = [qf, kf, vf]
    if mask is not None:
        in_specs.append(
            # mask is per-batch (heads share it): index by bh // h
            pl.BlockSpec((1, block_q, sk), lambda bh, qi, h=h: (bh // h, qi, 0))
        )
        operands.append(mask.astype(jnp.int8))
        kernel = functools.partial(_flash_fwd_kernel, block_k=block_k)
    else:
        def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref):
            _flash_fwd_kernel(q_ref, k_ref, v_ref, None, o_ref, lse_ref,
                              block_k=block_k)

    out, lse = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi: (bh, qi, 0)),
        ],
        interpret=interpret,
    )(*operands)
    return (out.reshape(b, h, sq, d).transpose(0, 2, 1, 3),
            lse.reshape(b, h, sq))


# ---------------------------------------------------------------------------
# Pallas backward kernels (FlashAttention-2 style: two passes, no atomics)


def _flash_bwd_dkv_kernel(q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref,
                          mask_ref, dk_ref, dv_ref, *, block_q: int):
    """One (batch·head, k-block) program: dK/dV over all Q blocks.

    TPU has no cross-program atomics, so the backward splits into a dKV
    pass (this kernel, parallel over K blocks) and a dQ pass (below,
    parallel over Q blocks) — each output is owned by exactly one
    program. Shapes in VMEM: q/g (1, Sq, D) full; k/v (1, Bk, D) block;
    lse/delta (1, Sq, 1) full; mask (1, Sq, Bk) int8 block or None.
    p is recomputed from the saved lse (p = exp(s − lse)), the same
    normalized-probability recomputation the chunked twin uses; ds =
    p ⊙ (dO·Vᵀ − delta) with delta = rowsum(dO ⊙ O) precomputed in XLA.
    """
    kb = k_ref[0].astype(jnp.float32)                      # (Bk, D)
    vb = v_ref[0].astype(jnp.float32)
    bk, d = kb.shape
    sq = q_ref.shape[1]
    n_blocks = sq // block_q

    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)

    def body(i, carry):
        dk, dv = carry
        qb = q_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        gb = g_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse_b = lse_ref[0, pl.ds(i * block_q, block_q), :]  # (Bq, 1) f32
        delta_b = delta_ref[0, pl.ds(i * block_q, block_q), :]
        s = jax.lax.dot_general(                            # (Bq, Bk)
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if mask_ref is not None:
            mb = mask_ref[0, pl.ds(i * block_q, block_q), :]
            s = jnp.where(mb != 0, s, _NEG_BIG)
        # fully-masked rows carry lse = +inf from the forward → p = 0
        p = jnp.exp(s - lse_b)
        gp = jax.lax.dot_general(                           # dO·Vᵀ (Bq, Bk)
            gb, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (gp - delta_b)
        dv_new = dv + jax.lax.dot_general(                  # pᵀ·dO (Bk, D)
            p, gb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_new = dk + jax.lax.dot_general(                  # dsᵀ·q (Bk, D)
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk_new, dv_new

    dk, dv = jax.lax.fori_loop(0, n_blocks, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref,
                         mask_ref, dq_ref, *, block_k: int):
    """One (batch·head, q-block) program: dQ over all K blocks."""
    qb = q_ref[0].astype(jnp.float32)                      # (Bq, D)
    gb = g_ref[0].astype(jnp.float32)
    lse_b = lse_ref[0]                                     # (Bq, 1) f32
    delta_b = delta_ref[0]
    bq, d = qb.shape
    sk = k_ref.shape[1]
    n_blocks = sk // block_k

    def body(i, dq):
        kb = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if mask_ref is not None:
            mb = mask_ref[0, :, pl.ds(i * block_k, block_k)]
            s = jnp.where(mb != 0, s, _NEG_BIG)
        p = jnp.exp(s - lse_b)
        gp = jax.lax.dot_general(
            gb, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (gp - delta_b)
        return dq + jax.lax.dot_general(                    # ds·K (Bq, D)
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    dq = jax.lax.fori_loop(0, n_blocks, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _pallas_backward(q, k, v, mask, out, lse, g, block_q, block_k, interpret):
    """(dq, dk, dv) via the two Pallas passes. Shapes pre-padded."""
    b, sq, h, d = q.shape
    sk = k.shape[1]

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    gf = g.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    of = out.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    # delta = rowsum(dO ⊙ O): one fused elementwise+reduce, cheaper in XLA
    # than re-deriving O inside the kernels
    delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1, keepdims=True)                # (B·H, Sq, 1)
    lsef = lse.reshape(b * h, sq, 1)
    m8 = mask.astype(jnp.int8) if mask is not None else None

    full_q = [
        pl.BlockSpec((1, sq, d), lambda bh, i: (bh, 0, 0)),      # q
        pl.BlockSpec((1, sq, d), lambda bh, i: (bh, 0, 0)),      # g
    ]
    stats = [
        pl.BlockSpec((1, sq, 1), lambda bh, i: (bh, 0, 0)),      # lse
        pl.BlockSpec((1, sq, 1), lambda bh, i: (bh, 0, 0)),      # delta
    ]

    # pass 1: dK/dV, one program per K block
    in_specs = full_q + [
        pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0)),  # k
        pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0)),  # v
    ] + stats
    operands = [qf, gf, kf, vf, lsef, delta]
    if m8 is not None:
        in_specs.append(
            pl.BlockSpec((1, sq, block_k),
                         lambda bh, ki, h=h: (bh // h, 0, ki))
        )
        operands.append(m8)
        dkv_kernel = functools.partial(_flash_bwd_dkv_kernel,
                                       block_q=block_q)
    else:
        def dkv_kernel(q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref):
            _flash_bwd_dkv_kernel(q_ref, g_ref, k_ref, v_ref, lse_ref,
                                  delta_ref, None, dk_ref, dv_ref,
                                  block_q=block_q)
    # the dKV pass reorders q/g/k/v operands: q/g are the full arrays
    dk, dv = pl.pallas_call(
        dkv_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        grid=(b * h, sk // block_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0)),
        ],
        interpret=interpret,
    )(*operands)

    # pass 2: dQ, one program per Q block
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),  # q
        pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),  # g
        pl.BlockSpec((1, sk, d), lambda bh, qi: (bh, 0, 0)),        # k
        pl.BlockSpec((1, sk, d), lambda bh, qi: (bh, 0, 0)),        # v
        pl.BlockSpec((1, block_q, 1), lambda bh, qi: (bh, qi, 0)),  # lse
        pl.BlockSpec((1, block_q, 1), lambda bh, qi: (bh, qi, 0)),  # delta
    ]
    operands = [qf, gf, kf, vf, lsef, delta]
    if m8 is not None:
        in_specs.append(
            pl.BlockSpec((1, block_q, sk),
                         lambda bh, qi, h=h: (bh // h, qi, 0))
        )
        operands.append(m8)
        dq_kernel = functools.partial(_flash_bwd_dq_kernel, block_k=block_k)
    else:
        def dq_kernel(q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref,
                      dq_ref):
            _flash_bwd_dq_kernel(q_ref, g_ref, k_ref, v_ref, lse_ref,
                                 delta_ref, None, dq_ref, block_k=block_k)
    dq = pl.pallas_call(
        dq_kernel,
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        grid=(b * h, sq // block_q),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
        interpret=interpret,
    )(*operands)

    unflat = lambda t, s: t.reshape(b, h, s, d).transpose(0, 2, 1, 3)  # noqa: E731
    return unflat(dq, sq), unflat(dk, sk), unflat(dv, sk)


# ---------------------------------------------------------------------------
# chunked (lax.scan) twin — pure XLA, any backend, dropout-capable


def _dropout_tile(key, i, keep, shape):
    """The (fwd ∩ bwd)-shared dropout mask for K-block i."""
    return jax.random.bernoulli(jax.random.fold_in(key, i), keep, shape)


def online_softmax_fold(s, v, m, l, acc, drop=None, keep=1.0):
    """Fold one masked score tile into online-softmax running statistics.

    The single source of truth for the blockwise-attention update — used by
    the chunked scan here AND by ring attention's per-hop step. s: (b, h,
    sq, bk) scores with mask already applied as ``_NEG_BIG`` fills; v: (b,
    h, bk, d); carries m/l: (b, h, sq, 1), acc: (b, h, sq, d). ``drop``
    applies attention-probability dropout with ``dropout(softmax)``
    semantics: l accumulates UNdropped mass (it is the softmax
    denominator), acc takes the dropped/rescaled tiles.
    """
    # floor the running max above the mask fill: a fully-masked tile would
    # otherwise get exp(s - m) = exp(0) = 1 (uniform attention)
    m_new = jnp.maximum(
        jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True)), 0.5 * _NEG_BIG
    )
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    if drop is not None:
        p = jnp.where(drop, p / keep, 0.0)
    acc_new = alpha * acc + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v, preferred_element_type=jnp.float32
    )
    return m_new, l_new, acc_new


def shard_map_nocheck(fn, mesh, in_specs, out_specs):
    """shard_map without the varying-manual-axes check.

    The blockwise-attention scans start their carries mesh-invariant and
    make them varying in the body — sound here, but the checker rejects it.
    """
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _chunked_forward(q, k, v, mask, block_k, dropout_rate, key):
    """(out, lse) via a lax.scan over K blocks; live tiles O(Sq·block_k).

    Dropout semantics match ``dropout(softmax(s)) @ V``: the denominator l
    accumulates undropped probabilities; the accumulator takes the dropped,
    1/keep-scaled ones.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    nk = sk // block_k
    qt = q.transpose(0, 2, 1, 3).astype(jnp.float32)      # (b,h,sq,d)
    kt = k.transpose(0, 2, 1, 3).astype(jnp.float32)      # (b,h,sk,d)
    vt = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    keep = 1.0 - dropout_rate

    def body(carry, i):
        m, l, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(kt, i * block_k, block_k, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(vt, i * block_k, block_k, axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qt, kb,
                       preferred_element_type=jnp.float32)
        if mask is not None:
            mb = jax.lax.dynamic_slice_in_dim(mask, i * block_k, block_k,
                                              axis=2)
            s = jnp.where(mb[:, None], s, _NEG_BIG)
        drop = (_dropout_tile(key, i, keep, s.shape)
                if dropout_rate > 0.0 else None)
        return online_softmax_fold(s, vb, m, l, acc, drop, keep), None

    m0 = jnp.full((b, h, sq, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), jnp.arange(nk))
    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    lse = jnp.where(
        l[..., 0] > 0, m[..., 0] + jnp.log(jnp.maximum(l[..., 0], 1e-30)),
        jnp.inf,
    )
    return out.transpose(0, 2, 1, 3), lse                 # (b,sq,h,d), (b,h,sq)


def _chunked_backward(q, k, v, mask, key, out, lse, g, block_k, dropout_rate):
    """Blockwise VJP from saved lse: p-tiles recomputed per K block.

    Softmax VJP with post-normalization dropout: with y = softmax rows and
    O = (pm/keep ⊙ y) V, the row term Σⱼ yⱼ·(dL/dyⱼ) collapses to
    rowsum(dO ⊙ O) — the standard delta trick survives dropout because the
    mask rides inside both factors.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    nk = sk // block_k
    qt = q.transpose(0, 2, 1, 3).astype(jnp.float32)
    kt = k.transpose(0, 2, 1, 3).astype(jnp.float32)
    vt = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    gt = g.transpose(0, 2, 1, 3).astype(jnp.float32)
    ot = out.transpose(0, 2, 1, 3).astype(jnp.float32)
    delta = jnp.sum(gt * ot, axis=-1, keepdims=True)      # (b,h,sq,1)
    keep = 1.0 - dropout_rate

    def body(dq, i):
        kb = jax.lax.dynamic_slice_in_dim(kt, i * block_k, block_k, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(vt, i * block_k, block_k, axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qt, kb,
                       preferred_element_type=jnp.float32)
        if mask is not None:
            mb = jax.lax.dynamic_slice_in_dim(mask, i * block_k, block_k,
                                              axis=2)
            s = jnp.where(mb[:, None], s, _NEG_BIG)
        p = jnp.exp(s - lse[..., None])                   # normalized probs
        gp = jnp.einsum("bhqd,bhkd->bhqk", gt, vb,
                        preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            pm = _dropout_tile(key, i, keep, p.shape)
            pd = jnp.where(pm, p / keep, 0.0)
            gp = jnp.where(pm, gp / keep, 0.0)
        else:
            pd = p
        dv_i = jnp.einsum("bhqk,bhqd->bhkd", pd, gt,
                          preferred_element_type=jnp.float32)
        ds = p * (gp - delta)
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kb,
                             preferred_element_type=jnp.float32)
        dk_i = jnp.einsum("bhqk,bhqd->bhkd", ds, qt,
                          preferred_element_type=jnp.float32)
        return dq, (dk_i, dv_i)

    dq0 = jnp.zeros_like(qt)
    dq, (dks, dvs) = jax.lax.scan(body, dq0, jnp.arange(nk))
    dk = jnp.moveaxis(dks, 0, 2).reshape(b, h, sk, d)     # (nk,b,h,bk,d)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(b, h, sk, d)
    to_in = lambda t, ref: t.transpose(0, 2, 1, 3).astype(ref.dtype)  # noqa: E731
    return to_in(dq, q), to_in(dk, k), to_in(dv, v)


# ---------------------------------------------------------------------------
# custom VJP plumbing


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, mask, key, dropout_rate, block_q, block_k, impl,
           interpret):
    out, _ = _flash_fwd_rule(
        q, k, v, mask, key, dropout_rate, block_q, block_k, impl, interpret
    )
    return out


@trace.scope("attention.core")
def _flash_fwd_rule(q, k, v, mask, key, dropout_rate, block_q, block_k, impl,
                    interpret):
    if impl == "pallas":
        out, lse = _pallas_forward(q, k, v, mask, block_q, block_k, interpret)
    else:
        out, lse = _chunked_forward(q, k, v, mask, block_k, dropout_rate, key)
    return out, (q, k, v, mask, key, out, lse)


@trace.scope("attention.core")  # a backward rule has no forward name stack
def _flash_bwd_rule(dropout_rate, block_q, block_k, impl, interpret,
                    residuals, g):
    q, k, v, mask, key, out, lse = residuals
    if impl == "pallas" and dropout_rate == 0.0:
        # the pallas forward never carries dropout (flash_attention routes
        # dropout to chunked), so the pallas backward needs no mask replay
        dq, dk, dv = _pallas_backward(
            q, k, v, mask, out, lse, g, block_q, block_k, interpret
        )
    else:
        dq, dk, dv = _chunked_backward(
            q, k, v, mask, key, out, lse, g, block_k, dropout_rate
        )
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@trace.scope("attention.core")
def _reference_attention(q, k, v, mask, dropout_rate=0.0, dropout_key=None):
    """Plain XLA attention (f32 softmax) — the O(S²)-HBM fallback/oracle."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    if mask is not None:
        s = jnp.where(mask[:, None], s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    # match the kernel: fully-masked rows produce zeros, not uniform garbage
    if mask is not None:
        any_valid = jnp.any(mask[:, None], axis=-1, keepdims=True)
        p = jnp.where(any_valid, p, 0.0)
    if dropout_rate > 0.0:
        keep = 1.0 - dropout_rate
        pm = jax.random.bernoulli(dropout_key, keep, p.shape)
        p = jnp.where(pm, p / keep, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)


# ---------------------------------------------------------------------------
# public API


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    *,
    dropout_rate: float = 0.0,
    dropout_key: Optional[jnp.ndarray] = None,
    block_q: int = 128,
    block_k: int = 128,
    impl: Optional[str] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Blocked online-softmax attention with a blockwise backward.

    q: (B, Sq, H, D) — pre-scaled (multiply by 1/sqrt(D) before calling);
    k, v: (B, Sk, H, D); mask: optional (B, Sq, Sk) bool, True = attend
    (shared across heads); dropout_rate applies to attention probabilities
    (chunked impl only) with dropout_key. Irregular Sq/Sk are padded to
    block multiples with masked tails. Returns (B, Sq, H, D) in q's dtype.

    ``interpret=True`` runs the Pallas kernels in the interpreter (tests
    off the chip). It is never chosen for the caller: ``impl="pallas"``
    compiles through Mosaic or raises with the compiler's message.
    """
    if impl is None:
        impl = "chunked" if dropout_rate > 0.0 else "pallas"
    if dropout_rate > 0.0 and impl == "pallas":
        raise ValueError("attention dropout requires impl='chunked'")
    if dropout_rate > 0.0 and dropout_key is None:
        raise ValueError("dropout_rate > 0 needs a dropout_key")

    b, sq, h, d = q.shape
    sk = k.shape[1]
    bq, sq_p = _block_and_pad(sq, block_q)
    bk, sk_p = _block_and_pad(sk, block_k)
    if sq_p != sq or sk_p != sk:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
        if mask is not None:
            mask = jnp.pad(mask, ((0, 0), (0, sq_p - sq), (0, sk_p - sk)))
        elif sk_p != sk:
            # padded K columns must not be attended; padded Q rows are
            # sliced off below and need no masking
            mask = jnp.broadcast_to(
                (jnp.arange(sk_p) < sk)[None, None, :], (b, sq_p, sk_p)
            )
    out = _flash(q, k, v, mask, dropout_key, float(dropout_rate), bq, bk,
                 impl, bool(interpret))
    return out[:, :sq]


def sharded_flash_attention(
    mesh,
    q, k, v,
    mask=None,
    *,
    dropout_rate: float = 0.0,
    dropout_key=None,
    impl: Optional[str] = None,
    batch_axis: str = "dp",
    head_axis: str = "tp",
    **kwargs,
):
    """shard_map the kernel over the trial mesh: batch on dp, heads on tp.

    Attention is embarrassingly parallel over (batch, head): each shard runs
    the kernel on its local (B/dp, S, H/tp, D) slab with zero collectives,
    so the Megatron column-split of q/k/v survives instead of GSPMD
    all-gathering the heads. The dropout key is decorrelated per shard by
    folding in the mesh coordinates.
    """
    from jax.sharding import PartitionSpec as P

    ab = batch_axis if batch_axis in mesh.shape else None
    ah = head_axis if head_axis in mesh.shape else None
    qs = P(ab, None, ah, None)
    ms = P(ab, None, None)

    def local(q, k, v, mask, key):
        if key is not None:
            for ax in (ab, ah):
                if ax is not None:
                    key = jax.random.fold_in(key, jax.lax.axis_index(ax))
        return flash_attention(
            q, k, v, mask, dropout_rate=dropout_rate, dropout_key=key,
            impl=impl, **kwargs,
        )

    wrapped = shard_map_nocheck(
        local, mesh,
        in_specs=(qs, qs, qs, ms if mask is not None else P(), P()),
        out_specs=qs,
    )
    return wrapped(q, k, v, mask, dropout_key)


def attention_impl() -> Optional[str]:
    """Which implementation MHA routes through, from ``METAOPT_TPU_FLASH``.

    - unset → backend default: **``chunked`` on TPU** (keeps live
      attention tiles O(Sq·block_k) instead of the reference path's O(S²)
      HBM logits tensor, and covers dropout), ``None`` (plain XLA
      reference) on CPU, where the O(S²) path is faster at test shapes
      and numerically the oracle.
    - ``0``/``off`` → ``None``: force the plain XLA reference attention.
    - ``1``/``pallas`` → the Pallas kernel, compiled through Mosaic (TPU
      only; it raises elsewhere). Attention dropout still routes those
      calls to the chunked twin. Pallas against chunked has no kernel
      timing on the current runtime (PERF.md); ``chip_smoke.py`` checks
      that the kernels compile and match the float32 reference.
    - ``chunked``/``scan`` → force the lax.scan twin on any backend.
    """
    env = (os.environ.get("METAOPT_TPU_FLASH") or "").strip().lower()
    if env in ("", None):
        return "chunked" if jax.default_backend() == "tpu" else None
    if env in ("0", "false", "no", "off"):
        return None
    if env in ("chunked", "scan", "2"):
        return "chunked"
    if env in ("1", "true", "yes", "on", "pallas"):
        return "pallas"
    # a typo must not silently select another path
    raise ValueError(
        f"METAOPT_TPU_FLASH={env!r}: expected off/pallas/chunked"
    )


def use_flash_attention() -> bool:
    """Back-compat boolean view of :func:`attention_impl`."""
    return attention_impl() is not None
