"""Flash attention for the model zoo: Pallas kernels + a chunked-XLA twin.

The plain XLA path materializes the (B, H, Sq, Sk) logits tensor in HBM —
O(S²) memory traffic, the classic attention bottleneck. The
memory-efficient implementations share one shape of custom VJP:

- ``impl="pallas"`` — Pallas TPU kernels: blocked **online-softmax**
  attention whose score tiles live and die in VMEM; the MXU takes the
  operands at the width they arrive in (bfloat16 from the models) and
  accumulates in float32, the VPU does the softmax in float32. Compiles
  via Mosaic on the TPU; tests off the chip pass ``interpret=True``. Two
  pairs of kernels, chosen by **how the mask is stated**:

  * a dense ``(B, Sq, Sk)`` array or none (the 2017 Transformer's padding
    and causal masks): ``_flash_fwd_kernel`` / ``_flash_bwd_kernel``. A
    program is one batch row, all its heads and one tile of the sequence
    (up to 512 long); the other sequence is resident in VMEM whole, as is
    the program's slab of the mask. Right for the short sequences those
    models train on (seq 256-1024).
  * a :class:`CausalMask` — causal, with a window length or none —
    ``_causal_fwd_kernel`` / ``_causal_bwd_kernel``: limits computed in
    the kernel from positions, K/V heads fewer than query heads (query
    head h reads K/V head ``h // group`` through the block index, no
    copy), q.k and v each at a width of its own (a latent layer's 192 and
    128), a program per (row, query head, tile), one head of the other
    sequence in VMEM (bounded at S 8192 and 16 384), and a walk over the
    tiles the structure lets through alone: the others are **skipped, not
    masked**, in forward and backward. Under a window no wider than a tile
    the walk's sibling: one slab a sub-tile (ops/window_attention.py).

- ``impl="chunked"`` — the same blocked online-softmax as a ``lax.scan``
  over K blocks in plain XLA. Live tiles are O(Sq·block_k), never
  O(Sq·Sk), but each scan step's float32 tile goes through HBM. This twin
  compiles on ANY backend and supports attention-probability dropout,
  reproduced bit-exactly in the backward from the same ``fold_in`` counter
  stream. It knows one head count and dense masks: grouped K/V heads are
  repeated and a ``CausalMask`` made dense for it (``_plain_operands``),
  as for the plain reference.

Backward is blockwise recompute from the saved (q, k, v, mask, lse) — the
forward emits the per-row logsumexp for exactly this — so peak memory
stays O(Sq·block_k) per step and the forward's HBM saving is preserved
through training. Each Pallas backward is ONE kernel: a program owns a K
tile's dK and dV and adds its share of dQ to a float32 scratch that the K
tiles of a batch row (of a head, under a ``CausalMask``), run in order,
sum (TPU has no cross-program atomics); everything else uses the chunked
``lax.scan`` formulation, which also replays dropout.

Irregular sequence lengths are padded up to block multiples with masked
tails; block sizes follow the shapes (``_derived_block`` for the kernels,
128-wide K blocks for the scan) unless a caller names them, and then never
exceed what it names (``_block_and_pad``).

``MHA`` in metaopt_tpu.models.transformer and ``GroupedAttention`` in
metaopt_tpu.models.lm_layers project, scale, build their mask and call
:func:`attend`, the one door; :func:`attention_route` is the one rule, from
what the call can see: an ``sp`` mesh axis takes ring attention (or
Ulysses), a backend other than the TPU the plain reference, a call with
dropout the chunked twin, and every other call (every evaluation step,
every training step at dropout 0) the Pallas kernels. On a trial mesh the
kernels' call is wrapped in ``shard_map`` (batch on "dp", heads on "tp")
via :func:`sharded_flash_attention` — attention is embarrassingly
parallel over (batch, head), so each shard runs the kernel locally and the
Megatron head split survives instead of GSPMD all-gathering q/k/v.
:func:`flash_attention` is the kernels' own entry and takes ``impl`` as
its caller states it.

A :class:`SelectedMask` (keys chosen a query: ops/selected_attention.py)
and a :class:`LatentKV` (a latent layer's K and V read where its matmuls
leave them: ops/latent_attention.py) have files of their own, loaded when
one arrives. Not here: key lengths, segment ids and packing.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from metaopt_tpu.parallel.mesh import active_mesh
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


def _derived_block(size: int) -> tuple:
    """(block, padded_size) for the Pallas kernels when the caller names none.

    A tile is on the lane axis of the score tile in one place and on its
    sublane axis in another, so it is a multiple of 128, or the whole
    (32-aligned: the int8 mask's sublane tile) axis when that is shorter.
    The largest of 512, 256, 128 that divides the length: at seq 512 and
    1024 a 512-tile halves the kernels' time against 256 (PERF.md), and a
    (512, 512) float32 score tile with its temporaries still sits well
    inside VMEM.
    """
    if size < 128:
        p = -(-size // 32) * 32
        return p, p
    block = next(t for t in (512, 256, 128) if size % t == 0 or t == 128)
    return block, -(-size // block) * block


# ---------------------------------------------------------------------------
# Pallas kernels: forward and backward
#
# One layout for both: FEATURE-MAJOR, ``(B, H*D, S)``. q, k, v, dO
# and the outputs are the projections' ``(B, S, H*D)`` with the last two
# axes swapped, which is how XLA's TPU matmuls leave and take a
# ``(B, S, 512)`` activation anyway (sequence on the lanes: the swap is a
# bitcast there, and no copy stands around a call); a head is an aligned
# D-row slice. A program is one batch row, ALL its heads and one tile of
# the sequence; the other sequence is resident in VMEM and walked tile by
# tile. Matmul operands keep the dtype they arrive in (bfloat16 from the
# models: one MXU pass, as XLA's default precision gives the chunked twin's
# float32 einsums) and accumulate in float32; running max, denominator,
# lse, delta, exp and the accumulators are float32, and p / ds are cast
# only as matmul operands.
#
# Both kernels work on the TRANSPOSED score tile ``sT = k @ q.T`` (keys on
# sublanes, queries on lanes). The softmax's reductions over keys then run
# down the sublanes, elementwise between vregs, and its row statistics
# (running max, denominator, lse, delta) are lane-dense ``(1, Bq)`` rows.
# With feature-major operands O.T = v.T @ pT, dQ.T = k.T @ dsT, dV.T =
# dO.T @ pT.T and dK.T = q.T @ dsT.T are the MXU's own forms, their (D, S)
# results are stored as they come, and only a (D, Bk) slice of k or v is
# ever transposed, never an (S, S) tile. The mask arrives transposed too,
# int8 ``(B, Sk, Sq)``, and becomes an additive float32 bias once a
# program, shared by its heads; lse and delta travel as ``(B, H, Sq)``.

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_VMEM_FLOOR = 32 << 20          # Mosaic's default scoped limit is 16 MiB
_VMEM_CEIL = 100 << 20          # of a v5e core's 128 MiB


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _walk(lo, n, body, init):
    """The loop over tiles lo..n-1 of the resident sequence.

    One tile is no loop: its offset is then the static 0, which a tile
    narrower than a lane tile (a short sequence) needs under Mosaic. A few
    are unrolled, many are a fori_loop.
    """
    if n - lo == 1:
        return body(lo, init)
    return jax.lax.fori_loop(lo, n, body, init, unroll=n <= 4)


def _tile(i, block):
    start = i * block if isinstance(i, int) else pl.multiple_of(i * block,
                                                                block)
    return pl.ds(start, block)


def _mask_bias(mask_ref):
    """int8 0/1 mask block -> additive float32 bias, 0 or the mask fill.

    int8 (not i1) in memory, and arithmetic rather than a select on
    ``mask != 0``: Mosaic's sub-byte bool tiling and its i1 relayouts are
    both pitfalls.
    """
    return (mask_ref[0].astype(jnp.float32) - 1.0) * -_NEG_BIG


def _flash_fwd_kernel(*refs, n_heads: int, block_k: int, masked: bool):
    """One (batch row, q tile) program: online softmax over K tiles.

    Shapes in VMEM: q, o (1, H*D, Bq); k, v (1, H*D, Sk); mask (1, Sk, Bq)
    int8; lse (1, H, Bq) float32; bias scratch (Sk, Bq) float32.
    """
    if masked:
        q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, bias_ref = refs
        bias_ref[...] = _mask_bias(mask_ref)
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
    bq = q_ref.shape[2]
    d = q_ref.shape[1] // n_heads
    n_k = k_ref.shape[2] // block_k

    def head(hh):
        return slice(hh * d, (hh + 1) * d)

    def scores(hh, i):
        """Head hh's masked sT against K tile i: (Bk, Bq)."""
        ks = _tile(i, block_k)
        st = _dot(k_ref[0, head(hh), ks].T, q_ref[0, head(hh), :])
        return st + bias_ref[ks, :] if masked else st

    def fold(hh, i, st, carry):
        m, l, acc = carry                                   # (1, Bq) x2, (D, Bq)
        # floor the running max above the mask fill: a fully-masked tile
        # would otherwise get exp(s - m) = exp(0) = 1
        m_new = jnp.maximum(
            jnp.maximum(m, jnp.max(st, axis=0, keepdims=True)),
            0.5 * _NEG_BIG)
        alpha = jnp.exp(m - m_new)                          # rescale old stats
        pt = jnp.exp(st - m_new)
        l_new = alpha * l + jnp.sum(pt, axis=0, keepdims=True)
        vt = v_ref[0, head(hh), _tile(i, block_k)]          # (D, Bk)
        return m_new, l_new, alpha * acc + _dot(vt, pt.astype(vt.dtype))

    first = scores(0, 0)
    for hh in range(n_heads):
        st = first
        if hh + 1 < n_heads:
            # the next head's first scores are issued BEFORE this head's
            # softmax: the two are independent, and in this order the MXU
            # works under the VPU's exp (42 % off the kernel at seq 256)
            first = scores(hh + 1, 0)
        carry = fold(hh, 0, st, (jnp.full((1, bq), -jnp.inf, jnp.float32),
                                 jnp.zeros((1, bq), jnp.float32),
                                 jnp.zeros((d, bq), jnp.float32)))
        if n_k > 1:
            carry = _walk(
                1, n_k, lambda i, c, hh=hh: fold(hh, i, scores(hh, i), c),
                carry)
        m, l, acc = carry
        # fully-masked rows have l == 0; emit zeros rather than NaNs, and
        # an lse of +inf so the backward recomputes p == 0 for them
        o_ref[0, head(hh), :] = (
            acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[0, hh:hh + 1, :] = jnp.where(
            l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), jnp.inf)


def _flash_bwd_kernel(*refs, n_heads: int, block_q: int, masked: bool):
    """One (batch row, k tile) program: the tile's dK and dV, and its share
    of dQ.

    p is recomputed from the saved lse (p = exp(s - lse)), the same
    normalized-probability recomputation the chunked twin uses; ds =
    p * (dO @ V.T - delta) with delta = rowsum(dO * O) made in XLA. One
    pass: each score tile is made once and feeds all three gradients. TPU
    has no cross-program atomics, so dQ is summed in a float32 scratch
    over the K tiles of a batch row, which run in order on one core (the
    grid's second axis is "arbitrary"), and written after the last.

    Shapes in VMEM: k, v, dk, dv (1, H*D, Bk); q, dO, dq (1, H*D, Sq); mask
    (1, Bk, Sq) int8; lse, delta (1, H, Sq); dq scratch (H*D, Sq) and bias
    scratch (Bk, Sq) float32.
    """
    if masked:
        (q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref, mask_ref,
         dq_ref, dk_ref, dv_ref, acc_ref, bias_ref) = refs
        bias_ref[...] = _mask_bias(mask_ref)
    else:
        (q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, acc_ref) = refs
    bk = k_ref.shape[2]
    d = k_ref.shape[1] // n_heads
    n_q = q_ref.shape[2] // block_q

    @pl.when(pl.program_id(1) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for hh in range(n_heads):
        head = slice(hh * d, (hh + 1) * d)
        kt = k_ref[0, head, :]                              # (D, Bk)
        kb = kt.T
        vb = v_ref[0, head, :].T

        def body(i, carry, kt=kt, kb=kb, vb=vb, hh=hh, head=head):
            dk, dv = carry
            qs = _tile(i, block_q)
            qt = q_ref[0, head, qs]                         # (D, Bq)
            gt = g_ref[0, head, qs]
            st = _dot(kb, qt)                               # (Bk, Bq)
            if masked:
                st = st + bias_ref[:, qs]
            # fully-masked rows carry lse = +inf from the forward -> p = 0
            pt = jnp.exp(st - lse_ref[0, hh:hh + 1, qs])
            dst = (pt * (_dot(vb, gt) - delta_ref[0, hh:hh + 1, qs])
                   ).astype(qt.dtype)
            acc_ref[head, qs] += _dot(kt, dst)              # dQ.T (D, Bq)
            return (dk + _dot(qt, dst, _NT),                # dK.T (D, Bk)
                    dv + _dot(gt, pt.astype(gt.dtype), _NT))

        dk, dv = _walk(0, n_q, body, (jnp.zeros((d, bk), jnp.float32),
                                   jnp.zeros((d, bk), jnp.float32)))
        dk_ref[0, head, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, head, :] = dv.astype(dv_ref.dtype)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _call(kernel, name, grid, semantics, in_specs, out_specs, out_shape,
          scratch, operands, interpret, **static):
    """pallas_call with the VMEM limit its blocks (double-buffered) and
    scratch need; a scratch is a shape (float32) or says its dtype too."""
    blocks = [(sp.block_shape, x.dtype) for sp, x in zip(in_specs, operands)]
    blocks += [(sp.block_shape, o.dtype) for sp, o in zip(out_specs, out_shape)]
    scratch = [s if hasattr(s, "dtype") else jax.ShapeDtypeStruct(
        s, jnp.float32) for s in scratch]
    need = 2 * sum(math.prod(shp) * jnp.dtype(dt).itemsize
                   for shp, dt in blocks)
    need += sum(math.prod(s.shape) * s.dtype.itemsize for s in scratch)
    # the score tiles and their temporaries live beside the blocks
    limit = min(max(2 * need, _VMEM_FLOOR), _VMEM_CEIL)
    return pl.pallas_call(
        functools.partial(kernel, **static),
        out_shape=out_shape, grid=grid, in_specs=in_specs,
        out_specs=out_specs, name=name,
        scratch_shapes=[pltpu.VMEM(s.shape, s.dtype) for s in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=limit),
        interpret=interpret,
    )(*operands)


# jitted, so that a model's call sites share one trace and one lowering:
# jax lowers a jitted callee once a module (18 call sites of a 6-layer
# Transformer: 2 Mosaic kernels lowered at every retrace, not 36), and XLA
# still names each inlined copy's ops after its own call site.
_shared = functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "interpret"))

_rows = lambda i, j: (i, j, 0)   # noqa: E731  a tile of rows, every lane
_lanes = lambda i, j: (i, 0, j)  # noqa: E731  every row, a tile of lanes
_whole = lambda i, j: (i, 0, 0)  # noqa: E731


def _feature_major(x):
    """(B, S, H, D) -> (B, H*D, S)."""
    b, s, h, d = x.shape
    return x.reshape(b, s, h * d).transpose(0, 2, 1)


def _mask_t(mask):
    """(B, Sq, Sk) bool -> (B, Sk, Sq) int8, as the kernels read it."""
    return mask.astype(jnp.int8).transpose(0, 2, 1)


def _heads_last(xt, h):
    """(B, H*D, S) -> (B, S, H, D)."""
    b, hd, s = xt.shape
    return xt.transpose(0, 2, 1).reshape(b, s, h, hd // h)


@_shared
def _pallas_forward(q, k, v, mask, block_q, block_k, interpret):
    """(out, lse) via the Pallas kernel. Shapes pre-padded to block multiples."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    hd = h * d

    in_specs = [pl.BlockSpec((1, hd, block_q), _lanes),
                pl.BlockSpec((1, hd, sk), _whole),
                pl.BlockSpec((1, hd, sk), _whole)]
    operands = [_feature_major(q), _feature_major(k), _feature_major(v)]
    scratch = []
    if mask is not None:
        in_specs.append(pl.BlockSpec((1, sk, block_q), _lanes))
        operands.append(_mask_t(mask))
        scratch.append((sk, block_q))
    out, lse = _call(
        _flash_fwd_kernel, "flash_fwd", (b, sq // block_q),
        ("parallel", "parallel"), in_specs,
        [pl.BlockSpec((1, hd, block_q), _lanes),
         pl.BlockSpec((1, h, block_q), _lanes)],
        [jax.ShapeDtypeStruct((b, hd, sq), q.dtype),
         jax.ShapeDtypeStruct((b, h, sq), jnp.float32)],
        scratch, operands, interpret, n_heads=h, block_k=block_k,
        masked=mask is not None)
    return _heads_last(out, h), lse


@_shared
def _pallas_backward(q, k, v, mask, out, lse, g, block_q, block_k, interpret):
    """(dq, dk, dv) via the Pallas kernel. Shapes pre-padded."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    hd = h * d

    # delta = rowsum(dO * O): one fused elementwise+reduce, cheaper in XLA
    # than re-deriving O inside the kernel
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)             # (B, H, Sq)
    in_specs = [pl.BlockSpec((1, hd, sq), _whole),          # q
                pl.BlockSpec((1, hd, sq), _whole),          # dO
                pl.BlockSpec((1, hd, block_k), _lanes),     # k
                pl.BlockSpec((1, hd, block_k), _lanes),     # v
                pl.BlockSpec((1, h, sq), _whole),           # lse
                pl.BlockSpec((1, h, sq), _whole)]           # delta
    operands = [_feature_major(q), _feature_major(g), _feature_major(k),
                _feature_major(v), lse, delta]
    scratch = [(hd, sq)]
    if mask is not None:
        in_specs.append(pl.BlockSpec((1, block_k, sq), _rows))
        operands.append(_mask_t(mask))
        scratch.append((block_k, sq))
    dq, dk, dv = _call(
        _flash_bwd_kernel, "flash_bwd", (b, sk // block_k),
        ("parallel", "arbitrary"), in_specs,
        [pl.BlockSpec((1, hd, sq), _whole),
         pl.BlockSpec((1, hd, block_k), _lanes),
         pl.BlockSpec((1, hd, block_k), _lanes)],
        [jax.ShapeDtypeStruct((b, hd, sq), q.dtype),
         jax.ShapeDtypeStruct((b, hd, sk), k.dtype),
         jax.ShapeDtypeStruct((b, hd, sk), v.dtype)],
        scratch, operands, interpret, n_heads=h, block_q=block_q,
        masked=mask is not None)
    return _heads_last(dq, h), _heads_last(dk, h), _heads_last(dv, h)


# ---------------------------------------------------------------------------
# Pallas kernels for a mask stated by structure (CausalMask)
#
# The same feature-major operands and transposed score tile as above, with
# three differences that long sequences force. (1) No mask array: a tile's
# limits come from its positions, ``0 <= i - j < window``, made from two
# iotas and one scalar. (2) A program is one batch row, ONE query head and
# one tile; the head's K/V head (``h // group``) is picked by the block
# index, so grouped heads cost no copy, and what is resident in VMEM is one
# head of the other sequence: (D, S), 2 MiB at S 8192 and D 128, whatever
# the number of heads. (3) The walk over the resident sequence covers only
# the tiles the structure lets through: the others are skipped, not masked,
# and of those walked only the ones an edge crosses pay for the iota compare.
# (3') A window no wider than a tile: ops/window_attention.py, no walk at all.


@dataclasses.dataclass(frozen=True)
class CausalMask:
    """A mask stated by structure: key ``j`` is seen by query ``i`` iff
    ``0 <= i - j`` and (``window`` is None or ``i - j < window``). For self
    attention (as many keys as queries, positions 0..S-1)."""

    window: Optional[int] = None

    def dense(self, sq: int, sk: int):
        """The (1, sq, sk) boolean array that says the same."""
        diff = jnp.arange(sq)[:, None] - jnp.arange(sk)[None, :]
        seen = diff >= 0
        if self.window is not None:
            seen &= diff < self.window
        return seen[None]


def _cdiv(a, b: int):
    return jax.lax.div(a + (b - 1), b)


def _walk_ranges(first, last_excl, full_first, full_last_excl):
    """(lo, full_lo, full_hi, hi): tiles [lo, hi) are walked, those in
    [full_lo, full_hi) need no mask."""
    full_lo = jnp.clip(full_first, first, last_excl)
    full_hi = jnp.clip(full_last_excl, full_lo, last_excl)
    return first, full_lo, full_hi, last_excl


def _k_tiles_of(q0, bq: int, bk: int, n_k: int, window):
    """K tiles that queries q0..q0+bq-1 see."""
    hi = jnp.minimum(n_k, _cdiv(q0 + bq, bk))
    full_hi = jax.lax.div(q0 + 1, bk)
    if window is None:
        return _walk_ranges(0, hi, 0, full_hi)
    lo = jax.lax.div(jnp.maximum(q0 - window + 1, 0), bk)
    full_lo = _cdiv(jnp.maximum(q0 + bq - window, 0), bk)
    return _walk_ranges(lo, hi, full_lo, full_hi)


def _q_tiles_of(k0, bk: int, bq: int, n_q: int, window):
    """Q tiles that see keys k0..k0+bk-1."""
    lo = jax.lax.div(k0, bq)
    full_lo = _cdiv(k0 + bk - 1, bq)
    if window is None:
        return _walk_ranges(lo, n_q, full_lo, n_q)
    hi = jnp.minimum(n_q, _cdiv(k0 + bk + window - 1, bq))
    return _walk_ranges(lo, hi, full_lo, jax.lax.div(k0 + window, bq))


def _seen(rel, shift, window):
    """rel + shift = i - j on a (Bk, Bq) tile -> which pairs are seen."""
    diff = rel + shift
    seen = diff >= 0
    return seen if window is None else seen & (diff < window)


def _rel(bk: int, bq: int):
    """(query's offset in its tile) - (key's offset in its tile), (Bk, Bq)."""
    return (jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
            - jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0))


def _walk3(ranges, body, init):
    """Masked edge, unmasked middle, masked edge: three loops whose bounds
    the program's place in the grid decides."""
    lo, full_lo, full_hi, hi = ranges
    c = jax.lax.fori_loop(lo, full_lo, functools.partial(body, True), init)
    c = jax.lax.fori_loop(full_lo, full_hi, functools.partial(body, False), c)
    return jax.lax.fori_loop(full_hi, hi, functools.partial(body, True), c)


def _causal_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                       window):
    """One (batch row, query head, q tile) program.

    Shapes in VMEM: q (1, Dqk, Bq); k (1, Dqk, Sk) and v (1, Dv, Sk), the
    head's K/V head; o (1, Dv, Bq); lse (1, 1, 1, Bq) float32.
    """
    d, bq = o_ref.shape[1], q_ref.shape[2]
    q0 = pl.program_id(2) * bq
    rel = _rel(block_k, bq)
    q = q_ref[0]

    def fold(masked, i, carry):
        m, l, acc = carry
        ks = _tile(i, block_k)
        st = _dot(k_ref[0, :, ks].T, q)                     # (Bk, Bq)
        if masked:
            st = jnp.where(_seen(rel, q0 - i * block_k, window), st,
                           _NEG_BIG)
        m_new = jnp.maximum(
            jnp.maximum(m, jnp.max(st, axis=0, keepdims=True)),
            0.5 * _NEG_BIG)
        alpha = jnp.exp(m - m_new)
        pt = jnp.exp(st - m_new)
        l_new = alpha * l + jnp.sum(pt, axis=0, keepdims=True)
        vt = v_ref[0, :, ks]                                # (Dv, Bk)
        return m_new, l_new, alpha * acc + _dot(vt, pt.astype(vt.dtype))

    m, l, acc = _walk3(
        _k_tiles_of(q0, bq, block_k, k_ref.shape[2] // block_k, window),
        fold, (jnp.full((1, bq), -jnp.inf, jnp.float32),
               jnp.zeros((1, bq), jnp.float32),
               jnp.zeros((d, bq), jnp.float32)))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)),
                              jnp.inf)


def _causal_bwd_kernel(q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref,
                       dq_ref, dk_ref, dv_ref, acc_ref, *, block_q: int,
                       window):
    """One (batch row, query head, k tile) program: this head's share of
    the tile's dK and dV (the heads of a group are summed outside), and
    the tile's share of the head's dQ, summed over the K tiles of the head
    in a float32 scratch as ``_flash_bwd_kernel`` does.

    Shapes in VMEM: k (1, Dqk, Bk), v (1, Dv, Bk); dk, dv likewise, float32;
    q, dq (1, Dqk, Sq), dO (1, Dv, Sq); lse, delta (1, 1, 1, Sq); dq
    scratch (Dqk, Sq) float32.
    """
    bk = k_ref.shape[2]
    k0 = pl.program_id(2) * bk
    rel = _rel(bk, block_q)

    @pl.when(pl.program_id(2) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kt = k_ref[0]                                           # (Dqk, Bk)
    kb = kt.T
    vb = v_ref[0].T

    def body(masked, i, carry):
        dk, dv = carry
        qs = _tile(i, block_q)
        qt = q_ref[0, :, qs]                                # (Dqk, Bq)
        gt = g_ref[0, :, qs]                                # (Dv, Bq)
        st = _dot(kb, qt)                                   # (Bk, Bq)
        if masked:
            st = jnp.where(_seen(rel, i * block_q - k0, window), st,
                           _NEG_BIG)
        pt = jnp.exp(st - lse_ref[0, 0, :, qs])
        dst = (pt * (_dot(vb, gt) - delta_ref[0, 0, :, qs])).astype(qt.dtype)
        acc_ref[:, qs] += _dot(kt, dst)                     # dQ.T (Dqk, Bq)
        return (dk + _dot(qt, dst, _NT),                    # dK.T (Dqk, Bk)
                dv + _dot(gt, pt.astype(gt.dtype), _NT))    # dV.T (Dv, Bk)

    dk, dv = _walk3(
        _q_tiles_of(k0, bk, block_q, q_ref.shape[2] // block_q, window),
        body, (jnp.zeros((k_ref.shape[1], bk), jnp.float32),
               jnp.zeros((v_ref.shape[1], bk), jnp.float32)))
    dk_ref[0] = dk
    dv_ref[0] = dv

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


_causal_jit = functools.partial(
    jax.jit, static_argnames=("window", "block_q", "block_k", "interpret"))


@_causal_jit
def _causal_forward(q, k, v, window, block_q, block_k, interpret):
    """(out, lse) under a CausalMask. q (B, S, H, Dqk); k (B, S, Hkv, Dqk),
    v (B, S, Hkv, Dv), query head h reading K/V head h // (H // Hkv); out
    (B, S, H, Dv); S pre-padded."""
    b, s, h, dqk = q.shape
    dv = v.shape[3]
    group = h // k.shape[2]
    at_q = lambda i, hh, j: (i, hh, j)            # noqa: E731
    at_kv = lambda i, hh, j: (i, hh // group, 0)  # noqa: E731
    out, lse = _call(
        _causal_fwd_kernel, "flash_fwd", (b, h, s // block_q),
        ("parallel", "parallel", "parallel"),
        [pl.BlockSpec((1, dqk, block_q), at_q),
         pl.BlockSpec((1, dqk, s), at_kv), pl.BlockSpec((1, dv, s), at_kv)],
        [pl.BlockSpec((1, dv, block_q), at_q),
         pl.BlockSpec((1, 1, 1, block_q), lambda i, hh, j: (i, hh, 0, j))],
        [jax.ShapeDtypeStruct((b, h * dv, s), q.dtype),
         jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32)],
        [], [_feature_major(q), _feature_major(k), _feature_major(v)],
        interpret, block_k=block_k, window=window)
    return _heads_last(out, h), lse


@_causal_jit
def _causal_backward(q, k, v, out, lse, g, window, block_q, block_k,
                     interpret):
    """(dq, dk, dv) under a CausalMask. Shapes as ``_causal_forward``."""
    b, s, h, dqk = q.shape
    hkv, dv_rows = k.shape[2], v.shape[3]
    group = h // hkv
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)[:, :, None]  # (B, H, 1, S)
    whole = lambda rows: pl.BlockSpec(  # noqa: E731
        (1, rows, s), lambda i, hh, j: (i, hh, 0))
    kv_spec = lambda rows: pl.BlockSpec(  # noqa: E731
        (1, rows, block_k), lambda i, hh, j: (i, hh // group, j))
    stat = pl.BlockSpec((1, 1, 1, s), lambda i, hh, j: (i, hh, 0, 0))
    tile = lambda rows: pl.BlockSpec(  # noqa: E731
        (1, rows, block_k), lambda i, hh, j: (i, hh, j))
    dq, dk, dv = _call(
        _causal_bwd_kernel, "flash_bwd", (b, h, s // block_k),
        ("parallel", "parallel", "arbitrary"),
        [whole(dqk), whole(dv_rows), kv_spec(dqk), kv_spec(dv_rows), stat,
         stat], [whole(dqk), tile(dqk), tile(dv_rows)],
        [jax.ShapeDtypeStruct((b, h * dqk, s), q.dtype),
         jax.ShapeDtypeStruct((b, h * dqk, s), jnp.float32),
         jax.ShapeDtypeStruct((b, h * dv_rows, s), jnp.float32)],
        [(dqk, s)],
        [_feature_major(q), _feature_major(g), _feature_major(k),
         _feature_major(v), lse, delta],
        interpret, block_q=block_q, window=window)

    def group_sum(x, like):
        """A K/V head's gradient: the sum over the query heads reading it."""
        d = like.shape[3]
        x = x.reshape(b, hkv, group, d, s).sum(axis=2).astype(like.dtype)
        return _heads_last(x.reshape(b, hkv * d, s), hkv)

    return _heads_last(dq, h), group_sum(dk, k), group_sum(dv, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_causal(q, k, v, window, block_q, block_k, interpret):
    return _flash_causal_fwd(q, k, v, window, block_q, block_k,
                             interpret)[0]


def _kept(out, lse):
    """The two residuals only the kernel can make, under the names a
    rematerialised block keeps them by (:data:`REMAT_KEEPS`). Named inside
    the forward RULE: a name on ``attend``'s return value would keep
    ``out`` and still run the kernel again, for ``lse``."""
    return tuple(checkpoint_name(x, name)
                 for x, name in zip((out, lse), REMAT_KEEPS))


@trace.scope("attention.core")
def _flash_causal_fwd(q, k, v, window, block_q, block_k, interpret):
    out, lse = _kept(*_causal_forward(q, k, v, window, block_q, block_k,
                                      interpret))
    return out, (q, k, v, out, lse)


@trace.scope("attention.core")
def _flash_causal_bwd(window, block_q, block_k, interpret, residuals, g):
    q, k, v, out, lse = residuals
    return _causal_backward(q, k, v, out, lse, g, window, block_q, block_k,
                            interpret)


_flash_causal.defvjp(_flash_causal_fwd, _flash_causal_bwd)


@dataclasses.dataclass(frozen=True)
class SelectedMask:
    """A mask stated by a selection: *causal and selected*, a key set of its
    own for every query row, the same for all heads (ops/sparse_index.py
    makes one from an indexer's scores). ``bits`` (B, S_p / 32, S_p) int32
    holds a bit a (key, query) pair, S_p the length padded to ``block``
    tiles: key ``r`` of key tile ``T`` is bit ``r // (block / 32)`` of word
    ``T * (block / 32) + r % (block / 32)`` in query ``i``'s column, so a
    kernel's (block / 32, Bq) slab of words unpacks into its (block, Bq)
    score tile with one shift a row (ops/selected_attention.py). 32 MB at
    16 384 x 16 384 where a dense float mask is 1 GB. No bit is set for a
    key after its query or for a padded key or query."""

    bits: jax.Array
    block: int

    def dense(self, sq: int, sk: int):
        """The (B, sq, sk) boolean array that says the same."""
        b, words, s_p = self.bits.shape
        per_tile = self.block // 32
        w = self.bits.transpose(0, 2, 1).reshape(
            b, s_p, words // per_tile, 1, per_tile)
        bit = jnp.arange(32, dtype=jnp.int32)[:, None]
        return (((w >> bit) & 1) != 0).reshape(b, s_p, s_p)[:, :sq, :sk]


@dataclasses.dataclass(frozen=True)
class LatentKV:
    """K and V of a latent layer where its matmuls leave them, feature-major
    (ops/latent_attention.py reads them in place): ``kv`` (B, H * (nope +
    v), S), a head's ``nope`` rows of keys above its v rows of values, and
    ``shared`` (B, rope, S), the one rotary key every head's scores add.
    Takes k's place in :func:`flash_attention` with q (B, H * (nope +
    rope), S) and no v, under a ``CausalMask()``, and the output comes
    back (B, H * v, S)."""

    kv: jax.Array
    shared: jax.Array
    nope: int


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
    acc0 = jnp.zeros((b, h, sq, v.shape[3]), jnp.float32)
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
    dv = jnp.moveaxis(dvs, 0, 2).reshape(b, h, sk, v.shape[3])
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
    out, lse = _kept(out, lse)
    return out, (q, k, v, mask, key, out, lse)


@trace.scope("attention.core")  # a backward rule has no forward name stack
def _flash_bwd_rule(dropout_rate, block_q, block_k, impl, interpret,
                    residuals, g):
    q, k, v, mask, key, out, lse = residuals
    if impl == "pallas" and dropout_rate == 0.0:
        # the pallas forward never carries dropout (flash_attention raises
        # on it), so the pallas backward needs no mask replay
        dq, dk, dv = _pallas_backward(
            q, k, v, mask, out, lse, g, block_q, block_k, interpret
        )
    else:
        dq, dk, dv = _chunked_backward(
            q, k, v, mask, key, out, lse, g, block_k, dropout_rate
        )
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _narrowest(q, k, v):
    """The kernels' matmul operands are as wide as the narrowest of q, k,
    v (a model's scaled q may be float32 beside bfloat16 k and v)."""
    return min((q.dtype, k.dtype, v.dtype),
               key=lambda t: jnp.dtype(t).itemsize)


def _plain_operands(q, k, v, mask):
    """What the paths that know neither grouped heads nor a structural mask
    take: each K/V head repeated for the query heads that read it, and the
    mask as a dense array."""
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    if isinstance(mask, (CausalMask, SelectedMask)):
        mask = jnp.broadcast_to(mask.dense(q.shape[1], k.shape[1]),
                                (q.shape[0], q.shape[1], k.shape[1]))
    return q, k, v, mask


@trace.scope("attention.core")
def _reference_attention(q, k, v, mask, dropout_rate=0.0, dropout_key=None):
    """Plain XLA attention (f32 softmax) — the O(S²)-HBM fallback/oracle."""
    q, k, v, mask = _plain_operands(q, k, v, mask)
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
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    impl: str = "pallas",
    interpret: bool = False,
) -> jnp.ndarray:
    """Blocked online-softmax attention with a blockwise backward.

    q: (B, Sq, H, D) — pre-scaled (multiply by 1/sqrt(D) before calling);
    k, v: (B, Sk, Hkv, D), H a multiple of Hkv and query head h reading
    K/V head h // (H // Hkv). Under a :class:`CausalMask`, and on the
    chunked route under any mask, v may have a width of its own, the
    output's (a latent layer's 192-wide q.k beside 128-wide values: no
    operand is padded to a common width). mask: optional (B, Sq, Sk) bool, True =
    attend (shared across heads), or a :class:`CausalMask`, which the
    Pallas route computes from positions, skipping the tiles it hides, or
    a :class:`SelectedMask`, whose packed bits the Pallas route unpacks
    tile by tile (its own kernels; the other routes take it dense);
    dropout_rate applies to attention probabilities
    (chunked impl only) with dropout_key. Irregular Sq/Sk are padded to
    block multiples with masked tails; ``block_q`` / ``block_k`` left at
    None follow the shapes. Returns (B, Sq, H, D) in q's dtype.

    ``interpret=True`` runs the Pallas kernels in the interpreter (tests
    off the chip). It is never chosen for the caller: ``impl="pallas"``
    compiles through Mosaic or raises with the compiler's message.
    """
    if isinstance(k, LatentKV):
        if impl != "pallas" or mask != CausalMask() or dropout_rate:
            raise ValueError("a latent layer's operands are read in place "
                             "by the causal Pallas kernels alone")
        from metaopt_tpu.ops.latent_attention import flash_latent

        return flash_latent(q, k, bool(interpret))
    if dropout_rate > 0.0 and impl == "pallas":
        raise ValueError("attention dropout requires impl='chunked'")
    if dropout_rate > 0.0 and dropout_key is None:
        raise ValueError("dropout_rate > 0 needs a dropout_key")
    if q.shape[2] % k.shape[2] or k.shape[2] != v.shape[2]:
        raise ValueError(f"{q.shape[2]} query heads cannot share "
                         f"{k.shape[2]} K/V heads")
    if q.shape[3] != k.shape[3]:
        raise ValueError(f"q is {q.shape[3]} wide, its keys {k.shape[3]}")
    if impl == "pallas" and v.shape[3] != q.shape[3] \
            and not isinstance(mask, CausalMask):
        raise ValueError("q.k and v of different widths have Pallas "
                         "kernels under a CausalMask alone")

    b, sq, h, d = q.shape
    sk = k.shape[1]
    if isinstance(mask, SelectedMask) and impl == "pallas":
        from metaopt_tpu.ops.selected_attention import flash_selected

        return flash_selected(q, k, v, mask, bool(interpret))
    if isinstance(mask, CausalMask) and impl == "pallas":
        if sq != sk:
            raise ValueError("a CausalMask is for self attention: "
                             f"{sq} queries against {sk} keys")
        # a padded key lies after every real query, so causality hides it
        bq, s_p = (_derived_block(sq) if block_q is None
                   else _block_and_pad(sq, block_q))
        bk = bq if block_k is None else block_k
        if s_p % bk:
            raise ValueError(f"block_k {bk} does not divide the padded "
                             f"length {s_p}")
        flash_kernels = _causal_kernels(mask.window, bq, bk, s_p)
        pad = lambda x: jnp.pad(x.astype(_narrowest(q, k, v)), (  # noqa: E731
            (0, 0), (0, s_p - sq), (0, 0), (0, 0)))
        out = flash_kernels(pad(q), pad(k), pad(v), mask.window, bq, bk,
                            bool(interpret))
        return out[:, :sq].astype(q.dtype)
    # the paths below know one head count and a dense mask
    q, k, v, mask = _plain_operands(q, k, v, mask)
    # blocks follow the shapes unless the caller names them: the Pallas
    # kernels take the largest tile the lengths allow, the chunked scan
    # its 128-wide K blocks
    derived = _derived_block if impl == "pallas" else (
        lambda size: _block_and_pad(size, 128))
    bq, sq_p = (derived(sq) if block_q is None
                else _block_and_pad(sq, block_q))
    bk, sk_p = (derived(sk) if block_k is None
                else _block_and_pad(sk, block_k))
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
    out_dtype = q.dtype
    if impl == "pallas":
        # the kernels' matmul operands are as wide as the narrowest of
        # q, k, v (MHA's scaled q is float32 beside bfloat16 k and v)
        narrow = _narrowest(q, k, v)
        q, k, v = q.astype(narrow), k.astype(narrow), v.astype(narrow)
    out = _flash(q, k, v, mask, dropout_key, float(dropout_rate), bq, bk,
                 impl, bool(interpret))
    return out[:, :sq].astype(out_dtype)


def sharded_flash_attention(
    mesh,
    q, k, v,
    mask=None,
    *,
    dropout_rate: float = 0.0,
    dropout_key=None,
    impl: str = "pallas",
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

    # a structural mask is no array: it rides in the closure
    by_structure = mask if isinstance(mask, CausalMask) else None
    if by_structure is not None:
        mask = None

    def local(q, k, v, mask, key):
        mask = by_structure or mask
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


#: What a rematerialised block keeps besides its input (the models'
#: ``remat`` asks for these names and no others): of a custom VJP's
#: residuals the two that cost the whole kernel to make again, ``out``
#: ((2 H D) bytes a token in bfloat16) and ``lse`` (4 H). q, k and v are a
#: norm, three projections and rotary away and are made again. Without a
#: policy the names are identities; on the routes that never reach the
#: rules (reference, ring, Ulysses) they do not occur and nothing is kept.
#: ``selected`` is a :class:`SelectedMask`'s packed bits (S^2 / 8 bytes a
#: row), which the backward kernel reads and which cost the indexer's scores
#: and an exact top-k to make again; it occurs only where a layer selects.
REMAT_KEEPS = ("attention.out", "attention.lse", "attention.selected")


def attention_route(dropout_rate: float, mesh=None) -> str:
    """The route a call takes, from what the call can see: the one place
    that decides (:func:`attend` and ``trial.setup``'s span both ask).

    An ``sp`` axis larger than 1 in ``mesh`` -> ``"ring"``, or ``"ulysses"``
    where ``METAOPT_TPU_SP_IMPL`` says so (ops/ulysses.sp_impl: neither has
    a chip record yet, ROADMAP D2); a backend other than the TPU ->
    ``"reference"``, the plain XLA attention, faster at test shapes and
    numerically the oracle; dropout -> ``"chunked"``, whose masks replay
    bit-exactly in the backward (the kernels carry no dropout RNG); else
    ``"pallas"``, the kernels that keep every score tile in VMEM (PERF.md
    has their device times against the chunked twin).
    """
    if mesh is not None and dict(mesh.shape).get("sp", 1) > 1:
        from metaopt_tpu.ops.ulysses import sp_impl

        return sp_impl()
    if jax.default_backend() != "tpu":
        return "reference"
    return "chunked" if dropout_rate > 0.0 else "pallas"


def attend(q, k, v, mask=None, *, dropout_rate: float = 0.0,
           dropout_key=None):
    """The models' one door into attention: what :func:`attention_route`
    names for this call under the ambient mesh. Operands and mask as
    :func:`flash_attention` takes them.

    On a trial mesh of more than one device the kernels run under
    ``shard_map`` (:func:`sharded_flash_attention`: batch on dp, heads on
    tp, so the Megatron head split stays local to each shard instead of
    GSPMD all-gathering q/k/v); the plain reference never does.
    """
    mesh = active_mesh()
    route = attention_route(dropout_rate, mesh)
    if route in ("ring", "ulysses"):
        # sequence-parallel mesh, the long-context path: K/V ride the ICI
        # ring (lowest per-chip memory), or Ulysses' all-to-all exchange of
        # heads for sequence (fewer collectives, needs per-device heads %
        # sp == 0). Those modules import this one.
        if isinstance(mask, (CausalMask, SelectedMask)):
            raise ValueError("the pattern's attention has no sequence-"
                             "parallel route: drop sp from the trial mesh")
        sp = mesh.shape["sp"]
        if q.shape[1] % sp or k.shape[1] % sp:
            # never silently fall back to sp-replicated attention: the
            # user asked for sequence sharding, and the fallback would
            # quietly pay the full O(S²) memory on every chip
            raise ValueError(
                f"seq lengths (q={q.shape[1]}, kv={k.shape[1]}) must be "
                f"multiples of the sp mesh axis ({sp}); pad the batch "
                f"or drop sp from the trial mesh"
            )
        if route == "ulysses":
            from metaopt_tpu.ops.ulysses import (
                ulysses_attention as sequence_parallel)
        else:
            from metaopt_tpu.ops.ring_attention import (
                ring_attention as sequence_parallel)
        return sequence_parallel(q, k, v, mask, mesh=mesh,
                                 dropout_rate=dropout_rate,
                                 dropout_key=dropout_key)
    if route == "reference":
        return _reference_attention(q, k, v, mask, dropout_rate, dropout_key)
    if mesh is not None and mesh.size > 1:
        if isinstance(mask, SelectedMask):
            raise ValueError("a selected mask has no route over a mesh of "
                             f"{mesh.size} devices yet: run the trial on one")
        return sharded_flash_attention(
            mesh, q, k, v, mask, dropout_rate=dropout_rate,
            dropout_key=dropout_key, impl=route)
    return flash_attention(q, k, v, mask, dropout_rate=dropout_rate,
                           dropout_key=dropout_key, impl=route)


def _causal_kernels(window, block_q: int, block_k: int, length: int):
    """The kernels a :class:`CausalMask` call takes, as ``_flash_causal``
    is called: the walk over the tiles the structure lets through, or, for
    a window no wider than a tile, the slab kernels
    (ops/window_attention.py::slab_sub is the one rule; down here because
    a moved line above :func:`attend` moves every kernel's body)."""
    if window is not None and block_q == block_k:
        from metaopt_tpu.ops import window_attention

        if window_attention.slab_sub(window, block_q, length):
            return window_attention.flash_window
    return _flash_causal
