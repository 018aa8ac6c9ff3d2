"""An indexer's scores and the exact top-k selection made from them.

For attention over the keys a learned indexer selects for each query (the
DeepSeek-Sparse-Attention form): index heads ``j`` with queries ``q[t, j]``,
one key head ``k[s]`` and per-query head weights ``w[t, j]`` give

    I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])          for s <= t,

and query ``t`` attends to ``S_t``, the ``min(top_k, t + 1)`` keys ``s <= t``
with the largest ``I[t, s]``, ties to the lower index: what
``jax.lax.top_k`` gives. :func:`select` returns the selection as an
``attention.SelectedMask`` (packed bits) and counts its pairs.

Everything is float32 at matmul precision highest: the choice hangs on the
scores' last bits, as a router's does. No gradient passes: the selection
is piecewise constant in what made it.

**How the top-k is found.** A sort of 16 384 candidates for each of 16 384
rows costs more than the attention it thins (a bitonic sort makes ~100
passes over 268 M (value, index) pairs). So the k-th largest score of a
row is found by counting: the scores are mapped onto unsigned integers of
the same order, and the threshold is built bit by bit, from the top, as
the largest value that at least ``top_k`` of the row's causal scores reach
(32 passes of compare-and-count over the row block, :func:`_kth_largest`);
what lies above it is selected, and of the scores equal to it the first by
index until the count is full (a prefix sum, made only when a row has such
a tie). Rows come in blocks of ``ROWS`` queries, a head at a time, so that
what is live is two (ROWS, S) arrays and not the (S, 16, S) scores of all
heads (17 GB at 16 384); a block is scored against the keys up to the end
of its quarter of the rows, not beyond (62 % of the square).

**How a block is scored.** Two forms of one sum, and one rule that names
which runs (:func:`index_scores_route`, from the backend and the shapes
alone). The plain form is a loop of XLA's products over the heads. On the
TPU, where a tile divides the block, it is one Pallas kernel,
``index_scores``: a program owns a (query tile, key tile) of the scores,
adds the heads' ``w relu(q . k)`` into it on the chip and writes it once;
a key tile that starts after the query tile's last row is written as zeros
(:func:`select_top_k` masks what lies after a query before it reads a
score). A float32 product at precision highest is the six bfloat16 products
``hh + hm + mh + mm + hl + lh`` of the operands' three parts (``x = hi + mid
+ lo``); the kernel's operands are those parts laid side by side along the
contraction (:func:`_stacked`), so that one bfloat16 product with float32
accumulation, 6 x 64 = 384 deep, makes all six at the MXU's full depth.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from metaopt_tpu.ops.attention import REMAT_KEEPS, SelectedMask
from metaopt_tpu.ops.selected_attention import selected_block
from metaopt_tpu.utils import trace

_HI = jax.lax.Precision.HIGHEST
#: query rows a block: a multiple of the kernels' tile
ROWS = 1024


#: the kernel's tile of scores, (queries, keys)
TILE = 512
#: which of an operand's (hi, mid, lo) parts stand side by side, so that
#: the stacked queries times the stacked keys are hh + mm + hm + mh + hl + lh
_Q_PARTS, _K_PARTS = (0, 1, 0, 1, 0, 2), (0, 1, 1, 0, 2, 0)


def index_scores_route(rows: int, keys: int, width: int) -> dict:
    """Which form of :func:`index_scores` scores ``rows`` queries of index
    heads ``width`` wide against ``keys`` keys: the one place that decides
    (:func:`index_scores` and ``trial.setup``'s span both ask). On a TPU,
    where the tile divides both extents and the six parts of a head fill
    whole passes of the MXU's depth, the Pallas kernel, with its tiles and
    the depth of a pass; elsewhere XLA's products."""
    if (jax.default_backend() == "tpu" and rows % TILE == 0
            and keys % TILE == 0 and 6 * width % 128 == 0):
        return {"route": "pallas", "tiles": [TILE, TILE], "depth": 128}
    return {"route": "xla"}


def index_scores(q, k, w, first_row=0):
    """``I`` (R, E) of query rows ``q`` (R, H, D), ``w`` (R, H) against
    keys ``k`` (E, D), by the form :func:`index_scores_route` names.
    ``first_row`` is the first query's position among the keys: what lies
    after a query's own position is 0 or its score, the caller's to mask."""
    if index_scores_route(q.shape[0], k.shape[0], q.shape[2])[
            "route"] == "pallas":
        return _scores_pallas(q, k, w, first_row)
    return _scores_xla(q, k, w)


def _scores_xla(q, k, w):
    """One head's (R, E) product at a time, the accumulator an array."""
    heads = jnp.moveaxis(q, 1, 0)                           # (H, R, D)
    weights = w.T                                           # (H, R)

    def add_head(j, acc):
        s = jnp.dot(heads[j], k.T, precision=_HI)
        return acc + weights[j][:, None] * jax.nn.relu(s)

    scores = jax.lax.fori_loop(
        0, heads.shape[0], add_head,
        jnp.zeros((q.shape[0], k.shape[0]), jnp.float32))
    # -0.0 and 0.0 are one score (all heads clipped): one bit pattern
    return jnp.where(scores == 0, 0.0, scores)


def _stacked(x, parts):
    """float32 (..., D) -> bfloat16 (..., 6 D): the three bfloat16 parts
    of ``x`` that a product at precision highest multiplies, in the order
    ``parts``. ``reduce_precision`` rounds as the conversion does and no
    pass of the compiler may take it for the identity."""
    hi = jax.lax.reduce_precision(x, 8, 7)
    mid = jax.lax.reduce_precision(x - hi, 8, 7)
    lo = x - hi - mid
    return jnp.concatenate([(hi, mid, lo)[i] for i in parts],
                           axis=-1).astype(jnp.bfloat16)


def _last_seen(i, first_ref, tq: int, tk: int):
    """The last key tile that a row of query tile ``i`` can see."""
    return (first_ref[0] + (i + 1) * tq - 1) // tk


def _index_scores_kernel(first_ref, q_ref, k_ref, w_ref, o_ref):
    """One (query tile, key tile) program: the heads' sum stays here.

    Shapes in VMEM: q (Tq, H * 6 D) bfloat16, head after head; k (6 D, Tk)
    bfloat16; w (Tq, H) float32; o (Tq, Tk) float32. ``first_ref`` (1,)
    int32, in SMEM: the block's first row.
    """
    tq, tk = o_ref.shape
    depth = k_ref.shape[0]
    seen = pl.program_id(1) <= _last_seen(pl.program_id(0), first_ref, tq,
                                          tk)

    @pl.when(seen)
    def _():
        k = k_ref[...]
        acc = jnp.zeros((tq, tk), jnp.float32)
        for j in range(w_ref.shape[1]):
            s = jnp.dot(q_ref[:, j * depth:(j + 1) * depth], k,
                        preferred_element_type=jnp.float32)
            acc = acc + w_ref[:, j:j + 1] * jnp.maximum(s, 0.0)
        # -0.0 and 0.0 are one score (all heads clipped): one bit pattern
        o_ref[...] = jnp.where(acc == 0, 0.0, acc)

    @pl.when(jnp.logical_not(seen))
    def _():
        o_ref[...] = jnp.zeros((tq, tk), jnp.float32)


# jitted, so that the call sites of one shape (a group of blocks in every
# layer) share one trace and one lowering of the Mosaic kernel
@functools.partial(jax.jit, static_argnames=("interpret",))
def _scores_pallas(q, k, w, first_row, interpret: bool = False):
    """:func:`index_scores` by the kernel; extents as the route asks."""
    r, h, d = q.shape
    e, depth = k.shape[0], 6 * d
    tq = tk = TILE
    seen = lambda i, j, first: (  # noqa: E731  no copy of an unseen tile
        0, jnp.minimum(j, _last_seen(i, first, tq, tk)))
    # the blocks twice (w's lanes padded to a tile), a head's product and
    # the sum beside them
    vmem = 2 * (2 * (tq * h * depth + depth * tk) + 4 * (tq * 128 + tq * tk)) \
        + 8 * 4 * tq * tk
    return pl.pallas_call(
        _index_scores_kernel, name="index_scores",
        out_shape=jax.ShapeDtypeStruct((r, e), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(r // tq, e // tk),
            in_specs=[
                pl.BlockSpec((tq, h * depth), lambda i, j, first: (i, 0)),
                pl.BlockSpec((depth, tk), seen),
                pl.BlockSpec((tq, h), lambda i, j, first: (i, 0))],
            out_specs=pl.BlockSpec((tq, tk), lambda i, j, first: (i, j))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
    )(jnp.asarray(first_row, jnp.int32).reshape(1),
      _stacked(q, _Q_PARTS).reshape(r, h * depth),
      _stacked(k, _K_PARTS).T, w)


def _ordered(x):
    """float32 -> uint32 in the same order (no NaN among the scores)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _kth_largest(u, k: int):
    """(R,) uint32: the largest ``v`` that at least ``k`` of a row of ``u``
    (R, E) reach, 0 where fewer than ``k`` are above 0."""

    def bit(i, prefix):
        reach = prefix | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        count = jnp.sum(u >= reach[:, None], axis=1, dtype=jnp.int32)
        return jnp.where(count >= k, reach, prefix)

    return jax.lax.fori_loop(0, 32, bit,
                             jnp.zeros((u.shape[0],), jnp.uint32))


def select_top_k(scores, first_row: int, top_k: int):
    """(R, E) bool: for query ``t = first_row + r`` the ``min(top_k, t +
    1)`` keys ``s <= t`` with the largest ``scores[r, s]``, ties to the
    lower index; never a key after ``t``."""
    r, e = scores.shape
    t = first_row + jnp.arange(r)[:, None]
    causal = jnp.arange(e)[None, :] <= t
    u = jnp.where(causal, _ordered(scores), 0)    # a causal score is above 0
    least = _kth_largest(u, top_k)[:, None]
    reach = u >= least
    above = u > least

    def first_of_the_ties(_):
        tied = reach & ~above
        room = top_k - jnp.sum(above, axis=1, keepdims=True, dtype=jnp.int32)
        return above | (tied & (jnp.cumsum(tied, axis=1, dtype=jnp.int32)
                                <= room))

    over = jnp.sum(reach, axis=1, dtype=jnp.int32) > top_k
    chosen = jax.lax.cond(jnp.any(over & (t[:, 0] >= top_k)),
                          first_of_the_ties, lambda _: reach, None)
    return jnp.where(t < top_k, causal, chosen & causal)


def pack(selected, block: int):
    """(R, E) bool, E a multiple of ``block`` -> (E / 32, R) int32 in
    ``SelectedMask``'s layout."""
    r, e = selected.shape
    per_tile = block // 32
    tiles = selected.reshape(r, e // block, 32, per_tile)
    bit = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))[:, None]
    words = jnp.sum(jnp.where(tiles, bit, jnp.uint32(0)), axis=2,
                    dtype=jnp.uint32)             # (R, E / block, per_tile)
    return jax.lax.bitcast_convert_type(words, jnp.int32).reshape(
        r, e // 32).T


def _blocks(block: int, s_p: int) -> tuple:
    """(query rows a block, rows a group of blocks) of ``s_p`` positions
    packed in tiles of ``block``: a group's extent is every later one's
    divisor."""
    rows = ROWS if ROWS % block == 0 and s_p % ROWS == 0 else block
    return rows, rows * -(-s_p // rows // 4)


def scores_of_a_row(size: int, width: int) -> dict:
    """What :func:`index_scores_route` says of the blocks that a row of
    ``size`` tokens is scored in, index heads ``width`` wide."""
    return index_scores_route(*_blocks(*selected_block(size)), width)


def _one_row(q, k, w, top_k: int, block: int, s_p: int):
    """(s_p / 32, s_p) int32: one batch row's packed selection. The blocks
    of ``rows`` queries come in up to four groups; a group's blocks all see
    the keys up to the group's last row and are one compiled loop (a
    program a block, each with its own causal extent, compiles 14 bodies
    a layer at 16 384 and computes 15 % less)."""
    s = q.shape[0]
    grow = lambda x: jnp.pad(  # noqa: E731
        x, ((0, s_p - s),) + ((0, 0),) * (x.ndim - 1))
    q, k, w = grow(q), grow(k), grow(w)
    rows, group = _blocks(block, s_p)

    def block_of(start, hi):
        """(hi / 32, rows): queries start .. start + rows - 1, keys < hi."""
        with trace.scope("attention.index"):
            scores = index_scores(
                jax.lax.dynamic_slice_in_dim(q, start, rows), k[:hi],
                jax.lax.dynamic_slice_in_dim(w, start, rows), start)
        with trace.scope("attention.select"):
            chosen = select_top_k(scores, start, top_k)
            # a padded query selects nothing (a padded key lies after
            # every real query)
            chosen &= (start + jnp.arange(rows) < s)[:, None]
            return pack(chosen, block)

    slabs = []
    for lo in range(0, s_p, group):
        hi = min(lo + group, s_p)
        if hi <= top_k:                           # every row takes all it sees
            seen = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
            slab = pack(seen & (jnp.arange(lo, hi) < s)[:, None], block)
        else:
            slab = jax.lax.map(
                lambda start, hi=hi: block_of(start, hi),
                lo + rows * jnp.arange((hi - lo) // rows))
            slab = slab.transpose(1, 0, 2).reshape(hi // 32, hi - lo)
        slabs.append(jnp.pad(slab, ((0, (s_p - hi) // 32), (0, 0))))
    return jnp.concatenate(slabs, axis=1)


def select(q, k, w, top_k: int):
    """(``SelectedMask``, selected pairs () int32) from index queries ``q``
    (B, S, H, D), keys ``k`` (B, S, D) and head weights ``w`` (B, S, H),
    float32, positions already in them. The bits carry the name a
    rematerialised block keeps them by (``REMAT_KEEPS``)."""
    block, s_p = selected_block(q.shape[1])
    q, k, w = (jax.lax.stop_gradient(x.astype(jnp.float32))
               for x in (q, k, w))
    bits = jnp.stack([_one_row(q[i], k[i], w[i], top_k, block, s_p)
                      for i in range(q.shape[0])])
    bits = checkpoint_name(bits, REMAT_KEEPS[2])
    with trace.scope("attention.select"):
        pairs = jnp.sum(jax.lax.population_count(bits), dtype=jnp.int32)
    return SelectedMask(bits, block), pairs
