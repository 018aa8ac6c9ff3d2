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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from metaopt_tpu.ops.attention import REMAT_KEEPS, SelectedMask
from metaopt_tpu.ops.selected_attention import selected_block
from metaopt_tpu.utils import trace

_HI = jax.lax.Precision.HIGHEST
#: query rows a block: a multiple of the kernels' tile
ROWS = 1024


def index_scores(q, k, w):
    """``I`` (R, E) of query rows ``q`` (R, H, D), ``w`` (R, H) against
    keys ``k`` (E, D), causal or not: one head's (R, E) product at a time."""
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
    rows = ROWS if ROWS % block == 0 and s_p % ROWS == 0 else block
    group = rows * -(-s_p // rows // 4)

    def block_of(start, hi):
        """(hi / 32, rows): queries start .. start + rows - 1, keys < hi."""
        with trace.scope("attention.index"):
            scores = index_scores(
                jax.lax.dynamic_slice_in_dim(q, start, rows), k[:hi],
                jax.lax.dynamic_slice_in_dim(w, start, rows))
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
