"""Mixture-of-Experts feed-forward with expert parallelism ("ep").

Top-k routing with **capacity-bounded dispatch**: ``router_top_k=1`` is
Switch (Fedus et al. — gate = the chosen expert's raw probability);
``router_top_k=2`` is GShard-style top-2 (gates renormalized over the
chosen pair). Each expert processes at most ``capacity = ceil(
capacity_factor · k · T / E)`` dispatch items per step: the (token,
choice) pairs are scattered into per-expert slabs of that static shape,
the expert FFNs run as batched einsums over ``(E, capacity, d)``, and
results gather back and sum per token — FLOPs scale with
``capacity_factor · k · T``, not with ``E × T`` like a dense all-experts
dispatch. Items that overflow an expert's queue are dropped for the layer
(that choice contributes zero; the transformer's residual connection
carries the token through — standard Switch/GShard behavior) and counted
in the ``"moe_stats"`` collection.

Everything is static-shaped for XLA: capacity comes from the (static)
token count, queue positions are a cumsum over dispatch order, and
drop-vs-keep is a branchless scatter to an overflow slot that is sliced
away. Expert weights shard E/ep per chip via ``nn.with_partitioning``;
GSPMD inserts the token-shuffle collectives around the scatter/gather, the
analogue of the hand-written all_to_all in CUDA-era MoE stacks. Inside
each expert the hidden dim still splits over "tp", so ep composes with the
Megatron split.

The router adds the standard switch load-balancing auxiliary loss
(``n_experts · Σ_e fraction_e · mean_prob_e``, assignment fractions
averaged over the k choices), surfaced through the module's
``"aux_loss"`` collection so the train step can weigh it in; the
dropped-item fraction rides the ``"moe_stats"`` collection the same way.

``capacity_factor <= 0`` selects the dense dispatch — O(k·E·T) compute,
no dropping — kept as the numerics oracle the capacity path is tested
against.

ref: the reference framework has no model code (SURVEY.md §2.8) — this is
demo-zoo surface, here so trials can exercise expert-parallel shardings
on gang-scheduled sub-slices.

:class:`DroplessMoE` is the other expert layer, for the decoders whose
description names ``moe_num_primary_experts``: top-k on the router's
LOGITS and a softmax over the chosen ones or, by the description's
:class:`RoutingRule`, sigmoid scores chosen with a correction bias and
weighed without it, with shared experts beside the routed ones; gated experts of three matrices
(ReLU or SiLU on the gate, as the description says), and **no capacity**:
every (token, choice) item is computed, whatever the imbalance. It is told which experts it holds (a contiguous
share of the published count), routes over all of them, and returns the
part of the layer's output that its own experts give: the items whose
expert lives here are ordered by expert and go through the held experts'
part (:func:`_held_experts`, one function with a gradient rule of its own):
the gate's and the up matrix joined are ONE grouped product of ``2 f``
columns, the gating ``act(gate) * up`` a pass over the filled tiles, the
down matrix a second grouped product; backward two grouped products for the
rows (the one through the joined matrix sums the gate's and the up
product's input gradients in float32) and two for the weights, which
contract over the rows where they lie. The buffers have the static
worst-case size, all ``tokens x k`` items, which is what makes the layer
dropless; **every pass over them has the size of what is filled**: dispatch,
combine and the backward of each are loops over chunks of rows whose trip
count is read from the count of held items (:func:`routing_plan`), up to
the buffers' end under the worst imbalance, and leave zeros behind the
filled rows; the experts' passes visit the tiles that hold a filled row and
leave the rest as the memory held it. On an ``ep`` mesh axis each chip holds ``held / ep`` of
the experts and the parts are summed over the axis; on one chip the layer
runs without that exchange.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from metaopt_tpu.models.lm_layers import (ACTIVATIONS, GatedFeedForward,
                                          GatedSpec, PlainFeedForward)
from metaopt_tpu.parallel.sharding import with_mesh_partitioning
from metaopt_tpu.utils import trace


class MoEFeedForward(nn.Module):
    d_model: int
    d_ff: int
    n_experts: int
    dropout: float = 0.0
    #: per-expert queue = capacity_factor·k·T/E items; <= 0 = dense oracle
    capacity_factor: float = 1.25
    #: experts per token: 1 = Switch (raw top prob gate), 2 = GShard-style
    #: top-2 (gates renormalized over the chosen pair)
    router_top_k: int = 1

    @nn.compact
    def __call__(self, x, *, train: bool):
        b, s, d = x.shape
        e, f = self.n_experts, self.d_ff
        k = max(1, min(int(self.router_top_k), e))

        router = nn.Dense(e, dtype=jnp.float32, name="router")
        wi = self.param(
            "wi",
            with_mesh_partitioning(nn.initializers.lecun_normal(),
                                   ("ep", None, "tp")),
            (e, d, f),
        )
        wo = self.param(
            "wo",
            with_mesh_partitioning(nn.initializers.lecun_normal(),
                                   ("ep", "tp", None)),
            (e, f, d),
        )

        logits = router(x.astype(jnp.float32))            # (b, s, E)
        probs = nn.softmax(logits, axis=-1)
        top_p, top_idx = jax.lax.top_k(probs, k)          # (b, s, k)
        if k == 1:
            gates = top_p                                 # Switch: raw prob
        else:  # GShard: renormalize over the chosen experts
            gates = top_p / jnp.clip(
                jnp.sum(top_p, axis=-1, keepdims=True), 1e-9, None
            )
        onehot_k = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)  # (b,s,k,E)
        assigned = jnp.sum(onehot_k, axis=2)              # (b, s, E)

        # switch load-balancing loss: fraction of assignments vs mean prob
        # per expert — pushes the router toward uniform utilization
        frac = jnp.mean(assigned, axis=(0, 1)) / k        # (E,)
        mean_prob = jnp.mean(probs, axis=(0, 1))          # (E,)
        self.sow("aux_loss", "moe_balance",
                 e * jnp.sum(frac * mean_prob))

        dropout = nn.Dropout(self.dropout, deterministic=not train)

        def expert_ffn(xe):
            """Batched-over-experts two-matmul FFN on bf16."""
            h = nn.relu(jnp.einsum(
                "e...d,edf->e...f",
                xe.astype(jnp.bfloat16), wi.astype(jnp.bfloat16)
            ))
            return jnp.einsum(
                "e...f,efd->e...d", dropout(h), wo.astype(jnp.bfloat16)
            )

        if self.capacity_factor <= 0:
            # dense oracle: every expert sees every (token, choice) copy —
            # k·E× the useful FLOPs, but exact (nothing dropped)
            y = jnp.zeros((b, s, d), jnp.float32)
            for j in range(k):
                oh = onehot_k[:, :, j]                    # (b, s, E)
                xe = jnp.einsum("bse,bsd->ebsd", oh, x.astype(jnp.float32))
                ye = expert_ffn(xe)
                yj = jnp.einsum("ebsd,bse->bsd", ye.astype(jnp.float32), oh)
                y = y + yj * gates[:, :, j][..., None]
            return y.astype(x.dtype)

        # ---- capacity-bounded scatter/gather dispatch over t·k items ----
        t = b * s
        cap = max(1, int(math.ceil(self.capacity_factor * k * t / e)))
        items = jnp.repeat(x.reshape(t, d), k, axis=0)    # (t·k, d)
        expf = top_idx.reshape(t * k)                     # item -> expert
        gatef = gates.reshape(t * k)
        # queue position of each item within its expert, in dispatch order
        ohf = onehot_k.reshape(t * k, e)
        pos_all = jnp.cumsum(ohf, axis=0) - 1.0           # (t·k, E)
        pos = jnp.take_along_axis(
            pos_all, expf[:, None], axis=1
        )[:, 0].astype(jnp.int32)                         # (t·k,)
        kept = pos < cap
        self.sow("moe_stats", "dropped_fraction",
                 1.0 - jnp.mean(kept.astype(jnp.float32)))

        # branchless scatter: overflowing items land in slot `cap`, which
        # is sliced away; kept (expert, slot) pairs are unique by cumsum
        dst = jnp.where(kept, pos, cap)                   # (t·k,)
        buf = jnp.zeros((e, cap + 1, d), x.dtype)
        expert_in = buf.at[expf, dst].set(items)[:, :cap]  # (E, cap, d)

        out = expert_ffn(expert_in)

        # gather back per item; dropped items contribute zero (the caller's
        # residual connection carries their token through)
        y = out[expf, jnp.minimum(dst, cap - 1)].astype(jnp.float32)
        y = jnp.where(kept[:, None], y, 0.0) * gatef[:, None]
        y = jnp.sum(y.reshape(t, k, d), axis=1).reshape(b, s, d)
        return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# dropless routing over the experts held here


#: bytes of VMEM the blocks of a grouped product may take together, by the
#: count below (Mosaic's scoped limit on a v5e is 16 MiB, and its own count
#: of megablox's kernels reads up to a MiB over this one)
_TILE_BYTES = 14 * 2 ** 20


def _divisor(size: int, tiles=(512, 256, 128)):
    """The largest of ``tiles`` that divides ``size``, None if none does."""
    return next((t for t in tiles if size % t == 0), None)


def _widest(size: int, fits):
    """The widest tile of whole lanes (128) that divides ``size`` and
    ``fits``; ``size`` is whole lanes and a tile of 128 fits."""
    return next(w for w in range(size, 0, -128)
                if size % w == 0 and (fits(w) or w == 128))


def _tiling(m: int, k: int, n: int, blocks):
    """(tm, tk, tn) for a grouped product over m rows, None for an axis no
    tile divides: rows in tiles of 256 (a group's first and last tile hold
    other groups' rows too, which are computed and masked: the shorter the
    tile the fewer); then k as whole as fits beside an n-tile of up to
    512, then n as wide as fits, by ``blocks(tm, tk, tn)``, the bytes of
    the kernel's blocks in VMEM, under ``_TILE_BYTES``. Measured on the v5e
    at the three MoE cells' shapes (d 2560 and 2048, f 768, 49 152 to
    131 072 rows of which a quarter to an eighth are filled, 16 groups;
    PERF.md section 6, PR 43), forward, transposed and ``tgmm``: a product
    whose k is one tile has no accumulation loop and a third to half as
    many programs, and takes 0.35-0.94 ms where 512-row tiles with k cut in
    two to five took 0.64-1.35; row tiles of 128 read 2 % under to 7 %
    over, of 512 slower or over Mosaic's VMEM; 128-tiles throughout, megablox's
    default, are over ten times slower."""
    tm, side = _divisor(m, (256, 128)), _divisor(n)
    if tm is None or (side is None and k % 128):
        return tm, _divisor(k), side

    def fits(tk, tn):
        return blocks(tm, tk, tn) <= _TILE_BYTES

    # a width that is no whole lanes (an expert 1856 = 29 x 64 wide) is ONE
    # block, the whole of it, on whichever axis it lies (a block as wide as
    # its array needs no whole lanes; the parameters are neither cut nor
    # padded), and the other axis is as wide as fits beside it
    if k % 128:
        return tm, k, _widest(n, lambda tn: fits(k, tn))
    if side is None:
        return tm, _widest(k, lambda tk: fits(tk, n)), n
    tk = _widest(k, lambda tk: fits(tk, side))
    return tm, tk, _widest(n, lambda tn: fits(tk, tn))


def _gmm_tiling(m: int, k: int, n: int):
    """megablox's ``gmm`` over (m, k) rows and (g, k, n) weights: both
    operands' and the output's bfloat16 blocks twice (one in flight), the
    float32 sums once."""
    return _tiling(m, k, n, lambda tm, tk, tn: 4 * (
        tm * tk + tk * tn + tm * tn) + 4 * tm * tn)


def _tgmm_tiling(m: int, k: int, n: int):
    """megablox's ``tgmm`` over (m, k) and (m, n) rows: the two operands'
    blocks twice, the output's (tk, tn) twice in bfloat16 and once in
    float32, in VMEM over all of a group's rows."""
    return _tiling(m, k, n, lambda tm, tk, tn: 4 * (
        tm * tk + tm * tn) + 8 * tk * tn)


def _gating_tile(n: int, f: int):
    """Rows a program of the gating kernels (ops/experts.py) takes over
    buffers of ``n`` rows and experts ``f`` wide: the largest of 512, 256,
    128 that divides n and whose blocks and float32 temporaries fit (the
    gradient's kernel takes ~36 bytes a row and column of f by Mosaic's
    count at f 768 to 1536; 256 and 512 rows read the same time at the
    cells' f 768)."""
    return _divisor(n, [t for t in (512, 256, 128)
                        if 36 * f * t <= _TILE_BYTES or t == 128])


def grouped_matmul_impl(m: int, k: int, n: int) -> str:
    """Which grouped matrix product :class:`DroplessMoE` runs on (m, k)
    rows and (g, k, n) weights: the Pallas ``megablox`` kernels on a TPU
    where a tile divides each axis, ``jax.lax.ragged_dot`` elsewhere. The
    one place that decides, for every pass of :func:`_held_experts`: the
    gating kernels of ops/experts.py go with megablox (where a tile divides
    m, k and n, one divides every product of the part, 2n columns, n deep,
    k wide, and the gating's rows), loops of :func:`_over_chunks` with
    ``ragged_dot``; the layer and ``trial.setup``'s span both ask.

    Measured on the v5e at the 8k decoder cell's shapes (PR 27: 49 152 rows
    of which a quarter are filled, 16 groups, a product 2560 x 768;
    PERF.md): XLA's own ``ragged_dot`` and megablox at 512-tiles took the
    same time, forward and backward, alone and inside the step, and both
    skip the rows no group fills. But XLA names its ragged products
    ``ragged-dot-none`` in a device trace, outside every scope of the
    program, and megablox's ``gmm`` calls keep theirs; so the TPU takes
    megablox, and the backends that cannot run its kernels take
    ``ragged_dot``. At the tiles :func:`_tiling` chooses (PR 43, the three
    MoE cells' shapes) megablox's products take about half of what they
    took at 512-tiles; ``ragged_dot`` was not measured again."""
    if jax.default_backend() == "tpu" and all(_gmm_tiling(m, k, n)):
        return "megablox"
    return "ragged_dot"


def describe_experts(n: int, d: int, f: int, gated: bool = True) -> dict:
    """What ``trial.setup``'s span says of the held experts' part over
    buffers of ``n`` rows, a model width ``d`` and experts ``f`` wide,
    ``gated`` or of two matrices: the answer of :func:`grouped_matmul_impl`,
    the gating (or the activation's pass) that goes with it (kernels beside
    megablox, loops of :func:`_over_chunks` beside ``ragged_dot``), how a
    width of no whole lanes is tiled (``width``) and, on megablox, the
    (rows, k, n) tiles of the six grouped products by the pass that makes
    them and the gating kernels' rows a program (None where XLA tiles)."""
    impl = grouped_matmul_impl(n, d, f)
    kernels = impl == "megablox"
    first = 2 * f if gated else f  # the first product's columns
    said = {"gate_up": f"one product of {first} columns" if gated
            else f"no gate: one product of {f} columns",
            "gating": "pallas" if kernels else "chunks",
            "products": impl,
            "tiles": {"gu": _gmm_tiling(n, d, first),
                      "out": _gmm_tiling(n, f, d),
                      "d_h": _gmm_tiling(n, d, f),
                      "d_rows": _gmm_tiling(n, first, d),
                      "d_w_gu": _tgmm_tiling(n, d, first),
                      "d_w_down": _tgmm_tiling(n, f, d),
                      "gating": _gating_tile(n, f)} if kernels else None}
    if kernels and f % 128:
        said["width"] = (f"{f} is no whole lanes: one block of the whole "
                         "width in every product, neither cut nor padded")
    return said


#: rows one trip of a loop over the buffers' rows moves, and of a loop over
#: the tokens (measured on the v5e at 49 152 x 2560 buffers; PERF.md)
_CHUNK_ROWS = 2048
_CHUNK_TOKENS = 512


def routing_chunk_rows(rows: int) -> int:
    """Rows one trip of the routing's loops moves, over buffers of ``rows``
    rows."""
    return min(_CHUNK_ROWS, rows)


def _trips(count, chunk: int):
    return (count + chunk - 1) // chunk


def _over_chunks(count, size: int, chunk: int, body, init):
    """``body(start, below, fresh, carry) -> carry`` over the first
    ``count`` of ``size`` rows, ``chunk`` at a time: ``ceil(count /
    chunk)`` trips, a number read on the device, ``ceil(size / chunk)`` at
    the most. Of the trip's rows ``start .. start + chunk - 1``, ``below``
    (chunk,) marks those under ``count`` and ``fresh`` those no earlier
    trip had (a last trip that would pass ``size`` starts early instead)."""

    def trip(i, carry):
        start = jnp.minimum(i * chunk, size - chunk)
        row = start + jnp.arange(chunk, dtype=jnp.int32)
        return body(start, row < count, row >= i * chunk, carry)

    return jax.lax.fori_loop(0, _trips(count, chunk), trip, init)


def _zeros(shape, dtype, plan):
    """Zeros that XLA does not take for a constant: it makes one unnamed
    fill of the constant ones of a shape, for every layer, and a trace
    then finds them under no scope."""
    return jnp.broadcast_to(
        jnp.minimum(plan["filled"], 0).astype(dtype), shape)


def _take(x, index):
    """Rows ``index`` of x, each of them a row that x has."""
    return x.at[index].get(mode="promise_in_bounds")


def _sum_to_tokens(buf, plan, weights, dtype):
    """(t, width): for each token the float32 sum of its held items' rows
    of ``buf`` (n, width), each times its weight (``weights`` (t, k) in the
    order of ``plan["slot"]``, None for 1): the passes that sum back to
    tokens. Level j adds the j-th held item of the tokens that have more
    than j; over the levels that is every filled row once."""
    t, k = plan["slot"].shape
    chunk = min(_CHUNK_TOKENS, t)
    acc = _zeros((t, buf.shape[1]), jnp.float32, plan)
    for j in range(k):
        slot = plan["slot"][:, j]
        weight = None if weights is None else weights[:, j]

        def body(start, below, fresh, acc, slot=slot, weight=weight):
            live = below & fresh
            at = jax.lax.dynamic_slice_in_dim(slot, start, chunk)
            rows = _take(buf, jnp.where(live, at, 0)).astype(jnp.float32)
            if weight is not None:
                rows = rows * jax.lax.dynamic_slice_in_dim(
                    weight, start, chunk)[:, None]
            old = jax.lax.dynamic_slice_in_dim(acc, start, chunk)
            return jax.lax.dynamic_update_slice_in_dim(
                acc, old + jnp.where(live[:, None], rows, 0), start, 0)

        acc = _over_chunks(plan["levels"][j], t, chunk, body, acc)
    return _take(acc.astype(dtype), plan["back"])


def routing_plan(group, held: int):
    """Where every item goes, from ``group`` (t, k): the held expert 0 ..
    held - 1 a choice names, ``held`` for an expert elsewhere. Integers
    only; no gradient passes through them:

    ``order`` (n,) the item in each buffer row, held items first and by
    expert; ``inverse`` (n,) the row of each item; ``token`` (n,) the token
    of each row; ``items`` (held,) the rows of each held expert and
    ``filled`` () their sum. ``by_count`` (t,) the tokens, those with most
    held items first, and ``back`` (t,) its inverse; for the tokens in that
    order ``slot`` (t, k), the rows of a token's held items first, and
    ``pick`` (t, k), the choice each came from; ``levels`` (k,) the tokens
    with more than j held items, which sum to ``filled``."""
    t, k = group.shape
    n = t * k
    flat = group.reshape(n)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    # a count by comparison: a scatter-add into 16 bins is slow on a TPU
    items = jnp.sum(flat[:, None] == jnp.arange(held)[None],
                    axis=0, dtype=jnp.int32)
    mine = group < held
    count = jnp.sum(mine, axis=1, dtype=jnp.int32)
    by_count = jnp.argsort(-count, stable=True).astype(jnp.int32)
    choice = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32), (t, k))
    slot, pick = jax.lax.sort(
        (jnp.where(mine, inverse.reshape(t, k), n), choice),
        dimension=1, num_keys=1)
    return {
        "order": order, "inverse": inverse, "token": order // k,
        "items": items, "filled": jnp.sum(items),
        "by_count": by_count, "back": jnp.argsort(by_count).astype(jnp.int32),
        "slot": slot[by_count], "pick": pick[by_count],
        "levels": jnp.sum(count[:, None] > jnp.arange(k)[None],
                          axis=0, dtype=jnp.int32)}


@jax.custom_vjp
def _dispatch(x, plan):
    """(n, d) buffer: row r < filled is the row of ``x`` (t, d) of the item
    there, the others zeros. The gradient sums a token's held rows."""
    n = plan["token"].shape[0]
    chunk = routing_chunk_rows(n)

    def body(start, below, fresh, buf):
        tokens = jax.lax.dynamic_slice_in_dim(plan["token"], start, chunk)
        rows = jnp.where(below[:, None], _take(x, tokens), 0)
        return jax.lax.dynamic_update_slice_in_dim(buf, rows, start, 0)

    return _over_chunks(plan["filled"], n, chunk, body,
                        _zeros((n, x.shape[1]), x.dtype, plan))


def _dispatch_fwd(x, plan):
    return _dispatch(x, plan), plan


def _dispatch_bwd(plan, g):
    return _sum_to_tokens(g, plan, None, g.dtype), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _slot_weights(weights, plan):
    """``weights`` (t, k) in the order of ``plan["slot"]``."""
    chosen = plan["pick"][..., None] == jnp.arange(weights.shape[1])
    return jnp.sum(jnp.where(chosen, weights[plan["by_count"]][:, None], 0),
                   axis=2)


@jax.custom_vjp
def _combine(out, weights, plan):
    """(t, d) float32: ``sum_j weights[t, j] * out[row of item (t, j)]``
    over a token's held items, ``out`` (n, d) in buffer order. The gradient
    to ``out`` is written in buffer order too, its rows past ``filled``
    zeros: row r is its token's incoming row times the item's weight."""
    return _sum_to_tokens(out, plan, _slot_weights(weights, plan),
                          jnp.float32)


def _combine_fwd(out, weights, plan):
    return _combine(out, weights, plan), (out, weights, plan)


def _combine_bwd(res, g):
    out, weights, plan = res
    n = out.shape[0]
    chunk = routing_chunk_rows(n)
    by_row = weights.reshape(n)[plan["order"]]

    def body(start, below, fresh, carry):
        d_out, d_by_row = carry
        rows = _take(g, jax.lax.dynamic_slice_in_dim(plan["token"], start,
                                                     chunk))
        w = jax.lax.dynamic_slice_in_dim(by_row, start, chunk)
        mine = jax.lax.dynamic_slice_in_dim(out, start, chunk)
        d_rows = jnp.where(below[:, None], rows * w[:, None], 0)
        d_w = jnp.where(below, jnp.sum(
            rows * mine.astype(jnp.float32), axis=1), 0)
        return (jax.lax.dynamic_update_slice_in_dim(
                    d_out, d_rows.astype(out.dtype), start, 0),
                jax.lax.dynamic_update_slice_in_dim(d_by_row, d_w, start, 0))

    d_out, d_by_row = _over_chunks(
        plan["filled"], n, chunk, body,
        (_zeros(out.shape, out.dtype, plan), jnp.zeros((n,), jnp.float32)))
    d_weights = d_by_row[plan["inverse"]].reshape(weights.shape)
    # the gather back to item order is made before the experts' backward
    # may start, while its (n,) operand is where the loop left it: behind
    # the experts' Pallas calls the compiler reads it from HBM, 2.7 ms a
    # layer for 0.9 at 131 072 rows (PERF.md section 6, PR 43)
    d_out, d_weights = jax.lax.optimization_barrier((d_out, d_weights))
    return d_out, d_weights.astype(weights.dtype), None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _kernels():
    """ops/experts.py, loaded when the chip's route first asks: Pallas and
    megablox stay out of the import of this module and of models/lm.py,
    which every cell pays in its set-up. (The tests put the same functions
    with ``interpret=True`` in its place.)"""
    from metaopt_tpu.ops import experts

    return experts


def _product(x, w, items, impl: str, transposed: bool = False):
    """``x`` (m, k) rows ordered by group, ``w`` (g, k, n), or (g, n, k)
    where ``transposed``, ``items`` (g,) int32 -> (m, n) in x's dtype,
    float32 sums: row r of group e times ``w[e]``, by ``impl``
    (:func:`grouped_matmul_impl`). Rows past ``sum(items)`` hold nothing a
    caller may read."""
    if impl == "megablox":
        return _kernels().gmm(
            x, w, items, _gmm_tiling(*x.shape, w.shape[1 if transposed else 2]),
            transpose_rhs=transposed)
    return jax.lax.ragged_dot(x, w.swapaxes(1, 2) if transposed else w, items,
                              preferred_element_type=x.dtype)


def _weight_gradient(x, g, items, impl: str):
    """(held, k, n) in x's dtype, float32 sums: ``x[rows of e].T @ g[rows
    of e]`` for each group, both read (rows, width) as they lie."""
    if impl == "megablox":
        return _kernels().tgmm(
            x, g, items, _tgmm_tiling(x.shape[0], x.shape[1], g.shape[1]))
    return jax.lax.ragged_dot_general(
        x, g, items, jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((0,), (0,)), ((), ())),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[]),
        preferred_element_type=x.dtype)


def _gate(gu, filled, activation, impl: str, gated: bool = True):
    """(n, f): ``activation(gu[:, :f]) * gu[:, f:]`` or, for experts that
    are not ``gated``, ``activation(gu)`` of the one product (n, f), in the
    filled rows (up to the end of the tile or chunk that holds the last):
    beside megablox the kernel, in float32 rounded once; else the loop, in
    the operands' dtype as XLA has it."""
    n, f = gu.shape[0], gu.shape[1] // 2 if gated else gu.shape[1]
    if impl == "megablox":
        kernel = _kernels().gating if gated else _kernels().activation
        return kernel(gu, filled, activation, _gating_tile(n, f))
    chunk = routing_chunk_rows(n)

    def body(start, below, fresh, h):
        mine = jax.lax.dynamic_slice_in_dim(gu, start, chunk)
        return jax.lax.dynamic_update_slice_in_dim(
            h, activation(mine[:, :f]) * mine[:, f:] if gated
            else activation(mine), start, 0)

    return _over_chunks(filled, n, chunk, body,
                        _zeros((n, f), gu.dtype, {"filled": filled}))


def _gate_bwd(d_h, gu, filled, activation, impl: str, gated: bool = True):
    """The gradient of :func:`_gate` to ``gu`` (like ``gu``), in the same
    rows."""
    n, f = d_h.shape
    if impl == "megablox":
        kernel = _kernels().gating_bwd if gated \
            else _kernels().activation_bwd
        return kernel(d_h, gu, filled, activation, _gating_tile(n, f))
    chunk = routing_chunk_rows(n)
    split = (lambda mine: (mine[:, :f], mine[:, f:])) if gated \
        else (lambda mine: (mine,))
    fn = (lambda g, u: activation(g) * u) if gated else activation

    def body(start, below, fresh, d_gu):
        mine = jax.lax.dynamic_slice_in_dim(gu, start, chunk)
        _, back = jax.vjp(fn, *split(mine))
        return jax.lax.dynamic_update_slice_in_dim(
            d_gu, jnp.concatenate(back(jax.lax.dynamic_slice_in_dim(
                d_h, start, chunk)), axis=1), start, 0)

    return _over_chunks(filled, n, chunk, body,
                        _zeros(gu.shape, gu.dtype, {"filled": filled}))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _held_experts(rows, w_gu, w_down, items, filled, activation, impl: str,
                  gated: bool = True):
    """(n, d): the held experts' feed-forward of each filled row of
    ``rows`` (n, d), ordered by expert, ``items`` (held,) rows an expert and
    ``filled`` their sum; ``w_gu`` (held, d, 2f) the gate's and the up
    matrix joined or, for experts that are not ``gated`` (two products and
    an activation of its own between them), the up matrix alone (held, d,
    f); ``w_down`` (held, f, d), all of one dtype (bfloat16); every pass by
    ``impl`` (:func:`grouped_matmul_impl`). No pass reads or writes a row
    past the tile that holds row ``filled - 1``; the rows behind hold what
    the memory held, and may meet nothing but a product that skips them or
    a ``where``."""
    return _held_experts_fwd(rows, w_gu, w_down, items, filled, activation,
                             impl, gated)[0]


def _held_experts_fwd(rows, w_gu, w_down, items, filled, activation, impl,
                      gated=True):
    gu = _product(rows, w_gu, items, impl)
    h = _gate(gu, filled, activation, impl, gated)
    out = _product(h, w_down, items, impl)
    return out, (rows, gu, h, w_gu, w_down, items, filled)


def _held_experts_bwd(activation, impl, gated, residuals, d_out):
    rows, gu, h, w_gu, w_down, items, filled = residuals
    d_h = _product(d_out, w_down, items, impl, transposed=True)
    d_gu = _gate_bwd(d_h, gu, filled, activation, impl, gated)
    # one product, its sums in float32 over the 2f columns: the gate's and
    # the up product's input gradients added before they are rounded
    d_rows = _product(d_gu, w_gu, items, impl, transposed=True)
    return (d_rows, _weight_gradient(rows, d_gu, items, impl),
            _weight_gradient(h, d_out, items, impl), None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


@dataclasses.dataclass(frozen=True)
class RoutingRule:
    """How a token's experts are chosen and weighed from the router's
    logits. ``softmax``: the k largest logits, a softmax over those k.
    ``sigmoid`` (the DeepSeek-V3 family's): scores sigmoid(logits); the k
    largest of score + bias where the family has a correction bias (in the
    choice alone, never a weight: no gradient reaches it); the chosen scores
    over their sum + ``eps`` (a family's own: 1e-20, ``lfm2_moe``'s 1e-6)
    where ``normalised``, times ``scale``."""
    scoring: str = "softmax"
    bias: bool = False
    normalised: bool = True
    scale: float = 1.0
    eps: float = 1e-20


def route_top_k(logits, k: int, rule: RoutingRule = RoutingRule(),
                bias=None):
    """(weights, experts), both (t, k), float32 and int32, by ``rule``; ties
    go to the lower index. ``bias`` (experts,): the rule's correction bias.
    The one place a choice is made."""
    with trace.scope("moe.router"):
        logits = logits.astype(jnp.float32)
        if rule.scoring == "softmax":
            top, experts = jax.lax.top_k(logits, k)
            return jax.nn.softmax(top, axis=-1), experts
        if rule.scoring != "sigmoid":
            raise ValueError(f"no routing rule scores by {rule.scoring!r}")
        scores = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(
            scores + bias if rule.bias else scores, k)
        weights = jnp.take_along_axis(scores, experts, axis=1)
        if rule.normalised:
            weights = weights / (jnp.sum(weights, axis=1, keepdims=True)
                                 + rule.eps)
        return weights * rule.scale, experts


def bias_moved_tokens(logits, experts):
    """() int32: the tokens whose chosen ``experts`` (t, k) are not the k
    of the largest sigmoid scores of ``logits`` (t, E): those where the
    least score among the chosen lies under the largest among the others,
    one masked maximum. 0 = the correction bias never moved a choice."""
    with trace.scope("moe.router"):
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        chosen = jnp.any(
            experts[:, :, None] == jnp.arange(scores.shape[1]), axis=1)
        least = jnp.min(jnp.where(chosen, scores, jnp.inf), axis=1)
        other = jnp.max(jnp.where(chosen, -jnp.inf, scores), axis=1)
        return jnp.sum(least < other, dtype=jnp.int32)


def dropless_experts(x, weights, experts, w_gate, w_up, w_down, first,
                     activation=nn.relu):
    """The held experts' part of an expert layer: gated (``activation`` on
    the gate's product: ReLU or, as the description names it, SiLU) or,
    with ``w_gate`` None, of two matrices, ``activation`` (a squared ReLU
    among them) on the up product.

    x (t, d); weights, experts (t, k) from :func:`route_top_k`; w_gate,
    w_up (held, d, f), w_down (held, f, d): experts ``first .. first +
    held - 1`` of the published ones. Returns (y (t, d) float32, counts):
    ``sum_k weights * expert(x)`` over the choices whose expert is held,
    and ``{"items": (held,) int32 a held expert, "dropped": () int32,
    "chunks": () int32}``. Nothing is dropped: the buffers hold all t * k
    items, the most that can be routed here. The routing's passes over them
    move the rows that held items fill, a chunk a trip, and leave zeros
    behind them; between them the experts' part (:func:`_held_experts`, by
    :func:`grouped_matmul_impl`) is two grouped products and the gating forward,
    four grouped products and the gating's gradient backward, each over the
    filled rows' tiles alone. ``chunks``: the trips of a pass in buffer
    order, ``ceil(filled / chunk)`` (a pass that sums back to tokens moves
    as many rows in at most k trips more).
    """
    t, d = x.shape
    k = experts.shape[1]
    held, gated = w_up.shape[0], w_gate is not None
    n = t * k
    with trace.scope("moe.dispatch"):
        local = experts - first
        # items of experts elsewhere sort behind every held expert's
        plan = routing_plan(jnp.where((local >= 0) & (local < held), local,
                                      held).astype(jnp.int32), held)
        rows = _dispatch(x.astype(jnp.bfloat16), plan)
    with trace.scope("moe.experts"):
        # gate and up are ONE product of 2f columns: joined in the cast
        # that is made anyway (one fusion where two casts ran); autodiff
        # cuts the joined matrix's gradient back into the two leaves'
        w_gu = jnp.concatenate([w_gate, w_up], axis=2) if gated else w_up
        out = _held_experts(rows, w_gu.astype(jnp.bfloat16),
                            w_down.astype(jnp.bfloat16), plan["items"],
                            plan["filled"], activation,
                            grouped_matmul_impl(n, d, w_up.shape[2]), gated)
    with trace.scope("moe.combine"):
        y = _combine(out, weights, plan)
    # the items that found no row in the buffers: 0 while the buffers have
    # a row for each of the n items, as they do; buffers cut below that
    # (a capacity) would move it
    dropped = jnp.maximum(plan["filled"] - rows.shape[0], 0)
    chunks = _trips(plan["filled"], routing_chunk_rows(n))
    return y, {"items": plan["items"], "dropped": dropped, "chunks": chunks}


class DroplessMoE(nn.Module):
    """Gated experts under dropless top-k routing, for the share of the
    experts held here (module docstring). ``held`` = (first, count) of the
    ``n_experts`` published ones; the router's logits come from outside
    (a decoder reads them before attention or after it), as does the
    correction bias of a ``rule`` that has one; ``activation`` is the
    gate's, by the name a published config gives it. ``shared_d_ff`` > 0
    adds the shared experts, one gated feed-forward of that width that
    every token meets (models/lm_layers.GatedFeedForward under ``moe.shared``):
    whole on every chip, and on an ``ep`` axis added once, after the sum
    over the axis."""

    d_model: int
    d_ff: int
    n_experts: int
    top_k: int
    held: Tuple[int, int]
    activation: str = "relu"
    rule: RoutingRule = RoutingRule()
    shared_d_ff: int = 0
    #: False: experts of two matrices, ``activation`` on the up product, no
    #: gate (the shared one too)
    gated: bool = True

    @nn.compact
    @trace.scope("moe")
    def __call__(self, x, logits, bias=None):
        from metaopt_tpu.parallel.mesh import active_mesh

        b, s, d = x.shape
        first, count = self.held
        if not 0 <= first <= first + count <= self.n_experts:
            raise ValueError(f"experts held {self.held} are not among the "
                             f"{self.n_experts} routed over")
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        w = {name: self.param(
            name, with_mesh_partitioning(init, ("ep", None, None)), shape)
            for name, shape in (("gate", (count, d, self.d_ff)),
                                ("up", (count, d, self.d_ff)),
                                ("down", (count, self.d_ff, d)))
            if self.gated or name != "gate"}
        logits = logits.reshape(b * s, -1)
        weights, experts = route_top_k(logits, self.top_k, self.rule, bias)
        act = ACTIVATIONS[self.activation]
        mesh = active_mesh()
        ep = dict(mesh.shape).get("ep", 1) if mesh is not None else 1
        if ep == 1:
            y, counts = dropless_experts(
                x.reshape(b * s, d), weights, experts, w.get("gate"),
                w["up"], w["down"], first, act)
        else:
            y, counts = _over_ep(mesh, ep, x.reshape(b * s, d), weights,
                                 experts, w, first, act)
        self.sow("moe_stats", "items", counts["items"])
        self.sow("moe_stats", "dropped", counts["dropped"])
        self.sow("moe_stats", "chunks", counts["chunks"])
        if self.rule.bias:
            self.sow("moe_stats", "bias_moved",
                     bias_moved_tokens(logits, experts))
        y = y.reshape(b, s, d)
        if self.shared_d_ff:
            with trace.scope("moe.shared"):
                shared = GatedFeedForward if self.gated else PlainFeedForward
                y = y + shared(d, self.shared_d_ff, self.activation,
                               name="shared")(x).astype(y.dtype)
        return y


@dataclasses.dataclass(frozen=True)
class RoutedSpec:
    """A routed feed-forward as a description has it (the answers a spec
    gives: models/lm_layers.py): top ``top_k`` of ``n_experts`` gated
    experts ``d_ff`` wide, ``held`` = (first, count) of them here, chosen
    and weighed by ``rule``, beside shared experts (one gated feed-forward
    ``shared_d_ff`` wide, 0: none); the router reads the block's first
    norm, BEFORE the mixer, or (``router_after_mixer``) the second's."""

    n_experts: int
    top_k: int
    d_ff: int
    held: Tuple[int, int]
    activation: str
    shared_d_ff: int
    rule: RoutingRule
    router_after_mixer: bool
    #: False: experts of two matrices and no gate, the shared one too
    gated: bool = True

    kind = "routed"

    def _router(self, read):
        with trace.scope("moe"), trace.scope("moe.router"):
            # float32 in earnest: a TPU's default precision would make
            # this product in bfloat16 passes, and the top-k choice
            # hangs on the logits' last bits
            return nn.Dense(
                self.n_experts, use_bias=False, name="router",
                precision=jax.lax.Precision.HIGHEST,
                kernel_init=with_mesh_partitioning(
                    nn.initializers.lecun_normal(), (None, None)))(read)

    def before_mixer(self, block, n):
        return None if self.router_after_mixer else self._router(n)

    def feed(self, block, m, logits):
        if logits is None:
            logits = self._router(m)
        # the rule's correction bias: a frozen leaf of the block's own
        # (models/lm.py::FROZEN)
        bias = block.param(
            "choice_bias", with_mesh_partitioning(
                nn.initializers.zeros, (None,)),
            (self.n_experts,)) if self.rule.bias else None
        return DroplessMoE(
            block.d_model, self.d_ff, self.n_experts, self.top_k, self.held,
            self.activation, self.rule, self.shared_d_ff, self.gated,
            name="experts")(m, logits, bias)

    def counts(self):
        """What ``DroplessMoE`` sows a step, by ``moe_counts``' keys."""
        bias = {"bias_moved": ()} if self.rule.bias else {}
        return {"items": (self.held[1],), "dropped": (), "chunks": (), **bias}

    def products(self, d_model):
        """The shared experts' three, under the gated feed-forward's names
        (the experts' own buffers are no candidates)."""
        if not self.shared_d_ff:
            return []
        down, (width, ups) = GatedSpec(
            self.shared_d_ff, self.activation).products(d_model)
        if not self.gated:  # two matrices: no gate's product to keep
            ups = {n: b for n, b in ups.items() if n != GatedSpec.KEPT["gate"]}
        return [down, (width, ups)]

    def under_tp(self, tp: int):
        # the experts split over ``ep``; the shared ones' width is counted
        # whole, as the rule always has
        return self

    def describe(self, step, layers, depth: int):
        """``trial.setup``'s ``attrs["moe"]``: the expert layers' share, the
        product they take and how the held experts' part runs, the rows of
        their buffers and of a trip of the routing's loops; where the rule
        is not the plain one, the rule, the shared experts and the leading
        dense layers beside the counts (``depth``: the pattern's layers that
        have a feed-forward); where the experts are not gated, that and the
        layers' published numbers."""
        rows = step.tokens * self.top_k
        # buffer rows, model width, an expert's width: what the route asks
        how = describe_experts(rows, step.d_model, self.d_ff, self.gated)
        said = {"routed_over": self.n_experts, "top_k": self.top_k,
                "held": list(self.held), "products": how["products"],
                "experts": how, "buffer_rows": rows,
                "chunk_rows": routing_chunk_rows(rows)}
        if self.rule != RoutingRule():
            said.update(
                scoring=self.rule.scoring, bias=self.rule.bias,
                scale=self.rule.scale, shared_d_ff=self.shared_d_ff,
                dense_layers=depth - len(layers), d_ff=step.d_ff)
        if not self.gated:  # the layers' published numbers, not the first n
            said.update(gated=False, layers=[la.number for la in layers])
        return {"moe": said}


def _over_ep(mesh, ep: int, x, weights, experts, w, first: int,
             activation=nn.relu):
    """The layer on an ``ep`` mesh axis: each chip holds ``held / ep``
    experts, computes their part for every token, and the parts are summed
    over the axis (tokens are not exchanged: every chip of the axis has
    them all). ``chunks`` is the fullest chip's."""
    from jax.sharding import PartitionSpec as P

    count = w["up"].shape[0]
    if count % ep:
        raise ValueError(f"{count} held experts do not divide over ep={ep}")
    names = [n for n in ("gate", "up", "down") if n in w]

    def local(x, weights, experts, *mats):
        mine = first + jax.lax.axis_index("ep") * (count // ep)
        held = dict(zip(names, mats))
        y, counts = dropless_experts(x, weights, experts, held.get("gate"),
                                     held["up"], held["down"], mine,
                                     activation)
        return (jax.lax.psum(y, "ep"), counts["items"],
                jax.lax.psum(counts["dropped"], "ep"),
                jax.lax.pmax(counts["chunks"], "ep"))

    y, items, dropped, chunks = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), P()) + (P("ep"),) * len(names),
        out_specs=(P(), P("ep"), P(), P()), check_vma=False,
    )(x, weights, experts, *(w[n] for n in names))
    return y, {"items": items, "dropped": dropped, "chunks": chunks}
