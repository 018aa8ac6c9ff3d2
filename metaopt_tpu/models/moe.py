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
LOGITS, a softmax over the chosen ones, gated ReLU experts of three
matrices, and **no capacity**: every (token, choice) item is computed,
whatever the imbalance. It is told which experts it holds (a contiguous
share of the published count), routes over all of them, and returns the
part of the layer's output that its own experts give: the items whose
expert lives here are ordered by expert and go through three grouped
matrix products (:func:`grouped_matmul`) whose buffers have the static
worst-case size, all ``tokens x k`` items. On an ``ep`` mesh axis each
chip holds ``held / ep`` of them and the parts are summed over the axis;
on one chip the layer runs without that exchange.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

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


def _gmm_tiling(m: int, k: int, n: int):
    """megablox's tiles for an (m, k) x (g, k, n) product: the largest of
    512, 256, 128 that divides each axis, None for an axis none divides
    (measured: 128-tiles, megablox's default, are five times slower)."""
    return tuple(next((t for t in (512, 256, 128) if size % t == 0), None)
                 for size in (m, k, n))


def grouped_matmul_impl(m: int, k: int, n: int) -> str:
    """Which grouped matrix product :class:`DroplessMoE` runs on (m, k)
    rows and (g, k, n) weights: the Pallas ``megablox`` kernels on a TPU
    where a tile divides each axis, ``jax.lax.ragged_dot`` elsewhere. The
    one place that decides: the layer and ``trial.setup``'s span both ask.

    Measured on the v5e at the benchmark cell's shapes (49 152 rows of
    which a quarter are filled, 16 groups, 2560 x 768; PERF.md): XLA's own
    ``ragged_dot`` and megablox at its best tiling take the same time,
    forward and backward, alone and inside the step, and both skip the
    rows no group fills. But XLA names its ragged products
    ``ragged-dot-none`` in a device trace, outside every scope of the
    program, and megablox's ``gmm`` / ``tgmm`` calls keep theirs; so the
    TPU takes megablox, and the backends that cannot run its kernels take
    ``ragged_dot``."""
    if jax.default_backend() == "tpu" and all(_gmm_tiling(m, k, n)):
        return "megablox"
    return "ragged_dot"


def grouped_matmul(x, w, group_sizes, impl: str):
    """``x`` (m, k) rows ordered by group, ``w`` (g, k, n), ``group_sizes``
    (g,) int32 -> (m, n) in x's dtype: row r of group e times ``w[e]``, by
    ``impl`` (:func:`grouped_matmul_impl`). Rows past ``sum(group_sizes)``
    hold nothing a caller may read."""
    if impl == "megablox":
        from jax.experimental.pallas.ops.tpu.megablox import ops

        return ops.gmm(x, w, group_sizes, preferred_element_type=x.dtype,
                       tiling=_gmm_tiling(*x.shape, w.shape[2]))
    return jax.lax.ragged_dot(x, w, group_sizes,
                              preferred_element_type=x.dtype)


@jax.custom_vjp
def _take_rows(x, index, inverse):
    """``x[index]`` for a permutation ``index`` of x's rows with its
    ``inverse``: the gradient is then a gather too, never a scatter."""
    return x[index]


def _take_rows_fwd(x, index, inverse):
    return x[index], inverse


def _take_rows_bwd(inverse, g):
    return g[inverse], None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _rows_of_items(x, order, inverse, mine, k: int):
    """Row ``order[i] // k`` of ``x`` (t, d) for each of the t * k items in
    dispatch order ``order`` (a permutation with its ``inverse``): what
    ``repeat(x, k)[order]`` gives, without the repeat. The gradient is a
    gather by ``inverse`` and a sum over a token's k items, those alone
    that ``mine`` (t, k) marks: the rows of the others hold nothing."""
    return x[order // k]


def _rows_of_items_fwd(x, order, inverse, mine, k):
    return x[order // k], (inverse, mine)


def _rows_of_items_bwd(k, res, g):
    inverse, mine = res
    items = g[inverse].reshape(*mine.shape, g.shape[-1])
    mine_only = jnp.where(mine[..., None], items, 0).astype(jnp.float32)
    return jnp.sum(mine_only, axis=1).astype(g.dtype), None, None, None


_rows_of_items.defvjp(_rows_of_items_fwd, _rows_of_items_bwd)


def route_top_k(logits, k: int):
    """(weights, experts), both (t, k): the k largest logits of each token
    and a softmax over those k alone, float32."""
    with trace.scope("moe.router"):
        top, experts = jax.lax.top_k(logits.astype(jnp.float32), k)
        return jax.nn.softmax(top, axis=-1), experts


def dropless_experts(x, weights, experts, w_gate, w_up, w_down, first):
    """The held experts' part of a gated-ReLU expert layer.

    x (t, d); weights, experts (t, k) from :func:`route_top_k`; w_gate,
    w_up (held, d, f), w_down (held, f, d): experts ``first .. first +
    held - 1`` of the published ones. Returns (y (t, d) float32, counts):
    ``sum_k weights * expert(x)`` over the choices whose expert is held,
    and ``{"items": (held,) int32 a held expert, "dropped": () int32}``.
    Nothing is dropped: the buffers hold all t * k items, the most that
    can be routed here. Rows of the buffers that no held item fills are
    never read into a result: the products skip them, and what comes back
    in item order is masked by ``mine``.
    """
    t, d = x.shape
    k = experts.shape[1]
    held = w_gate.shape[0]
    n = t * k
    with trace.scope("moe.dispatch"):
        local = experts - first
        mine = (local >= 0) & (local < held)                 # (t, k)
        # items of experts elsewhere sort behind every held expert's
        group = jnp.where(mine, local, held).astype(jnp.int32).reshape(n)
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        inverse = jnp.argsort(order).astype(jnp.int32)
        # a count by comparison: a scatter-add into 16 bins is slow on a TPU
        items = jnp.sum(group[:, None] == jnp.arange(held)[None],
                        axis=0, dtype=jnp.int32)
        rows = _rows_of_items(x.astype(jnp.bfloat16), order, inverse, mine, k)
    with trace.scope("moe.experts"):
        product = functools.partial(
            grouped_matmul, group_sizes=items,
            impl=grouped_matmul_impl(n, d, w_gate.shape[2]))
        h = nn.relu(product(rows, w_gate.astype(jnp.bfloat16))) \
            * product(rows, w_up.astype(jnp.bfloat16))
        out = product(h, w_down.astype(jnp.bfloat16))
    with trace.scope("moe.combine"):
        out = _take_rows(out, inverse, order).reshape(t, k, d)
        y = jnp.sum(jnp.where(mine[..., None], out, 0).astype(jnp.float32)
                    * weights[..., None], axis=1)
    # the items that found no row in the buffers: 0 while the buffers have
    # a row for each of the n items, as they do; buffers cut below that
    # (a capacity) would move it
    dropped = jnp.maximum(jnp.sum(items) - rows.shape[0], 0)
    return y, {"items": items, "dropped": dropped}


class DroplessMoE(nn.Module):
    """Gated-ReLU experts under dropless top-k routing, for the share of the
    experts held here (module docstring). ``held`` = (first, count) of the
    ``n_experts`` published ones; the router's logits come from outside
    (these decoders read them before attention)."""

    d_model: int
    d_ff: int
    n_experts: int
    top_k: int
    held: Tuple[int, int]

    @nn.compact
    @trace.scope("moe")
    def __call__(self, x, logits):
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
                                ("down", (count, self.d_ff, d)))}
        weights, experts = route_top_k(logits.reshape(b * s, -1), self.top_k)
        mesh = active_mesh()
        ep = dict(mesh.shape).get("ep", 1) if mesh is not None else 1
        if ep == 1:
            y, counts = dropless_experts(
                x.reshape(b * s, d), weights, experts, w["gate"], w["up"],
                w["down"], first)
        else:
            y, counts = _over_ep(mesh, ep, x.reshape(b * s, d), weights,
                                 experts, w, first)
        self.sow("moe_stats", "items", counts["items"])
        self.sow("moe_stats", "dropped", counts["dropped"])
        return y.reshape(b, s, d)


def _over_ep(mesh, ep: int, x, weights, experts, w, first: int):
    """The layer on an ``ep`` mesh axis: each chip holds ``held / ep``
    experts, computes their part for every token, and the parts are summed
    over the axis (tokens are not exchanged: every chip of the axis has
    them all)."""
    from jax.sharding import PartitionSpec as P

    count = w["gate"].shape[0]
    if count % ep:
        raise ValueError(f"{count} held experts do not divide over ep={ep}")

    def local(x, weights, experts, gate, up, down):
        mine = first + jax.lax.axis_index("ep") * (count // ep)
        y, counts = dropless_experts(x, weights, experts, gate, up, down,
                                     mine)
        return (jax.lax.psum(y, "ep"), counts["items"],
                jax.lax.psum(counts["dropped"], "ep"))

    y, items, dropped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), P(), P("ep"), P("ep"), P("ep")),
        out_specs=(P(), P("ep"), P()), check_vma=False,
    )(x, weights, experts, w["gate"], w["up"], w["down"])
    return y, {"items": items, "dropped": dropped}
