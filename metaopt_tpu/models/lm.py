"""Decoder-only language models: one causal trunk over a library of blocks.

``DecoderOnlyLM`` is a stack of layers chosen by the model's description
(the hyperparameters of :func:`make_lm`), not by flags or the environment:

- without a layer pattern it is the GPT-shaped sibling of the seq2seq zoo:
  learned positions, ``EncoderLayer`` under a dense causal mask (pre-LN
  self-attention + ReLU FFN, or ``MoEFeedForward``'s capacity routing),
  tied readout;
- with one (``rope_layout`` / ``sliding_window_layout``, a 0/1 a layer, as
  SmallThinker's published config has them) every layer is a
  :class:`PatternBlock`: RMS norms, ``num_key_value_heads`` K/V heads of
  width ``head_dim`` shared by groups of query heads, rotary positions on
  the layers the pattern marks and none on the others, global or
  ``sliding_window_size`` causal attention stated by structure
  (ops/attention.CausalMask: the Pallas kernels skip the tiles it hides),
  the router read BEFORE attention, and a dropless expert layer
  (models/moe.DroplessMoE: top-k of ``moe_num_primary_experts`` on the
  logits, gated ReLU experts of width ``moe_ffn_hidden_size``) or, with no
  experts, a gated ReLU feed-forward; no learned positions, an untied head.
  A description in the Qwen3-MoE family's words (``num_experts``,
  ``num_experts_per_tok``, ``moe_intermediate_size``, ``hidden_act``) has
  that family's layer: RMS norms of q and k over a head's width, the router
  read AFTER attention from the second norm, the experts' activation as
  named. With ``sa_config`` every layer is of a third kind, *selected*:
  an :class:`Indexer` (``indexer_num_heads`` heads of ``indexer_head_dim``
  on one key head) scores every causal key for every query and attention
  runs over the ``topk`` best (ops/sparse_index.py,
  ops/attention.SelectedMask). The indexer reads a ``stop_gradient`` and its
  choice is piecewise constant, so the next-token loss sends it no
  gradient: its parameters are a frozen part of the trial, outside the
  gradient tree and outside AdamW (:func:`split_frozen`).
  With ``layer_types`` (the Olmo hybrid family's word) a layer is
  *linear*, a :class:`LinearAttention` mixer (the gated delta rule of
  ops/linear_attention.py: a recurrence, no positions), or full attention
  with RMS norms of q and k over the projected width; the block has that
  family's norm placement, x + norm(mixer(x)) then x + norm(ffn(x)), and
  the feed-forward is gated by ``hidden_act``.
  With ``kv_lora_rank`` (the DeepSeek-V3 family's word) every layer is of
  a fifth kind, *latent* (:class:`LatentAttention`: K and V come up from
  one compressed latent a token, q.k is ``qk_nope_head_dim`` +
  ``qk_rope_head_dim`` wide, the second part on ONE rotary key all heads
  share, rotary pairs adjacent with ``rope_interleave``, v
  ``v_head_dim`` wide); the first ``first_k_dense_replace`` layers take a
  gated feed-forward of ``intermediate_size`` and the others route over
  ``n_routed_experts`` by sigmoid scores (``scoring_func``), chosen with
  a correction bias (``topk_method`` noaux_tc) that is a frozen leaf as
  the indexer is, weighed without it (``norm_topk_prob``,
  ``routed_scaling_factor``), beside ``n_shared_experts`` shared ones.
  With ``model_type`` ``phi4flash`` (the SambaY decoder-hybrid-decoder of
  arXiv:2507.06607, read at the PUBLISHED depth N) a layer is one of five
  more kinds: even layers up to N/2 are *state-space*
  (:class:`StateSpaceMixer`: Mamba-1's selective scan,
  ops/selective_scan.py: a recurrence, no positions), later even ones
  *gated memory units* (:class:`GatedMemoryUnit`) that gate layer N/2's
  scan output; odd layers are :class:`DifferentialAttention` (pairs of
  heads, a_1 - lambda a_2 under a norm over the pair's joined value),
  ``sliding_window`` wide below N/2, full at N/2 + 1, and from N/2 + 3 on
  *cross*: q of their own on layer N/2 + 1's K and V. What a layer hands
  to later ones the trunk carries (:class:`HybridBlock` returns it); the
  block is x + mixer(LN(x)) then + ffn(LN(.)), LayerNorm with bias, the
  feed-forward gated by ``hidden_act``, no positions anywhere, the head
  tied to the embedding. ``layers_held`` lists the published numbers of
  the layers this chip holds (a pipeline stage's; not the first n): kinds
  and lambda_init are read at those numbers.
  The chip's share of a deployment is part of the description too:
  ``experts_held`` = (first, count) of the routed experts,
  ``vocab_held`` = (first, count) of the vocabulary's rows (ids are drawn
  from that slice, and logits and loss are over it) and ``heads_held`` =
  (first, count) of ``num_attention_heads``: every kind of head is built
  in that proportion.

``MHA`` / :class:`GroupedAttention` call ops/attention.attend, whose one
rule (``attention_route``) names the route from the mesh, the backend and
the dropout rate; an ``sp`` mesh (ring/Ulysses sequence parallelism) is
for the 2017 blocks' dense masks only.
The loss rides ``readout_xent``, so the per-device logits-bytes routing
between materializing and blocked online-softmax xent
(transformer.blocked_xent_enabled) applies to both kinds of stack.

:class:`LMTrial` is the train loop handed out step by step: the set-up and
one ``step(i)``, which :func:`train_lm` itself drives and a benchmark can
drive too.

SURVEY.md §2.8/§5 context: the reference ships no model code at all; the
zoo exists to exercise the executor/topology stack with real TPU-shaped
trial workloads.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from metaopt_tpu.models.transformer import (
    EncoderLayer,
    _pinit,
    blocked_xent_enabled,
    held_parameters,
    layer_norm,
    masked_mean_with_aux,
    readout_xent,
    rematerialised,
    residual,
    sharded_init,
)
from metaopt_tpu.models.moe import RoutingRule
from metaopt_tpu.ops.attention import (REMAT_KEEPS, CausalMask, LatentKV,
                                       attend, attention_route)
from metaopt_tpu.ops.embed import embed_gradient_route, embed_rows
from metaopt_tpu.parallel.sharding import with_mesh_partitioning
from metaopt_tpu.utils import trace


# ---------------------------------------------------------------------------
# the pattern's blocks


class RMSNorm(nn.Module):
    """Under the scope ``norm``; inside a mixer (q/k norms, the gated norm
    of a linear layer) the mixer's scope is the outer one and owns the
    operations (utils/trace.py::layer_of)."""

    eps: float = 1e-6

    @nn.compact
    @trace.scope("norm")
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps) * scale


def rope(x, theta: float, adjacent: bool = False):
    """Rotary positions 0..S-1 on ``x`` (B, S, H, D), float32: pair j turns
    by pos * theta^(-2j/D). The two halves of a head are the pairs,
    channels (j, j + D/2) (the ``rotate_half`` convention), or, with
    ``adjacent``, channels (2j, 2j + 1) (``rope_interleave``)."""
    s, d = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]  # (S, D/2)
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    x = x.astype(jnp.float32)
    if adjacent:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


#: the kinds of layer: (name, has positions to say). A layer is of the
#: first kind it can be said to be, in :func:`layer_kind`'s order.
KINDS = (("linear", False), ("latent", True), ("selected", True),
         ("window", True), ("global", True))


def layer_kind(sliding: bool, rotary: bool, selected: bool = False,
               linear: bool = False, latent: bool = False) -> str:
    for (name, positions), is_it in zip(
            KINDS, (linear, latent, selected, sliding, True)):
        if is_it:
            return name + (("-rope" if rotary else "-nope") if positions
                           else "")


class Indexer(nn.Module):
    """Which keys each query attends to: ``n_heads`` index heads of width
    ``head_dim`` on one layer-normed key head, a weight a (query, head)
    read from the hidden state, and the ``top_k`` causal keys with the
    largest ``sum_j w[t, j] relu(q[t, j] . k[s])`` (ops/sparse_index.py).
    Float32 at matmul precision highest throughout: the choice hangs on the
    scores' last bits. Its parameters get no gradient (module docstring)."""

    n_heads: int
    head_dim: int
    top_k: int
    rope_theta: Optional[float]

    @nn.compact
    def __call__(self, n):
        from metaopt_tpu.ops import sparse_index

        n = jax.lax.stop_gradient(n.astype(jnp.float32))
        with trace.scope("attention.index"):
            proj = lambda name, features: nn.DenseGeneral(  # noqa: E731
                features, use_bias=False, name=name,
                precision=jax.lax.Precision.HIGHEST,
                kernel_init=with_mesh_partitioning(
                    nn.initializers.lecun_normal(),
                    (None,) * (1 + len(features))))(n)
            q = proj("q", (self.n_heads, self.head_dim))
            k = nn.LayerNorm(name="k_norm")(proj("k", (self.head_dim,)))
            if self.rope_theta is not None:
                q = rope(q, self.rope_theta)
                k = rope(k[:, :, None], self.rope_theta)[:, :, 0]
            w = proj("w", (self.n_heads,)) * (
                self.n_heads ** -0.5 * self.head_dim ** -0.5)
        mask, selected = sparse_index.select(q, k, w, self.top_k)
        b, s = n.shape[:2]
        self.sow("attn_stats", "selected_pairs", selected)
        self.sow("attn_stats", "causal_pairs",
                 jnp.asarray(b * s * (s + 1) // 2, jnp.int32))
        return mask


#: What a rematerialised block keeps of its mixer's projections where
#: :func:`remat_keeps` finds the room: the products as the matmuls leave
#: them, q, k, v (and a linear layer's g, a, b) BEFORE the norms, the
#: convolutions, rotary, the scale and the casts, which are made again from
#: them, elementwise: a norm's backward needs the product itself, so a
#: kept normed value would bring the matmul back. The last name of each is
#: the output projection's.
ATTENTION_REMAT_KEEPS = ("attention.q_proj", "attention.k_proj",
                         "attention.v_proj", "attention.out_proj")
LINEAR_REMAT_KEEPS = tuple(
    f"linear_attention.{n}_proj" for n in ("q", "k", "v", "g", "a", "b",
                                           "out"))


class GroupedAttention(nn.Module):
    """Causal self attention with fewer K/V heads than query heads, no
    bias; rotary or no positions; a window, none, or the keys an
    :class:`Indexer` selects (``selection``: its heads, their width and
    ``topk``); RMS norms of q and k over a head's width, over the
    projected width (``qk_norm_whole``: the heads held here together, one
    scale vector of heads x width) or none. The four projections' products
    carry the names of ``ATTENTION_REMAT_KEEPS``: identities unless a
    block's policy asks for them."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    window: Optional[int]
    rope_theta: Optional[float]
    qk_norm: Optional[float] = None     # the norms' eps
    selection: Optional[Tuple[int, int, int]] = None
    qk_norm_whole: bool = False

    @nn.compact
    @trace.scope("attention")
    def __call__(self, x):
        proj = lambda name, heads: nn.DenseGeneral(  # noqa: E731
            (heads, self.head_dim), axis=-1, dtype=jnp.bfloat16, name=name,
            use_bias=False, kernel_init=_pinit(True, (None, "tp", None)))
        mask = CausalMask(self.window)
        if self.selection is not None:
            mask = Indexer(*self.selection, self.rope_theta,
                           name="indexer")(x)
        x = x.astype(jnp.bfloat16)
        kept_q, kept_k, kept_v, kept_out = ATTENTION_REMAT_KEEPS
        q, k, v = (checkpoint_name(proj("q", self.n_heads)(x), kept_q),
                   checkpoint_name(proj("k", self.n_kv_heads)(x), kept_k),
                   checkpoint_name(proj("v", self.n_kv_heads)(x), kept_v))
        if self.qk_norm is not None:
            whole = lambda y: y.reshape(  # noqa: E731
                *y.shape[:2], -1) if self.qk_norm_whole else y
            q = RMSNorm(self.qk_norm, name="q_norm")(whole(q)).reshape(q.shape)
            k = RMSNorm(self.qk_norm, name="k_norm")(whole(k)).reshape(k.shape)
        if self.rope_theta is not None:
            q, k = rope(q, self.rope_theta), rope(k, self.rope_theta)
        q = (q / math.sqrt(self.head_dim)).astype(jnp.bfloat16)
        k = k.astype(jnp.bfloat16)
        out = attend(q, k, v, mask)
        return checkpoint_name(nn.DenseGeneral(
            self.d_model, axis=(-2, -1), dtype=jnp.bfloat16, name="out",
            use_bias=False, kernel_init=_pinit(True, ("tp", None, None)),
        )(out), kept_out)


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    """What a description says of its latent attention: the K/V latent's
    ``rank``, a head's q.k width without positions (``nope``) and with
    (``rope``: one rotary key for all heads), its value width ``v``, and
    whether the rotary pairs are adjacent channels."""

    rank: int
    nope: int
    rope: int
    v: int
    adjacent: bool


#: What a rematerialised block keeps of a latent layer's projections where
#: :func:`remat_keeps` finds the room: q's product, the down-projection's
#: (the latent and the shared rotary key before its rotation: 2 (rank +
#: rope) bytes a token, the cheapest thing a block can keep), the
#: up-projection's (2 heads (nope + v): the dearest, and made again from
#: the kept latent by a norm and a rank-deep matmul) and the output
#: projection's.
LATENT_REMAT_KEEPS = ("attention.q_proj", "attention.kv_latent",
                      "attention.kv_up", "attention.out_proj")


def _pairs_first(w):
    """The last axis' channels (0, 1, 2, 3, ...) as (0, 2, ..., 1, 3, ...):
    rotary on the adjacent pairs of ``w`` is rotary on the halves of this,
    channel for channel, and a score sums over the channels in any order
    that q and the key share."""
    return jnp.swapaxes(w.reshape(*w.shape[:-1], -1, 2), -1, -2).reshape(
        w.shape)


class LatentAttention(nn.Module):
    """Causal self attention over a compressed K/V (the DeepSeek-V3
    family's): q of ``n_heads`` heads ``nope + rope`` wide straight from x
    (no q rank); c, k_pe = split(x W_kva, [rank, rope]); k_nope, v =
    split(rmsnorm(c) W_kvb, [nope, v]) a head; rotary on q's last ``rope``
    columns and on k_pe, the ONE key all heads share; scores (q_nope .
    k_nope + q_pe . k_pe) (nope + rope)^-1/2; out ``v`` wide a head, then
    the output projection. No bias, no q/k norms. The down-projection, the
    latent's norm, the up-projection and the shared key's rotary are under
    the scope ``attention.latent``; the four matmuls' products carry the
    names of ``LATENT_REMAT_KEEPS``.

    How the operands reach attention is ops/latent_attention.hand_over's
    to say. ``"copies"``: q, k, v (B, S, H, D), rotary and the scale in
    float32 arrays, a copy of the shared key joined to every head's
    k_nope: what the reference takes, and the tests' oracle. ``"in
    place"``: the matmuls leave their products feature-major and the
    kernels read them where they lie; q's product reaches them in one
    pass, with the rotary pairs' de-interleaving on W_q's and the shared
    key's rotary columns (the parameters keep the published order)."""

    d_model: int
    n_heads: int
    spec: LatentSpec
    rope_theta: float
    eps: float

    @nn.compact
    @trace.scope("attention")
    def __call__(self, x):
        from metaopt_tpu.ops import latent_attention as la
        from metaopt_tpu.parallel.mesh import active_mesh

        sp = self.spec
        mesh = active_mesh()
        in_place = la.hand_over(attention_route(0.0, mesh), mesh, sp.nope,
                                sp.v) == "in place"
        # in place the pairs are made halves where that costs a weight's
        # bytes (W_q's rotary columns) or the one key's, not q's
        halves = _pairs_first if in_place and sp.adjacent else (lambda w: w)
        adjacent = sp.adjacent and not in_place
        how = {"q": {}, "kv_b": {}, "out": {"axis": (-2, -1)}}
        if in_place:
            how = {"q": {"dot_general": lambda x, w, *a, **kw: la.project_t(
                       x, jnp.concatenate([w[..., :sp.nope],
                                           halves(w[..., sp.nope:])], -1),
                       *a, **kw)},
                   "kv_b": {"dot_general": la.project_t},
                   "out": {"axis": (1, 2), "dot_general": la.contract_t}}
        heads = lambda name, width: nn.DenseGeneral(  # noqa: E731
            (self.n_heads, width), axis=-1, dtype=jnp.bfloat16, name=name,
            use_bias=False, kernel_init=_pinit(True, (None, "tp", None)),
            **how[name])
        kept_q, kept_latent, kept_up, kept_out = LATENT_REMAT_KEEPS
        x = x.astype(jnp.bfloat16)
        q = checkpoint_name(heads("q", sp.nope + sp.rope)(x), kept_q)
        with trace.scope("attention.latent"):
            down = checkpoint_name(nn.Dense(
                sp.rank + sp.rope, dtype=jnp.bfloat16, name="kv_a",
                use_bias=False, kernel_init=_pinit(True, (None, None)))(x),
                kept_latent)
            c = RMSNorm(self.eps, name="kv_a_norm")(down[..., :sp.rank])
            kv = checkpoint_name(heads("kv_b", sp.nope + sp.v)(
                c.astype(jnp.bfloat16)), kept_up)
            k_pe = rope(halves(down[..., None, sp.rank:]), self.rope_theta,
                        adjacent)[:, :, 0].astype(jnp.bfloat16)
        if in_place:  # q (B, H (nope + rope), S), kv (B, H (nope + v), S)
            q = la.rotary_scaled(q, self.n_heads, sp.nope, self.rope_theta,
                                 1.0 / math.sqrt(sp.nope + sp.rope))
            out = attend(q, LatentKV(kv, k_pe.transpose(0, 2, 1), sp.nope),
                         None, CausalMask())
            out = out.reshape(out.shape[0], self.n_heads, sp.v, -1)
        else:         # q (B, S, H, nope + rope), kv (B, S, H, nope + v)
            q_pe = rope(q[..., sp.nope:], self.rope_theta, adjacent)
            q = (jnp.concatenate(
                [q[..., :sp.nope].astype(jnp.float32), q_pe], axis=-1)
                / math.sqrt(sp.nope + sp.rope)).astype(jnp.bfloat16)
            k = jnp.concatenate([kv[..., :sp.nope], jnp.broadcast_to(
                k_pe[:, :, None], (*kv.shape[:3], sp.rope))], axis=-1)
            out = attend(q, k, kv[..., sp.nope:], CausalMask())
        return checkpoint_name(nn.DenseGeneral(
            self.d_model, dtype=jnp.bfloat16, name="out", use_bias=False,
            kernel_init=_pinit(True, ("tp", None, None)), **how["out"],
        )(out), kept_out)


#: What a rematerialised block keeps of its gated feed-forward where
#: :func:`remat_keeps` finds the room, in order of gain a byte: the down
#: product, the module's output (2 d_model bytes a token; with the block's
#: norm on the branch's output the backward pass needs it, and would run
#: the matmul again for it), then the gate and the up product (2 d_ff
#: each); ``act(gate) * up`` is made again from those two, elementwise.
FFN_REMAT_KEEPS = ("ffn.down", "ffn.gate", "ffn.up")


class GatedFeedForward(nn.Module):
    """(act(x W_gate) * (x W_up)) W_down, no bias; ``activation`` by name,
    as ``DroplessMoE`` takes it. The three products carry the names of
    ``FFN_REMAT_KEEPS``: identities unless a block's policy asks for them.
    The gating is kept out of the matmuls around it (a barrier, below)."""

    d_model: int
    d_ff: int
    activation: str = "relu"

    @nn.compact
    @trace.scope("ffn")
    def __call__(self, x):
        dense = lambda name, n, axes: nn.Dense(  # noqa: E731
            n, dtype=jnp.bfloat16, name=name, use_bias=False,
            kernel_init=_pinit(True, axes))
        x = x.astype(jnp.bfloat16)
        act = {"relu": nn.relu, "silu": nn.silu}[self.activation]
        kept_down, kept_gate, kept_up = FFN_REMAT_KEEPS
        # The two products stand in memory before the gating reads them
        # and, by the barrier's transpose, so do their gradients before the
        # four matmuls that read those. Left to itself XLA makes
        # act(gate) * up and its derivative inside those matmuls' operands,
        # again for every pass over a tile: on a v5e they then take 7.7-9.5
        # ms where a matmul on operands that stand takes 3.9 (PERF.md
        # section 6, PR 33).
        gate, up = jax.lax.optimization_barrier((
            checkpoint_name(dense("gate", self.d_ff, (None, "tp"))(x),
                            kept_gate),
            checkpoint_name(dense("up", self.d_ff, (None, "tp"))(x),
                            kept_up)))
        return checkpoint_name(
            dense("down", self.d_model, ("tp", None))(act(gate) * up),
            kept_down)


@dataclasses.dataclass(frozen=True)
class LinearSpec:
    """What a description says of its linear-attention layers."""

    heads: int              # held here
    of: int                 # the layer's published count
    key_dim: int
    value_dim: int
    conv: int               # taps of the short convolutions
    neg_eigval: bool        # beta in (0, 2), not (0, 1)


@dataclasses.dataclass(frozen=True)
class HybridSpec:
    """What a ``phi4flash`` description says of its decoder-hybrid-decoder:
    the published numbers of the ``layers`` held here and the kind of each
    (``HYBRID_KINDS``), read at the published depth ``of``; the
    state-space mixers' sizes; which layer's scan output is the memory and
    which layer's K and V the cross layers read."""

    layers: Tuple[int, ...]
    kinds: Tuple[str, ...]
    of: int
    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int
    memory_layer: int
    kv_layer: int

    def held(self, *kinds: str):
        """The published numbers of the held layers of ``kinds``."""
        return [n for n, k in zip(self.layers, self.kinds) if k in kinds]


#: a ``phi4flash`` layer's kinds and what each reads of an earlier layer
HYBRID_KINDS = {"ssm": (), "window": (), "full": (), "gmu": ("memory",),
                "cross": ("kv",)}


def hybrid_kind(layer: int, of: int) -> str:
    """The kind of published layer ``layer`` of a ``phi4flash`` model ``of``
    layers deep (``mb_per_layer`` 2: every other layer is a state-space or
    memory layer; the decoders split at ``of`` / 2)."""
    half = of // 2
    if layer % 2 == 0:
        return "ssm" if layer <= half else "gmu"
    if layer < half:
        return "window"
    if layer == half + 1:
        return "full"
    if layer >= half + 3:
        return "cross"
    raise ValueError(f"layer {layer} of {of} has no kind: the full layer is "
                     f"{half + 1}, the cross layers start at {half + 3}")


def lambda_init(layer: int) -> float:
    """The differential transformer's 0.8 - 0.6 exp(-0.3 l), at the
    PUBLISHED layer number."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


@dataclasses.dataclass(frozen=True)
class Pattern:
    """What a description with a layer pattern says of every layer, and
    ``layers``: (sliding window?, rotary positions?) of each."""

    layers: Tuple[Tuple[bool, bool], ...]
    n_kv_heads: int
    head_dim: int
    window: int
    rope_theta: float
    rms_eps: float
    n_experts: int          # routed over; 0 = a gated feed-forward
    top_k: int
    expert_d_ff: int
    experts_held: Tuple[int, int]
    vocab_held: Tuple[int, int]
    #: the Qwen3-MoE family's layer: q/k norms, the router after attention
    qk_norm: bool = False
    router_after_attention: bool = False
    activation: str = "relu"
    #: (index heads, their width, top k) of ``sa_config``: every layer
    #: attends to the keys its indexer selects
    selection: Optional[Tuple[int, int, int]] = None
    #: the Olmo hybrid family's layer: which layers are linear (none where
    #: empty) and what of; the norms on the branches' outputs; q/k norms
    #: over the projected width; (first, count) of the heads held
    linear_layers: Tuple[bool, ...] = ()
    linear: Optional[LinearSpec] = None
    norm_after: bool = False
    qk_norm_whole: bool = False
    heads_held: Optional[Tuple[int, int]] = None
    #: the DeepSeek-V3 family's layer: every layer's attention is latent;
    #: the first ``dense_layers`` layers take a gated feed-forward of
    #: ``d_ff`` where the others route; beside the routed experts every
    #: token meets shared ones, one gated feed-forward ``shared_d_ff``
    #: wide; how the routing chooses and weighs
    latent: Optional[LatentSpec] = None
    dense_layers: int = 0
    shared_d_ff: int = 0
    routing: RoutingRule = RoutingRule()
    #: the ``phi4flash`` family's decoder-hybrid-decoder: every layer is a
    #: :class:`HybridBlock` of the kind ``hybrid`` names, the head tied
    hybrid: Optional[HybridSpec] = None

    def is_linear(self, i: int) -> bool:
        return bool(self.linear_layers) and self.linear_layers[i]

    def is_routed(self, i: int) -> bool:
        return bool(self.n_experts) and i >= self.dense_layers

    def routed_layers(self) -> int:
        return sum(map(self.is_routed, range(len(self.layers))))

    def kind(self, i: int) -> str:
        if self.hybrid is not None:
            kind = self.hybrid.kinds[i]
            return kind if kind in ("ssm", "gmu") else \
                {"full": "global"}.get(kind, kind) + "-nope"
        return layer_kind(*self.layers[i], self.selection is not None,
                          self.is_linear(i), self.latent is not None)

    def kinds(self):
        """The distinct layer kinds, in the pattern's order."""
        return list(dict.fromkeys(map(self.kind, range(len(self.layers)))))


class PatternBlock(nn.Module):
    """x + attention(norm(x)), then + experts(norm(.)) routed by logits
    read from the FIRST norm's output, before attention, or (the pattern's
    ``router_after_attention``) from the second's; with the pattern's
    ``norm_after``, x + norm(mixer(x)) then + norm(ffn(.)), the mixer a
    :class:`LinearAttention` where the layer is ``linear``. The attention
    is a :class:`LatentAttention` where the pattern has a latent; a layer
    that is not ``routed`` (a model without experts, a leading dense layer)
    takes the gated feed-forward of ``d_ff``. The residual stream is
    float32."""

    d_model: int
    n_heads: int
    d_ff: int
    pattern: Pattern
    sliding: bool
    rotary: bool
    linear: bool = False
    routed: bool = False

    @nn.compact
    def __call__(self, x):
        p = self.pattern

        def router(read):
            with trace.scope("moe"), trace.scope("moe.router"):
                # float32 in earnest: a TPU's default precision would make
                # this product in bfloat16 passes, and the top-k choice
                # hangs on the logits' last bits
                return nn.Dense(
                    p.n_experts, use_bias=False, name="router",
                    precision=jax.lax.Precision.HIGHEST,
                    kernel_init=with_mesh_partitioning(
                        nn.initializers.lecun_normal(), (None, None)))(read)

        attention = lambda: LatentAttention(  # noqa: E731
            self.d_model, self.n_heads, p.latent, p.rope_theta, p.rms_eps,
            name="attn") if p.latent is not None else GroupedAttention(
            self.d_model, self.n_heads, p.n_kv_heads, p.head_dim,
            p.window if self.sliding else None,
            p.rope_theta if self.rotary else None,
            p.rms_eps if p.qk_norm else None, p.selection, p.qk_norm_whole,
            name="attn")
        if p.norm_after:
            mixer = LinearAttention(self.d_model, p.linear, p.rms_eps,
                                    name="linear") if self.linear \
                else attention()
            x = residual(x, RMSNorm(p.rms_eps, name="norm_mixer")(mixer(x)))
            return residual(x, RMSNorm(p.rms_eps, name="norm_ffn")(
                GatedFeedForward(self.d_model, self.d_ff, p.activation,
                                 name="mlp")(x)))
        n = RMSNorm(p.rms_eps, name="norm_in")(x)
        if self.routed and not p.router_after_attention:
            logits = router(n)
        x = residual(x, attention()(n))
        m = RMSNorm(p.rms_eps, name="norm_post")(x)
        if self.routed:
            from metaopt_tpu.models.moe import DroplessMoE

            if p.router_after_attention:
                logits = router(m)
            # the rule's correction bias: a frozen leaf (``FROZEN``)
            bias = self.param(
                "choice_bias", with_mesh_partitioning(
                    nn.initializers.zeros, (None,)),
                (p.n_experts,)) if p.routing.bias else None
            return residual(x, DroplessMoE(
                self.d_model, p.expert_d_ff, p.n_experts, p.top_k,
                p.experts_held, p.activation, p.routing, p.shared_d_ff,
                name="experts")(m, logits, bias))
        return residual(x, GatedFeedForward(
            self.d_model, self.d_ff, p.activation, name="mlp")(m))


class DecoderOnlyLM(nn.Module):
    """Causal LM: embed (+ learned positions) → the layers → readout, tied
    without a ``pattern`` and an untied head over the held rows with one."""

    vocab: int = 1000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: int = 2048
    dropout: float = 0.1
    max_len: int = 512
    #: >0 turns every FFN into a top-k-routed MoE (see models/moe.py)
    n_experts: int = 0
    capacity_factor: float = 1.25
    router_top_k: int = 1
    #: rematerialize each block in the backward pass (the HBM/FLOPs trade):
    #: a block keeps its input and what ``keeps`` names
    #: (transformer.rematerialised), and makes the rest again
    remat: bool = False
    #: the layer pattern of a description that has one (module docstring)
    pattern: Optional[Pattern] = None
    #: the names a rematerialised :class:`PatternBlock` keeps, as
    #: :func:`remat_keeps` decided them for the trial's sizes and device
    #: (:class:`LMTrial`); None: what it says of the pattern alone, the
    #: kernels' outputs
    keeps: Optional[Tuple[str, ...]] = None

    @nn.compact
    def __call__(self, tokens, *, train: bool, features: bool = False):
        if self.pattern is not None:
            return self._patterned(tokens, features)
        emb = nn.Embed(
            self.vocab, self.d_model, dtype=jnp.bfloat16, name="embed",
            embedding_init=nn.with_partitioning(
                nn.initializers.normal(1.0), (None, None)
            ),
        )
        pos = self.param(
            "pos_embed",
            nn.with_partitioning(nn.initializers.normal(0.02), (None, None)),
            (self.max_len, self.d_model),
        )
        t_len = tokens.shape[1]
        if t_len > self.max_len:
            raise ValueError(
                f"sequence length {t_len} exceeds the positional table "
                f"(max_len={self.max_len}); pass max_len>=seq to make_lm"
            )
        with trace.scope("attention"):  # the mask is attention's operand
            pad = (tokens != 0)[:, None, None, :]                 # (b,1,1,k)
            causal = jnp.tril(jnp.ones((t_len, t_len), bool))[None, None]
            mask = causal & pad
        block_cls = (rematerialised(EncoderLayer, static_argnums=(3,))
                     if self.remat else EncoderLayer)
        with trace.scope("embed"):
            x = emb(tokens) + pos[None, :t_len].astype(jnp.bfloat16)
        for i in range(self.n_layers):
            x = block_cls(self.d_model, self.n_heads, self.d_ff,
                          self.dropout, self.n_experts,
                          self.capacity_factor, True, self.router_top_k,
                          name=f"h{i}")(x, mask, train)
        x = layer_norm("ln_f", x)
        if features:
            # pre-readout features for the blocked xent: the (B, T, V)
            # logits tensor never materializes (see readout_xent)
            return x
        with trace.scope("readout_xent"):
            logits = jnp.einsum(
                "btd,vd->btv", x.astype(jnp.bfloat16), emb.embedding
            )
            return logits.astype(jnp.float32)

    def held_vocab(self) -> Tuple[int, int]:
        """(first id, rows) of the vocabulary this model embeds and reads
        out: the pattern's held slice, or all of ``vocab``."""
        return self.pattern.vocab_held if self.pattern else (0, self.vocab)

    def _patterned(self, tokens, features: bool):
        p = self.pattern
        if p.hybrid is not None:
            return self._hybrid(tokens, features)
        first, rows = p.vocab_held
        # the embedding at size 1 (the first norm rescales it), the head
        # at 1/sqrt(d): logits of size 1, a loss near log(rows) at the start
        table = lambda name, size=1.0: nn.Embed(  # noqa: E731
            rows, self.d_model, dtype=jnp.bfloat16, name=name,
            embedding_init=nn.with_partitioning(
                nn.initializers.normal(size), (None, None)))
        block_cls = PatternBlock
        if self.remat:
            block_cls = rematerialised(
                PatternBlock, keeps=remat_keeps(p)["keeps"]
                if self.keeps is None else self.keeps)
        with trace.scope("embed"):
            x = embed_rows(table("embed").embedding,
                           tokens - first).astype(jnp.float32)
        heads = p.heads_held[1] if p.heads_held else self.n_heads
        for i, (sliding, rotary) in enumerate(p.layers):
            x = block_cls(self.d_model, heads, self.d_ff, p, sliding,
                          rotary, p.is_linear(i), p.is_routed(i),
                          name=f"h{i}")(x)
        x = RMSNorm(p.rms_eps, name="norm_f")(x)
        head = table("head", self.d_model ** -0.5)
        if features:
            # the head's table has to exist for readout_xent to fold in
            head.embedding  # noqa: B018
            return x
        return _logits(x, head.embedding)

    def _hybrid(self, tokens, features: bool):
        """The ``phi4flash`` trunk: it carries what a layer hands on (the
        memory layer's scan output, the full layer's K and V) to the
        layers that read it. A handed value is an output of its block and
        an input of each reader, so a rematerialised block keeps it and
        its gradient is the sum over the readers."""
        p = self.pattern
        sp = p.hybrid
        first, rows = p.vocab_held
        # the tied table at 1/sqrt(d): logits of size 1; the first norm
        # rescales the stream
        emb = nn.Embed(
            rows, self.d_model, dtype=jnp.bfloat16, name="embed",
            embedding_init=nn.with_partitioning(
                nn.initializers.normal(self.d_model ** -0.5), (None, None)))
        block_cls = HybridBlock
        if self.remat:
            block_cls = rematerialised(
                HybridBlock, keeps=remat_keeps(p)["keeps"]
                if self.keeps is None else self.keeps)
        with trace.scope("embed"):
            x = embed_rows(emb.embedding, tokens - first).astype(jnp.float32)
        heads = p.heads_held[1] if p.heads_held else self.n_heads
        handed = {}
        for i, (layer, kind) in enumerate(zip(sp.layers, sp.kinds)):
            x, on = block_cls(self.d_model, heads, self.d_ff, p, i,
                              name=f"h{layer}")(
                x, *(handed[r] for r in HYBRID_KINDS[kind]))
            handed.update(on)
        x = _layer_norm("norm_f", x, p.rms_eps)
        if features:
            return x
        return _logits(x, emb.embedding)


@trace.scope("readout_xent")
def _logits(x, table):
    """The float32 logits of a pattern decoder's last norm ``x`` over the
    rows of ``table`` (its untied head, or the tied embedding).

    The cast stands in memory before the head's matmuls read it. Left to
    itself XLA makes it inside their operands from the float32 stream, and
    the weight-gradient matmul then runs at its rate only while the
    compiler also happens to move that stream on chip for it: on a v5e it
    takes 10.2 ms so and 17.7 without, and which it is turned on what the
    blocks keep (PERF.md section 6, PR 35)."""
    return jnp.einsum(
        "btd,vd->btv", jax.lax.optimization_barrier(x.astype(jnp.bfloat16)),
        table.astype(jnp.bfloat16), preferred_element_type=jnp.float32)


#: a description's published names beside the zoo's own
_PUBLISHED = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
              "num_hidden_layers": "n_layers", "vocab_size": "vocab",
              # the Qwen3-MoE family's words for what pattern_of reads
              "num_experts": "moe_num_primary_experts",
              "num_experts_per_tok": "moe_num_active_primary_experts",
              "moe_intermediate_size": "moe_ffn_hidden_size",
              # the Olmo hybrid family's
              "intermediate_size": "d_ff",
              # the DeepSeek-V3 family's
              "n_routed_experts": "moe_num_primary_experts"}

#: a published ``layer_types`` entry -> is the layer linear?
_LAYER_TYPES = {"linear_attention": True, "full_attention": False}


def _own_names(hparams: Dict[str, Any]) -> Dict[str, Any]:
    h = dict(hparams)
    for published, own in _PUBLISHED.items():
        if published in h:
            h.setdefault(own, h[published])
    return h


#: what a family's layer is, by the family and not by how a key of its
#: description is spelt: RMS norms of q and k (over a head, or ``whole``:
#: over the projected width), where the router reads, the norms' placement
_FAMILIES = {
    # SmallThinker's: the layouts' own keys
    "layouts": {},
    "qwen3_moe": {"qk_norm": True, "router_after_attention": True},
    "olmo_hybrid": {"qk_norm": True, "qk_norm_whole": True,
                    "norm_after": True},
    "deepseek_v3": {"router_after_attention": True},
    # the SambaY decoder-hybrid-decoder: its own block (HybridBlock)
    "phi4flash": {},
}


def family_of(h: Dict[str, Any]) -> Optional[str]:
    """The family whose words a description with a layer pattern speaks
    (a key of ``_FAMILIES``), None for one without a pattern:
    ``kv_lora_rank`` is the DeepSeek-V3 family's, ``layer_types`` the Olmo
    hybrid's, ``num_experts`` the Qwen3-MoE family's, the two layouts or
    ``sa_config`` alone SmallThinker's; ``model_type`` ``phi4flash`` names
    its family itself."""
    if h.get("model_type") == "phi4flash":
        return "phi4flash"
    for key, family in (("kv_lora_rank", "deepseek_v3"),
                        ("layer_types", "olmo_hybrid"),
                        ("num_experts", "qwen3_moe"),
                        ("sa_config", "layouts"),
                        ("rope_layout", "layouts"),
                        ("sliding_window_layout", "layouts")):
        if key in h:
            return family
    return None


def pattern_of(h: Dict[str, Any]) -> Optional[Pattern]:
    """The layer pattern a description names, or None. The two layouts are
    read up to ``n_layers`` (a cut in depth keeps the leading layers);
    without them every layer is global and rotary. The family
    (:func:`family_of`) brings its layer: the Qwen3-MoE family's q/k norms
    and router placement, with ``sa_config`` the selected attention; the
    Olmo hybrid family's block, its linear layers and, with
    ``rope_parameters.rope_theta`` null, no positions anywhere; the
    DeepSeek-V3 family's latent attention, leading dense layers, shared
    experts and routing rule (:func:`_latent`, :func:`_routing`); the
    ``phi4flash`` family's kinds of layer at the published numbers of
    ``layers_held`` (:func:`_hybrid_spec`), ``sliding_window`` and
    ``layer_norm_eps``."""
    family = family_of(h)
    if family is None:
        return None
    n_layers = int(h.get("n_layers", 6))
    if family == "phi4flash":
        hybrid = _hybrid_spec(h, n_layers)
        h = {"sliding_window_size": h.get("sliding_window", 512),
             "rms_norm_eps": h.get("layer_norm_eps", 1e-5),
             "sliding_window_layout": [k == "window" for k in hybrid.kinds],
             "rope_parameters": {"rope_theta": None}, **h}
        n_layers = len(hybrid.layers)
    types = h.get("layer_types")
    unknown = sorted(set(types or ()) - set(_LAYER_TYPES))
    if unknown:
        raise ValueError(f"layer_types names {unknown}; known: "
                         f"{sorted(_LAYER_TYPES)}")
    theta = (h.get("rope_parameters") or h).get("rope_theta", 10000.0)
    rotary = list(h.get("rope_layout") or [int(theta is not None)] * n_layers)
    sliding = list(h.get("sliding_window_layout") or [0] * n_layers)
    if min(len(rotary), len(sliding), len(types or rotary)) < n_layers:
        raise ValueError(f"the layouts name {len(rotary)}, {len(sliding)} "
                         f"and {len(types or rotary)} layers, the model has "
                         f"{n_layers}")
    n_experts = int(h.get("moe_num_primary_experts", 0))
    vocab = int(h.get("vocab", 1000))
    held = lambda key, whole: tuple(  # noqa: E731
        int(v) for v in h.get(key) or (0, whole))
    n_heads = int(h.get("n_heads", 8))
    heads_held = held("heads_held", n_heads)
    share = lambda heads: int(heads) * heads_held[1] // n_heads  # noqa: E731
    linear_layers = tuple(_LAYER_TYPES[t] for t in (types or ())[:n_layers])
    expert_d_ff = int(h.get("moe_ffn_hidden_size", h.get("d_ff", 2048)))
    of_the_family = dict(_FAMILIES[family])
    if family == "phi4flash":
        of_the_family.update(hybrid=hybrid)
    if family == "deepseek_v3":
        of_the_family.update(
            latent=_latent(h), routing=_routing(h),
            dense_layers=min(int(h.get("first_k_dense_replace", 0)),
                             n_layers),
            shared_d_ff=int(h.get("n_shared_experts") or 0) * expert_d_ff)
    return Pattern(
        layers=tuple((bool(s), bool(r)) for s, r in
                     zip(sliding[:n_layers], rotary[:n_layers])),
        n_kv_heads=share(h.get("num_key_value_heads", n_heads)),
        head_dim=int(h.get("head_dim") or int(h.get("d_model", 512))
                     // n_heads),
        window=int(h.get("sliding_window_size", 4096)),
        rope_theta=float(10000.0 if theta is None else theta),
        rms_eps=float(h.get("rms_norm_eps", 1e-6)),
        n_experts=n_experts,
        top_k=int(h.get("moe_num_active_primary_experts", 1)),
        expert_d_ff=expert_d_ff,
        experts_held=held("experts_held", n_experts),
        vocab_held=held("vocab_held", vocab),
        activation=str(h.get("hidden_act", "relu")),
        selection=_selection(h.get("sa_config")),
        linear_layers=linear_layers,
        linear=_linear(h, share) if any(linear_layers) else None,
        heads_held=heads_held if "heads_held" in h else None,
        **of_the_family,
    )


def _hybrid_spec(h: Dict[str, Any], of: int) -> HybridSpec:
    """The decoder-hybrid-decoder of a ``phi4flash`` description ``of``
    layers deep: the layers held (all, without ``layers_held``), their
    kinds, and Mamba-1's sizes by the family's convention where the
    description is silent (``mamba_expand`` 2, ``mamba_d_state`` 16,
    ``mamba_d_conv`` 4, ``mamba_dt_rank`` hidden / 16). A layer that reads
    what a layer not held would hand on is refused by name."""
    layers = tuple(int(n) for n in h.get("layers_held") or range(of))
    if list(layers) != sorted(set(layers)) or not layers \
            or not 0 <= layers[0] <= layers[-1] < of:
        raise ValueError(f"layers_held {list(layers)}: the published "
                         f"numbers of layers 0..{of - 1}, ascending")
    kinds = tuple(hybrid_kind(n, of) for n in layers)
    spec = HybridSpec(
        layers=layers, kinds=kinds, of=of,
        d_inner=int(h.get("mamba_expand", 2)) * int(h.get("d_model", 512)),
        d_state=int(h.get("mamba_d_state", 16)),
        d_conv=int(h.get("mamba_d_conv", 4)),
        dt_rank=int(h.get("mamba_dt_rank")
                    or -(-int(h.get("d_model", 512)) // 16)),
        memory_layer=of // 2, kv_layer=of // 2 + 1)
    for kind, source, what in (("gmu", spec.memory_layer, "the memory"),
                               ("cross", spec.kv_layer, "K and V")):
        if spec.held(kind) and source not in layers:
            raise ValueError(
                f"layer {spec.held(kind)[0]} reads {what} of layer "
                f"{source}, which is not among layers_held {list(layers)}")
    return spec


def _latent(h: Dict[str, Any]) -> LatentSpec:
    """The latent attention of a description in the DeepSeek-V3 family's
    words. What has no layer here is refused by its name: a q rank (and
    the norm that comes with it), rotary scaling (its ``mscale``)."""
    for key in ("q_lora_rank", "rope_scaling"):
        if h.get(key) is not None:
            raise ValueError(f"{key} {h[key]!r}: a latent layer has none "
                             "here")
    return LatentSpec(
        rank=int(h["kv_lora_rank"]), nope=int(h["qk_nope_head_dim"]),
        rope=int(h["qk_rope_head_dim"]), v=int(h["v_head_dim"]),
        adjacent=bool(h.get("rope_interleave", False)))


def _routing(h: Dict[str, Any]) -> RoutingRule:
    """The routing rule of a description in the DeepSeek-V3 family's words:
    sigmoid scores and a correction bias (``topk_method`` noaux_tc), in
    one group. A choice limited to groups of experts, an expert layer
    every other layer, another scoring or another method have no rule
    here and are refused by name."""
    for key in ("n_group", "topk_group", "moe_layer_freq"):
        if int(h.get(key, 1)) != 1:
            raise ValueError(f"{key} {h[key]}: the routing knows one group "
                             "of experts and an expert layer every layer")
    scoring, method = h.get("scoring_func", "sigmoid"), \
        h.get("topk_method", "noaux_tc")
    if scoring != "sigmoid" or method != "noaux_tc":
        raise ValueError(f"scoring_func {scoring!r} with topk_method "
                         f"{method!r}: the family's rule here is sigmoid "
                         "scores under noaux_tc")
    return RoutingRule("sigmoid", bias=True,
                       normalised=bool(h.get("norm_topk_prob", False)),
                       scale=float(h.get("routed_scaling_factor", 1.0)))


def _linear(h: Dict[str, Any], share) -> LinearSpec:
    """The linear layers of a description in the Olmo hybrid family's
    words, ``share`` of each kind of head held."""
    heads = int(h["linear_num_value_heads"])
    if int(h.get("linear_num_key_heads", heads)) != heads:
        raise ValueError("a linear layer has as many key heads as value "
                         f"heads here, not {h['linear_num_key_heads']} and "
                         f"{heads}")
    return LinearSpec(
        heads=share(heads), of=heads, key_dim=int(h["linear_key_head_dim"]),
        value_dim=int(h["linear_value_head_dim"]),
        conv=int(h.get("linear_conv_kernel_dim", 4)),
        neg_eigval=bool(h.get("linear_allow_neg_eigval", False)))


#: bytes of a trial's state a parameter: its value, AdamW's two moments and
#: its gradient, float32 each
STATE_BYTES_A_PARAMETER = 16


def remat_keeps(p: Optional[Pattern], *, tokens: int = 0, d_model: int = 0,
                d_ff: int = 0, n_heads: int = 0, parameters: int = 0,
                bytes_limit: Optional[int] = None) -> Dict[str, Any]:
    """What a rematerialised block of the model keeps besides its input, as
    ``trial.setup``'s ``attrs["remat"]`` says it. ``keeps``: the attention
    kernels' names, the scan's where a layer is linear and, for a model
    with a layer pattern, as many of its layers' matrix products as fit the
    device. It is one trade, time for memory, whose right side depends on
    size, so the rule reads the sizes: one device's ``tokens`` a step, its
    ``d_model``, ``d_ff`` and ``n_heads`` (the pattern's other head counts
    and widths are in ``p``), its ``parameters`` and its memory's
    ``bytes_limit``. The candidates (:func:`_products`) are taken in order
    of gain a byte: a product's FLOPs over the bytes of its output, which
    is its contracting width. The products of a name stand over all the
    layers that make it at once (``bytes``, each candidate name's) and a
    candidate is kept if it fits what is left of ``room``: half of what the
    limit leaves beside the state (``STATE_BYTES_A_PARAMETER``), the other
    half being the step's own (the blocks' inputs, one block's backward
    pass, the head's logits); one that does not fit is declined and the
    next is held against the same room. Without a limit (a backend that
    reports none, a model outside a trial) no product is kept. Every
    argument is explicit: the answer is made once, outside the traced
    function (:class:`LMTrial`)."""
    keeps = REMAT_KEEPS
    if p is None:
        return {"keeps": list(keeps)}
    if p.linear is not None:
        from metaopt_tpu.ops import linear_attention

        keeps += linear_attention.REMAT_KEEPS
    if p.hybrid is not None:
        from metaopt_tpu.ops import selective_scan

        keeps += selective_scan.REMAT_KEEPS
    room = None if bytes_limit is None else max(
        0, bytes_limit - STATE_BYTES_A_PARAMETER * parameters) // 2
    products = _products(p, tokens, d_model, d_ff, n_heads)
    left = room
    for _, sizes in sorted(products, key=lambda c: -c[0]):
        need = sum(sizes.values())
        if left is not None and need <= left:
            left -= need
            keeps += tuple(sizes)
    return {"keeps": list(keeps), "room": room,
            "bytes": {n: b for _, sizes in products
                      for n, b in sizes.items()}}


def _products(p: Pattern, tokens: int, d_model: int, d_ff: int,
              n_heads: int):
    """The matrix products a rematerialised block of the pattern can keep,
    as :func:`remat_keeps` takes them: (contracting width, {name: the bytes
    of its outputs over all the layers that make it}) a candidate, those of
    one width listed in the order they are tried. Products that read one
    input at one width are one candidate, kept or declined together: a
    gated feed-forward's gate and up, a mixer's input projections (a linear
    layer's two float32 gates among them: nothing in bytes, six passes at
    precision highest in time); a latent layer's q and its down-projection
    are a candidate each (the second is a tenth of the first and the last
    thing worth giving up). A ``phi4flash`` pattern's attention layers make
    q (the cross layers too) and, but for the cross layers, k and v; its
    state-space mixers' four products and its memory units' two are a
    candidate each, at their own contracting widths."""
    linear = sum(map(p.is_linear, range(len(p.layers))))
    out = []

    def add(width, layers, bytes_a_token):
        if layers:
            out.append((width, {n: layers * tokens * b
                                for n, b in bytes_a_token.items()}))

    # the gated feed-forwards: a layer that is not routed has one d_ff
    # wide, a routed one its shared experts'; one name is kept or declined
    # over all of them, at the mean width of what it stands for
    widths = [p.shared_d_ff if p.is_routed(i) else d_ff
              for i in range(len(p.layers))]
    if any(widths):
        down, gate, up = FFN_REMAT_KEEPS
        made = sum(map(bool, widths))
        add(sum(widths) / made, made, {down: 2 * d_model})
        add(d_model, 1, {gate: 2 * sum(widths), up: 2 * sum(widths)})
    if p.latent is not None:
        sp = p.latent
        q, latent, up, last = LATENT_REMAT_KEEPS
        add(d_model, len(p.layers),
            {q: 2 * n_heads * (sp.nope + sp.rope)})
        add(d_model, len(p.layers), {latent: 2 * (sp.rank + sp.rope)})
        add(sp.rank, len(p.layers), {up: 2 * n_heads * (sp.nope + sp.v)})
        add(n_heads * sp.v, len(p.layers), {last: 2 * d_model})
        return out
    if p.hybrid is not None:
        sp = p.hybrid
        count = lambda *kinds: len(sp.held(*kinds))  # noqa: E731
        q, k, v, last = ATTENTION_REMAT_KEEPS
        own, every = count("window", "full"), \
            count("window", "full", "cross")
        kv = 2 * own * p.n_kv_heads * p.head_dim
        add(d_model, 1, {q: 2 * every * n_heads * p.head_dim, k: kv, v: kv})
        add(n_heads * p.head_dim, every, {last: 2 * d_model})
        proj, x_proj, dt_proj, last = SSM_REMAT_KEEPS
        add(d_model, count("ssm"), {proj: 2 * 2 * sp.d_inner})
        add(sp.d_inner, count("ssm"),
            {x_proj: 4 * (sp.dt_rank + 2 * sp.d_state)})
        add(sp.dt_rank, count("ssm"), {dt_proj: 4 * sp.d_inner})
        add(sp.d_inner, count("ssm"), {last: 2 * d_model})
        proj, last = GMU_REMAT_KEEPS
        add(d_model, count("gmu"), {proj: 2 * sp.d_inner})
        add(sp.d_inner, count("gmu"), {last: 2 * d_model})
        return out
    q, k, v, last = ATTENTION_REMAT_KEEPS
    add(d_model, len(p.layers) - linear, {
        q: 2 * n_heads * p.head_dim, k: 2 * p.n_kv_heads * p.head_dim,
        v: 2 * p.n_kv_heads * p.head_dim})
    add(n_heads * p.head_dim, len(p.layers) - linear, {last: 2 * d_model})
    if p.linear is not None:
        sp = p.linear
        q, k, v, g, a, b, last = LINEAR_REMAT_KEEPS
        add(d_model, linear, {
            q: 2 * sp.heads * sp.key_dim, k: 2 * sp.heads * sp.key_dim,
            v: 2 * sp.heads * sp.value_dim, g: 2 * sp.heads * sp.value_dim,
            a: 4 * sp.heads, b: 4 * sp.heads})
        add(sp.heads * sp.value_dim, linear, {last: 2 * d_model})
    return out


def param_init(model: "DecoderOnlyLM", batch_shape):
    """key -> ``model.init``'s parameters for rows of ``batch_shape``,
    jitted: ONE function, so that whoever asks for its shapes
    (``jax.eval_shape``: :func:`remat_on`'s count) and the sharded init
    that calls it share one trace of the model."""
    return jax.jit(lambda key: model.init(
        key, jnp.zeros(batch_shape, jnp.int32), train=False)["params"])


def remat_on(model: "DecoderOnlyLM", mesh: Mesh, batch_shape,
             init_params=None) -> Dict[str, Any]:
    """:func:`remat_keeps` of ``model`` for steps of ``batch_shape`` on
    ``mesh``: the share of the step one device sees, the parameters it
    holds (counted from the shapes of ``init_params``, the caller's
    :func:`param_init`, or of one made here), its share of every kind of
    head and what its memory reports. Only a model with a layer pattern
    needs the count."""
    p = model.pattern
    if p is None:
        return remat_keeps(p)
    from metaopt_tpu.parallel.mesh import use_mesh

    with use_mesh(mesh):  # as the init will trace it: the trace is shared
        shapes = jax.eval_shape(
            init_params or param_init(model, batch_shape),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
    b, s = batch_shape
    across = lambda *axes: math.prod(  # noqa: E731
        mesh.shape.get(a, 1) for a in axes)
    tp = across("tp")
    return remat_keeps(
        dataclasses.replace(
            p, n_kv_heads=p.n_kv_heads // tp,
            linear=p.linear and dataclasses.replace(
                p.linear, heads=p.linear.heads // tp),
            hybrid=p.hybrid and dataclasses.replace(
                p.hybrid, d_inner=p.hybrid.d_inner // tp)),
        tokens=b * s // across("dp", "sp"), d_model=model.d_model,
        d_ff=model.d_ff // tp,
        n_heads=(p.heads_held[1] if p.heads_held else model.n_heads) // tp,
        parameters=held_parameters(shapes, mesh),
        bytes_limit=device_bytes_limit(mesh))


def device_bytes_limit(mesh: Mesh) -> Optional[int]:
    """What a device of the mesh says its memory holds, None where the
    backend does not say (the CPU's)."""
    return (mesh.devices.flat[0].memory_stats() or {}).get("bytes_limit")


def _selection(sa: Optional[Dict[str, Any]]):
    """(index heads, their width, top k) of a published ``sa_config``; its
    chunk sizes tile the computation and do not change the result."""
    if not sa:
        return None
    if int(sa.get("indexer_num_kv_heads", 1)) != 1:
        raise ValueError("the indexer has one key head, not "
                         f"{sa['indexer_num_kv_heads']}")
    return (int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"]),
            int(sa["topk"]))


def describe_pattern(hparams: Dict[str, Any], route: str, tokens: int,
                     seq_len: Optional[int] = None,
                     mesh: Optional[Mesh] = None) -> Dict[str, Any]:
    """What ``trial.setup``'s span says of a description with a layer
    pattern ({} without one), for steps of ``tokens`` tokens in rows of
    ``seq_len`` on attention route ``route`` under ``mesh``: the route the
    embedding's gradient takes (ops/embed.embed_gradient_route: ``"sorted"``
    or ``"take"``), the table's held rows and width, the tokens a step
    looks up and whether the head reads the same table; for each kind
    of layer the route and the form of its mask, for a layer that selects
    its keys the form its index scores take at that length, for a latent
    layer its widths and how its operands reach attention
    (ops/latent_attention.hand_over: ``"in place"`` or ``"copies"``), and
    the expert layers' share, the product they take and how the held
    experts' part runs (models/moe.describe_experts), the rows of their
    buffers, the rows a trip of the routing's loops moves and, for the
    DeepSeek-V3 family, its routing rule, shared experts and leading dense
    layers; for the ``phi4flash`` family the scan's route, chunk and state
    type, the differential pairs' widths, and which layers hand on and
    which read."""
    from metaopt_tpu.models.moe import describe_experts, routing_chunk_rows

    h = _own_names(hparams)
    p = pattern_of(h)
    if p is None:
        return {}
    by_structure = route == "pallas"

    def mask_of(kind):
        if kind.startswith("selected"):
            heads, _, top_k = p.selection
            return (f"selected: causal, top {top_k} of the index scores, "
                    f"{heads} index heads")
        return ("structure" if by_structure else "dense") + (
            f": causal, window {p.window}" if kind.startswith("window")
            else ": causal")

    out = {"attention_layers": {kind: {"route": route, "mask": mask_of(kind)}
                                for kind in p.kinds()
                                if kind not in ("linear", "ssm", "gmu")},
           "embed": {"gradient": embed_gradient_route(mesh),
                     "rows": p.vocab_held[1],
                     "width": int(h.get("d_model", 512)), "tokens": tokens,
                     "tied": p.hybrid is not None}}
    if p.hybrid is not None:
        from metaopt_tpu.ops.selective_scan import selective_scan_route

        sp = p.hybrid
        for kind, said in out["attention_layers"].items():
            said.update(
                layers=[n for i, n in enumerate(sp.layers)
                        if p.kind(i) == kind],
                differential=[int(h.get("n_heads", 8)) // 2, p.n_kv_heads // 2,
                              p.head_dim, 2 * p.head_dim])
            if kind.startswith("cross"):
                said["reads"] = sp.kv_layer
        out["attention_layers"]["ssm"] = {
            **selective_scan_route(seq_len or tokens, mesh),
            "layers": sp.held("ssm"), "d_inner": sp.d_inner,
            "d_state": sp.d_state, "conv": sp.d_conv, "dt_rank": sp.dt_rank,
            "hands_on": sp.held("gmu") and sp.memory_layer}
        if sp.held("gmu"):
            out["attention_layers"]["gmu"] = {
                "layers": sp.held("gmu"), "reads": sp.memory_layer,
                "d_inner": sp.d_inner}
    if p.latent is not None:
        from metaopt_tpu.ops.latent_attention import hand_over

        sp = p.latent
        for kind, said in out["attention_layers"].items():
            said.update(
                layers=[i for i in range(len(p.layers)) if p.kind(i) == kind],
                heads=int(h.get("n_heads", 8)), nope=sp.nope, rope=sp.rope,
                v=sp.v, rank=sp.rank,
                hand_over=hand_over(route, mesh, sp.nope, sp.v))
    if p.linear is not None:
        from metaopt_tpu.ops.linear_attention import linear_attention_route

        spec = p.linear
        out["attention_layers"]["linear"] = {
            **linear_attention_route(),
            "layers": [i for i, lin in enumerate(p.linear_layers) if lin],
            "heads": [spec.heads, spec.of], "key_dim": spec.key_dim,
            "value_dim": spec.value_dim, "conv": spec.conv}
    if p.selection is not None and seq_len:
        from metaopt_tpu.ops.sparse_index import scores_of_a_row

        for kind, said in out["attention_layers"].items():
            if kind.startswith("selected"):
                said["index_scores"] = scores_of_a_row(seq_len,
                                                       p.selection[1])
    if p.n_experts:
        # buffer rows, model width, an expert's width: what the route asks
        how = describe_experts(tokens * p.top_k, int(h.get("d_model", 512)),
                               p.expert_d_ff)
        out["moe"] = {"routed_over": p.n_experts, "top_k": p.top_k,
                      "held": list(p.experts_held),
                      "products": how["products"], "experts": how,
                      "buffer_rows": tokens * p.top_k,
                      "chunk_rows": routing_chunk_rows(tokens * p.top_k)}
        if p.latent is not None:  # the family's routing, beside the counts
            out["moe"].update(
                scoring=p.routing.scoring, bias=p.routing.bias,
                scale=p.routing.scale, shared_d_ff=p.shared_d_ff,
                dense_layers=p.dense_layers, d_ff=int(h.get("d_ff", 2048)))
    return out


def make_lm(hparams: Optional[Dict[str, Any]] = None,
            **overrides) -> DecoderOnlyLM:
    """The model a description names: the zoo's own keys (``d_model``,
    ``n_layers`` ...) or a published config's (``hidden_size``,
    ``num_hidden_layers``, ``rope_layout`` ...), plus the chip's share
    (``experts_held``, ``vocab_held``: (first, count); ``layers_held``: the
    published numbers of a ``phi4flash`` model's layers)."""
    h = _own_names({**(hparams or {}), **overrides})
    pattern = pattern_of(h)
    return DecoderOnlyLM(
        vocab=int(h.get("vocab", 1000)),
        d_model=int(h.get("d_model", 512)),
        n_heads=int(h.get("n_heads", 8)),
        n_layers=len(pattern.layers) if pattern else int(
            h.get("n_layers", 6)),
        d_ff=int(h.get("d_ff", 2048)),
        dropout=float(h.get("dropout", 0.0 if pattern else 0.1)),
        max_len=int(h.get("max_len", 512)),
        n_experts=int(h.get("n_experts", 0)),
        capacity_factor=float(h.get("capacity_factor", 1.25)),
        router_top_k=int(h.get("router_top_k", 1)),
        remat=bool(h.get("remat", False)),
        pattern=pattern,
    )


def lm_loss_fn(model, params, tokens, dropout_key,
               moe_aux_weight: float = 0.01, with_stats: bool = False):
    """Next-token loss: predict ``tokens[:, 1:]`` from ``tokens[:, :-1]``.
    ``with_stats``: (loss, what the expert layers counted this step)."""
    from metaopt_tpu.parallel.sharding import pin_batch_layout

    first, vocab = model.held_vocab()
    with trace.scope("loss"):  # the shifted rows and the mask
        inp, labels = pin_batch_layout(tokens[:, :-1]), tokens[:, 1:]
        mask = (labels != 0).astype(jnp.float32)
        held = labels - first
    blocked = blocked_xent_enabled(labels.shape[0], labels.shape[1], vocab)
    out, mutated = model.apply(
        {"params": params}, inp, train=True, features=blocked,
        rngs={"dropout": dropout_key},
        mutable=["aux_loss", "moe_stats", "attn_stats"],
    )
    loss = readout_xent(out, params, held, vocab, blocked)
    loss = masked_mean_with_aux(loss, mask, mutated, moe_aux_weight)
    if not with_stats:
        return loss
    with trace.scope("loss"):  # the counts ride out beside the loss
        return loss, {**moe_counts(mutated), **selection_counts(mutated)}


def moe_counts(mutated) -> Dict[str, Any]:
    """{"items": (layers, held) int32, "dropped": (layers,) int32,
    "chunks": (layers,) int32} from the ``moe_stats`` the dropless expert
    layers sowed, routed layer by routed layer, and ``bias_moved``
    (layers,) int32 where the routing rule has a correction bias; empty for
    a model without such layers."""
    layers = [v for v in _by_layer(mutated.get("moe_stats", {}))
              if "items" in v.get("experts", {})]
    if not layers:
        return {}
    return {key: jnp.stack([v["experts"][key][0] for v in layers])
            for key in ("items", "dropped", "chunks", "bias_moved")
            if key in layers[0]["experts"]}


def _by_layer(collection) -> list:
    """A sown collection's per-layer entries, h0, h1, ... in order."""
    return [v for _, v in sorted(collection.items(),
                                 key=lambda kv: int(kv[0][1:]))]


#: the pairs' counts are kept as (high, low) int32 words of _LIMB bits in
#: the low one: a step of 16 384 tokens selects 31 M pairs a layer, which
#: a plain int32 sum holds for 68 steps
_LIMB = 24
_LOW = (1 << _LIMB) - 1


def selection_counts(mutated) -> Dict[str, Any]:
    """{"selected_pairs", "causal_pairs": (layers,) int32} from what the
    selected-attention layers sowed this step; empty without such layers."""
    layers = [v["attn"]["indexer"] for v in
              _by_layer(mutated.get("attn_stats", {}))]
    if not layers:
        return {}
    return {key: jnp.stack([v[key][0] for v in layers])
            for key in ("selected_pairs", "causal_pairs")}


def _add_counts(total, new):
    """The running sums with a step's counts added. The expert layers'
    are plain sums; the pairs' (layers, 2) carry from the low word."""
    out = {}
    for key, step in sorted(new.items()):
        if key.endswith("_pairs"):
            low = total[key][:, 1] + (step & _LOW)
            out[key] = jnp.stack(
                [total[key][:, 0] + (step >> _LIMB) + (low >> _LIMB),
                 low & _LOW], axis=1)
        else:
            out[key] = total[key] + step
    return out


#: the names no gradient reaches: a module's (:class:`Indexer`: its choice
#: is piecewise constant) and a leaf's (a routing rule's correction bias,
#: which enters the choice alone)
FROZEN = ("indexer", "choice_bias")


def split_frozen(params):
    """(trained, frozen): ``params`` without and with only the subtrees
    named in ``FROZEN``. The trained tree is what is differentiated and
    what AdamW holds moments for; for a model without such names it is
    ``params``' own structure and ``frozen`` is empty."""
    trained, frozen = {}, {}
    for name, sub in params.items():
        if name in FROZEN:
            frozen[name] = sub
        elif isinstance(sub, dict):
            trained[name], below = split_frozen(sub)
            if below:
                frozen[name] = below
        else:
            trained[name] = sub
    return trained, frozen


def merge_frozen(trained, frozen):
    """The inverse of :func:`split_frozen`."""
    out = dict(trained)
    for name, sub in frozen.items():
        out[name] = merge_frozen(trained[name], sub) if name in trained \
            else sub
    return out


def make_lm_train_step(model, tx):
    """The jittable train step (donated params/opt state). ``counts`` is
    the running sum of :func:`moe_counts` and :func:`selection_counts`
    over the steps, on the device (an empty dict for a model that counts
    nothing, zeros before the first step otherwise). The frozen part of
    ``params`` (:func:`split_frozen`) goes through unchanged."""

    def train_step(params, opt_state, counts, tokens, step_key):
        trained, frozen = split_frozen(params)
        (loss, new), grads = jax.value_and_grad(
            lambda p: lm_loss_fn(model, merge_frozen(p, frozen), tokens,
                                 step_key, with_stats=True),
            has_aux=True)(trained)
        with trace.scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, trained)
            trained = optax.apply_updates(trained, updates)
        with trace.scope("loss"):  # its second output, summed over steps
            counts = _add_counts(counts, new)
        return merge_frozen(trained, frozen), opt_state, counts, loss

    return train_step


def init_sharded_lm(model: DecoderOnlyLM, mesh: Mesh, tx,
                    batch_shape, seed: int = 0, init_params=None):
    """Params/opt state materialized directly on the mesh (one token
    input). ``init_params``: the model's :func:`param_init` where the
    caller has traced it already."""
    init_params = init_params or param_init(model, batch_shape)

    def init_fn(key):
        params = init_params(key)
        return params, tx.init(split_frozen(params)[0])

    return sharded_init(init_fn, mesh, seed)


class LMTrial:
    """One trial's train loop, handed out step by step: everything
    :func:`train_lm` sets up (mesh, optimizer, data, sharded state, the
    jitted step) and ``step(i)``, which feeds and dispatches step ``i``
    and returns its loss without waiting for it. Steps run inside
    ``with trial:``, the trial's mesh."""

    def __init__(self, hparams: Dict[str, Any], *, mesh=None, tp=1, sp=1,
                 ep=1, n_train=2048, batch_size=32, seq_len=64, steps=100,
                 seed=0, restore_dir=None):
        from metaopt_tpu.models.data import synthetic_lm
        from metaopt_tpu.models.transformer import maybe_restore, trial_setup
        from metaopt_tpu.parallel.mesh import use_mesh
        from metaopt_tpu.parallel.sharding import shard_batch

        if n_train < batch_size:
            raise ValueError(
                f"n_train ({n_train}) must be >= batch_size ({batch_size})")
        self.batch_size, self.n_train = batch_size, n_train
        self._shard_batch, self._use_mesh = shard_batch, use_mesh
        model = make_lm(hparams, max_len=max(
            int(hparams.get("max_len", 512)), seq_len))
        # what a rematerialised block keeps depends on the mesh, which
        # trial_setup makes: asked there, once, and the model is given the
        # same answer; the one trace of the model it counts parameters
        # from serves the init too
        init_params = param_init(model, (batch_size, seq_len))
        remat = functools.cache(lambda mesh: remat_on(
            model, mesh, (batch_size, seq_len), init_params))
        # the model's own dropout: a layer pattern has none unless it says so
        self.mesh, tx = trial_setup(
            {**hparams, "dropout": model.dropout}, mesh, tp, sp, ep,
            steps, describe=functools.partial(
                describe_pattern, hparams, tokens=batch_size * seq_len,
                seq_len=seq_len),
            remat_blocks=model.n_layers if model.remat else 0,
            remat_keeps=remat)
        self.model = model.clone(
            keeps=tuple(remat(self.mesh)["keeps"])) if model.remat else model
        first, vocab = self.model.held_vocab()
        kd, self._kstep = jax.random.split(jax.random.PRNGKey(seed))
        self.tokens = first + synthetic_lm(kd, n_train, seq_len + 1, vocab)
        with use_mesh(self.mesh):
            params, opt_state, self.shardings = init_sharded_lm(
                self.model, self.mesh, tx, (batch_size, seq_len), seed,
                init_params)
            self.params, self.opt_state = maybe_restore(
                restore_dir, params, opt_state, self.shardings)
            # the counts go in as they come out, replicated: a first step
            # fed fresh zeros of no stated placement compiles a second time
            whole = NamedSharding(self.mesh, P())
            self._step_fn = jax.jit(
                make_lm_train_step(self.model, tx),
                in_shardings=(self.shardings[0], self.shardings[1], whole,
                              NamedSharding(self.mesh, P("dp")), None),
                out_shardings=(self.shardings[0], self.shardings[1], whole,
                               None),
                donate_argnums=(0, 1, 2),
            )
        #: the expert layers' counts, and the selected-attention layers',
        #: summed over the steps, on the device
        self.counts: Dict[str, Any] = {}
        p = self.model.pattern
        layers = len(p.layers) if p is not None else 0
        if p is not None and p.n_experts:
            routed = p.routed_layers()
            self.counts = {
                "items": jnp.zeros((routed, p.experts_held[1]), jnp.int32),
                "dropped": jnp.zeros((routed,), jnp.int32),
                "chunks": jnp.zeros((routed,), jnp.int32)}
            if p.routing.bias:
                self.counts["bias_moved"] = jnp.zeros((routed,), jnp.int32)
        if p is not None and p.selection:
            self.counts.update(
                selected_pairs=jnp.zeros((layers, 2), jnp.int32),
                causal_pairs=jnp.zeros((layers, 2), jnp.int32))
        self.counts = jax.device_put(self.counts, whole)

    def __enter__(self):
        self._scope = self._use_mesh(self.mesh)
        self._scope.__enter__()
        return self

    def __exit__(self, *exc):
        return self._scope.__exit__(*exc)

    def rows(self, i: int):
        """Step ``i``'s token rows."""
        lo = (i * self.batch_size) % (self.n_train - self.batch_size + 1)
        return self.tokens[lo:lo + self.batch_size]

    def step(self, i: int):
        with trace.span("slice_and_shard_batch"):
            batch = self._shard_batch(self.mesh, self.rows(i))
        with trace.span("dispatch_step"):
            self.params, self.opt_state, self.counts, loss = self._step_fn(
                self.params, self.opt_state, self.counts, batch,
                jax.random.fold_in(self._kstep, i))
        return loss

    def read_counts(self) -> Dict[str, Any]:
        """The counts so far, copied to the host (one round trip): items a
        held expert, items dropped and the trips of a pass of the routing
        over the buffers, a routed layer, with the tokens whose choice the
        routing rule's bias moved where it has one; and, where layers
        select their keys, the
        (query, key) pairs selected and the causal pairs they were chosen
        among, a layer."""
        return {k: [(hi << _LIMB) + lo for hi, lo in v.tolist()]
                if k.endswith("_pairs") else v.tolist()
                for k, v in jax.device_get(self.counts).items()}


def train_lm(
    hparams: Dict[str, Any],
    *,
    mesh: Optional[Mesh] = None,
    tp: int = 1,
    sp: int = 1,
    ep: int = 1,
    n_train: int = 2048,
    batch_size: int = 32,
    seq_len: int = 64,
    steps: int = 100,
    seed: int = 0,
    restore_dir: Optional[str] = None,
    save_dir: Optional[str] = None,
) -> float:
    """Train on the permutation-walk LM task; return final masked loss.

    ``seq_len`` is the length the MODEL trains on (inputs and labels):
    the stream generator produces ``seq_len + 1`` tokens so the shift in
    :func:`lm_loss_fn` lands back on ``seq_len`` — which therefore only
    needs to divide the ``sp`` mesh axis, exactly like the seq2seq
    harness. ``restore_dir``/``save_dir``: orbax trial checkpoints, same
    PBT-handoff/suspend-resume contract as ``train_and_eval``. The loop
    is :class:`LMTrial`'s; the expert layers' counts are read once after
    it, into ``trial.train``'s ``attrs["moe"]``, and the selected and
    causal pairs of layers that select their keys into
    ``attrs["selection"]``.
    """
    trial = LMTrial(hparams, mesh=mesh, tp=tp, sp=sp, ep=ep, n_train=n_train,
                    batch_size=batch_size, seq_len=seq_len, steps=steps,
                    seed=seed, restore_dir=restore_dir)
    loss = None
    with trial, trace.span("trial.train", steps=steps) as train:
        for i in range(steps):
            loss = trial.step(i)
        if loss is not None:
            # the loop runs ahead of the device; the save and the
            # float(loss) below would wait for it anyway, once
            loss.block_until_ready()
        if trial.counts:
            counts = trial.read_counts()
            pairs = {k: counts.pop(k) for k in list(counts)
                     if k.endswith("_pairs")}
            if counts:
                train["attrs"]["moe"] = counts
            if pairs:
                train["attrs"]["selection"] = pairs
    if save_dir:
        from metaopt_tpu.models.checkpoint import save_state

        save_state(save_dir + "/params", trial.params)
        save_state(save_dir + "/opt_state", trial.opt_state)
    return float(loss)


# ---------------------------------------------------------------------------
# the linear-attention mixer (at the file's end: the lines above lm_loss_fn
# are part of the Pallas kernels' compile-cache keys, PERF.md PRs 27-29)


def _decay_init(key, shape, dtype=jnp.float32):
    """``A_log`` as Gated DeltaNet's published initialiser draws it:
    log of U(0, 16) (from 2**-6 on, so that the log is finite)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 2.0 ** -6, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """``dt_bias`` likewise: softplus^-1 of dt, log dt ~ U(log 1e-3, log
    1e-1): with ``A_log``, a decay of exp(-A dt) a token at a zero input."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def short_conv(x, taps):
    """Causal depthwise convolution along axis 1 of ``x`` (B, T, ...) with
    ``taps`` (K, ...), no bias: y_t = sum_i taps[i] x_{t - (K - 1) + i},
    x before the row's start = 0."""
    k, t = taps.shape[0], x.shape[1]
    x = jnp.pad(x, ((0, 0), (k - 1, 0)) + ((0, 0),) * (x.ndim - 2))
    return sum(taps[i] * x[:, i:i + t] for i in range(k))


class LinearAttention(nn.Module):
    """A gated-delta-rule mixer (Gated DeltaNet, arXiv:2412.06464) over the
    ``spec.heads`` heads held here: q, k (width ``key_dim``) and v (width
    ``value_dim``) each projected, passed through a causal depthwise
    convolution of ``conv`` taps and a SiLU; q and k L2-normalised over a
    head (q times key_dim^-1/2); a step beta = sigmoid(x W_b) (twice that
    with ``neg_eigval``) and a log decay g = -exp(A_log) softplus(x W_a +
    dt_bias) a (token, head); the recurrence (ops/linear_attention.py, the
    one rule there names its route); an RMS norm over a head's
    ``value_dim`` gated by silu(x W_g); the output projection. Element-wise
    work, gates and norms in float32; the two gates' projections float32 at
    matmul precision highest, as a router's are. The seven projections'
    products carry the names of ``LINEAR_REMAT_KEEPS`` (identities unless a
    block's policy asks for them)."""

    d_model: int
    spec: LinearSpec
    eps: float

    @nn.compact
    @trace.scope("linear_attention")
    def __call__(self, x):
        from metaopt_tpu.ops.linear_attention import gated_delta_rule

        sp = self.spec
        proj = lambda name, width: nn.DenseGeneral(  # noqa: E731
            (sp.heads, width), axis=-1, dtype=jnp.bfloat16, name=name,
            use_bias=False, kernel_init=_pinit(True, (None, "tp", None)))
        gate = lambda name: nn.DenseGeneral(  # noqa: E731
            sp.heads, use_bias=False, name=name,
            precision=jax.lax.Precision.HIGHEST,
            kernel_init=with_mesh_partitioning(
                nn.initializers.lecun_normal(), (None, "tp")))
        own = lambda name, init, shape, axes: self.param(  # noqa: E731
            name, with_mesh_partitioning(init, axes), shape)
        taps = lambda name, width: own(  # noqa: E731  U(-1/2, 1/2) at 4 taps
            name, nn.initializers.variance_scaling(
                1 / 3, "fan_in", "uniform", in_axis=0, out_axis=(1, 2)),
            (sp.conv, sp.heads, width), (None, "tp", None))
        kept = dict(zip(("q", "k", "v", "g", "a", "b", "out"),
                        LINEAR_REMAT_KEEPS))
        mixed = lambda name, width: jax.nn.silu(short_conv(  # noqa: E731
            checkpoint_name(proj(name, width)(xb), kept[name])
            .astype(jnp.float32), taps("conv_" + name, width)))
        unit = lambda y: y * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(jnp.square(y), axis=-1, keepdims=True) + 1e-6)
        xb, xf = x.astype(jnp.bfloat16), x.astype(jnp.float32)
        q = unit(mixed("q", sp.key_dim)) * sp.key_dim ** -0.5
        k = unit(mixed("k", sp.key_dim))
        v = mixed("v", sp.value_dim)
        beta = jax.nn.sigmoid(checkpoint_name(gate("b")(xf), kept["b"])) \
            * (2.0 if sp.neg_eigval else 1.0)
        g = -jnp.exp(own("A_log", _decay_init, (sp.heads,), ("tp",))) \
            * jax.nn.softplus(checkpoint_name(gate("a")(xf), kept["a"]) + own(
                "dt_bias", _dt_bias_init, (sp.heads,), ("tp",)))
        o = gated_delta_rule(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                             v.astype(jnp.bfloat16), g, beta)
        y = RMSNorm(self.eps, name="norm")(o) * jax.nn.silu(checkpoint_name(
            proj("g", sp.value_dim)(xb), kept["g"]).astype(jnp.float32))
        return checkpoint_name(nn.DenseGeneral(
            self.d_model, axis=(-2, -1), dtype=jnp.bfloat16, name="out",
            use_bias=False, kernel_init=_pinit(True, ("tp", None, None)),
        )(y.astype(jnp.bfloat16)), kept["out"])


# ---------------------------------------------------------------------------
# the phi4flash family's block and its four mixers (at the file's end too)

#: What a rematerialised block keeps of a state-space mixer's projections
#: where :func:`remat_keeps` finds the room: the input projection's product
#: (x and z before the convolution and the gate), x_proj's (delta, B, C
#: before the step's projection) and dt_proj's (before the softplus), and
#: the output projection's.
SSM_REMAT_KEEPS = ("ssm.in_proj", "ssm.x_proj", "ssm.dt_proj",
                   "ssm.out_proj")
GMU_REMAT_KEEPS = ("gmu.in_proj", "gmu.out_proj")


def _layer_norm(name: str, x, eps: float):
    """A float32 LayerNorm with weight and bias, under the scope ``norm``."""
    with trace.scope("norm"):
        return nn.LayerNorm(epsilon=eps, dtype=jnp.float32, name=name)(x)


def _state_decay_init(key, shape, dtype=jnp.float32):
    """``A_log`` as Mamba's published initialiser has it: A[d, n] = n + 1."""
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape)


def _taps_init(key, shape, dtype=jnp.float32):
    """U(-1/2, 1/2): a depthwise convolution's default at 4 taps, for the
    taps and their bias."""
    return jax.random.uniform(key, shape, dtype, -0.5, 0.5)


class StateSpaceMixer(nn.Module):
    """Mamba-1 (arXiv:2312.00752) over ``spec.d_inner`` channels: (x, z) =
    u W_in; x = silu(conv(x) + b), a causal depthwise convolution of
    ``d_conv`` taps; (delta, B, C) = x W_x, split dt_rank / d_state /
    d_state; Delta = softplus(delta W_dt + b_dt); A = -exp(A_log); the
    selective scan (ops/selective_scan.py, the one rule there names its
    route) with y += D x; out = (y silu(z)) W_out. Returns (out, y): y,
    BEFORE the gate, is what the memory layer hands on. Element-wise work,
    the convolution and the scan in float32; x_proj and dt_proj float32 at
    matmul precision highest (they make the scan's steps, whose decays
    exp(Delta A) reach A = -16), as a linear layer's gates are. The four
    projections' products carry the names of ``SSM_REMAT_KEEPS``."""

    d_model: int
    spec: HybridSpec

    @nn.compact
    @trace.scope("ssm")
    def __call__(self, u):
        from metaopt_tpu.ops.selective_scan import selective_scan

        sp = self.spec
        own = lambda name, init, shape, axes: self.param(  # noqa: E731
            name, with_mesh_partitioning(init, axes), shape)
        exact = lambda name, width, axes, **kw: nn.Dense(  # noqa: E731
            width, name=name, precision=jax.lax.Precision.HIGHEST,
            kernel_init=with_mesh_partitioning(
                nn.initializers.lecun_normal(), axes), **kw)
        kept_in, kept_x, kept_dt, kept_out = SSM_REMAT_KEEPS
        xz = checkpoint_name(nn.DenseGeneral(
            (2, sp.d_inner), dtype=jnp.bfloat16, name="in_proj",
            use_bias=False, kernel_init=_pinit(True, (None, None, "tp")),
        )(u.astype(jnp.bfloat16)), kept_in)
        x = jax.nn.silu(
            short_conv(xz[..., 0, :].astype(jnp.float32), own(
                "conv", _taps_init, (sp.d_conv, sp.d_inner), (None, "tp")))
            + own("conv_bias", _taps_init, (sp.d_inner,), ("tp",)))
        dbc = checkpoint_name(exact(
            "x_proj", sp.dt_rank + 2 * sp.d_state, ("tp", None),
            use_bias=False)(x), kept_x)
        delta, b, c = jnp.split(
            dbc, [sp.dt_rank, sp.dt_rank + sp.d_state], axis=-1)
        dt = jax.nn.softplus(checkpoint_name(exact(
            "dt_proj", sp.d_inner, (None, "tp"), bias_init=_dt_bias_init,
        )(delta), kept_dt))
        a = -jnp.exp(own("A_log", _state_decay_init,
                         (sp.d_inner, sp.d_state), ("tp", None)))
        y = selective_scan(x, dt, a, b, c) \
            + own("D", nn.initializers.ones, (sp.d_inner,), ("tp",)) * x
        gated = y * jax.nn.silu(xz[..., 1, :].astype(jnp.float32))
        return checkpoint_name(nn.Dense(
            self.d_model, dtype=jnp.bfloat16, name="out_proj",
            use_bias=False, kernel_init=_pinit(True, ("tp", None)),
        )(gated.astype(jnp.bfloat16)), kept_out), y


class GatedMemoryUnit(nn.Module):
    """out = (memory * silu(u W_in)) W_out: an earlier layer's scan output
    (``memory``, float32, ``d_inner`` wide) gated by this layer's own
    projection of its input. No bias. The two products carry the names of
    ``GMU_REMAT_KEEPS``."""

    d_model: int
    d_inner: int

    @nn.compact
    @trace.scope("gmu")
    def __call__(self, u, memory):
        kept_in, kept_out = GMU_REMAT_KEEPS
        gate = checkpoint_name(nn.Dense(
            self.d_inner, dtype=jnp.bfloat16, name="in_proj", use_bias=False,
            kernel_init=_pinit(True, (None, "tp")),
        )(u.astype(jnp.bfloat16)), kept_in)
        gated = memory * jax.nn.silu(gate.astype(jnp.float32))
        return checkpoint_name(nn.Dense(
            self.d_model, dtype=jnp.bfloat16, name="out_proj",
            use_bias=False, kernel_init=_pinit(True, ("tp", None)),
        )(gated.astype(jnp.bfloat16)), kept_out)


class DifferentialAttention(nn.Module):
    """Differential attention (arXiv:2410.05258) under a causal mask, with
    bias on the projections. The ``n_heads`` query heads are pairs (2p, 2p +
    1) = (q_1, q_2), the ``n_kv_heads`` K/V heads pairs (2j, 2j + 1) =
    (k_1, k_2) whose two values are joined to one v twice as wide; query
    pair p reads K/V pair p // (query pairs / K/V pairs). a_i =
    softmax(q_i k_i^T / sqrt(head_dim) + mask) v; o = (1 - lambda_init)
    rmsnorm(a_1 - lambda a_2) over the joined width, lambda =
    exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init; then the output
    projection. ``kv``: another layer's (k, v) after its projection, read in
    place of this layer's own (a cross layer, which then has no k and v
    projections). Returns (out, (k, v)).

    The pairs reach the ``CausalMask`` kernels as two calls, q_1 on k_1 and
    q_2 on k_2 (every other head: the kernels' grouping, query head h on
    K/V head h // group, is then the pairs' own order), both on the one
    joined v, which is a reshape and no copy; the kernels run at q.k
    ``head_dim`` and v 2 ``head_dim`` wide. The combination is under the
    scope ``attention.diff``."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    window: Optional[int]
    lambda_init: float
    eps: float

    @nn.compact
    @trace.scope("attention")
    def __call__(self, u, kv=None):
        proj = lambda name, heads: nn.DenseGeneral(  # noqa: E731
            (heads, self.head_dim), axis=-1, dtype=jnp.bfloat16, name=name,
            kernel_init=_pinit(True, (None, "tp", None)))
        kept_q, kept_k, kept_v, kept_out = ATTENTION_REMAT_KEEPS
        u = u.astype(jnp.bfloat16)
        q = checkpoint_name(proj("q", self.n_heads)(u), kept_q)
        if kv is None:
            kv = (checkpoint_name(proj("k", self.n_kv_heads)(u), kept_k),
                  checkpoint_name(proj("v", self.n_kv_heads)(u), kept_v))
        k, v = kv
        q = (q / math.sqrt(self.head_dim)).astype(jnp.bfloat16)
        joined = v.reshape(*v.shape[:2], -1, 2 * self.head_dim)
        mask = CausalMask(self.window)
        a1, a2 = (attend(q[:, :, i::2], k[:, :, i::2], joined, mask)
                  for i in (0, 1))
        with trace.scope("attention.diff"):
            lam = lambda name: self.param(  # noqa: E731
                name, nn.initializers.normal(0.1), (self.head_dim,))
            weight = jnp.exp(jnp.sum(lam("lambda_q1") * lam("lambda_k1"))) \
                - jnp.exp(jnp.sum(lam("lambda_q2") * lam("lambda_k2"))) \
                + self.lambda_init
            out = RMSNorm(self.eps, name="subln")(
                a1.astype(jnp.float32) - weight * a2.astype(jnp.float32)) \
                * (1.0 - self.lambda_init)
        return checkpoint_name(nn.DenseGeneral(
            self.d_model, axis=(-2, -1), dtype=jnp.bfloat16, name="out",
            kernel_init=_pinit(True, ("tp", None, None)),
        )(out.astype(jnp.bfloat16)), kept_out), kv


class HybridBlock(nn.Module):
    """The ``phi4flash`` family's block for held layer ``index`` of the
    pattern: x + mixer(LN(x)), then + ffn(LN(.)), LayerNorm with weight and
    bias, the mixer of the layer's kind (``HYBRID_KINDS``: a memory unit
    takes the memory, a cross layer K and V, as further arguments).
    Returns (x, what the layer hands on): ``{"memory": y}`` from the memory
    layer, ``{"kv": (k, v)}`` from the full layer, else ``{}``."""

    d_model: int
    n_heads: int
    d_ff: int
    pattern: Pattern
    index: int

    @nn.compact
    def __call__(self, x, *read):
        p, sp = self.pattern, self.pattern.hybrid
        layer, kind = sp.layers[self.index], sp.kinds[self.index]
        n = _layer_norm("norm_in", x, p.rms_eps)
        on = {}
        if kind == "ssm":
            branch, y = StateSpaceMixer(self.d_model, sp, name="ssm")(n)
            if layer == sp.memory_layer:
                on["memory"] = y
        elif kind == "gmu":
            branch = GatedMemoryUnit(self.d_model, sp.d_inner,
                                     name="gmu")(n, *read)
        else:
            branch, kv = DifferentialAttention(
                self.d_model, self.n_heads, p.n_kv_heads, p.head_dim,
                p.window if kind == "window" else None, lambda_init(layer),
                p.rms_eps, name="attn")(n, *read)
            if layer == sp.kv_layer:
                on["kv"] = kv
        x = residual(x, branch)
        return residual(x, GatedFeedForward(
            self.d_model, self.d_ff, p.activation, name="mlp")(
                _layer_norm("norm_post", x, p.rms_eps))), on
