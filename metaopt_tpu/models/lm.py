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
  The chip's share of a deployment is part of the description too:
  ``experts_held`` = (first, count) of the routed experts and
  ``vocab_held`` = (first, count) of the vocabulary's rows (ids are drawn
  from that slice, and logits and loss are over it).

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
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from metaopt_tpu.models.transformer import (
    EncoderLayer,
    _pinit,
    blocked_xent_enabled,
    masked_mean_with_aux,
    readout_xent,
    rematerialised,
    sharded_init,
)
from metaopt_tpu.ops.attention import CausalMask, attend
from metaopt_tpu.parallel.sharding import with_mesh_partitioning
from metaopt_tpu.utils import trace


# ---------------------------------------------------------------------------
# the pattern's blocks


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps) * scale


def rope(x, theta: float):
    """Rotary positions 0..S-1 on ``x`` (B, S, H, D), float32: the two
    halves of a head are the pairs (the ``rotate_half`` convention)."""
    s, d = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]  # (S, D/2)
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    x = x.astype(jnp.float32)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def layer_kind(sliding: bool, rotary: bool) -> str:
    return ("window" if sliding else "global") + \
        ("-rope" if rotary else "-nope")


class GroupedAttention(nn.Module):
    """Causal self attention with fewer K/V heads than query heads, no
    bias; rotary or no positions, a window or none."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    window: Optional[int]
    rope_theta: Optional[float]

    @nn.compact
    @trace.scope("attention")
    def __call__(self, x):
        proj = lambda name, heads: nn.DenseGeneral(  # noqa: E731
            (heads, self.head_dim), axis=-1, dtype=jnp.bfloat16, name=name,
            use_bias=False, kernel_init=_pinit(True, (None, "tp", None)))
        x = x.astype(jnp.bfloat16)
        q, k, v = (proj("q", self.n_heads)(x), proj("k", self.n_kv_heads)(x),
                   proj("v", self.n_kv_heads)(x))
        if self.rope_theta is not None:
            q, k = rope(q, self.rope_theta), rope(k, self.rope_theta)
        q = (q / math.sqrt(self.head_dim)).astype(jnp.bfloat16)
        k = k.astype(jnp.bfloat16)
        out = attend(q, k, v, CausalMask(self.window))
        return nn.DenseGeneral(
            self.d_model, axis=(-2, -1), dtype=jnp.bfloat16, name="out",
            use_bias=False, kernel_init=_pinit(True, ("tp", None, None)),
        )(out)


class GatedFeedForward(nn.Module):
    """relu(x W_gate) * (x W_up) W_down, no bias."""

    d_model: int
    d_ff: int

    @nn.compact
    @trace.scope("ffn")
    def __call__(self, x):
        dense = lambda name, n, axes: nn.Dense(  # noqa: E731
            n, dtype=jnp.bfloat16, name=name, use_bias=False,
            kernel_init=_pinit(True, axes))
        x = x.astype(jnp.bfloat16)
        h = nn.relu(dense("gate", self.d_ff, (None, "tp"))(x)) \
            * dense("up", self.d_ff, (None, "tp"))(x)
        return dense("down", self.d_model, ("tp", None))(h)


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

    def kinds(self):
        """The distinct layer kinds, in the pattern's order."""
        return list(dict.fromkeys(layer_kind(*l) for l in self.layers))


class PatternBlock(nn.Module):
    """x + attention(norm(x)), then + experts(norm(.)) routed by logits
    read from the FIRST norm's output, before attention. The residual
    stream is float32."""

    d_model: int
    n_heads: int
    d_ff: int
    pattern: Pattern
    sliding: bool
    rotary: bool

    @nn.compact
    def __call__(self, x):
        p = self.pattern
        n = RMSNorm(p.rms_eps, name="norm_in")(x)
        if p.n_experts:
            with trace.scope("moe"), trace.scope("moe.router"):
                # float32 in earnest: a TPU's default precision would make
                # this product in bfloat16 passes, and the top-k choice
                # hangs on the logits' last bits
                logits = nn.Dense(
                    p.n_experts, use_bias=False, name="router",
                    precision=jax.lax.Precision.HIGHEST,
                    kernel_init=with_mesh_partitioning(
                        nn.initializers.lecun_normal(), (None, None)))(n)
        x = x + GroupedAttention(
            self.d_model, self.n_heads, p.n_kv_heads, p.head_dim,
            p.window if self.sliding else None,
            p.rope_theta if self.rotary else None, name="attn")(n)
        m = RMSNorm(p.rms_eps, name="norm_post")(x)
        if p.n_experts:
            from metaopt_tpu.models.moe import DroplessMoE

            return x + DroplessMoE(self.d_model, p.expert_d_ff, p.n_experts,
                                   p.top_k, p.experts_held,
                                   name="experts")(m, logits)
        return x + GatedFeedForward(self.d_model, self.d_ff, name="mlp")(m)


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
    #: a block keeps its input and its attention kernel's ``out`` and
    #: ``lse`` (transformer.rematerialised), and makes the rest again
    remat: bool = False
    #: the layer pattern of a description that has one (module docstring)
    pattern: Optional[Pattern] = None

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
        pad = (tokens != 0)[:, None, None, :]                     # (b,1,1,k)
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
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
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
        first, rows = p.vocab_held
        # the embedding at size 1 (the first norm rescales it), the head
        # at 1/sqrt(d): logits of size 1, a loss near log(rows) at the start
        table = lambda name, size=1.0: nn.Embed(  # noqa: E731
            rows, self.d_model, dtype=jnp.bfloat16, name=name,
            embedding_init=nn.with_partitioning(
                nn.initializers.normal(size), (None, None)))
        block_cls = (rematerialised(PatternBlock) if self.remat
                     else PatternBlock)
        with trace.scope("embed"):
            x = table("embed")(tokens - first).astype(jnp.float32)
        for i, (sliding, rotary) in enumerate(p.layers):
            x = block_cls(self.d_model, self.n_heads, self.d_ff, p, sliding,
                          rotary, name=f"h{i}")(x)
        x = RMSNorm(p.rms_eps, name="norm_f")(x)
        head = table("head", self.d_model ** -0.5)
        if features:
            # the head's table has to exist for readout_xent to fold in
            head.embedding  # noqa: B018
            return x
        with trace.scope("readout_xent"):
            return jnp.einsum(
                "btd,vd->btv", x.astype(jnp.bfloat16),
                head.embedding.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32)


#: a description's published names beside the zoo's own
_PUBLISHED = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
              "num_hidden_layers": "n_layers", "vocab_size": "vocab"}


def _own_names(hparams: Dict[str, Any]) -> Dict[str, Any]:
    h = dict(hparams)
    for published, own in _PUBLISHED.items():
        if published in h:
            h.setdefault(own, h[published])
    return h


def pattern_of(h: Dict[str, Any]) -> Optional[Pattern]:
    """The layer pattern a description names, or None. The two layouts are
    read up to ``n_layers`` (a cut in depth keeps the leading layers)."""
    if "rope_layout" not in h and "sliding_window_layout" not in h:
        return None
    n_layers = int(h.get("n_layers", 6))
    rotary = list(h.get("rope_layout") or [1] * n_layers)
    sliding = list(h.get("sliding_window_layout") or [0] * n_layers)
    if min(len(rotary), len(sliding)) < n_layers:
        raise ValueError(f"the layouts name {len(rotary)} and {len(sliding)} "
                         f"layers, the model has {n_layers}")
    n_experts = int(h.get("moe_num_primary_experts", 0))
    vocab = int(h.get("vocab", 1000))
    held = lambda key, whole: tuple(  # noqa: E731
        int(v) for v in h.get(key) or (0, whole))
    return Pattern(
        layers=tuple((bool(s), bool(r)) for s, r in
                     zip(sliding[:n_layers], rotary[:n_layers])),
        n_kv_heads=int(h.get("num_key_value_heads", h.get("n_heads", 8))),
        head_dim=int(h.get("head_dim", int(h.get("d_model", 512))
                           // int(h.get("n_heads", 8)))),
        window=int(h.get("sliding_window_size", 4096)),
        rope_theta=float(h.get("rope_theta", 10000.0)),
        rms_eps=float(h.get("rms_norm_eps", 1e-6)),
        n_experts=n_experts,
        top_k=int(h.get("moe_num_active_primary_experts", 1)),
        expert_d_ff=int(h.get("moe_ffn_hidden_size", h.get("d_ff", 2048))),
        experts_held=held("experts_held", n_experts),
        vocab_held=held("vocab_held", vocab),
    )


def describe_pattern(hparams: Dict[str, Any], route: str,
                     tokens: int) -> Dict[str, Any]:
    """What ``trial.setup``'s span says of a description with a layer
    pattern ({} without one), for steps of ``tokens`` tokens on attention
    route ``route``: for each kind of layer the route and the form of its
    mask, and the expert layers' share, the product they take, the rows
    of their buffers and the rows a trip of the routing's loops moves."""
    from metaopt_tpu.models.moe import (grouped_matmul_impl,
                                        routing_chunk_rows)

    h = _own_names(hparams)
    p = pattern_of(h)
    if p is None:
        return {}
    by_structure = route == "pallas"
    out = {"attention_layers": {
        kind: {"route": route,
               "mask": ("structure" if by_structure else "dense") + (
                   f": causal, window {p.window}" if kind.startswith("window")
                   else ": causal")}
        for kind in p.kinds()}}
    if p.n_experts:
        out["moe"] = {"routed_over": p.n_experts, "top_k": p.top_k,
                      "held": list(p.experts_held),
                      "products": grouped_matmul_impl(
                          tokens * p.top_k, int(h.get("d_model", 512)),
                          p.expert_d_ff),
                      "buffer_rows": tokens * p.top_k,
                      "chunk_rows": routing_chunk_rows(tokens * p.top_k)}
    return out


def make_lm(hparams: Optional[Dict[str, Any]] = None,
            **overrides) -> DecoderOnlyLM:
    """The model a description names: the zoo's own keys (``d_model``,
    ``n_layers`` ...) or a published config's (``hidden_size``,
    ``num_hidden_layers``, ``rope_layout`` ...), plus the chip's share
    (``experts_held``, ``vocab_held``: (first, count))."""
    h = _own_names({**(hparams or {}), **overrides})
    pattern = pattern_of(h)
    return DecoderOnlyLM(
        vocab=int(h.get("vocab", 1000)),
        d_model=int(h.get("d_model", 512)),
        n_heads=int(h.get("n_heads", 8)),
        n_layers=int(h.get("n_layers", 6)),
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

    inp, labels = pin_batch_layout(tokens[:, :-1]), tokens[:, 1:]
    first, vocab = model.held_vocab()
    blocked = blocked_xent_enabled(labels.shape[0], labels.shape[1], vocab)
    out, mutated = model.apply(
        {"params": params}, inp, train=True, features=blocked,
        rngs={"dropout": dropout_key},
        mutable=["aux_loss", "moe_stats"],
    )
    mask = (labels != 0).astype(jnp.float32)
    loss = readout_xent(out, params, labels - first, vocab, blocked)
    loss = masked_mean_with_aux(loss, mask, mutated, moe_aux_weight)
    return (loss, moe_counts(mutated)) if with_stats else loss


def moe_counts(mutated) -> Dict[str, Any]:
    """{"items": (layers, held) int32, "dropped": (layers,) int32,
    "chunks": (layers,) int32} from the ``moe_stats`` the dropless expert
    layers sowed, layer by layer; empty for a model without such layers."""
    layers = [v for _, v in sorted(mutated.get("moe_stats", {}).items(),
                                   key=lambda kv: int(kv[0][1:]))  # h0, h1..
              if "items" in v.get("experts", {})]
    if not layers:
        return {}
    return {key: jnp.stack([v["experts"][key][0] for v in layers])
            for key in ("items", "dropped", "chunks")}


def make_lm_train_step(model, tx):
    """The jittable train step (donated params/opt state). ``counts`` is
    the running sum of :func:`moe_counts` over the steps, on the device
    (an empty dict for a model that counts nothing, zeros before the
    first step otherwise)."""

    def train_step(params, opt_state, counts, tokens, step_key):
        (loss, new), grads = jax.value_and_grad(
            lambda p: lm_loss_fn(model, p, tokens, step_key,
                                 with_stats=True), has_aux=True)(params)
        with trace.scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, jax.tree.map(jnp.add, counts, new), loss

    return train_step


def init_sharded_lm(model: DecoderOnlyLM, mesh: Mesh, tx,
                    batch_shape, seed: int = 0):
    """Params/opt state materialized directly on the mesh (one token input)."""
    b, s = batch_shape
    toks = jnp.zeros((b, s), jnp.int32)

    def init_fn(key):
        params = model.init(key, toks, train=False)["params"]
        return params, tx.init(params)

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
        self.model = make_lm(hparams, max_len=max(
            int(hparams.get("max_len", 512)), seq_len))
        # the model's own dropout: a layer pattern has none unless it says so
        self.mesh, tx = trial_setup(
            {**hparams, "dropout": self.model.dropout}, mesh, tp, sp, ep,
            steps, describe=functools.partial(
                describe_pattern, hparams, tokens=batch_size * seq_len),
            remat_blocks=self.model.n_layers if self.model.remat else 0)
        first, vocab = self.model.held_vocab()
        kd, self._kstep = jax.random.split(jax.random.PRNGKey(seed))
        self.tokens = first + synthetic_lm(kd, n_train, seq_len + 1, vocab)
        with use_mesh(self.mesh):
            params, opt_state, self.shardings = init_sharded_lm(
                self.model, self.mesh, tx, (batch_size, seq_len), seed)
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
        #: the expert layers' counts summed over the steps, on the device
        self.counts: Dict[str, Any] = {}
        p = self.model.pattern
        if p is not None and p.n_experts:
            layers = len(p.layers)
            self.counts = jax.device_put({
                "items": jnp.zeros((layers, p.experts_held[1]), jnp.int32),
                "dropped": jnp.zeros((layers,), jnp.int32),
                "chunks": jnp.zeros((layers,), jnp.int32)}, whole)

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
        over the buffers, a layer."""
        return {k: v.tolist() for k, v in
                jax.device_get(self.counts).items()}


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
    it, into ``trial.train``'s ``attrs["moe"]``.
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
            train["attrs"]["moe"] = trial.read_counts()
    if save_dir:
        from metaopt_tpu.models.checkpoint import save_state

        save_state(save_dir + "/params", trial.params)
        save_state(save_dir + "/opt_state", trial.opt_state)
    return float(loss)
