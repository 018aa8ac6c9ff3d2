"""Decoder-only language models: one causal trunk, one block, a library of
layers.

``DecoderOnlyLM`` is a stack of layers chosen by the model's description
(the hyperparameters of :func:`make_lm`), not by flags or the environment.
Without a layer pattern it is the GPT-shaped sibling of the seq2seq zoo:
learned positions, ``EncoderLayer`` under a dense causal mask (pre-LN
self-attention + ReLU FFN, or ``MoEFeedForward``'s capacity routing), tied
readout. With one (models/lm_description.py::pattern_of reads it from the
description's published words) every held layer is data, a
models/lm_description.py::Layer of two specs, and :class:`PatternBlock` reads
it: norm, mixer, residual, norm, feed-forward, residual, in the placement
the pattern names, taking what the layer reads of earlier layers and
returning what it hands on; no learned positions, the head untied over the
held rows or the embedding's table.

=================  ================  =====================  =================
kind               spec              module                 kernels (ops/)
=================  ================  =====================  =================
global-, window-   GroupedSpec       GroupedAttention       attention
                   + ``rotary``,     (a rotary rule a
                   ``gate``          layer, an output gate)
selected-          + ``selection``   + Indexer              + sparse_index
latent-rope        LatentSpec        LatentAttention        latent_attention
linear             LinearSpec        LinearAttention        linear_attention
ssm                StateSpaceSpec    StateSpaceMixer        selective_scan
gmu                MemoryUnitSpec    GatedMemoryUnit        none
ssd                ScalarDecaySpec   ScalarDecayMixer       linear_attention
short-conv         ShortConvSpec     ShortConvMixer         short_conv
window-, global-,  DifferentialSpec  DifferentialAttention  attention, twice
cross-nope
gated (ffn)        GatedSpec         GatedFeedForward       none
routed (ffn)       moe.RoutedSpec    router, DroplessMoE    experts, megablox
                   + ``gated``       (experts of two
                   false             matrices, no gate)
absent (either)    Absent            no sublayer there      none
=================  ================  =====================  =================

Who names them (models/lm_description.py, the one module that knows a
family): SmallThinker's layouts and the Qwen3-MoE family's words the
grouped kinds (selected with ``sa_config``) and the routed feed-forward;
the Olmo hybrid family's linear and global-nope, with a gated
feed-forward; the DeepSeek-V3 family's latent, routed behind leading gated
layers; ``phi4flash`` ssm, gmu and the differential kinds, gated;
``laguna`` the grouped kinds with a head count, a rotary rule (YaRN on half
a head, the plain rule on the whole) and an output gate a layer, gated or
routed by the layer; ``nemotron_h`` one sublayer a block by its pattern's
letter: ssd (Mamba-2), global-nope attention, or experts of two matrices
under a squared ReLU; ``lfm2_moe`` short-conv and global-rope, routed.

The specs and modules are models/lm_layers.py's (the routed feed-forward's
models/moe.py's); what a rematerialised block keeps is
models/lm_remat.py's rule. Every attention module calls
ops/attention.attend, whose one rule (``attention_route``) names the route
from the mesh, the backend and the dropout rate; an ``sp`` mesh (ring/Ulysses
sequence parallelism) is for the 2017 blocks' dense masks only. The loss
rides ``readout_xent``, so the per-device logits-bytes routing between
materializing and blocked online-softmax xent
(transformer.blocked_xent_enabled) applies to both kinds of stack.

:class:`LMTrial` is the train loop handed out step by step: the set-up and
one ``step(i)``, which :func:`train_lm` itself drives and a benchmark can
drive too.

SURVEY.md §2.8/§5 context: the reference ships no model code at all; the
zoo exists to exercise the executor/topology stack with real TPU-shaped
trial workloads.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from metaopt_tpu.models.data import synthetic_lm
from metaopt_tpu.models.lm_description import (Layer, Pattern, _own_names,
                                               describe_pattern, pattern_of)
# chipbench/runners/mla_lm_trial_steps.py::has_mechanism asks
# hasattr(lm, "LatentAttention") and chipbench/ is not this PR's to edit
from metaopt_tpu.models.lm_layers import (NORMS, Absent,  # noqa: F401
                                          LatentAttention)
from metaopt_tpu.models.lm_remat import param_init, remat_keeps, remat_on
from metaopt_tpu.models.transformer import (
    EncoderLayer,
    blocked_xent_enabled,
    layer_norm,
    masked_mean_with_aux,
    maybe_restore,
    readout_xent,
    rematerialised,
    residual,
    sharded_init,
    trial_setup,
)
from metaopt_tpu.ops.embed import embed_rows
from metaopt_tpu.parallel.mesh import use_mesh
from metaopt_tpu.parallel.sharding import pin_batch_layout, shard_batch
from metaopt_tpu.utils import trace


class PatternBlock(nn.Module):
    """One held layer of a pattern, as its entry says (models/lm_layers.py:
    the specs build the modules): x + mixer(norm(x)) then + ffn(norm(.))
    or, with the norm on the branches, x + norm(mixer(x)) then +
    norm(ffn(.)); a sublayer whose spec is ``Absent`` is passed over, branch
    and norm (a pattern of one sublayer a block). The feed-forward may read
    the first norm's output before
    the mixer runs (a router). ``read``: what the layer reads of earlier
    layers, in ``layer.reads``' order. Returns (x, {name: what the layer
    hands on}). The residual stream is float32."""

    d_model: int
    norm: str
    eps: float
    layer: Layer

    @nn.compact
    def __call__(self, x, *read):
        norm, before = NORMS[self.norm]
        at = lambda name, y, here: (  # noqa: E731
            norm(name, y, self.eps) if here else y)
        mixer, ffn = self.layer.mixer, self.layer.ffn
        early, offered = None, {}
        if not isinstance(mixer, Absent):
            n = at("norm_in", x, before)
            early = ffn.before_mixer(self, n)
            branch, offered = mixer.mix(self, n, *read)
            x = residual(x, at("norm_mixer", branch, not before))
        if not isinstance(ffn, Absent):
            m = at("norm_post", x, before)
            x = residual(x, at("norm_ffn", ffn.feed(self, m, early),
                                not before))
        return x, {name: offered[name] for name in self.layer.hands_on}


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
    #: models/lm_remat.py::remat_keeps decided them for the trial's sizes
    #: and device (:class:`LMTrial`); None: what it says of the pattern
    #: alone, the kernels' outputs
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
        """The pattern's trunk: it carries what a layer hands on (a memory
        layer's scan output, a full layer's K and V) to the layers that
        read it. A handed value is an output of its block and an input of
        each reader, so a rematerialised block keeps it and its gradient is
        the sum over the readers."""
        p = self.pattern
        first, rows = p.vocab_held
        table = lambda name, size: nn.Embed(  # noqa: E731
            rows, self.d_model, dtype=jnp.bfloat16, name=name,
            embedding_init=nn.with_partitioning(
                nn.initializers.normal(size), (None, None)))
        block_cls = PatternBlock
        if self.remat:
            block_cls = rematerialised(
                PatternBlock, keeps=remat_keeps(p)["keeps"]
                if self.keeps is None else self.keeps)
        # the head's table at 1/sqrt(d): logits of size 1, a loss near
        # log(rows) at the start; an embedding of its own at size 1 (the
        # first norm rescales the stream)
        emb = table("embed", self.d_model ** -0.5 if p.tied else 1.0)
        with trace.scope("embed"):
            x = embed_rows(emb.embedding, tokens - first).astype(jnp.float32)
        handed = {}
        for layer in p.layers:
            x, on = block_cls(self.d_model, p.norm, p.eps, layer,
                              name=f"h{layer.number}")(
                x, *(handed[name] for name in layer.reads))
            handed.update(on)
        x = NORMS[p.norm][0]("norm_f", x, p.eps)
        head = emb if p.tied else table("head", self.d_model ** -0.5)
        if features:
            # the head's table has to exist for readout_xent to fold in
            head.embedding  # noqa: B018
            return x
        return _logits(x, head.embedding)


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
        if n_train < batch_size:
            raise ValueError(
                f"n_train ({n_train}) must be >= batch_size ({batch_size})")
        self.batch_size, self.n_train = batch_size, n_train
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
        #: what the layers' modules count a step (the expert layers', the
        #: selected-attention layers': each spec's ``counts``), summed over
        #: the steps, on the device: a row a layer that counts it
        p = self.model.pattern
        shapes: Dict[str, list] = {}
        for layer in p.layers if p is not None else ():
            for spec in (layer.mixer, layer.ffn):
                for key, shape in spec.counts().items():
                    shapes.setdefault(key, []).append(shape)
        self.counts: Dict[str, Any] = jax.device_put(
            {key: jnp.zeros((len(rows), *rows[0]), jnp.int32)
             for key, rows in shapes.items()}, whole)

    def __enter__(self):
        self._scope = use_mesh(self.mesh)
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
            batch = shard_batch(self.mesh, self.rows(i))
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

