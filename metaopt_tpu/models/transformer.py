"""Transformer-base seq2seq — BASELINE config 4 (Hyperband/BOHB on WMT14,

4-chip sub-slice per trial). The zoo's flagship: encoder-decoder
Transformer-base (d_model 512, 8 heads, 6+6 layers, d_ff 2048) trained on the
synthetic translation-shaped task, sharded dp×tp over the trial's sub-slice
mesh:

- batch over ``dp``,
- attention heads and MLP hidden over ``tp`` (Megatron-style column/row
  split: qkv/wi kernels P(None, "tp"), out/wo kernels P("tp", None)) so the
  per-layer collective is one psum riding ICI,
- everything bf16 on the MXU with f32 layernorm/softmax accumulation.

__graft_entry__.entry() compile-checks the forward; dryrun_multichip() jits
the FULL train step over an n-device dp×tp mesh.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from metaopt_tpu.models.data import synthetic_seq2seq
from metaopt_tpu.ops.attention import REMAT_KEEPS, attend, attention_route
from metaopt_tpu.parallel.sharding import shard_batch, with_mesh_partitioning
from metaopt_tpu.utils import trace


def _pinit(partitioned: bool, axes):
    """Megatron partitioning metadata, or a plain init.

    ``partitioned=False`` exists for trunks that run INSIDE another
    shard_map (the pipeline stages): flax's ``Partitioned.unbox`` applies
    a sharding constraint whenever any mesh is active, and a "tp" spec
    inside a pp x dp manual mesh is an error, not a no-op.
    """
    init = nn.initializers.lecun_normal()
    return with_mesh_partitioning(init, axes) if partitioned else init


@trace.scope("residual")
def residual(x, branch):
    """``x + branch`` on the residual stream: the sum and the cast of the
    branch to the stream's dtype, under a scope of their own."""
    return x + branch


def layer_norm(name: str, x, out_dtype=jnp.float32):
    """A float32 LayerNorm of the trunk and the cast of its output, under
    the scope ``norm``."""
    with trace.scope("norm"):
        return nn.LayerNorm(dtype=jnp.float32, name=name)(x).astype(out_dtype)


class MHA(nn.Module):
    d_model: int
    n_heads: int
    dropout: float = 0.0  # attention-weight dropout (Transformer-base: 0.1)
    partitioned: bool = True

    @nn.compact
    @trace.scope("attention")
    def __call__(self, q_in, kv_in, mask=None, *, train: bool = False):
        d_head = self.d_model // self.n_heads
        dense = lambda name: nn.DenseGeneral(  # noqa: E731
            (self.n_heads, d_head), axis=-1, dtype=jnp.bfloat16, name=name,
            kernel_init=_pinit(self.partitioned, (None, "tp", None)),
        )
        q = dense("q")(q_in) / np.sqrt(d_head)
        k = dense("k")(kv_in)
        v = dense("v")(kv_in)
        # masks here are (b, 1, q|1, k) with heads shared — flatten to the
        # kernel's (b, q, k) convention
        m3 = None
        if mask is not None:
            m3 = jnp.broadcast_to(
                mask[:, 0], (q.shape[0], q.shape[1], k.shape[1])
            )
        rate = self.dropout if train else 0.0
        key = self.make_rng("dropout") if rate > 0.0 else None
        out = attend(q, k, v, m3, dropout_rate=rate, dropout_key=key)
        return nn.DenseGeneral(
            self.d_model, axis=(-2, -1), dtype=jnp.bfloat16, name="out",
            kernel_init=_pinit(self.partitioned, ("tp", None, None)),
        )(out)


class FeedForward(nn.Module):
    d_model: int
    d_ff: int
    dropout: float
    partitioned: bool = True

    @nn.compact
    @trace.scope("ffn")
    def __call__(self, x, *, train: bool):
        wi = nn.Dense(
            self.d_ff, dtype=jnp.bfloat16, name="wi",
            kernel_init=_pinit(self.partitioned, (None, "tp")),
        )
        wo = nn.Dense(
            self.d_model, dtype=jnp.bfloat16, name="wo",
            kernel_init=_pinit(self.partitioned, ("tp", None)),
        )
        h = nn.relu(wi(x))
        h = nn.Dropout(self.dropout, deterministic=not train)(h)
        return wo(h)


def rematerialised(block_cls, keeps=REMAT_KEEPS, **kwargs):
    """``block_cls`` run again in the backward pass, keeping its input and
    the values named ``keeps``: ops/attention.REMAT_KEEPS, the attention
    kernels' ``out`` and ``lse``, which only the kernel could make again;
    for a pattern decoder what models/lm_remat.py::remat_keeps says (the scan's
    output and states, a gated feed-forward's products where they fit the
    device). The one rule for every ``remat`` site of the zoo's blocks."""
    return nn.remat(
        block_cls, **kwargs,
        policy=jax.checkpoint_policies.save_only_these_names(*keeps))


def _make_mlp(d_model, d_ff, dropout, n_experts, capacity_factor=1.25,
              partitioned=True, router_top_k=1):
    if n_experts > 0:
        from metaopt_tpu.models.moe import MoEFeedForward

        return MoEFeedForward(d_model, d_ff, n_experts, dropout,
                              capacity_factor, router_top_k, name="mlp")
    return FeedForward(d_model, d_ff, dropout, partitioned, name="mlp")


class EncoderLayer(nn.Module):
    d_model: int
    n_heads: int
    d_ff: int
    dropout: float
    n_experts: int = 0
    capacity_factor: float = 1.25
    partitioned: bool = True
    router_top_k: int = 1

    @nn.compact
    def __call__(self, x, pad_mask, train: bool = False):
        y = layer_norm("ln1", x)
        x = residual(x, MHA(self.d_model, self.n_heads, self.dropout,
                            self.partitioned,
                            name="self_attn")(y, y, pad_mask, train=train))
        y = layer_norm("ln2", x)
        return residual(x, _make_mlp(
            self.d_model, self.d_ff, self.dropout, self.n_experts,
            self.capacity_factor, self.partitioned,
            self.router_top_k)(y, train=train))


class DecoderLayer(nn.Module):
    d_model: int
    n_heads: int
    d_ff: int
    dropout: float
    n_experts: int = 0
    capacity_factor: float = 1.25
    partitioned: bool = True
    router_top_k: int = 1

    @nn.compact
    def __call__(self, x, enc, causal_mask, cross_mask, train: bool = False):
        y = layer_norm("ln1", x)
        x = residual(x, MHA(self.d_model, self.n_heads, self.dropout,
                            self.partitioned, name="self_attn")(
            y, y, causal_mask, train=train))
        y = layer_norm("ln2", x)
        x = residual(x, MHA(self.d_model, self.n_heads, self.dropout,
                            self.partitioned, name="cross_attn")(
            y, enc, cross_mask, train=train))
        y = layer_norm("ln3", x)
        return residual(x, _make_mlp(
            self.d_model, self.d_ff, self.dropout, self.n_experts,
            self.capacity_factor, self.partitioned,
            self.router_top_k)(y, train=train))


class Transformer(nn.Module):
    """Encoder-decoder; Transformer-base defaults."""

    vocab: int = 1000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: int = 2048
    dropout: float = 0.1
    max_len: int = 512
    #: >0 turns every FFN into a top-1-routed MoE with this many experts
    #: (weights sharded over the "ep" mesh axis when present)
    n_experts: int = 0
    #: per-expert queue = capacity_factor*T/E tokens; <=0 = dense dispatch
    capacity_factor: float = 1.25
    #: experts per token: 1 = Switch, 2 = GShard-style top-2
    router_top_k: int = 1
    #: rematerialize each layer in the backward pass: activation memory
    #: drops from O(layers) to O(1) layers, buying batch size (and with it
    #: MFU) at ~1/3 extra FLOPs — the standard TPU HBM trade. A layer
    #: keeps its input and its attention kernels' ``out`` and ``lse``
    #: (:func:`rematerialised`)
    remat: bool = False

    @nn.compact
    def __call__(self, src, tgt_in, *, train: bool, features: bool = False):
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
        s_len, t_len = src.shape[1], tgt_in.shape[1]
        if max(s_len, t_len) > self.max_len:
            # shapes are static under jit, so this fires at trace time with
            # a readable message instead of a broadcast error deep in XLA
            raise ValueError(
                f"sequence length {max(s_len, t_len)} exceeds the positional "
                f"table (max_len={self.max_len}); pass max_len>=seq to "
                f"make_model"
            )
        with trace.scope("attention"):  # the masks are attention's operands
            src_pad = (src != 0)[:, None, None, :]                # (b,1,1,k)
            causal = jnp.tril(jnp.ones((t_len, t_len), bool))[None, None]
            tgt_pad = (tgt_in != 0)[:, None, None, :]
            causal_mask = causal & tgt_pad
            cross_mask = src_pad

        # static_argnums pins `train` (python control flow inside);
        # counting includes self, so train sits at index 3 / 5
        enc_cls = (rematerialised(EncoderLayer, static_argnums=(3,))
                   if self.remat else EncoderLayer)
        dec_cls = (rematerialised(DecoderLayer, static_argnums=(5,))
                   if self.remat else DecoderLayer)
        with trace.scope("embed"):
            x = emb(src) + pos[None, :s_len].astype(jnp.bfloat16)
        for i in range(self.n_layers):
            x = enc_cls(self.d_model, self.n_heads, self.d_ff,
                        self.dropout, self.n_experts,
                        self.capacity_factor, True, self.router_top_k,
                        name=f"enc{i}")(x, src_pad, train)
        enc = layer_norm("enc_ln", x, jnp.bfloat16)

        with trace.scope("embed"):
            y = emb(tgt_in) + pos[None, :t_len].astype(jnp.bfloat16)
        for i in range(self.n_layers):
            y = dec_cls(self.d_model, self.n_heads, self.d_ff,
                        self.dropout, self.n_experts,
                        self.capacity_factor, True, self.router_top_k,
                        name=f"dec{i}")(
                y, enc, causal_mask, cross_mask, train
            )
        y = layer_norm("dec_ln", y)
        if features:
            # pre-readout features for the blocked-xent loss (ops/xent.py):
            # the caller folds the tied embedding table in blockwise and
            # the (B, T, V) logits tensor never exists
            return y
        # weight-tied readout against the (bf16) embedding table
        with trace.scope("readout_xent"):
            logits = jnp.einsum(
                "btd,vd->btv", y.astype(jnp.bfloat16), emb.embedding
            )
            return logits.astype(jnp.float32)


# ---------------------------------------------------------------------------


def make_model(hparams: Optional[Dict[str, Any]] = None, **overrides) -> Transformer:
    h = dict(hparams or {})
    h.update(overrides)
    return Transformer(
        vocab=int(h.get("vocab", 1000)),
        d_model=int(h.get("d_model", 512)),
        n_heads=int(h.get("n_heads", 8)),
        n_layers=int(h.get("n_layers", 6)),
        d_ff=int(h.get("d_ff", 2048)),
        dropout=float(h.get("dropout", 0.1)),
        max_len=int(h.get("max_len", 512)),
        n_experts=int(h.get("n_experts", 0)),
        capacity_factor=float(h.get("capacity_factor", 1.25)),
        router_top_k=int(h.get("router_top_k", 1)),
        remat=bool(h.get("remat", False)),
    )


#: materialized f32 (B, T, V) logits size above which loss_fn switches to
#: the blocked xent. Below it the plain optax path is simpler AND faster:
#: measured on the v5e (2026-08-01, vocab 32000) the 2.1 GB flagship
#: tensor fits HBM comfortably and materializing beats blocked 58.5 vs
#: 65.3 ms/step at seq256 (parity at seq512) — the blocked path only pays
#: for itself once the tensor genuinely threatens HBM capacity
_BLOCKED_XENT_MIN_LOGITS_BYTES = 4 << 30


def blocked_xent_enabled(
    batch: int, seq: int, vocab: int, shards: Optional[int] = None,
) -> bool:
    """True when :func:`loss_fn` folds the readout into the blocked xent.

    Gates on the PER-DEVICE materialized f32 logits size: on a parallel
    mesh the batch dims are sharded over dp/sp, so HBM pressure is
    ``global_bytes / batch_shards``, not the global tensor.

    Routing: ``shards`` is the number of ways the (B, T) batch dims are
    split. With the default ``shards=None`` the predicate reads the
    ambient mesh (``active_mesh()``): inside a ``with mesh:`` scope it
    divides by ``dp * sp``; outside any mesh it treats the tensor as
    unsharded. Callers deciding routing FOR a mesh they have not entered
    yet (launchers, planners) pass the shard
    count explicitly — the ambient lookup would silently read whatever
    mesh the caller happens to be inside, or none.
    """
    if shards is None:
        from metaopt_tpu.parallel.mesh import active_mesh

        shards = 1
        mesh = active_mesh()
        if mesh is not None:
            shape = dict(mesh.shape)
            shards = shape.get("dp", 1) * shape.get("sp", 1)
    per_device = 4 * batch * seq * vocab // max(shards, 1)
    return per_device >= _BLOCKED_XENT_MIN_LOGITS_BYTES


@trace.scope("readout_xent")
def readout_xent(out, params, labels, vocab, blocked):
    """Per-token xent from the model output against the readout's table
    (the tied embedding, or the model's untied ``head``).

    ``out`` is pre-readout features when ``blocked`` (the f32 (B, T, V)
    logits tensor never exists in HBM — ops/xent.py folds the tied readout
    into a blocked online-softmax), else full logits. Shared by the
    seq2seq loss below and the decoder-only LM (models/lm.py), so the
    routing measured on the bench applies to both families.
    """
    if blocked:
        from metaopt_tpu.ops.xent import blocked_softmax_xent, pick_block_v

        # an untied head where the model has one (models/lm.py's pattern)
        emb = params.get("head", params["embed"])["embedding"]
        if hasattr(emb, "unbox"):  # nn.Partitioned leaf (sharded init path)
            emb = emb.unbox()
        feats = out.reshape(-1, out.shape[-1]).astype(jnp.bfloat16)
        return blocked_softmax_xent(
            feats, emb.astype(jnp.bfloat16), labels.reshape(-1),
            pick_block_v(vocab),
        ).reshape(labels.shape)
    return optax.softmax_cross_entropy_with_integer_labels(out, labels)


@trace.scope("loss")
def masked_mean_with_aux(loss, mask, mutated, moe_aux_weight):
    """Masked token-mean plus the MoE switch load-balancing term."""
    total = (loss * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    aux = jax.tree.leaves(mutated.get("aux_loss", {}))
    if aux:
        total = total + moe_aux_weight * sum(jnp.sum(a) for a in aux)
    return total


def loss_fn(model, params, batch, dropout_key, moe_aux_weight: float = 0.01):
    from metaopt_tpu.parallel.sharding import pin_batch_layout

    src, tgt = batch
    with trace.scope("loss"):  # the shifted rows and the mask
        bos = jnp.ones((tgt.shape[0], 1), tgt.dtype)
        tgt_in = pin_batch_layout(
            jnp.concatenate([bos, tgt[:, :-1]], axis=1))
        mask = (tgt != 0).astype(jnp.float32)
    blocked = blocked_xent_enabled(tgt.shape[0], tgt.shape[1], model.vocab)
    out, mutated = model.apply(
        {"params": params}, src, tgt_in, train=True, features=blocked,
        rngs={"dropout": dropout_key},
        mutable=["aux_loss"],
    )
    loss = readout_xent(out, params, tgt, model.vocab, blocked)
    return masked_mean_with_aux(loss, mask, mutated, moe_aux_weight)


def make_train_step(model, tx):
    """The jittable train step (donated params/opt state)."""

    def train_step(params, opt_state, batch, step_key):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(model, p, batch, step_key)
        )(params)
        with trace.scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


def init_sharded(
    model: Transformer, mesh: Mesh, tx, batch_shape: Tuple[int, int], seed: int = 0
):
    """Initialize params/opt state already laid out on the mesh.

    flax's ``nn.with_partitioning`` annotations (tp axes above) flow into
    jax.eval_shape → NamedSharding here, so big kernels materialize directly
    sharded — no host-resident full copy.
    """
    b, s = batch_shape
    src = jnp.zeros((b, s), jnp.int32)

    def init_fn(key):
        params = model.init(key, src, src, train=False)["params"]
        return params, tx.init(params)

    return sharded_init(init_fn, mesh, seed)


def _prune_spec(spec, mesh):
    """Drop partition-axis names the mesh doesn't have (→ replicated).

    Model code annotates the FULL parallel surface (tp/ep/...); a
    trial mesh that only carves out some axes still initializes — the
    un-carved axes just stay unsharded.
    """
    if not isinstance(spec, P):
        return spec
    cleaned = []
    for entry in spec:
        if entry is None:
            cleaned.append(None)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in mesh.axis_names)
            cleaned.append(kept if kept else None)
        else:
            cleaned.append(entry if entry in mesh.axis_names else None)
    return P(*cleaned)


def held_parameters(shapes, mesh: Mesh) -> int:
    """The parameters one device of ``mesh`` holds of a tree of shapes as
    ``model.init`` annotates it (``jax.eval_shape``): a leaf divided over
    the mesh axes its partitioning names."""
    return sum(
        math.prod(NamedSharding(mesh, _prune_spec(spec, mesh))
                  .shard_shape(x.shape))
        for x, spec in zip(
            jax.tree.leaves(nn.meta.unbox(shapes)),
            jax.tree.leaves(nn.get_partition_spec(shapes),
                            is_leaf=lambda s: isinstance(s, P))))


@trace.span("trial.init")
def sharded_init(init_fn, mesh: Mesh, seed: int = 0):
    """Run ``init_fn(key)`` with outputs materialized directly sharded.

    Shared by the seq2seq ``init_sharded`` above and the decoder-only LM
    (models/lm.py): partition annotations flow through jax.eval_shape →
    NamedSharding, so big kernels never exist host-resident/replicated.
    """
    key = jax.random.PRNGKey(seed)
    shapes = jax.eval_shape(init_fn, key)
    specs = nn.get_partition_spec(shapes)
    shardings = jax.tree.map(
        lambda sp: NamedSharding(mesh, _prune_spec(sp, mesh)), specs)
    out = jax.jit(init_fn, out_shardings=shardings)(key)
    return (*out, shardings)


def trial_setup(hparams: Dict[str, Any], mesh: Optional[Mesh],
                tp: int, sp: int, ep: int, steps: int, describe=None,
                remat_blocks: int = 0, remat_keeps=REMAT_KEEPS):
    """The shared trial-harness preamble: mesh assembly + optimizer.

    sp > 1 shards the sequence axis (ring attention over ICI); ep > 1
    carves an expert axis for MoE FFNs (n_experts hparam). Used by both
    zoo training harnesses (seq2seq below, decoder-only LM in lm.py) so
    mesh/scheduler behavior cannot drift between families.

    Its span says which attention route the trial's steps take
    (``attrs["attention"]``), for the training and the evaluation rate, as
    ops/attention.attention_route names it on this mesh. ``describe``, a
    harness's own, is asked what else the span should say, given the route
    without dropout and the mesh (lm_description.py: each kind of layer's route and
    mask form, what the expert layers hold and run their products with).
    ``remat_blocks``, the blocks the model runs again in the backward
    pass, puts what each keeps besides its input into
    ``attrs["remat"]``: ``remat_keeps``, the
    names, or a harness's function of the mesh that gives the names and
    what they were held against (lm_remat.py::remat_on: the bytes of every
    product a block could keep and the device's room).
    """
    from metaopt_tpu.parallel.mesh import trial_mesh

    # the span holds the first jax.devices() of a trial, as a rule
    with trace.span("trial.setup") as setup:
        extra = []
        if sp > 1:
            extra.append(("sp", sp))
        if ep > 1:
            extra.append(("ep", ep))
        mesh = mesh or trial_mesh(tp=tp, extra_axes=tuple(extra))
        dropout = float(hparams.get("dropout", 0.1))
        evaluation = attention_route(0.0, mesh)
        setup["attrs"]["attention"] = {
            "dropout": dropout, "train": attention_route(dropout, mesh),
            "eval": evaluation}
        if describe is not None:
            setup["attrs"].update(describe(evaluation, mesh=mesh))
        if remat_blocks:
            setup["attrs"]["remat"] = {
                "blocks": remat_blocks,
                **(remat_keeps(mesh) if callable(remat_keeps)
                   else {"keeps": list(remat_keeps)})}
    lr = float(hparams.get("lr", 1e-3))
    warmup = int(hparams.get("warmup", 10))
    sched = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup, max(steps, warmup + 1))
    tx = optax.adamw(sched,
                     weight_decay=float(hparams.get("weight_decay", 0.0)))
    return mesh, tx


def maybe_restore(restore_dir: Optional[str], params, opt_state, shardings):
    """Orbax trial-checkpoint restore (no-op when dir is empty/absent).

    How a PBT continuation inherits its parent's training state and a
    suspended trial resumes (models/checkpoint.py).
    """
    if restore_dir:
        from metaopt_tpu.models.checkpoint import has_state, restore_state

        if has_state(restore_dir):
            params = restore_state(restore_dir + "/params", params,
                                   shardings[0])
            opt_state = restore_state(restore_dir + "/opt_state",
                                      opt_state, shardings[1])
    return params, opt_state


def train_and_eval(
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
    """Train on the synthetic translation task; return final masked loss.

    ``restore_dir``/``save_dir``: orbax trial checkpoints (params +
    optimizer state) — how a PBT continuation inherits its parent's
    training state and a suspended trial resumes (models/checkpoint.py).
    """
    from metaopt_tpu.parallel.mesh import use_mesh

    if n_train < batch_size:
        raise ValueError(
            f"n_train ({n_train}) must be >= batch_size ({batch_size})")
    model = make_model(hparams)
    mesh, tx = trial_setup(
        hparams, mesh, tp, sp, ep, steps,
        remat_blocks=2 * model.n_layers if model.remat else 0)

    key = jax.random.PRNGKey(seed)
    kd, kstep = jax.random.split(key)
    src, tgt = synthetic_seq2seq(kd, n_train, seq_len, model.vocab)

    with use_mesh(mesh):
        params, opt_state, shardings = init_sharded(
            model, mesh, tx, (batch_size, seq_len), seed
        )
        params, opt_state = maybe_restore(
            restore_dir, params, opt_state, shardings)
        step_fn = jax.jit(
            make_train_step(model, tx),
            in_shardings=(
                shardings[0], shardings[1],
                NamedSharding(mesh, P("dp")), None,
            ),
            out_shardings=(shardings[0], shardings[1], None),
            donate_argnums=(0, 1),
        )
        loss = None
        with trace.span("trial.train", steps=steps):
            for i in range(steps):
                with trace.span("slice_and_shard_batch"):
                    lo = (i * batch_size) % (n_train - batch_size + 1)
                    sl = slice(lo, lo + batch_size)
                    batch = shard_batch(mesh, (src[sl], tgt[sl]))
                with trace.span("dispatch_step"):
                    params, opt_state, loss = step_fn(
                        params, opt_state, batch,
                        jax.random.fold_in(kstep, i)
                    )
            if loss is not None:
                # the loop runs ahead of the device; the save and the
                # float(loss) below would wait for it anyway, once
                loss.block_until_ready()
    if save_dir:
        from metaopt_tpu.models.checkpoint import save_state

        save_state(save_dir + "/params", params)
        save_state(save_dir + "/opt_state", opt_state)
    return float(loss)


def make_objective(**fixed):
    def objective(params: Dict[str, Any]) -> float:
        kw = dict(fixed)
        if "epochs" in params:  # fidelity axis maps to train steps
            kw["steps"] = int(params["epochs"]) * kw.get("steps_per_epoch", 50)
            kw.pop("steps_per_epoch", None)
        return train_and_eval(params, **kw)

    return objective
