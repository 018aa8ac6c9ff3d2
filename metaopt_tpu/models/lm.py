"""Decoder-only language model — the GPT-shaped sibling of the seq2seq zoo.

The encoder-decoder Transformer (transformer.py) covers BASELINE config 4's
WMT-shaped trials; this is the modern flagship shape for long-context work:
one causal trunk, tied readout, next-token loss. It deliberately reuses the
seq2seq building blocks rather than duplicating them —

- ``EncoderLayer`` under a causal mask IS a decoder-only block (pre-LN
  self-attention + FFN; MoE FFNs and Megatron tp partitioning included),
- ``MHA`` routes through chunked/Pallas flash attention on one chip and
  ring/Ulysses sequence parallelism on an ``sp`` mesh (ops/ring_attention,
  ops/ulysses) — exactly where a decoder-only model at long sequence needs
  them,
- the loss rides ``readout_xent``, so the measured per-device logits-bytes
  routing between materializing and blocked online-softmax xent
  (transformer.blocked_xent_enabled, calibrated on the 2026-08-01 v5e A/B)
  applies here unchanged — and a decoder-only model at big vocab × long
  sequence is precisely where the blocked path's HBM win binds.

SURVEY.md §2.8/§5 context: the reference ships no model code at all; the
zoo exists to exercise the executor/topology stack with real TPU-shaped
trial workloads.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from metaopt_tpu.models.transformer import (
    EncoderLayer,
    blocked_xent_enabled,
    masked_mean_with_aux,
    readout_xent,
    sharded_init,
)
from metaopt_tpu.utils import trace


class DecoderOnlyLM(nn.Module):
    """Causal LM: embed + pos → n_layers pre-LN blocks → tied readout."""

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
    #: rematerialize each block in the backward pass (the HBM/FLOPs trade)
    remat: bool = False

    @nn.compact
    def __call__(self, tokens, *, train: bool, features: bool = False):
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
        block_cls = (nn.remat(EncoderLayer, static_argnums=(3,))
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


def make_lm(hparams: Optional[Dict[str, Any]] = None,
            **overrides) -> DecoderOnlyLM:
    h = dict(hparams or {})
    h.update(overrides)
    return DecoderOnlyLM(
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


def lm_loss_fn(model, params, tokens, dropout_key,
               moe_aux_weight: float = 0.01):
    """Next-token loss: predict ``tokens[:, 1:]`` from ``tokens[:, :-1]``."""
    from metaopt_tpu.parallel.sharding import pin_batch_layout

    inp, labels = pin_batch_layout(tokens[:, :-1]), tokens[:, 1:]
    blocked = blocked_xent_enabled(
        labels.shape[0], labels.shape[1], model.vocab)
    out, mutated = model.apply(
        {"params": params}, inp, train=True, features=blocked,
        rngs={"dropout": dropout_key},
        mutable=["aux_loss"],
    )
    mask = (labels != 0).astype(jnp.float32)
    loss = readout_xent(out, params, labels, model.vocab, blocked)
    return masked_mean_with_aux(loss, mask, mutated, moe_aux_weight)


def make_lm_train_step(model, tx):
    """The jittable train step (donated params/opt state)."""

    def train_step(params, opt_state, tokens, step_key):
        loss, grads = jax.value_and_grad(
            lambda p: lm_loss_fn(model, p, tokens, step_key)
        )(params)
        with trace.scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

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
    PBT-handoff/suspend-resume contract as ``train_and_eval``.
    """
    from metaopt_tpu.models.data import synthetic_lm
    from metaopt_tpu.models.transformer import maybe_restore, trial_setup
    from metaopt_tpu.parallel.mesh import use_mesh
    from metaopt_tpu.parallel.sharding import shard_batch

    if n_train < batch_size:
        raise ValueError(
            f"n_train ({n_train}) must be >= batch_size ({batch_size})")
    mesh, tx = trial_setup(hparams, mesh, tp, sp, ep, steps)
    model = make_lm(hparams, max_len=max(int(hparams.get("max_len", 512)),
                                         seq_len))

    key = jax.random.PRNGKey(seed)
    kd, kstep = jax.random.split(key)
    toks = synthetic_lm(kd, n_train, seq_len + 1, model.vocab)

    with use_mesh(mesh):
        params, opt_state, shardings = init_sharded_lm(
            model, mesh, tx, (batch_size, seq_len), seed
        )
        params, opt_state = maybe_restore(
            restore_dir, params, opt_state, shardings)
        step_fn = jax.jit(
            make_lm_train_step(model, tx),
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
                    batch = shard_batch(mesh, toks[lo:lo + batch_size])
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
