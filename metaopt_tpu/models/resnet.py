"""ResNet — BASELINE config 3 (ASHA on ResNet-50/CIFAR-10, 1 chip/trial).

Bottleneck-block ResNet in flax with the CIFAR stem (3×3, no max-pool).
Depth 50 by default; the ASHA fidelity axis is ``epochs``. bf16 conv/matmul
for the MXU, f32 batch-norm statistics, one jitted scan per epoch.
Searchable hparams in the BASELINE config: lr, momentum, weight_decay,
batch_size — see examples/resnet_cifar.py.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn

from metaopt_tpu.models.data import synthetic_images
from metaopt_tpu.utils import trace

#: layers-per-stage tables for the classic depths
STAGES = {18: (2, 2, 2, 2), 34: (3, 4, 6, 3), 50: (3, 4, 6, 3),
          101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
BOTTLENECK = {50, 101, 152}


class Bottleneck(nn.Module):
    filters: int
    strides: int = 1

    @nn.compact
    def __call__(self, x, *, train: bool):
        conv = partial(nn.Conv, use_bias=False, dtype=jnp.bfloat16)
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, dtype=jnp.float32)
        residual = x
        y = conv(self.filters, (1, 1))(x)
        y = nn.relu(norm()(y))
        y = conv(self.filters, (3, 3), strides=(self.strides, self.strides))(y)
        y = nn.relu(norm()(y))
        y = conv(self.filters * 4, (1, 1))(y)
        y = norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(self.filters * 4, (1, 1),
                            strides=(self.strides, self.strides))(residual)
            residual = norm()(residual)
        return nn.relu(y + residual)


class BasicBlock(nn.Module):
    filters: int
    strides: int = 1

    @nn.compact
    def __call__(self, x, *, train: bool):
        conv = partial(nn.Conv, use_bias=False, dtype=jnp.bfloat16)
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, dtype=jnp.float32)
        residual = x
        y = conv(self.filters, (3, 3), strides=(self.strides, self.strides))(x)
        y = nn.relu(norm()(y))
        y = conv(self.filters, (3, 3))(y)
        y = norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(self.filters, (1, 1),
                            strides=(self.strides, self.strides))(residual)
            residual = norm()(residual)
        return nn.relu(y + residual)


class ResNet(nn.Module):
    depth: int = 50
    n_classes: int = 10
    width: int = 64

    @nn.compact
    def __call__(self, x, *, train: bool):
        block = Bottleneck if self.depth in BOTTLENECK else BasicBlock
        x = x.astype(jnp.bfloat16)
        # CIFAR stem: 3x3 stride 1 (no 7x7/maxpool — inputs are 32x32)
        x = nn.Conv(self.width, (3, 3), use_bias=False, dtype=jnp.bfloat16)(x)
        x = nn.relu(nn.BatchNorm(use_running_average=not train,
                                 dtype=jnp.float32)(x))
        for i, n_blocks in enumerate(STAGES[self.depth]):
            for j in range(n_blocks):
                strides = 2 if i > 0 and j == 0 else 1
                x = block(self.width * (2 ** i), strides)(x, train=train)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.n_classes, dtype=jnp.float32)(x)


def train_and_eval(
    hparams: Dict[str, Any],
    *,
    depth: int = 50,
    n_train: int = 4096,
    n_val: int = 1024,
    epochs: int = 1,
    seed: int = 0,
    hw: int = 32,
    on_epoch=None,
) -> float:
    """Train on synthetic CIFAR-shaped data; return validation error.

    ``on_epoch(epoch, val_error)`` fires after each epoch so multi-fidelity
    scripts can stream partials (client.report_partial) from ONE continuous
    training run — the fidelity axis continues training, it never restarts.
    """
    lr = float(hparams.get("lr", 0.1))
    momentum = float(hparams.get("momentum", 0.9))
    weight_decay = float(hparams.get("weight_decay", 1e-4))
    batch_size = int(hparams.get("batch_size", 128))

    model = ResNet(
        depth=int(hparams.get("depth", depth)),
        width=int(hparams.get("width", 64)),
    )
    key = jax.random.PRNGKey(seed)
    kd, kv, ki = jax.random.split(key, 3)
    x, y = synthetic_images(kd, n_train, hw=hw, channels=3)
    xv, yv = synthetic_images(kv, n_val, hw=hw, channels=3)

    with trace.span("trial.init"):
        variables = model.init(ki, x[:1], train=False)
        params, batch_stats = variables["params"], variables["batch_stats"]
        tx = optax.chain(
            optax.add_decayed_weights(weight_decay),
            optax.sgd(lr, momentum=momentum, nesterov=True),
        )
        opt_state = tx.init(params)
    steps = max(1, n_train // batch_size)

    def loss_fn(p, bs, xb, yb):
        logits, new_model_state = model.apply(
            {"params": p, "batch_stats": bs}, xb, train=True,
            mutable=["batch_stats"],
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, yb).mean()
        return loss, new_model_state["batch_stats"]

    @jax.jit
    def epoch(carry, ekey):
        def step(c, _):
            p, bs, o, k = c
            k, sk = jax.random.split(k)
            idx = jax.random.permutation(sk, n_train)[:batch_size]
            (loss, bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                p, bs, x[idx], y[idx]
            )
            with trace.scope("optimizer"):
                updates, o = tx.update(grads, o, p)
                p = optax.apply_updates(p, updates)
            return (p, bs, o, k), loss

        (p, bs, o, _), losses = jax.lax.scan(
            step, (*carry, ekey), jnp.arange(steps)
        )
        return (p, bs, o), losses.mean()

    @jax.jit
    @trace.scope("eval")
    def val_error(p, bs):
        logits = model.apply({"params": p, "batch_stats": bs}, xv, train=False)
        return 1.0 - jnp.mean(jnp.argmax(logits, -1) == yv)

    def evaluate(carry) -> float:
        with trace.span("trial.eval"):
            return float(val_error(carry[0], carry[1]))

    carry = (params, batch_stats, opt_state)
    err = 1.0
    for e in range(int(epochs)):
        with trace.span("trial.train", epoch=e + 1, steps=steps):
            carry, loss = epoch(carry, jax.random.fold_in(key, 1000 + e))
            if on_epoch is not None or e + 1 == int(epochs):
                # the evaluation that follows would wait for the device
                # anyway: its wait belongs to training. Epochs that nothing
                # follows stay queued, as ever
                loss.block_until_ready()
        if on_epoch is not None:
            err = evaluate(carry)
            on_epoch(e + 1, err)
    if on_epoch is None:
        err = evaluate(carry)
    return err


def make_objective(**fixed):
    def objective(params: Dict[str, Any]) -> float:
        kw = dict(fixed)
        if "epochs" in params:
            kw["epochs"] = int(params["epochs"])  # ASHA fidelity
        return train_and_eval(params, **kw)

    return objective
