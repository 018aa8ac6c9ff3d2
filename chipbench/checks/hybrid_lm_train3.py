"""A hybrid linear-attention decoder: the reference's side of the first
AdamW steps, as ``lm_train3`` is for the pattern decoders with a window.

The plain float32 model (reference/hybrid_lm.py: the linear layers token
by token) and the written-out AdamW (reference/optim.py) follow the
program's first steps from the same seeded weights (weights_hybrid_lm.py)
on the same rows, with the learning rate of the configuration's own
warm-up. Every leaf is trained. The reference's state at the published
widths is 12.3 GB of the chip's 16: the first gradient and the parameters
go back to the host as they are made.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import hybrid_lm_config, weights_hybrid_lm
from chipbench.reference import hybrid_lm as reference, optim


def weights(config, seed):
    return weights_hybrid_lm.make_weights(
        seed, reference.param_shapes(hybrid_lm_config.reference_cfg(config)))


def reference_readings(config, seed, rows, mode):
    """``losses`` of the steps over ``rows`` (one (B, S + 1) array a step),
    the first step's ``grad`` and the ``params`` after the last, in the
    arithmetic ``mode``; gradient and parameters on the host."""
    hp = config["hparams"]
    cfg = hybrid_lm_config.reference_cfg(config)
    if len(rows) > hp["warmup"]:
        raise ValueError("the reference follows steps on the warm-up's "
                         "straight line only")

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, state, tokens, i):
        loss, grads = jax.value_and_grad(reference.loss)(
            params, tokens, cfg, mode)
        params, state = optim.adamw(
            params, state, grads, lr=hp["lr"] * i / hp["warmup"],
            weight_decay=hp.get("weight_decay", 0.0))
        return params, state, loss, grads

    params = weights(config, seed)
    state, losses, first = optim.adamw_init(params), [], None
    # adamw_init hands out one tree of zeros twice; a donated step needs two
    state["nu"] = jax.tree.map(jnp.zeros_like, params)
    for i, tokens in enumerate(rows):
        params, state, loss, grads = step(
            params, state, jnp.asarray(tokens), jnp.asarray(i, jnp.float32))
        losses.append(float(loss))
        if i == 0:
            first = jax.device_get(grads)
        del grads
    return {"losses": losses, "grad": first,
            "params": jax.device_get(params)}
