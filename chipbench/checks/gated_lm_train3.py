"""A decoder with a gate on attention's output: the reference's side of the
first AdamW steps, as ``lm_train3`` is for the pattern decoders of one head
count.

The plain float32 model (reference/gated_lm.py) and the written-out AdamW
(reference/optim.py) follow the program's first steps from the same seeded
weights (weights_gated_lm.py) on the same rows. Every leaf is trained (the
routing has no correction bias).

The reference's state at the published widths is 11.07 GB of the chip's 16
(691.6 M parameters, their gradient and AdamW's two moments in float32),
which leaves a float32 gradient pass at 8192 tokens no room. So a step is
two programs, as ``mla_lm_train3`` has it: the gradient, with AdamW's two
moments on the host meanwhile (5.5 GB there and back, twice in three
steps), and the written-out update with everything donated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import gated_lm_config, weights_gated_lm
from chipbench.reference import gated_lm as reference, optim


def weights(config, seed):
    return weights_gated_lm.make_weights(
        seed, reference.param_shapes(gated_lm_config.reference_cfg(config)))


def reference_readings(config, seed, rows, mode, faults=()):
    """``losses`` of the steps over ``rows`` (one (B, S + 1) array a step),
    the first step's ``grad`` and the ``params`` after the last, in the
    arithmetic ``mode`` (with ``faults`` planted: reference/gated_lm.py);
    gradient and parameters on the host."""
    hp = config["hparams"]
    cfg = gated_lm_config.reference_cfg(config)
    if len(rows) > hp["warmup"]:
        raise ValueError("the reference follows steps on the warm-up's "
                         "straight line only")

    @jax.jit
    def gradient(params, tokens):
        return jax.value_and_grad(lambda p: reference.loss(
            p, tokens, cfg, mode, tuple(faults)))(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(params, state, grads, i):
        return optim.adamw(
            params, state, grads, lr=hp["lr"] * i / hp["warmup"],
            weight_decay=hp.get("weight_decay", 0.0))

    params = weights(config, seed)
    state, losses, first = None, [], None
    for i, tokens in enumerate(rows):
        loss, grads = gradient(params, jnp.asarray(tokens))
        losses.append(float(loss))
        if i == 0:
            first = jax.device_get(grads)
            state = optim.adamw_init(params)
            # adamw_init hands out one tree of zeros twice; a donated
            # update needs two
            state["nu"] = jax.tree.map(jnp.zeros_like, params)
        else:
            state = jax.device_put(state)
        params, state = update(params, state, grads,
                               jnp.asarray(i, jnp.float32))
        del grads
        if i + 1 < len(rows):
            # the two moments wait on the host while the next gradient is
            # made (module docstring)
            on_device = state
            state = jax.device_get(on_device)
            jax.tree.map(lambda x: x.delete(), on_device)
    return {"losses": losses, "grad": first,
            "params": jax.device_get(params)}
