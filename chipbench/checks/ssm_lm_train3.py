"""A decoder-hybrid-decoder with state-space layers: the reference's side
of the first AdamW steps, as ``lm_train3`` is for the pattern decoders with
a window.

The plain float32 model (reference/ssm_lm.py: the scans token by token)
and the written-out AdamW (reference/optim.py) follow the program's first
steps from the same seeded weights (weights_ssm_lm.py) on the same rows,
with the learning rate of the configuration's own warm-up. Every leaf is
trained.

The reference's state at the published widths is 11.15 GB of the chip's
16, and a step's float32 gradient pass at 8192 tokens needs the parameters,
their gradient and a layer's intermediates beside it (a Mamba layer's
alone are a dozen arrays of 168 MB). So a step is two programs, as
``mla_lm_train3`` has them: the gradient, with AdamW's two moments on the
host meanwhile (5.6 GB there and back, twice in three steps), and the
written-out update with everything donated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import ssm_lm_config, weights_ssm_lm
from chipbench.reference import optim, ssm_lm as reference


def weights(config, seed):
    return weights_ssm_lm.make_weights(
        seed, reference.param_shapes(ssm_lm_config.reference_cfg(config)))


def reference_readings(config, seed, rows, mode, faults=()):
    """``losses`` of the steps over ``rows`` (one (B, S + 1) array a step),
    the first step's ``grad`` and the ``params`` after the last, in the
    arithmetic ``mode``; gradient and parameters on the host. ``faults``
    (chipbench/tests/test_ssm_lm_cell.py): planted faults of the model."""
    hp = config["hparams"]
    cfg = ssm_lm_config.reference_cfg(config)
    if len(rows) > hp["warmup"]:
        raise ValueError("the reference follows steps on the warm-up's "
                         "straight line only")

    @jax.jit
    def gradient(params, tokens):
        return jax.value_and_grad(reference.loss)(
            params, tokens, cfg, mode, tuple(faults))

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
