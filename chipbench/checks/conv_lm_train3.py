"""A decoder whose mixers are gated short convolutions beside grouped
attention: the reference's side of the first AdamW steps, as ``lm_train3``
is for the pattern decoders with a window.

The plain float32 model (reference/conv_lm.py) and the written-out AdamW
(reference/optim.py) follow the program's first steps from the same seeded
weights (weights_conv_lm.py) on the same rows. The routing's correction
bias gets no gradient: the loss is differentiated with respect to the other
leaves, the gradient both sides hand over names those alone, and the
parameters after the steps name every leaf, so that a bias a step has moved
shows as an update where the reference has none (``mla_lm_train3``'s way).

The reference's state at the published widths is 10.37 GB of the chip's 16
(647.8 M parameters, their gradient and AdamW's two moments in float32),
which leaves a float32 gradient pass at 8192 tokens no room. So a step is
two programs, as ``mla_lm_train3`` has it: the gradient, with AdamW's two
moments on the host meanwhile (5.2 GB there and back, twice in three
steps), and the written-out update with everything donated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import conv_lm_config, weights_conv_lm
from chipbench.reference import conv_lm as reference, optim


def weights(config, seed):
    cfg = conv_lm_config.reference_cfg(config)
    return weights_conv_lm.make_weights(seed, reference.param_shapes(cfg))


def reference_readings(config, seed, rows, mode, faults=()):
    """``losses`` of the steps over ``rows`` (one (B, S + 1) array a step),
    the first step's ``grad`` (the trained leaves) and the ``params`` after
    the last (every leaf), in the arithmetic ``mode`` (with ``faults``
    planted: reference/conv_lm.py); gradient and parameters on the host."""
    hp = config["hparams"]
    cfg = conv_lm_config.reference_cfg(config)
    if len(rows) > hp["warmup"]:
        raise ValueError("the reference follows steps on the warm-up's "
                         "straight line only")
    whole = weights(config, seed)
    biases = reference.frozen(whole)

    @jax.jit
    def gradient(params, biases, tokens):
        return jax.value_and_grad(lambda p: reference.loss(
            reference.with_frozen(p, biases), tokens, cfg, mode,
            tuple(faults)))(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(params, state, grads, i):
        return optim.adamw(
            params, state, grads, lr=hp["lr"] * i / hp["warmup"],
            weight_decay=hp.get("weight_decay", 0.0))

    params = reference.trained(whole)
    del whole
    state, losses, first = None, [], None
    for i, tokens in enumerate(rows):
        loss, grads = gradient(params, biases, jnp.asarray(tokens))
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
            "params": jax.device_get(reference.with_frozen(params, biases))}
