"""A decoder with selected attention: the reference's side of the first
AdamW steps, as ``lm_train3`` is for the pattern decoders with a window.

The plain float32 model (reference/sparse_lm.py) and the written-out AdamW
(reference/optim.py) follow the program's first steps from the same seeded
weights (weights_lm.py draws every leaf by what it is; the indexer's and
the q/k norms' fall under its rules) on the same rows. The indexer gets no
gradient: the loss is differentiated with respect to the other leaves, the
gradient both sides hand over names those alone, and the parameters after
the steps name every leaf, so that an indexer a step has moved shows as an
update where the reference has none.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import sparse_lm_config, weights_lm
from chipbench.reference import optim, sparse_lm as reference


def weights(config, seed):
    return weights_lm.make_weights(
        seed, reference.param_shapes(sparse_lm_config.reference_cfg(config)))


def reference_readings(config, seed, rows, mode):
    """``losses`` of the steps over ``rows`` (one (B, S + 1) array a step),
    the first step's ``grad`` (the trained leaves) and the ``params`` after
    the last (every leaf), in the arithmetic ``mode``; gradient and
    parameters on the host."""
    hp = config["hparams"]
    cfg = sparse_lm_config.reference_cfg(config)
    if len(rows) > hp["warmup"]:
        raise ValueError("the reference follows steps on the warm-up's "
                         "straight line only")
    whole = weights(config, seed)
    frozen = {name: {"attn": {"indexer": sub["attn"]["indexer"]}}
              for name, sub in whole.items() if "attn" in sub}

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, state, frozen, tokens, i):
        loss, grads = jax.value_and_grad(
            lambda p: reference.loss(reference.with_indexers(p, frozen),
                                     tokens, cfg, mode))(params)
        params, state = optim.adamw(
            params, state, grads, lr=hp["lr"] * i / hp["warmup"],
            weight_decay=hp.get("weight_decay", 0.0))
        return params, state, loss, grads

    params = reference.trained(whole)
    state, losses, first = optim.adamw_init(params), [], None
    # adamw_init hands out one tree of zeros twice; a donated step needs two
    state["nu"] = jax.tree.map(jnp.zeros_like, params)
    for i, tokens in enumerate(rows):
        params, state, loss, grads = step(
            params, state, frozen, jnp.asarray(tokens),
            jnp.asarray(i, jnp.float32))
        losses.append(float(loss))
        if i == 0:
            first = jax.device_get(grads)
        del grads
    return {"losses": losses, "grad": first,
            "params": jax.device_get(reference.with_indexers(params, frozen))}
