"""Transformer: the reference's side of the first AdamW steps.

The program's side is read by the runner from the object it times
(runners/steady_steps.py::first_steps). Here the plain float32 model
(reference/transformer.py) and the written-out AdamW (reference/optim.py)
follow the same steps from the same seeded weights on the same rows, with
the learning rate of the configuration's own warm-up.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import optim, transformer as reference
from chipbench.weights import make_weights


def weights(config, seed):
    a = config["script_args"]
    return make_weights(seed, reference.param_shapes(
        vocab=a["vocab"], d_model=a["d_model"],
        n_heads=max(1, a["d_model"] // 64), n_layers=a["n_layers"],
        d_ff=a["d_ff"], max_len=a["max_len"]))


def reference_readings(config, seed, rows, mode):
    """``losses`` of the steps over ``rows``, the first step's ``grad`` and
    the ``params`` after the last, in the arithmetic ``mode``."""
    hp, n_layers = config["hparams"], config["script_args"]["n_layers"]
    if hp["dropout"] or len(rows) > hp["warmup"]:
        raise ValueError("the reference has no dropout, and follows steps "
                         "on the warm-up's straight line only")

    @jax.jit
    def step(params, state, src, tgt, i):
        loss, grads = jax.value_and_grad(reference.loss)(
            params, src, tgt, n_layers=n_layers, mode=mode)
        params, state = optim.adamw(
            params, state, grads, lr=hp["lr"] * i / hp["warmup"],
            weight_decay=hp.get("weight_decay", 0.0))
        return params, state, loss, grads

    params = weights(config, seed)
    state, losses, first = optim.adamw_init(params), [], None
    for i, (src, tgt) in enumerate(rows):
        params, state, loss, grads = step(params, state, src, tgt,
                                          jnp.asarray(i, jnp.float32))
        losses.append(float(loss))
        first = grads if i == 0 else first
        del grads
    return {"losses": losses, "grad": first, "params": params}
