"""AdamW, written out (optax is not consulted)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def adamw_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"count": jnp.zeros((), jnp.float32), "mu": zeros, "nu": zeros}


def adamw(params, state, grads, *, lr, weight_decay, b1=0.9, b2=0.999,
          eps=1e-8):
    """``lr`` is the schedule read at the count before this step. Returns
    ``(new_params, new_state)``."""
    t = state["count"] + 1.0
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["nu"],
                      grads)
    params = jax.tree.map(
        lambda p, m, v: p - lr * (
            (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
            + weight_decay * p),
        params, mu, nu)
    return params, {"count": t, "mu": mu, "nu": nu}
