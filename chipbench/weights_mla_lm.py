"""Seeded weights for the latent-attention MoE decoders
(reference/mla_lm.py's tree), beside ``weights_lm.py``, whose rules draw
every leaf by what it is: a projection, a feed-forward's or an expert's
matrix ~ N(0, 1/fan_in) with fan_in its first axis (the hidden width, the
latent's rank for the up-projection, a feed-forward's own width for its
down matrix), the output projection ~ N(0, 1/(heads x width)), the
embedding ~ N(0, 1), the head ~ N(0, 1/d), norm scales 1 + 0.1 N, the
router ~ N(0, ROUTER_SPREAD^2 / d). No leaf of this family needed another
rule at the tests' sizes: the latent's norm rescales c before the
up-projection, so k_nope and v have size 1 a channel as q has, and the
scores q.k (nope + rope)^-1/2 have size 1.

One leaf is this family's own, the routing's correction bias
(``h1/choice_bias``) ~ N(0, BIAS_SPREAD^2) an expert. The router's logits
have spread ``weights_lm.ROUTER_SPREAD`` (2), so a token's sixth and
seventh largest scores of 128 lie ~0.01 apart; a bias of spread 0.005 then
changes the chosen six for about two tokens in five and not for the
others (counted by the program, ``mla_lm_moe_choice_bias_share``): a
program that left the bias out of the choice, or let it into the weights,
meets tokens on which that shows and tokens on which the plain top-k has to
hold.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import weights_lm
from chipbench.weights import _leaf_name

BIAS_SPREAD = 0.005


def _draw(key, name: str, shape):
    if name.split("/")[-1] == "choice_bias":
        return BIAS_SPREAD * jax.random.normal(key, shape, jnp.float32)
    return weights_lm._draw(key, name, shape)


def make_weights(seed: int, shapes, stack: bool = False):
    """A tree like ``shapes`` (reference/mla_lm.py::param_shapes), from
    ``seed``; ``stack``: in the program's form (``weights_lm.stacked``), the
    same numbers."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        tree = jax.tree_util.tree_unflatten(treedef, [
            _draw(jax.random.fold_in(key, i), _leaf_name(path), leaf.shape)
            for i, (path, leaf) in enumerate(leaves)])
        return weights_lm.stacked(tree) if stack else tree

    return jax.jit(build)(jax.random.PRNGKey(seed))
