"""Seeded weights for the decoders whose mixers are gated short convolutions
(reference/conv_lm.py's tree), beside ``weights_lm.py``, whose rules draw
the leaves the families share: a projection's, a feed-forward's or an
expert's matrix ~ N(0, 1/fan_in) with fan_in its first axis, attention's
output projection ~ N(0, 1/(heads x width)), norm scales 1 + 0.1 N (the q
and k norms' a head among them). This family's own:

- the TIED table ~ N(0, 1/d): it is the head, whose logits then have size
  1 on a normed stream; as the embedding it starts the residual stream
  small, which the blocks' pre-norms rescale (``weights_ssm_lm.py``'s rule);
- a mixer's taps ~ U(-1/2, 1/2) a channel, as the program's initialiser
  draws them: B, C and X have size 1 a channel (a normed input on a
  projection ~ N(0, 1/d)), so ``C * conv(B * X)`` has size ~1/2 and the
  output projection (~ N(0, 1/d)) hands the stream a branch of that size:
  neither gate nor a tap hides behind the others;
- the router ~ N(0, ROUTER_SPREAD^2 / d): logits of spread 1, so that a
  token's four chosen of 64 are not all saturated at 1 (``weights_gated_lm``
  has the reasoning); the correction bias (``choice_bias``) ~ N(0,
  BIAS_SPREAD^2) an expert, as ``weights_mla_lm.py`` seeds kanana's, which
  changes the chosen four for some tokens and not for the others (counted by
  the program: ``conv_lm_moe_choice_bias_share``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import weights_lm
from chipbench.weights import _leaf_name

ROUTER_SPREAD = 1.0
BIAS_SPREAD = 0.005


def _draw(key, name: str, shape):
    parts = name.split("/")
    z = lambda: jax.random.normal(key, shape, jnp.float32)  # noqa: E731
    if parts[0] == "embed":
        return z() / math.sqrt(shape[-1])
    if parts[-1] == "choice_bias":
        return BIAS_SPREAD * z()
    if parts[-2:] == ["conv", "conv"]:
        return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
    if parts[1] == "router":
        return z() * ROUTER_SPREAD / math.sqrt(shape[0])
    return weights_lm._draw(key, name, shape)


def make_weights(seed: int, shapes, stack: bool = False):
    """A tree like ``shapes`` (reference/conv_lm.py::param_shapes), from
    ``seed``; ``stack``: in the program's form (``weights_lm.stacked``), the
    same numbers."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        tree = jax.tree_util.tree_unflatten(treedef, [
            _draw(jax.random.fold_in(key, i), _leaf_name(path), leaf.shape)
            for i, (path, leaf) in enumerate(leaves)])
        return weights_lm.stacked(tree) if stack else tree

    return jax.jit(build)(jax.random.PRNGKey(seed))
