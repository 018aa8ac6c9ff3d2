"""Seeded weights for the decoders of one sublayer a block
(reference/ssd_lm.py's tree), beside ``weights_lm.py``, whose rules draw the
leaves the families share: a projection's or an expert's matrix
~ N(0, 1/fan_in) with fan_in its first axis, the output projections
~ N(0, 1/(the axes they contract)), the embedding ~ N(0, 1), the head
~ N(0, 1/d), norm scales 1 + 0.1 N. This family's own:

- ``A_log`` = log of U(1, 16) a head (Mamba-2's published initialiser) and
  ``dt_bias`` = softplus^-1(dt), log dt ~ U(log ``time_step_min``, log
  ``time_step_max``) floored at ``time_step_floor`` (0.001 to 0.1, floor
  1e-4: the configuration's own): a step's decay exp(-dt A) then lies from
  exp(-1.6) to exp(-0.001) a token at a zero input, where a trained model's
  do, and neither 0 nor 1 hides the scan; dt's columns of ``in_proj``
  ~ N(0, DT_SPREAD^2 / d): a token's step spreads by ~e^+-0.5 around its
  head's;
- ``D`` = 1 + 0.1 N (the published 1, spread so that its gradient is a
  leaf's and not a constant's); the gated norm's scale (``ssd/norm``)
  1 + 0.1 N as the other norms';
- the convolution's taps and bias ~ U(-1/2, 1/2) (a depthwise conv's
  default at 4 taps), so that a bias left out shows;
- the router ~ N(0, ROUTER_SPREAD^2 / d): logits of spread 1, so that a
  token's six chosen of 128 are not all saturated at 1 (``weights_gated_lm``
  has the reasoning); the correction bias (``choice_bias``) ~ N(0,
  BIAS_SPREAD^2) an expert, which changes the chosen six for some tokens and
  not for the others (counted by the program:
  ``ssd_lm_moe_choice_bias_share``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import weights_lm
from chipbench.weights import _leaf_name

DT_SPREAD = 0.5
ROUTER_SPREAD = 1.0
BIAS_SPREAD = 0.005


def _draw(key, name: str, shape, time_step):
    parts = name.split("/")
    leaf = parts[-1]
    z = lambda: jax.random.normal(key, shape, jnp.float32)  # noqa: E731
    if leaf == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if leaf == "dt_bias":
        low, high, floor = time_step
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(low), math.log(high))), floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    if leaf == "D" or parts[-2:] == ["ssd", "norm"]:
        return 1.0 + 0.1 * z()
    if leaf in ("conv", "conv_bias"):
        return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
    if leaf == "choice_bias":
        return BIAS_SPREAD * z()
    if leaf == "in_proj":
        # dt's columns are scaled where the tree is built (make_weights):
        # how many they are is A_log's to say
        return z() / math.sqrt(shape[0])
    if parts[1] == "router":
        return z() * ROUTER_SPREAD / math.sqrt(shape[0])
    return weights_lm._draw(key, name, shape)


def make_weights(seed: int, shapes, time_step=(0.001, 0.1, 1e-4),
                 stack: bool = False):
    """A tree like ``shapes`` (reference/ssd_lm.py::param_shapes), from
    ``seed``; ``time_step``: the configuration's (min, max, floor);
    ``stack``: in the program's form (``weights_lm.stacked``), the same
    numbers."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        tree = jax.tree_util.tree_unflatten(treedef, [
            _draw(jax.random.fold_in(key, i), _leaf_name(path), leaf.shape,
                  time_step)
            for i, (path, leaf) in enumerate(leaves)])
        for block in tree.values():
            if isinstance(block, dict) and "ssd" in block:
                heads = block["ssd"]["A_log"].shape[0]
                w = block["ssd"]["in_proj"]
                block["ssd"]["in_proj"] = jnp.concatenate(
                    [w[:, :-heads], DT_SPREAD * w[:, -heads:]], axis=1)
        return weights_lm.stacked(tree) if stack else tree

    return jax.jit(build)(jax.random.PRNGKey(seed))
