"""Seeded weights for the decoders with a gate on attention's output
(reference/gated_lm.py's tree), beside ``weights_lm.py``, whose rules draw
every other leaf by what it is: a projection, a feed-forward's or an
expert's matrix ~ N(0, 1/fan_in) with fan_in its first axis, the output
projection ~ N(0, 1/(heads x width)), the embedding ~ N(0, 1), the head
~ N(0, 1/d), norm scales 1 + 0.1 N (the q and k norms' among them).

Two leaves are drawn so that what they feed is not all 0.5. The gate's
projection (``h0/attn/gate/kernel``) ~ N(0, GATE_SPREAD^2 / d): gate logits
of spread 2 on a normed input, gates from ~0.1 to ~0.9 across heads and
tokens, so a program that left the gate out, or its sigmoid, changes every
head's output by a factor of its own. The router ~ N(0, ROUTER_SPREAD^2 /
d): logits of spread 1, so that a token's eight chosen of 256 score 0.88
to 0.95 (not all saturated at 1, which spread 2 would give so far into the
tail: the weights would be 2.5 / 8 each whatever the scores) and its eighth
and ninth lie ~0.07 apart in the logits (top-8 is not a coin toss).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import weights_lm
from chipbench.weights import _leaf_name

GATE_SPREAD = 2.0
ROUTER_SPREAD = 1.0


def _draw(key, name: str, shape):
    parts = name.split("/")
    spread = GATE_SPREAD if parts[1:3] == ["attn", "gate"] \
        else ROUTER_SPREAD if parts[1] == "router" else None
    if spread is None:
        return weights_lm._draw(key, name, shape)
    return jax.random.normal(key, shape, jnp.float32) * spread \
        / math.sqrt(shape[0])


def make_weights(seed: int, shapes, stack: bool = False):
    """A tree like ``shapes`` (reference/gated_lm.py::param_shapes), from
    ``seed``; ``stack``: in the program's form (``weights_lm.stacked``), the
    same numbers."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        tree = jax.tree_util.tree_unflatten(treedef, [
            _draw(jax.random.fold_in(key, i), _leaf_name(path), leaf.shape)
            for i, (path, leaf) in enumerate(leaves)])
        return weights_lm.stacked(tree) if stack else tree

    return jax.jit(build)(jax.random.PRNGKey(seed))
