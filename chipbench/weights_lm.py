"""Seeded weights for the pattern decoders (reference/lm.py's tree).

``weights.py::_draw`` scales a kernel by every axis but its last, which is
wrong for an expert's matrices once they are stacked. Here every leaf is
drawn by what it is: an expert's matrix, one leaf each, ~ N(0, 1/fan_in)
with fan_in its own first axis (d or f); a projection ~ N(0, 1/d), the
output projection ~ N(0, 1/(heads x width)); the embedding ~ N(0, 1) (the
residual stream then has the size the layers' outputs have), the head
~ N(0, 1/d); norm scales 1 + 0.1 N; the router ~ N(0, (ROUTER_SPREAD)^2 / d):
logits of spread 2, so that a token's sixth and seventh largest lie ~0.15
apart (top-6 is not a coin toss) and its sixth chosen expert still weighs
~6 % (all six count).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.weights import _leaf_name

ROUTER_SPREAD = 2.0


def _draw(key, name: str, shape):
    z = jax.random.normal(key, shape, jnp.float32)
    parts = name.split("/")
    if parts[-1] == "scale":
        return 1.0 + 0.1 * z
    if parts[0] == "embed":
        return z
    if parts[0] == "head":
        return z / math.sqrt(shape[-1])
    if parts[1] == "router":
        return z * ROUTER_SPREAD / math.sqrt(shape[0])
    if parts[1] == "attn" and parts[2] == "out":
        return z / math.sqrt(shape[0] * shape[1])
    return z / math.sqrt(shape[0])  # q, k, v, an expert's gate, up, down


def make_weights(seed: int, shapes, stack: bool = False):
    """A tree like ``shapes`` (reference/lm.py::param_shapes), from ``seed``;
    ``stack``: in the program's form (``stacked``), the same numbers."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        tree = jax.tree_util.tree_unflatten(treedef, [
            _draw(jax.random.fold_in(key, i), _leaf_name(path), leaf.shape)
            for i, (path, leaf) in enumerate(leaves)])
        return stacked(tree) if stack else tree

    return jax.jit(build)(jax.random.PRNGKey(seed))


def stacked(tree):
    """The program's form of the tree: an expert layer's matrices stacked
    over the held experts (``h0/experts/gate``: (held, d, f))."""
    out = {}
    for name, sub in tree.items():
        if name in ("gate", "up", "down") and isinstance(sub, dict) \
                and all(k.startswith("e") for k in sub):
            out[name] = jnp.stack([sub[e] for e in sorted(sub)])
        else:
            out[name] = stacked(sub) if isinstance(sub, dict) else sub
    return out


def split(tree):
    """The reference's form from the program's: the inverse of ``stacked``
    (works on host arrays too)."""
    out = {}
    for name, sub in tree.items():
        if isinstance(sub, dict):
            out[name] = split(sub)
        elif name in ("gate", "up", "down") and getattr(sub, "ndim", 0) == 3:
            out[name] = {f"e{e:02d}": sub[e] for e in range(sub.shape[0])}
        else:
            out[name] = sub
    return out
