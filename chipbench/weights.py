"""Weights made by the benchmark from the seed, in one jitted call.

The output check feeds the same weights to the program's model and to
the plain reference, so neither takes anything the other has made. Given
a tree of shapes (``jax.eval_shape`` of the program's init), every leaf
is drawn on the device from ``fold_in(seed, position)``:
kernels ~ N(0, 1/fan_in), scales 1 + 0.1 N, biases 0.02 N. No scale is
zero, so every branch of the model takes part in the output.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _leaf_name(path) -> str:
    keys = [getattr(p, "key", None) for p in path]
    return "/".join(str(k) for k in keys if k is not None)


def _draw(key, name: str, shape, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    last = name.rsplit("/", 1)[-1]
    if last == "scale":
        out = 1.0 + 0.1 * z
    elif last == "bias" or len(shape) < 2:
        out = 0.02 * z
    elif last in ("embedding", "pos_embed"):
        out = z / math.sqrt(shape[-1])
    else:
        projected = name.rsplit("/", 2)[-2] in ("q", "k", "v")
        fan_in = shape[0] if projected else math.prod(shape[:-1])
        out = z / math.sqrt(fan_in)
    return out.astype(dtype)


def make_weights(seed: int, shapes):
    """A tree like ``shapes`` (plain, unboxed), filled from ``seed``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        return [
            _draw(jax.random.fold_in(key, i), _leaf_name(path), leaf.shape,
                  leaf.dtype)
            for i, (path, leaf) in enumerate(leaves)
        ]

    out = jax.jit(build)(jax.random.PRNGKey(seed))
    return jax.tree_util.tree_unflatten(treedef, out)
