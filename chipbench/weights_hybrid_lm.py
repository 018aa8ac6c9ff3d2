"""Seeded weights for the hybrid linear-attention decoders
(reference/hybrid_lm.py's tree), beside ``weights_lm.py``, whose rules draw
every leaf the two families share (projections ~ N(0, 1/d), output
projections ~ N(0, 1/(heads x width)), the embedding ~ N(0, 1), the head
~ N(0, 1/d), norm scales 1 + 0.1 N). A linear layer's own leaves are drawn
as Gated DeltaNet's published initialiser draws them, so that the decays
spread and the recurrence neither forgets everything nor nothing:

- ``A_log`` = log A, A ~ U(2^-6, 16) (the published U(0, 16), from 2^-6
  on so that the log is finite);
- ``dt_bias`` = softplus^-1(dt), log dt ~ U(log 1e-3, log 1e-1): at a zero
  input a token's decay is exp(-A dt); the seeded ``a`` projection
  (N(0, 1/d) on a stream of size 1) spreads a token's by a factor e^N(0,1);
- the convolutions' taps ~ U(-1/2, 1/2) (a depthwise conv's default at 4
  taps);
- the gates' projections ``a``, ``b`` ~ N(0, GATE_SPREAD^2 / d). A mixer
  of this family reads the residual stream itself (the norms sit on the
  branches' outputs), whose size grows to ~2.9 by the fourth layer, so at
  N(0, 1/d) a gate's input would spread by +-3 and more: decays of e^-20
  and steps of 0.01 at the same token, an output of size 1e-5, and the
  gated RMS norm (eps 1e-6) then multiplies that token's rounding by a
  thousand and lets it rule the gradient (seen at the rehearsal's size:
  PERF.md, PR 32). At 0.2 the inputs spread by ~0.5: beta over 0.5-1.5, a
  token's decay by e^+-1 around its head's.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import weights_lm
from chipbench.weights import _leaf_name

GATE_SPREAD = 0.2
#: how much of a linear layer's key path (projection and taps) is its query
#: path's: see ``make_weights``
KEY_QUERY_SHARE = 0.8


def _draw(key, name: str, shape):
    leaf = name.split("/")[-1]
    if leaf == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          2.0 ** -6, 16.0))
    if leaf == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if leaf.startswith("conv_"):
        return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
    parts = name.split("/")
    if parts[1:3] in (["linear", "a"], ["linear", "b"]):
        return jax.random.normal(key, shape, jnp.float32) \
            * GATE_SPREAD / math.sqrt(shape[0])
    if parts[1:3] == ["linear", "out"]:
        return jax.random.normal(key, shape, jnp.float32) \
            / math.sqrt(shape[0] * shape[1])
    return weights_lm._draw(key, name, shape)


def make_weights(seed: int, shapes):
    """A tree like ``shapes`` (reference/hybrid_lm.py::param_shapes), from
    ``seed``; the program's tree has the same form.

    A linear layer's key projection and key taps are ``KEY_QUERY_SHARE`` of
    its query's plus the rest of a draw of their own (the variance kept),
    so that a token's query reads its own key back: k.q ~ 0.6, as a trained
    delta-rule layer has it. With independent draws the row's FIRST token,
    whose state holds one pair, gives o_0 = beta (k_0.q_0) v_0 / sqrt(d_k),
    a random scalar of either sign times v_0: in some head of most seeds it
    is a cancellation to ~1e-3 of its terms' size, which bfloat16 operands
    get wrong by its whole size or its sign, and the gated RMS norm (eps
    1e-6) turns that into a unit vector's worth of difference at a token
    every later one attends to (seen at the tests' size: PERF.md, PR 32)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rest = math.sqrt(1.0 - KEY_QUERY_SHARE ** 2)

    def build(key):
        tree = jax.tree_util.tree_unflatten(treedef, [
            _draw(jax.random.fold_in(key, i), _leaf_name(path), leaf.shape)
            for i, (path, leaf) in enumerate(leaves)])
        for layer in tree.values():
            lin = layer.get("linear") if isinstance(layer, dict) else None
            if lin is not None:
                lin["k"]["kernel"] = KEY_QUERY_SHARE * lin["q"]["kernel"] \
                    + rest * lin["k"]["kernel"]
                lin["conv_k"] = KEY_QUERY_SHARE * lin["conv_q"] \
                    + rest * lin["conv_k"]
        return tree

    return jax.jit(build)(jax.random.PRNGKey(seed))
