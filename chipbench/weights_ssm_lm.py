"""Seeded weights for the decoder-hybrid-decoders with state-space layers
(reference/ssm_lm.py's tree), beside ``weights_lm.py``, whose rules draw
the leaves the families share: a projection or a feed-forward's matrix
~ N(0, 1/fan_in) with fan_in its first axis, norm scales 1 + 0.1 N. This
family's own:

- the TIED table ~ N(0, 1/d): it is the head, whose logits then have size
  1 on a normed stream; as the embedding it starts the residual stream
  small, which the blocks' pre-norms rescale;
- norm biases and projection biases ~ 0.1 N, so that a bias left out shows;
- ``A_log`` = log(n + 1) a state (Mamba's published initialiser: A[d, n] =
  n + 1, decays from exp(-Delta) to exp(-16 Delta));
- ``dt_proj``'s bias = softplus^-1(dt), log dt ~ U(log 1e-3, log 1e-1), its
  kernel ~ N(0, DT_SPREAD^2 / rank): on delta of size ~1 (x_proj on a
  silu'd x) the step's input then spreads by ~0.5 around the bias, a
  token's step by e^+-0.5 around its channel's;
- ``D`` = 1 + 0.1 N (the published 1, spread so that its gradient is a
  leaf's and not a constant's);
- the convolution's taps and bias ~ U(-1/2, 1/2) (a depthwise conv's
  default at 4 taps);
- the four lambda vectors ~ N(0, 0.1^2) (the differential transformer's
  initialiser): lambda starts at lambda_init + O(0.06);
- the output projections (attention's, the mixer's, the memory unit's)
  ~ N(0, 1/fan_in) over the axes they contract.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import weights_lm
from chipbench.weights import _leaf_name

DT_SPREAD = 0.5


def _draw(key, name: str, shape):
    parts = name.split("/")
    leaf = parts[-1]
    z = lambda: jax.random.normal(key, shape, jnp.float32)  # noqa: E731
    if parts[0] == "embed":
        return z() / math.sqrt(shape[-1])
    if leaf == "A_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape)
    if leaf == "D":
        return 1.0 + 0.1 * z()
    if leaf in ("conv", "conv_bias"):
        return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
    if leaf.startswith("lambda_"):
        return 0.1 * z()
    if parts[-2:] == ["dt_proj", "bias"]:
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if parts[-2:] == ["dt_proj", "kernel"]:
        return z() * DT_SPREAD / math.sqrt(shape[0])
    if leaf == "bias":
        return 0.1 * z()
    if parts[-2:] == ["out", "kernel"]:
        return z() / math.sqrt(shape[0] * shape[1])
    return weights_lm._draw(key, name, shape)


def make_weights(seed: int, shapes):
    """A tree like ``shapes`` (reference/ssm_lm.py::param_shapes), from
    ``seed``; the program's tree has the same form."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _draw(jax.random.fold_in(key, i), _leaf_name(path), leaf.shape)
            for i, (path, leaf) in enumerate(leaves)])

    return jax.jit(build)(jax.random.PRNGKey(seed))
