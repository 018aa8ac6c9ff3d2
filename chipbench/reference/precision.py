"""The arithmetic the references are computed in.

``float32``: every product at matmul precision ``highest`` (on a TPU a
float32 product otherwise runs in bfloat16 passes). ``fp8``: the control
of a configuration that states bfloat16 -- each operand of every product
is rounded to 4 exponent and 3 mantissa bits (float8 e4m3) under one
scale per tensor (max |x| -> 240, that format's largest finite value)
(the backward pass multiplies float32 cotangents by those rounded
operands), the step below bfloat16 that would tempt a later PR; sums stay
float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MODES = ("float32", "fp8")
_HI = jax.lax.Precision.HIGHEST


def _fp8(x):
    """Round to e4m3 going forward; the gradient passes straight through
    (a cotangent cast to float8 without a scale of its own underflows to
    zero, which would make the control fail for the wrong reason)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
    # reduce_precision, not a cast there and back: the TPU compiler drops
    # such a pair of converts (xla_allow_excess_precision), and the control
    # then computes in float32 (seen on the chip, PR 23)
    rounded = jax.lax.reduce_precision(x / scale, 4, 3) * scale
    return x + jax.lax.stop_gradient(rounded - x)


def _operands(mode, a, b):
    if mode not in MODES:
        raise ValueError(f"unknown reference precision {mode!r}")
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return (_fp8(a), _fp8(b)) if mode == "fp8" else (a, b)


def einsum(mode: str, eq: str, a, b):
    a, b = _operands(mode, a, b)
    return jnp.einsum(eq, a, b, precision=_HI)
