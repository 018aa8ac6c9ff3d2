"""The Pallas side of the held experts' part (models/moe.py): what runs
between dispatch and combine on a TPU, over buffers of ``tokens x k`` rows
of which the held experts fill the first ``filled``.

- :func:`gmm`: megablox's grouped product, as the layer calls it; it
  visits the tiles that hold a group's rows and no other.
- :func:`gating` and :func:`gating_bwd`: ``h = act(gate) * up`` from the
  joined product ``gu`` (n, 2f) and ``d_gu`` from ``d_h`` and ``gu``, a
  program a tile of rows, the grid as long as the filled rows need: a tile
  past ``filled`` is not visited and its output rows hold what the memory
  held. In float32 from bfloat16 operands, rounded once.
- :func:`activation` and :func:`activation_bwd`: the same pass for experts
  that are not gated, ``h = act(u)`` over the one product (n, f).
- :func:`tgmm`: a group's weight gradient ``lhs[rows].T @ rhs[rows]``,
  megablox's too, from both operands as they lie, (rows, width) row-major.

Every function takes ``interpret`` for the tests; nothing here decides a
route (models/moe.py::grouped_matmul_impl does, from the backend and the
shapes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox import ops as _megablox_ops

#: megablox's kernels without its VJP (the package's ``gmm`` is the
#: function, not this module)
megablox = _megablox_ops.backend


def gmm(x, w, group_sizes, tiling, *, transpose_rhs=False, interpret=False):
    """``x`` (m, k) rows ordered by group, ``w`` (g, k, n), or (g, n, k)
    where ``transpose_rhs``, ``group_sizes`` (g,) int32 -> (m, n) in x's
    dtype, float32 sums: row r of group e times ``w[e]``, in tiles of
    ``tiling`` = (rows, k, n). Rows past ``sum(group_sizes)`` hold nothing
    a caller may read."""
    return megablox.gmm(x, w, group_sizes, x.dtype, tiling,
                        transpose_rhs=transpose_rhs, interpret=interpret)


def _gating_kernel(gu_ref, h_ref, *, activation):
    f = h_ref.shape[1]
    gate = gu_ref[:, :f].astype(jnp.float32)
    up = gu_ref[:, f:].astype(jnp.float32)
    h_ref[...] = (activation(gate) * up).astype(h_ref.dtype)


def _gating_bwd_kernel(d_h_ref, gu_ref, d_gu_ref, *, activation):
    f = d_h_ref.shape[1]
    gate = gu_ref[:, :f].astype(jnp.float32)
    up = gu_ref[:, f:].astype(jnp.float32)
    d_h = d_h_ref[...].astype(jnp.float32)
    act, back = jax.vjp(activation, gate)
    d_gu_ref[:, :f] = back(d_h * up)[0].astype(d_gu_ref.dtype)
    d_gu_ref[:, f:] = (d_h * act).astype(d_gu_ref.dtype)


def _over_filled_tiles(kernel, name, filled, tile, operands, width, interpret):
    """``kernel`` over row tiles of ``operands`` (each (n, its width)), the
    tiles that hold a row under ``filled`` and no other -> (n, width)."""
    n = operands[0].shape[0]
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, width), operands[0].dtype),
        grid=(pl.cdiv(filled, tile),),
        in_specs=[pl.BlockSpec((tile, x.shape[1]), lambda i: (i, 0))
                  for x in operands],
        out_specs=pl.BlockSpec((tile, width), lambda i: (i, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(*operands)


def gating(gu, filled, activation, tile: int, *, interpret=False):
    """(n, f): ``activation(gu[:, :f]) * gu[:, f:]`` in the rows of the
    tiles that hold a row under ``filled`` (() int32); ``tile`` divides n
    and f is whole lanes."""
    return _over_filled_tiles(
        functools.partial(_gating_kernel, activation=activation),
        "expert_gating", filled, tile, (gu,), gu.shape[1] // 2, interpret)


def gating_bwd(d_h, gu, filled, activation, tile: int, *, interpret=False):
    """(n, 2f): the gradient of :func:`gating` to ``gu`` from ``d_h``
    (n, f), in the same tiles."""
    return _over_filled_tiles(
        functools.partial(_gating_bwd_kernel, activation=activation),
        "expert_gating_bwd", filled, tile, (d_h, gu), gu.shape[1], interpret)


def _activation_kernel(u_ref, h_ref, *, activation):
    h_ref[...] = activation(u_ref[...].astype(jnp.float32)).astype(
        h_ref.dtype)


def _activation_bwd_kernel(d_h_ref, u_ref, d_u_ref, *, activation):
    _, back = jax.vjp(activation, u_ref[...].astype(jnp.float32))
    d_u_ref[...] = back(d_h_ref[...].astype(jnp.float32))[0].astype(
        d_u_ref.dtype)


def activation(u, filled, act, tile: int, *, interpret=False):
    """(n, f): ``act(u)`` of experts that are not gated, in the rows
    of the tiles that hold a row under ``filled``; ``tile`` divides n, f is
    the whole width (no whole lanes needed)."""
    return _over_filled_tiles(
        functools.partial(_activation_kernel, activation=act),
        "expert_activation", filled, tile, (u,), u.shape[1], interpret)


def activation_bwd(d_h, u, filled, act, tile: int, *, interpret=False):
    """(n, f): the gradient of :func:`activation` to ``u`` from ``d_h``, in
    the same tiles."""
    return _over_filled_tiles(
        functools.partial(_activation_bwd_kernel, activation=act),
        "expert_activation_bwd", filled, tile, (d_h, u), u.shape[1],
        interpret)


def tgmm(lhs, rhs, group_sizes, tiling, *, interpret=False):
    """(g, k, n) in lhs's dtype: ``lhs[rows of group e].T @ rhs[rows of
    group e]``, float32 sums, zeros for an empty group, in tiles of
    ``tiling`` = (rows, k, n). ``lhs`` (m, k) and ``rhs`` (m, n) are read
    row-major, rows ordered by group: megablox's ``tgmm`` asks for (k, m)
    and turns it back before its kernel, which transposes a tile on chip,
    so XLA cancels the pair and no copy of a buffer is made. Rows of no
    group are selected away before the product, whatever they hold."""
    return megablox.tgmm(lhs.swapaxes(0, 1), rhs, group_sizes, lhs.dtype,
                         tiling, interpret=interpret)
