"""A latent layer's hand-over to the causal kernels (attention.CausalMask).

models/lm_layers.LatentAttention makes q, one product that holds every head's
``k_nope`` and v, and ONE rotary key all heads share. The causal kernels
of ops/attention.py read feature-major ``(B, rows, S)`` operands, so on the
Pallas route the layer's matmuls leave their products in that layout and
the kernels read them where they lie:

- :func:`project_t` / :func:`contract_t`: the projections with the
  sequence on the product's (the operand's) last axis, in every direction:
  no transposed copy of an activation stands before or after a kernel.
- :func:`rotary_scaled`: from q's product to the kernels' operand in one
  pass (the Pallas call ``latent_q``): rotary on the halves of a head's
  last ``rope`` rows, the score scale and one rounding, float32 in
  registers only; its transpose is the same pass with the angle negated.
- :func:`flash_latent`: ``flash_fwd`` / ``flash_bwd`` as
  ``attention._flash_causal`` calls them, the kernel bodies the same
  functions, with block indices that take a head's ``k_nope`` and v rows
  out of the up-projection's ONE product and the shared key's rows out of
  an operand of its own; a head's keys are joined in VMEM (one ``nope +
  rope`` deep score product a tile, as with keys joined in HBM). The
  backward call writes a head's ``dk_nope`` and ``dv`` into one array, the
  product's cotangent, and the shared key's rows into another, summed over
  the heads in float32.

:func:`hand_over` is the one rule for whether a layer takes this form;
loaded by ``attention.flash_attention`` when a :class:`attention.LatentKV`
arrives and by models/lm_layers.py for a model with latent layers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from metaopt_tpu.ops.attention import (LatentKV, _call, _causal_bwd_kernel,
                                       _causal_fwd_kernel, _derived_block,
                                       _kept)
from metaopt_tpu.utils import trace


def hand_over(route: str, mesh, nope: int, v: int) -> str:
    """How a latent layer's operands reach attention on ``route``:
    ``"in place"`` (this module) on the Pallas route of one device where a
    head's ``k_nope`` and v are as wide as each other (a block index then
    names either half of the up-projection's product), else ``"copies"``:
    q, k, v built ``(B, S, H, D)`` with the shared key copied to every
    head, as the reference and the sharded kernels take them."""
    one_device = mesh is None or mesh.size == 1
    return "in place" if route == "pallas" and one_device and nope == v \
        else "copies"


# ---------------------------------------------------------------------------
# the projections, feature-major


def project_t(x, w, dimension_numbers=None, precision=None):
    """x (B, S, D) @ w (D, H, K) -> (B, H*K, S): the product as
    ``nn.DenseGeneral`` makes it, laid out by the matmul with the sequence
    last (``w`` is the left operand; for one row the move of the batch axis
    is a bitcast). Takes ``DenseGeneral``'s ``dot_general`` arguments."""
    y = jax.lax.dot_general(w, x, (((0,), (2,)), ((), ())),
                            precision=precision)           # (H, K, B, S)
    h, k, b, s = y.shape
    return jnp.moveaxis(y.reshape(h * k, b, s), 1, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def contract_t(y, w, dimension_numbers=None, precision=None):
    """y (B, H, K, S) . w (H, K, D) -> (B, S, D), and y's cotangent made
    feature-major by the matmul that makes it (autodiff would make it (B,
    S, H, K) and transpose)."""
    return jax.lax.dot_general(y, w, (((1, 2), (0, 1)), ((), ())),
                               precision=precision)


def _contract_t_fwd(y, w, dimension_numbers, precision):
    return contract_t(y, w, dimension_numbers, precision), (y, w)


def _contract_t_bwd(dimension_numbers, precision, residuals, g):
    y, w = residuals
    dy = project_t(g, w.transpose(2, 0, 1), precision=precision)
    dw = jax.lax.dot_general(y, g, (((0, 3), (0, 1)), ((), ())),
                             precision=precision)
    return dy.reshape(y.shape), dw


contract_t.defvjp(_contract_t_fwd, _contract_t_bwd)


# ---------------------------------------------------------------------------
# q: rotary, scale and rounding in one pass


def _q_kernel(x_ref, cos_ref, sin_ref, o_ref, *, nope: int, scale: float):
    """One (row, tile of the sequence, head) program. Shapes in VMEM: x, o
    (1, nope + rope, Bs); cos, sin (rope / 2, Bs) float32."""
    half = (x_ref.shape[1] - nope) // 2
    x = x_ref[0].astype(jnp.float32)
    a, b = x[nope:nope + half], x[nope + half:]
    cos, sin = cos_ref[...], sin_ref[...]
    o_ref[0, :nope] = (x[:nope] * scale).astype(o_ref.dtype)
    o_ref[0, nope:nope + half] = ((a * cos - b * sin) * scale
                                  ).astype(o_ref.dtype)
    o_ref[0, nope + half:] = ((b * cos + a * sin) * scale).astype(o_ref.dtype)


_q_jit = functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "theta", "scale", "back", "interpret"))


@_q_jit
def _q_pass(x, heads, nope, theta, scale, back, interpret):
    b, rows, s = x.shape
    width = rows // heads
    rope = width - nope
    block = next((t for t in (2048, 1024, 512, 256, 128) if s % t == 0), s)
    freq = theta ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    angle = freq[:, None] * jnp.arange(s, dtype=jnp.float32)[None]
    sin = jnp.sin(angle)
    head = pl.BlockSpec((1, width, block), lambda i, j, hh: (i, hh, j))
    table = pl.BlockSpec((rope // 2, block), lambda i, j, hh: (0, j))
    return _call(
        _q_kernel, "latent_q", (b, s // block, heads),
        ("parallel", "parallel", "parallel"), [head, table, table], [head],
        [jax.ShapeDtypeStruct(x.shape, x.dtype)], [],
        [x, jnp.cos(angle), -sin if back else sin], interpret, nope=nope,
        scale=scale)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def rotary_scaled(x, heads: int, nope: int, theta: float, scale: float,
                  interpret: bool = False):
    """x (B, heads * (nope + rope), S), q's product with a head's rotary
    pairs as the halves of its last ``rope`` rows (channels 2j at row j,
    2j + 1 at rope / 2 + j) -> the kernels' q: pair j turned by pos *
    theta^(-2j / rope), all of it times ``scale``, rounded once."""
    return _q_pass(x, heads, nope, theta, scale, False, interpret)


def _rotary_scaled_fwd(x, heads, nope, theta, scale, interpret):
    return rotary_scaled(x, heads, nope, theta, scale, interpret), None


def _rotary_scaled_bwd(heads, nope, theta, scale, interpret, _, g):
    return (_q_pass(g, heads, nope, theta, scale, True, interpret),)


rotary_scaled.defvjp(_rotary_scaled_fwd, _rotary_scaled_bwd)


# ---------------------------------------------------------------------------
# the causal kernels on operands read in place


def _join(k_ref, own_ref, shared_ref):
    """A head's keys: its own rows above the shared key's, in VMEM."""
    nope = own_ref.shape[1]
    k_ref[0, :nope] = own_ref[0]
    k_ref[0, nope:] = shared_ref[0]


def _latent_fwd_kernel(q_ref, own_ref, shared_ref, v_ref, o_ref, lse_ref,
                       k_ref, *, block_k: int):
    """``_causal_fwd_kernel`` on keys joined once a head: the q tiles of a
    head run in order (the grid's last axis is "arbitrary") and the first
    fills k (1, nope + rope, S) from own (1, nope, S) and shared (1, rope,
    S)."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        _join(k_ref, own_ref, shared_ref)

    _causal_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, block_k=block_k,
                       window=None)


def _latent_bwd_kernel(q_ref, g_ref, own_ref, shared_ref, v_ref, lse_ref,
                       delta_ref, dq_ref, dkv_ref, dshared_ref, acc_ref,
                       k_ref, dk_ref, dv_ref, *, block_q: int):
    """``_causal_bwd_kernel`` on a tile of joined keys; its float32 dk
    (1, nope + rope, Bk) and dv (1, v, Bk) are scratch here and leave as
    dkv (1, nope + v, Bk), a head's rows of the up-projection's cotangent
    in that product's dtype, and dshared (1, rope, Bk) float32."""
    nope = own_ref.shape[1]
    _join(k_ref, own_ref, shared_ref)
    _causal_bwd_kernel(q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref, dq_ref,
                       dk_ref, dv_ref, acc_ref, block_q=block_q, window=None)
    dkv_ref[0, :nope] = dk_ref[0, :nope].astype(dkv_ref.dtype)
    dkv_ref[0, nope:] = dv_ref[0].astype(dkv_ref.dtype)
    dshared_ref[0] = dk_ref[0, nope:]


_jit = functools.partial(jax.jit, static_argnames=(
    "nope", "block_q", "block_k", "interpret"))


def _sizes(q, kv, shared, nope):
    """(B, S, heads, q.k width, v width) of feature-major operands."""
    b, _, s = q.shape
    dqk = nope + shared.shape[1]
    h = q.shape[1] // dqk
    return b, s, h, dqk, kv.shape[1] // h - nope


@_jit
def _forward(q, kv, shared, nope, block_q, block_k, interpret):
    """(out (B, H*v, S), lse). q (B, H*(nope+rope), S); kv (B, H*(nope+v),
    S), a head's k_nope rows above its v rows; shared (B, rope, S)."""
    b, s, h, dqk, dv = _sizes(q, kv, shared, nope)
    at_q = lambda i, hh, j: (i, hh, j)  # noqa: E731
    return _call(
        _latent_fwd_kernel, "flash_fwd", (b, h, s // block_q),
        ("parallel", "parallel", "arbitrary"),
        [pl.BlockSpec((1, dqk, block_q), at_q),
         pl.BlockSpec((1, nope, s), lambda i, hh, j: (i, 2 * hh, 0)),
         pl.BlockSpec((1, dqk - nope, s), lambda i, hh, j: (i, 0, 0)),
         pl.BlockSpec((1, dv, s), lambda i, hh, j: (i, 2 * hh + 1, 0))],
        [pl.BlockSpec((1, dv, block_q), at_q),
         pl.BlockSpec((1, 1, 1, block_q), lambda i, hh, j: (i, hh, 0, j))],
        [jax.ShapeDtypeStruct((b, h * dv, s), q.dtype),
         jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32)],
        [jax.ShapeDtypeStruct((1, dqk, s), kv.dtype)],
        [q, kv, shared, kv], interpret, block_k=block_k)


@_jit
def _backward(q, kv, shared, out, lse, g, nope, block_q, block_k, interpret):
    """(dq, dkv, dshared), shaped and typed as q, kv and shared."""
    b, s, h, dqk, dv = _sizes(q, kv, shared, nope)
    rope = dqk - nope
    by_head = lambda x: x.reshape(b, h, -1, s)  # noqa: E731
    delta = jnp.sum(by_head(g).astype(jnp.float32)
                    * by_head(out).astype(jnp.float32), axis=2,
                    keepdims=True)                          # (B, H, 1, S)
    whole = lambda rows: pl.BlockSpec(  # noqa: E731
        (1, rows, s), lambda i, hh, j: (i, hh, 0))
    tile = lambda rows, at: pl.BlockSpec(  # noqa: E731
        (1, rows, block_k), lambda i, hh, j: (i, at(hh), j))
    stat = pl.BlockSpec((1, 1, 1, s), lambda i, hh, j: (i, hh, 0, 0))
    own = lambda hh: hh  # noqa: E731
    dq, dkv, dshared = _call(
        _latent_bwd_kernel, "flash_bwd", (b, h, s // block_k),
        ("parallel", "parallel", "arbitrary"),
        [whole(dqk), whole(dv), tile(nope, lambda hh: 2 * hh),
         tile(rope, lambda hh: 0), tile(dv, lambda hh: 2 * hh + 1), stat,
         stat],
        [whole(dqk), tile(nope + dv, own), tile(rope, own)],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct(kv.shape, kv.dtype),
         jax.ShapeDtypeStruct((b, h * rope, s), jnp.float32)],
        [(dqk, s), jax.ShapeDtypeStruct((1, dqk, block_k), kv.dtype),
         (1, dqk, block_k), (1, dv, block_k)],
        [q, g, kv, shared, kv, lse, delta], interpret, block_q=block_q)
    return dq, dkv, by_head(dshared).sum(axis=1).astype(shared.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_latent(q, kv, shared, nope, block_q, block_k, interpret):
    return _flash_latent_fwd(q, kv, shared, nope, block_q, block_k,
                             interpret)[0]


@trace.scope("attention.core")
def _flash_latent_fwd(q, kv, shared, nope, block_q, block_k, interpret):
    out, lse = _kept(*_forward(q, kv, shared, nope, block_q, block_k,
                               interpret))
    return out, (q, kv, shared, out, lse)


@trace.scope("attention.core")
def _flash_latent_bwd(nope, block_q, block_k, interpret, residuals, g):
    return _backward(*residuals, g, nope, block_q, block_k, interpret)


_flash_latent.defvjp(_flash_latent_fwd, _flash_latent_bwd)


def flash_latent(q, k: LatentKV, interpret: bool = False):
    """``attention.flash_attention`` under a ``CausalMask()`` for a latent
    layer's operands as its matmuls leave them: q (B, H*(nope+rope), S)
    rotated and scaled, ``k``; returns out (B, H*v, S). The length is
    padded to the kernels' tile (a padded key lies after every real query:
    causality hides it)."""
    s = q.shape[2]
    block, s_p = _derived_block(s)
    pad = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, s_p - s)))  # noqa: E731
    out = _flash_latent(pad(q), pad(k.kv), pad(k.shared), k.nope, block,
                        block, bool(interpret))
    return out[:, :, :s]
