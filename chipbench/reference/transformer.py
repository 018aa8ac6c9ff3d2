"""Encoder-decoder Transformer, training loss, float32.

Vaswani et al. 2017 (arXiv:1706.03762) with the arrangement the program
documents: pre-layer-norm residual blocks (as tensor2tensor's
transformer_base has them), learned positions, queries scaled by
1/sqrt(d_head), projections with biases, ReLU feed-forward, a final layer
norm on each stack, the readout tied to the embedding table, targets
shifted right behind a BOS token (id 1), padding id 0 masked out of
attention and of the token-mean loss. No dropout: a plain reference
cannot repeat the program's masks, so the configuration it checks runs
without. Parameters are a nested dict named as the program's flax model
names them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import precision


def _ln(x, p, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _mha(mode, p, q_in, kv_in, mask):
    d_head = p["q"]["kernel"].shape[-1]
    proj = lambda n, x: precision.einsum(  # noqa: E731
        mode, "bsd,dhk->bshk", x, p[n]["kernel"]) + p[n]["bias"]
    q = proj("q", q_in) / math.sqrt(d_head)
    k, v = proj("k", kv_in), proj("v", kv_in)
    scores = precision.einsum(mode, "bqhk,bshk->bhqs", q, k)
    scores = jnp.where(mask, scores, -1e30)
    ctx = precision.einsum(mode, "bhqs,bshk->bqhk",
                           jax.nn.softmax(scores, axis=-1), v)
    return precision.einsum(mode, "bqhk,hkd->bqd", ctx, p["out"]["kernel"]) \
        + p["out"]["bias"]


def _ffn(mode, p, x):
    h = jax.nn.relu(precision.einsum(mode, "bsd,df->bsf", x,
                                     p["wi"]["kernel"]) + p["wi"]["bias"])
    return precision.einsum(mode, "bsf,fd->bsd", h, p["wo"]["kernel"]) \
        + p["wo"]["bias"]


def logits(params, src, tgt_in, *, n_layers: int, mode: str = "float32"):
    emb = params["embed"]["embedding"].astype(jnp.float32)
    pos = params["pos_embed"].astype(jnp.float32)
    s_len, t_len = src.shape[1], tgt_in.shape[1]
    src_pad = (src != 0)[:, None, None, :]
    causal = jnp.tril(jnp.ones((t_len, t_len), bool))[None, None]
    causal_mask = causal & (tgt_in != 0)[:, None, None, :]

    x = emb[src] + pos[None, :s_len]
    for i in range(n_layers):
        p = params[f"enc{i}"]
        y = _ln(x, p["ln1"])
        x = x + _mha(mode, p["self_attn"], y, y, src_pad)
        x = x + _ffn(mode, p["mlp"], _ln(x, p["ln2"]))
    enc = _ln(x, params["enc_ln"])

    y = emb[tgt_in] + pos[None, :t_len]
    for i in range(n_layers):
        p = params[f"dec{i}"]
        h = _ln(y, p["ln1"])
        y = y + _mha(mode, p["self_attn"], h, h, causal_mask)
        y = y + _mha(mode, p["cross_attn"], _ln(y, p["ln2"]), enc, src_pad)
        y = y + _ffn(mode, p["mlp"], _ln(y, p["ln3"]))
    y = _ln(y, params["dec_ln"])
    return precision.einsum(mode, "btd,vd->btv", y, emb)


def shift_right(tgt):
    """The decoder's input: BOS (id 1), then the targets but the last."""
    bos = jnp.ones((tgt.shape[0], 1), tgt.dtype)
    return jnp.concatenate([bos, tgt[:, :-1]], axis=1)


def loss(params, src, tgt, *, n_layers: int, mode: str = "float32"):
    lg = logits(params, src, shift_right(tgt), n_layers=n_layers, mode=mode)
    logp = jax.nn.log_softmax(lg, axis=-1)
    tok = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    mask = (tgt != 0).astype(jnp.float32)
    return jnp.sum(tok * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def param_shapes(*, vocab: int, d_model: int, n_heads: int, n_layers: int,
                 d_ff: int, max_len: int):
    """The tree of float32 shapes ``logits`` reads."""
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    d, h, k = d_model, n_heads, d_model // n_heads
    ln = lambda: {"scale": f32(d), "bias": f32(d)}  # noqa: E731
    proj = lambda: {"kernel": f32(d, h, k), "bias": f32(h, k)}  # noqa: E731
    mha = lambda: {"q": proj(), "k": proj(), "v": proj(),  # noqa: E731
                   "out": {"kernel": f32(h, k, d), "bias": f32(d)}}
    ffn = lambda: {"wi": {"kernel": f32(d, d_ff), "bias": f32(d_ff)},  # noqa: E731
                   "wo": {"kernel": f32(d_ff, d), "bias": f32(d)}}
    out = {"embed": {"embedding": f32(vocab, d)}, "pos_embed": f32(max_len, d),
           "enc_ln": ln(), "dec_ln": ln()}
    for i in range(n_layers):
        out[f"enc{i}"] = {"ln1": ln(), "self_attn": mha(), "ln2": ln(),
                          "mlp": ffn()}
        out[f"dec{i}"] = {"ln1": ln(), "self_attn": mha(), "ln2": ln(),
                          "cross_attn": mha(), "ln3": ln(), "mlp": ffn()}
    return out
