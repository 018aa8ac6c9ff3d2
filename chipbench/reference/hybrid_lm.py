"""Decoder-only language model with linear-attention and full-attention
layers, training loss, float32.

The layers of Olmo-Hybrid-7B as ISSUE 32 writes them down (config:
huggingface.co/allenai/Olmo-Hybrid-7B), for the share of a deployment that
one chip holds: ``H`` heads of each mixer, a slice of the vocabulary.
``x`` is a layer's input, (S, d):

    x1  = x + rmsnorm(mixer(x); norm_mixer)
    out = x1 + rmsnorm(ffn(x1); norm_ffn)
    ffn(y) = (silu(y W_gate) * (y W_up)) W_down

A **linear** layer's mixer, a head (key width d_k, value width d_v):

    q, k, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))
              conv: causal, depthwise, K taps a channel, no bias:
              y_t = sum_i c_i x_{t-(K-1)+i}, x before the row = 0
    q = q / sqrt(|q|^2 + 1e-6) * d_k^-1/2 ;  k = k / sqrt(|k|^2 + 1e-6)
    beta_t = 2 sigmoid(x_t W_b)
    g_t = -exp(A_log) softplus(x_t W_a + dt_bias) ;  a_t = exp(g_t)
    S_0 = 0 ;  S_t = a_t S_{t-1} + beta_t k_t (v_t - a_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t
    y_t = rmsnorm(o_t; norm) * silu(x_t W_g)       over a head's d_v
    mixer(x) = concat_heads(y) W_o

computed as written, **token by token** (a ``lax.scan`` over t, blocks of
``TOKEN_BLOCK`` tokens recomputed in the backward pass): not the chunked
algebra the program runs, so that an error in that form cannot sit on both
sides. A **full** layer's mixer: q, k, v of H heads, RMS norms of q and k
over the projected width (the heads held here together: the departure
``assumed.qk_norm`` notes), no positions, causal softmax at
head_dim^-1/2, the output projection.

Then a last rmsnorm and the untied head over the held rows of the
vocabulary; the loss is the mean next-token cross-entropy over those rows.
The gates' two projections and the recurrence itself are float32 in every
``mode``; the control rounds the recurrence's operands q, k, v as it rounds
every other product's. Parameters are a nested dict named as the program's
flax model names them. Nothing of the program is imported.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import precision
from chipbench.reference.lm import HEAD_BLOCK, _rms

_HI = jax.lax.Precision.HIGHEST
#: tokens of the recurrence kept as one block of the backward pass
TOKEN_BLOCK = 64
_ACT = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def short_conv(x, taps):
    """x (S, H, W), taps (K, H, W): y_t = sum_i taps[i] x_{t-(K-1)+i}."""
    k, s = taps.shape[0], x.shape[0]
    x = jnp.concatenate([jnp.zeros((k - 1,) + x.shape[1:], x.dtype), x])
    return sum(taps[i] * x[i:i + s] for i in range(k))


def recurrence(q, k, v, g, beta):
    """o (S, H, d_v) of the gated delta rule, a token at a time, from q, k
    (S, H, d_k), v (S, H, d_v), g and beta (S, H)."""
    s, h, dk = q.shape
    blk = math.gcd(s, TOKEN_BLOCK)

    def token(state, x):
        qt, kt, vt, gt, bt = x
        kept = jnp.exp(gt)[:, None, None] * state          # (H, d_k, d_v)
        seen = jnp.einsum("hkv,hk->hv", kept, kt, precision=_HI)
        state = kept + jnp.einsum("hk,hv->hkv", bt[:, None] * kt, vt - seen,
                                  precision=_HI)
        return state, jnp.einsum("hkv,hk->hv", state, qt, precision=_HI)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    _, o = jax.lax.scan(
        block, jnp.zeros((h, dk, v.shape[-1]), jnp.float32),
        jax.tree.map(lambda x: x.reshape(s // blk, blk, *x.shape[1:]),
                     (q, k, v, g, beta)))
    return o.reshape(s, h, -1)


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)


def gated_norm(o, z, scale, eps):
    """rmsnorm(o; scale) * silu(z), over a head's value width."""
    return _rms(o, scale, eps) * jax.nn.silu(z)


def _linear_mixer(mode, p, x, cfg):
    proj = lambda name: precision.einsum(  # noqa: E731
        mode, "sd,dhk->shk", x, p[name]["kernel"])
    mixed = lambda name: jax.nn.silu(short_conv(  # noqa: E731
        proj(name), p["conv_" + name]))
    gate = lambda name: jnp.einsum(  # noqa: E731
        "sd,dh->sh", x, p[name]["kernel"], precision=_HI)
    q = _unit(mixed("q")) * cfg["key_dim"] ** -0.5
    k, v = _unit(mixed("k")), mixed("v")
    beta = jax.nn.sigmoid(gate("b")) * (2.0 if cfg["neg_eigval"] else 1.0)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(gate("a") + p["dt_bias"])
    if mode != "float32":
        q, k, v = precision._fp8(q), precision._fp8(k), precision._fp8(v)
    y = gated_norm(recurrence(q, k, v, g, beta), proj("g"),
                   p["norm"]["scale"], cfg["rms_eps"])
    return precision.einsum(mode, "shk,hkd->sd", y, p["out"]["kernel"])


def _full_mixer(mode, p, x, cfg):
    proj = lambda name: precision.einsum(  # noqa: E731
        mode, "sd,dhk->shk", x, p[name]["kernel"])
    q, k, v = proj("q"), proj("k"), proj("v")
    s, h, d_head = q.shape
    whole = lambda y, name: _rms(  # noqa: E731
        y.reshape(s, -1), p[name]["scale"], cfg["rms_eps"]).reshape(s, h, -1)
    q, k = whole(q, "q_norm"), whole(k, "k_norm")
    seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv                                     # (S, D) each
        scores = precision.einsum(mode, "qk,sk->qs",
                                  qh / math.sqrt(d_head), kh)
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return precision.einsum(mode, "qs,sk->qk", probs, vh)

    ctx = jax.lax.map(head, tuple(jnp.moveaxis(y, 1, 0) for y in (q, k, v)))
    return precision.einsum(mode, "hqk,hkd->qd", ctx, p["out"]["kernel"])


def _ffn(mode, p, x, act):
    mm = lambda eq, a, name: precision.einsum(  # noqa: E731
        mode, eq, a, p[name]["kernel"])
    return mm("sf,fd->sd", act(mm("sd,df->sf", x, "gate"))
              * mm("sd,df->sf", x, "up"), "down")


def features(params, tokens, cfg, mode="float32"):
    """tokens (S,) of one row -> the last norm's output (S, d)."""
    x = params["embed"]["embedding"][tokens - cfg["vocab_held"][0]]
    eps = cfg["rms_eps"]

    def layer(x, p, linear):
        mixer = _linear_mixer(mode, p["linear"], x, cfg) if linear \
            else _full_mixer(mode, p["attn"], x, cfg)
        x = x + _rms(mixer, p["norm_mixer"]["scale"], eps)
        return x + _rms(_ffn(mode, p["mlp"], x, _ACT[cfg["activation"]]),
                        p["norm_ffn"]["scale"], eps)

    for i, linear in enumerate(cfg["linear"]):
        x = jax.checkpoint(layer, static_argnums=(2,))(
            x, params[f"h{i}"], linear)
    return _rms(x, params["norm_f"]["scale"], eps)


def loss(params, rows, cfg, mode="float32"):
    """Mean next-token cross-entropy over ``rows`` (B, S + 1) of ids inside
    the held slice."""
    head = params["head"]["embedding"]
    first_id = cfg["vocab_held"][0]

    @jax.checkpoint
    def block(args):
        feats, labels = args
        logp = jax.nn.log_softmax(
            precision.einsum(mode, "sd,vd->sv", feats, head), axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]

    total = 0.0
    for b in range(rows.shape[0]):
        feats = features(params, rows[b, :-1], cfg, mode)
        labels = rows[b, 1:] - first_id
        s = feats.shape[0]
        blk = math.gcd(s, HEAD_BLOCK)
        total = total + jnp.sum(jax.lax.map(
            block, (feats.reshape(s // blk, blk, -1),
                    labels.reshape(s // blk, blk))))
    return total / (rows.shape[0] * (rows.shape[1] - 1))


def param_shapes(cfg):
    """The tree of float32 shapes ``loss`` reads."""
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    d, f, rows = cfg["d_model"], cfg["d_ff"], cfg["vocab_held"][1]
    h, w = cfg["n_heads"], cfg["head_dim"]
    lh, dk, dv, taps = cfg["linear_heads"], cfg["key_dim"], \
        cfg["value_dim"], cfg["conv"]
    out = {"embed": {"embedding": f32(rows, d)},
           "head": {"embedding": f32(rows, d)},
           "norm_f": {"scale": f32(d)}}
    for i, linear in enumerate(cfg["linear"]):
        layer = {"norm_mixer": {"scale": f32(d)},
                 "norm_ffn": {"scale": f32(d)},
                 "mlp": {"gate": {"kernel": f32(d, f)},
                         "up": {"kernel": f32(d, f)},
                         "down": {"kernel": f32(f, d)}}}
        if linear:
            layer["linear"] = {
                "q": {"kernel": f32(d, lh, dk)},
                "k": {"kernel": f32(d, lh, dk)},
                "v": {"kernel": f32(d, lh, dv)},
                "g": {"kernel": f32(d, lh, dv)},
                "a": {"kernel": f32(d, lh)}, "b": {"kernel": f32(d, lh)},
                "conv_q": f32(taps, lh, dk), "conv_k": f32(taps, lh, dk),
                "conv_v": f32(taps, lh, dv),
                "A_log": f32(lh), "dt_bias": f32(lh),
                "norm": {"scale": f32(dv)},
                "out": {"kernel": f32(lh, dv, d)}}
        else:
            layer["attn"] = {
                "q": {"kernel": f32(d, h, w)}, "k": {"kernel": f32(d, h, w)},
                "v": {"kernel": f32(d, h, w)},
                "q_norm": {"scale": f32(h * w)},
                "k_norm": {"scale": f32(h * w)},
                "out": {"kernel": f32(h, w, d)}}
        out[f"h{i}"] = layer
    return out
