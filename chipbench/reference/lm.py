"""Decoder-only language model with a layer pattern, training loss, float32.

The layer of SmallThinker-21BA3B-Instruct as ISSUE 26 writes it down
(config: huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct), for the
share of a deployment that one chip holds. ``x`` is a layer's input:

    n   = rmsnorm(x; norm_in)
    r   = n @ W_r                       router logits, read BEFORE attention
    q, k, v = n @ W_q, n @ W_k, n @ W_v            no bias
    rotary positions on q, k where the layer's ``rope_layout`` is 1
    key j is seen by query i  iff  0 <= i - j  and  (the layer is global
                                        or  i - j < sliding_window_size)
    a   = softmax(q k^T / sqrt(head_dim) over seen keys) v ; query head h
          reads K/V head h // (heads / kv heads)
    x1  = x + concat_heads(a) @ W_o
    m   = rmsnorm(x1; norm_post)
    S   = the top_k largest of r ;  w = softmax(r[S])
    y   = sum over e in S that is HELD of w_e (relu(m W_gate_e) * (m W_up_e)) W_down_e
    out = x1 + y

then a last rmsnorm and the untied head over the held rows of the
vocabulary; the loss is the mean next-token cross-entropy over those rows.
Every held expert is applied to every token and weighed by the routing
(zero where the token did not choose it); attention is explicit scores
under the ``iff`` rule. Heads, experts and the head's rows are walked in
blocks whose intermediates are recomputed in the backward pass, so that a
row of 8192 tokens fits one chip. Parameters are a nested dict named as
the program's flax model names them, except that an expert's three
matrices are leaves of their own (``h0/experts/gate/e03``): a lost expert
then shows as a leaf, not as a sixteenth of one.

Departures from the published model, each under ``assumed`` in the
configuration's file: the router reads the normed attention input; no q/k
norm and no projection bias; the window counts the current token; no
auxiliary loss; rotary pairs are a head's two halves.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import precision

#: rows of the head's logits made at a time
HEAD_BLOCK = 1024


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (S, H, D): positions 0..S-1, pairs (x[i], x[i + D/2])."""
    s, d = x.shape[0], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(mode, p, n, *, window, theta, group):
    """n (S, d) -> (S, d): one row's causal self attention."""
    proj = lambda name: precision.einsum(  # noqa: E731
        mode, "sd,dhk->shk", n, p[name]["kernel"])
    q, k, v = proj("q"), proj("k"), proj("v")
    if theta is not None:
        q, k = _rope(q, theta), _rope(k, theta)
    s, d_head = q.shape[0], q.shape[-1]
    diff = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = diff >= 0
    if window is not None:
        seen &= diff < window

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv                                     # (S, D) each
        scores = precision.einsum(mode, "qk,sk->qs",
                                  qh / math.sqrt(d_head), kh)
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return precision.einsum(mode, "qs,sk->qk", probs, vh)

    heads = jnp.moveaxis(q, 1, 0)                            # (H, S, D)
    ctx = jax.lax.map(head, (heads,
                             jnp.repeat(jnp.moveaxis(k, 1, 0), group, axis=0),
                             jnp.repeat(jnp.moveaxis(v, 1, 0), group, axis=0)))
    return precision.einsum(mode, "hqk,hkd->qd", ctx, p["out"]["kernel"])


def routing_weights(logits, top_k):
    """(S, E): the softmax over a token's top_k largest logits at their
    experts, 0 elsewhere."""
    top, idx = jax.lax.top_k(logits, top_k)
    return jnp.zeros_like(logits).at[
        jnp.arange(logits.shape[0])[:, None], idx].set(
            jax.nn.softmax(top, axis=-1))


def _experts(mode, p, m, weights, first):
    """Every held expert on every token, weighed: (S, d)."""
    names = sorted(p["gate"])                                # e00, e01, ...
    stack = lambda which: jnp.stack([p[which][e] for e in names])  # noqa: E731

    @jax.checkpoint
    def one(y, expert):
        gate, up, down, w = expert
        h = jax.nn.relu(precision.einsum(mode, "sd,df->sf", m, gate)) \
            * precision.einsum(mode, "sd,df->sf", m, up)
        return y + w[:, None] * precision.einsum(mode, "sf,fd->sd", h,
                                                 down), None

    held = weights[:, first:first + len(names)].T            # (held, S)
    y, _ = jax.lax.scan(one, jnp.zeros_like(m),
                        (stack("gate"), stack("up"), stack("down"), held))
    return y


def features(params, tokens, cfg, mode="float32"):
    """tokens (S,) of one row -> the last norm's output (S, d)."""
    first_id = cfg["vocab_held"][0]
    x = params["embed"]["embedding"][tokens - first_id]
    group = cfg["n_heads"] // cfg["n_kv_heads"]
    for i, (sliding, rotary) in enumerate(cfg["layers"]):
        p = params[f"h{i}"]
        n = _rms(x, p["norm_in"]["scale"], cfg["rms_eps"])
        logits = jnp.einsum("sd,de->se", n, p["router"]["kernel"],
                            precision=jax.lax.Precision.HIGHEST)
        x = x + _attention(mode, p["attn"], n,
                           window=cfg["window"] if sliding else None,
                           theta=cfg["rope_theta"] if rotary else None,
                           group=group)
        m = _rms(x, p["norm_post"]["scale"], cfg["rms_eps"])
        x = x + _experts(mode, p["experts"], m,
                         routing_weights(logits, cfg["top_k"]),
                         cfg["experts_held"][0])
    return _rms(x, params["norm_f"]["scale"], cfg["rms_eps"])


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
    d, h, kv, k = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], \
        cfg["head_dim"]
    f, rows = cfg["expert_d_ff"], cfg["vocab_held"][1]
    each = lambda *s: {f"e{e:02d}": f32(*s)  # noqa: E731
                       for e in range(cfg["experts_held"][1])}
    out = {"embed": {"embedding": f32(rows, d)},
           "head": {"embedding": f32(rows, d)},
           "norm_f": {"scale": f32(d)}}
    for i in range(len(cfg["layers"])):
        out[f"h{i}"] = {
            "norm_in": {"scale": f32(d)}, "norm_post": {"scale": f32(d)},
            "router": {"kernel": f32(d, cfg["n_experts"])},
            "attn": {"q": {"kernel": f32(d, h, k)},
                     "k": {"kernel": f32(d, kv, k)},
                     "v": {"kernel": f32(d, kv, k)},
                     "out": {"kernel": f32(h, k, d)}},
            "experts": {"gate": each(d, f), "up": each(d, f),
                        "down": each(f, d)}}
    return out
