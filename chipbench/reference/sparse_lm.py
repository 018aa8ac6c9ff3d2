"""Decoder-only language model whose attention runs over the keys an
indexer selects, training loss, float32.

The layer of Keye-VL-2.0-30B-A3B's language model as ISSUE 30 writes it
down (config: huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B), for the share
of a deployment that one chip holds. ``x`` is a layer's input, (S, d):

    n    = rmsnorm(x; norm_in)
    q, k, v = n @ W_q, n @ W_k, n @ W_v          no bias
    q, k = rmsnorm(q; q_norm), rmsnorm(k; k_norm)  over a head's width
    rotary positions 0..S-1 on q, k
    -- the indexer, on stop_gradient(n) --
    qI   = n @ W_qI   (S, 16, 64) ;  kI = layernorm(n @ W_kI)   (S, 64)
    rotary positions on qI, kI ;  w = (n @ W_w) * 16**-0.5 * 64**-0.5
    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])           for s <= t
    S_t  = the min(top_keys, t + 1) keys s <= t with the largest I[t, s],
           ties to the lower index (jax.lax.top_k on the whole row)
    a    = softmax(q k^T / sqrt(head_dim) over S_t) v ; query head h reads
           K/V head h // (heads / kv heads)
    x1   = x + concat_heads(a) @ W_o
    m    = rmsnorm(x1; norm_post)
    r    = m @ W_r                     router logits, read AFTER attention
    T    = the top_k largest of r ;  p = softmax(r[T])
    y    = sum over e in T that is HELD of p_e (act(m W_gate_e) * (m W_up_e)) W_down_e
    out  = x1 + y

then a last rmsnorm and the untied head over the held rows of the
vocabulary; the loss is the mean next-token cross-entropy over those rows.
Every held expert is applied to every token and weighed by the routing;
attention is explicit scores under the selected set, made dense. The
selection is made in blocks of query rows, heads and query blocks of the
attention and the head's rows are walked in blocks whose intermediates are
recomputed in the backward pass, so that a row of 16 384 tokens fits one
chip. The index scores and the router's logits are float32 at matmul
precision highest in every ``mode``: the control lowers the precision the
configuration states for the other products, not theirs.

Parameters are a nested dict named as the program's flax model names them,
except that an expert's three matrices are leaves of their own
(``h0/experts/gate/e03``). The indexer's (``h0/attn/indexer``) get no
gradient: ``loss`` is differentiated with respect to the tree
``trained(params)`` leaves out.

Departures from the published model, each under ``assumed`` in the
configuration's file: q/k norms and the router's placement and softmax as
in the Qwen3-MoE code; the indexer as DeepSeek-V3.2-Exp publishes it, its
input the normed hidden state, rotary over its whole width; the chunk sizes
of ``sa_config`` without effect; text-only positions; no auxiliary loss.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import precision
from chipbench.reference.lm import (HEAD_BLOCK, _rms, _rope,  # noqa: F401
                                    routing_weights)

_HI = jax.lax.Precision.HIGHEST
#: query rows the selection and a head's attention are made for at a time
ROW_BLOCK = 512
_ACT = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def _layernorm(x, p, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _blocks(s: int) -> int:
    return math.gcd(s, ROW_BLOCK)


def index_operands(p, n, cfg):
    """(qI (S, H, K), kI (S, K), w (S, H)) from the normed hidden state."""
    theta = cfg["rope_theta"]
    q = _rope(jnp.einsum("sd,dhk->shk", n, p["q"]["kernel"], precision=_HI),
              theta)
    k = _rope(_layernorm(jnp.einsum("sd,dk->sk", n, p["k"]["kernel"],
                                    precision=_HI), p["k_norm"])[:, None],
              theta)[:, 0]
    w = jnp.einsum("sd,dh->sh", n, p["w"]["kernel"], precision=_HI) \
        * (cfg["index_heads"] ** -0.5 * cfg["index_dim"] ** -0.5)
    return q, k, w


def index_scores(q, k, w):
    """I (R, S) float32 of query rows q (R, H, K), w (R, H) against every
    key k (S, K), causal or not."""
    s = jnp.einsum("rhk,sk->rhs", q, k, precision=_HI)
    return jnp.einsum("rh,rhs->rs", w, jax.nn.relu(s), precision=_HI)


def select_keys(scores, t, top_keys: int):
    """(R, S) bool from scores (R, S) of queries at positions ``t`` (R, 1):
    the min(top_keys, t + 1) keys s <= t with the largest scores, ties to
    the lower index: ``jax.lax.top_k`` of the whole row, the keys after
    ``t`` at -inf. What it returns is made into a mask without a scatter:
    everything above the last value taken, and of the scores equal to it
    those up to the last index taken (``top_k`` takes the lower first)."""
    keys = jnp.arange(scores.shape[1])[None, :]
    scores = jnp.where(keys <= t, scores, -jnp.inf)
    top, idx = jax.lax.top_k(scores, min(top_keys, scores.shape[1]))
    least = top[:, -1:]
    last = jnp.max(jnp.where(top == least, idx, -1), axis=1, keepdims=True)
    chosen = (scores > least) | ((scores == least) & (keys <= last))
    return chosen & (keys <= t)          # a short row's fill-ins go again


def selected(p, n, cfg):
    """(S, S) bool: key s is seen by query t iff s is among the
    min(top_keys, t + 1) causal keys with the largest index scores."""
    q, k, w = index_operands(p, jax.lax.stop_gradient(n), cfg)
    s = n.shape[0]
    blk = _blocks(s)

    def block(args):
        qb, wb, t = args
        return select_keys(index_scores(qb, k, wb), t[:, None],
                           cfg["top_keys"])

    return jax.lax.map(block, (
        q.reshape(s // blk, blk, *q.shape[1:]), w.reshape(s // blk, blk, -1),
        jnp.arange(s).reshape(s // blk, blk))).reshape(s, s)


def _attention(mode, p, n, seen, *, theta, eps, group):
    """n (S, d) -> (S, d): one row's self attention under ``seen``."""
    proj = lambda name: precision.einsum(  # noqa: E731
        mode, "sd,dhk->shk", n, p[name]["kernel"])
    q, k, v = proj("q"), proj("k"), proj("v")
    q = _rope(_rms(q, p["q_norm"]["scale"], eps), theta)
    k = _rope(_rms(k, p["k_norm"]["scale"], eps), theta)
    s, d_head = q.shape[0], q.shape[-1]
    blk = _blocks(s)

    def head(qkv):
        qh, kh, vh = qkv                                     # (S, D) each

        @jax.checkpoint
        def rows(args):
            qb, mask = args                                  # (blk, D), (blk, S)
            scores = precision.einsum(mode, "qk,sk->qs",
                                      qb / math.sqrt(d_head), kh)
            probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
            return precision.einsum(mode, "qs,sk->qk", probs, vh)

        return jax.lax.map(rows, (qh.reshape(s // blk, blk, -1),
                                  seen.reshape(s // blk, blk, s))
                           ).reshape(s, -1)

    ctx = jax.lax.map(head, (jnp.moveaxis(q, 1, 0),
                             jnp.repeat(jnp.moveaxis(k, 1, 0), group, axis=0),
                             jnp.repeat(jnp.moveaxis(v, 1, 0), group, axis=0)))
    return precision.einsum(mode, "hqk,hkd->qd", ctx, p["out"]["kernel"])


def _experts(mode, p, m, weights, first, act):
    """Every held expert on every token, weighed: (S, d)."""
    names = sorted(p["gate"])                                # e00, e01, ...
    stack = lambda which: jnp.stack([p[which][e] for e in names])  # noqa: E731

    @jax.checkpoint
    def one(y, expert):
        gate, up, down, w = expert
        h = act(precision.einsum(mode, "sd,df->sf", m, gate)) \
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
    for i in range(cfg["n_layers"]):
        p = params[f"h{i}"]
        n = _rms(x, p["norm_in"]["scale"], cfg["rms_eps"])
        seen = selected(p["attn"]["indexer"], n, cfg)
        x = x + _attention(mode, p["attn"], n, seen, theta=cfg["rope_theta"],
                           eps=cfg["rms_eps"], group=group)
        m = _rms(x, p["norm_post"]["scale"], cfg["rms_eps"])
        logits = jnp.einsum("sd,de->se", m, p["router"]["kernel"],
                            precision=_HI)
        x = x + _experts(mode, p["experts"], m,
                         routing_weights(logits, cfg["top_k"]),
                         cfg["experts_held"][0], _ACT[cfg["activation"]])
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


def trained(params):
    """``params`` without the indexers: what the loss sends a gradient."""
    return {name: ({**sub, "attn": {k: v for k, v in sub["attn"].items()
                                    if k != "indexer"}}
                   if "attn" in sub else sub)
            for name, sub in params.items()}


def with_indexers(part, params):
    """``part`` (a tree as ``trained`` gives) with ``params``' indexers."""
    return {name: ({**sub, "attn": {**sub["attn"], "indexer":
                                    params[name]["attn"]["indexer"]}}
                   if "attn" in sub else sub)
            for name, sub in part.items()}


def param_shapes(cfg):
    """The tree of float32 shapes ``loss`` reads."""
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    d, h, kv, k = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], \
        cfg["head_dim"]
    ih, ik = cfg["index_heads"], cfg["index_dim"]
    f, rows = cfg["expert_d_ff"], cfg["vocab_held"][1]
    each = lambda *s: {f"e{e:02d}": f32(*s)  # noqa: E731
                       for e in range(cfg["experts_held"][1])}
    out = {"embed": {"embedding": f32(rows, d)},
           "head": {"embedding": f32(rows, d)},
           "norm_f": {"scale": f32(d)}}
    for i in range(cfg["n_layers"]):
        out[f"h{i}"] = {
            "norm_in": {"scale": f32(d)}, "norm_post": {"scale": f32(d)},
            "router": {"kernel": f32(d, cfg["n_experts"])},
            "attn": {"q": {"kernel": f32(d, h, k)},
                     "k": {"kernel": f32(d, kv, k)},
                     "v": {"kernel": f32(d, kv, k)},
                     "out": {"kernel": f32(h, k, d)},
                     "q_norm": {"scale": f32(k)}, "k_norm": {"scale": f32(k)},
                     "indexer": {"q": {"kernel": f32(d, ih, ik)},
                                 "k": {"kernel": f32(d, ik)},
                                 "w": {"kernel": f32(d, ih)},
                                 "k_norm": {"scale": f32(ik),
                                            "bias": f32(ik)}}},
            "experts": {"gate": each(d, f), "up": each(d, f),
                        "down": each(f, d)}}
    return out
