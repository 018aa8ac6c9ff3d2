"""Decoder-only language model with latent attention, a leading dense
layer, sigmoid routing and shared experts, training loss, float32.

The layer of kanana-2-30b-a3b-instruct-2601 (``model_type`` deepseek_v3)
as ISSUE 37 writes it down (config: huggingface.co/kakaocorp/
kanana-2-30b-a3b-instruct-2601), for the share of a deployment that one
chip holds. ``x`` is a layer's input, (S, d), H heads:

    n        = rmsnorm(x; norm_in)
    q        = n W_q                      (S, H, nope + rope) -> q_nope, q_pe
    c, k_pe  = split(n W_kva, [rank, rope])   k_pe (S, rope): ONE key for all heads
    kv       = rmsnorm(c; kv_a_norm) W_kvb    (S, H, nope + v) -> k_nope, v
    q_pe, k_pe = rope(q_pe), rope(k_pe)   positions 0..S-1, pairs = ADJACENT
                                          channels (2j, 2j + 1), angle
                                          pos * theta^(-2j / rope)
    s[h,t,u] = (q_nope[t,h] . k_nope[u,h] + q_pe[t,h] . k_pe[u])
               * (nope + rope)^-0.5 ,  u <= t
    x1       = x + concat_h(softmax_u(s) v[:, h]) W_o
    m        = rmsnorm(x1; norm_post)
    layer i < dense_layers:   out = x1 + (silu(m W_gate) * (m W_up)) W_down
    else:
      score  = sigmoid(m W_r)             float32, precision highest
      chosen = top_k(score + b, k)        b = choice_bias (E,), ties to the
                                          lower index
      w      = score[chosen] / (sum(score[chosen]) + 1e-20) * scale
      out    = x1 + sum over e in chosen that is HELD of w_e expert_e(m)
                  + shared(m)

expert_e and shared are gated as the dense layer is. Then a last rmsnorm
and the untied head over the held rows of the vocabulary; the loss is the
mean next-token cross-entropy over those rows. Every held expert is applied
to every token and weighed by the routing (zero where the token did not
choose it); attention is explicit scores under the causal ``u <= t``, made
dense, a head at a time and in blocks of query rows whose intermediates are
recomputed in the backward pass, so that a row of 16 384 tokens fits one
chip. The router's scores are float32 at matmul precision highest in every
``mode``: the control lowers the precision the configuration states for the
other products, not theirs.

Parameters are a nested dict named as the program's flax model names them,
except that an expert's three matrices are leaves of their own
(``h1/experts/gate/e03``). The correction bias (``h1/choice_bias``) enters
the choice alone and gets no gradient: ``loss`` is differentiated with
respect to the tree ``trained(params)`` leaves it out of.

Departures from the published model, each under ``assumed`` in the
configuration's file: every equation above where the config gives a word
and not a formula follows DeepSeek-V3's published modelling code; the bias
is frozen and its balance update is not here; no auxiliary loss.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import precision
from chipbench.reference.lm import HEAD_BLOCK, _rms

_HI = jax.lax.Precision.HIGHEST
#: query rows a head's attention is made for at a time
ROW_BLOCK = 512
_ACT = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def rope_adjacent(x, theta):
    """x (S, H, D): positions 0..S-1, pairs (x[2j], x[2j + 1])."""
    s, d = x.shape[0], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _attention(mode, p, n, cfg):
    """n (S, d) -> (S, d): one row's latent self attention."""
    nope, rank = cfg["nope"], cfg["rank"]
    q = precision.einsum(mode, "sd,dhk->shk", n, p["q"]["kernel"])
    down = precision.einsum(mode, "sd,dk->sk", n, p["kv_a"]["kernel"])
    c, k_pe = down[:, :rank], down[:, rank:]
    kv = precision.einsum(
        mode, "sr,rhk->shk", _rms(c, p["kv_a_norm"]["scale"], cfg["rms_eps"]),
        p["kv_b"]["kernel"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = rope_adjacent(q[..., nope:], cfg["rope_theta"])
    k_pe = rope_adjacent(k_pe[:, None], cfg["rope_theta"])[:, 0]
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1) \
        * (nope + cfg["rope"]) ** -0.5
    s = n.shape[0]
    blk = math.gcd(s, ROW_BLOCK)

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv                     # (S, nope + rope), (S, nope), (S, v)
        keys = jnp.concatenate([kh, k_pe], axis=-1)

        @jax.checkpoint
        def rows(args):
            qb, t = args                                     # (blk, .), (blk,)
            scores = precision.einsum(mode, "qk,sk->qs", qb, keys)
            seen = jnp.arange(s)[None, :] <= t[:, None]
            probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
            return precision.einsum(mode, "qs,sk->qk", probs, vh)

        return jax.lax.map(rows, (qh.reshape(s // blk, blk, -1),
                                  jnp.arange(s).reshape(s // blk, blk))
                           ).reshape(s, -1)

    ctx = jax.lax.map(head, (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k_nope, 1, 0),
                             jnp.moveaxis(v, 1, 0)))
    return precision.einsum(mode, "hqk,hkd->qd", ctx, p["out"]["kernel"])


def _gated(mode, p, m, act):
    """(act(m W_gate) * (m W_up)) W_down of a feed-forward's three kernels."""
    h = act(precision.einsum(mode, "sd,df->sf", m, p["gate"]["kernel"])) \
        * precision.einsum(mode, "sd,df->sf", m, p["up"]["kernel"])
    return precision.einsum(mode, "sf,fd->sd", h, p["down"]["kernel"])


def routing_weights(logits, bias, cfg):
    """(S, E): a token's weight at each of its chosen experts, 0 elsewhere."""
    score = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(score + bias, cfg["top_k"])
    chosen = jnp.take_along_axis(score, idx, axis=1)
    if cfg["normalised"]:
        chosen = chosen / (jnp.sum(chosen, axis=1, keepdims=True) + 1e-20)
    return jnp.zeros_like(score).at[
        jnp.arange(score.shape[0])[:, None], idx].set(chosen * cfg["scale"])


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


def _layer(x, p, i, cfg, mode):
    """Layer ``i``: x (S, d) -> (S, d)."""
    act = _ACT[cfg["activation"]]
    n = _rms(x, p["norm_in"]["scale"], cfg["rms_eps"])
    x = x + _attention(mode, p["attn"], n, cfg)
    m = _rms(x, p["norm_post"]["scale"], cfg["rms_eps"])
    gated = jax.checkpoint(lambda q, y: _gated(mode, q, y, act))
    if i < cfg["dense_layers"]:
        return x + gated(p["mlp"], m)
    logits = jnp.einsum("sd,de->se", m, p["router"]["kernel"], precision=_HI)
    e = p["experts"]
    routed = _experts(mode, {k: e[k] for k in ("gate", "up", "down")}, m,
                      routing_weights(logits, p["choice_bias"], cfg),
                      cfg["experts_held"][0], act)
    return x + routed + gated(e["shared"], m)


def features(params, tokens, cfg, mode="float32"):
    """tokens (S,) of one row -> the last norm's output (S, d). A layer's
    intermediates are made again in the backward pass (its input is what
    stands): at 16 384 tokens five layers' q, keys and values in float32
    do not fit beside 9.2 GB of state."""
    x = params["embed"]["embedding"][tokens - cfg["vocab_held"][0]]
    for i in range(cfg["n_layers"]):
        x = jax.checkpoint(
            lambda x, p, i=i: _layer(x, p, i, cfg, mode))(x, params[f"h{i}"])
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


FROZEN = "choice_bias"


def trained(params):
    """``params`` without the correction biases: what the loss sends a
    gradient."""
    return {name: ({k: v for k, v in sub.items() if k != FROZEN}
                   if isinstance(sub, dict) else sub)
            for name, sub in params.items()}


def frozen(params):
    """The correction biases alone, a layer that has one."""
    return {name: {FROZEN: sub[FROZEN]} for name, sub in params.items()
            if isinstance(sub, dict) and FROZEN in sub}


def with_frozen(part, biases):
    """``part`` (a tree as ``trained`` gives) with ``biases`` (``frozen``)."""
    return {name: ({**sub, **biases[name]} if name in biases else sub)
            for name, sub in part.items()}


def param_shapes(cfg):
    """The tree of float32 shapes ``loss`` reads."""
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    d, h = cfg["d_model"], cfg["n_heads"]
    f, rows = cfg["expert_d_ff"], cfg["vocab_held"][1]
    each = lambda *s: {f"e{e:02d}": f32(*s)  # noqa: E731
                       for e in range(cfg["experts_held"][1])}
    gated = lambda width: {"gate": {"kernel": f32(d, width)},  # noqa: E731
                           "up": {"kernel": f32(d, width)},
                           "down": {"kernel": f32(width, d)}}
    out = {"embed": {"embedding": f32(rows, d)},
           "head": {"embedding": f32(rows, d)},
           "norm_f": {"scale": f32(d)}}
    for i in range(cfg["n_layers"]):
        layer = out[f"h{i}"] = {
            "norm_in": {"scale": f32(d)}, "norm_post": {"scale": f32(d)},
            "attn": {"q": {"kernel": f32(d, h, cfg["nope"] + cfg["rope"])},
                     "kv_a": {"kernel": f32(d, cfg["rank"] + cfg["rope"])},
                     "kv_a_norm": {"scale": f32(cfg["rank"])},
                     "kv_b": {"kernel": f32(cfg["rank"], h,
                                            cfg["nope"] + cfg["v_dim"])},
                     "out": {"kernel": f32(h, cfg["v_dim"], d)}}}
        if i < cfg["dense_layers"]:
            layer["mlp"] = gated(cfg["d_ff"])
        else:
            layer.update(
                router={"kernel": f32(d, cfg["n_experts"])},
                choice_bias=f32(cfg["n_experts"]),
                experts={"gate": each(d, f), "up": each(d, f),
                         "down": each(f, d),
                         "shared": gated(cfg["shared_d_ff"])})
    return out
