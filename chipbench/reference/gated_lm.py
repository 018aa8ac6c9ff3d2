"""Decoder-only language model whose full and window layers differ in head
count and rotary rule, with a gate a head on attention's output, a leading
dense layer and sigmoid routing beside one shared expert; training loss,
float32.

The layer of Laguna-XS.2 (``model_type`` laguna) as ISSUE 45 writes it down
(config: huggingface.co/poolside/Laguna-XS.2), for the share of a deployment
that one chip holds. ``x`` is layer l's input, (S, d); H_l its query heads,
G the K/V heads, D a head's width:

    n        = rmsnorm(x; norm_in)
    q, k, v  = n W_q, n W_k, n W_v          (S, H_l, D), (S, G, D) twice; no bias
    q, k     = rmsnorm(q; q_norm), rmsnorm(k; k_norm)   over a head's D channels,
                                            one scale vector each a layer
    q, k     = R_l(q), R_l(k)               positions 0..S-1 on the FIRST
                                            ``turned`` channels of a head,
                                            pairs (j, j + turned/2), angle
                                            pos * w_j, cos and sin times
                                            ``factor``; the other channels pass
        window layers: turned = D, w_j = theta^(-2j/D), factor 1
        full layers:   turned = D/2; YaRN: w_j = theta^(-2j/turned) below pair
                       ``low``, that over ``scale`` from pair ``high`` on, a
                       straight ramp between (low, high = floor, ceil of the
                       pair that turns beta_fast, beta_slow times in the
                       original positions); factor = attention_factor
    s[h,t,u] = q[t,h] . k[u, h // (H_l / G)] / sqrt(D),  seen iff 0 <= t - u
               (< window on a window layer: the window counts the current token)
    o[t,h]   = softmax_u(s) v[:, h // (H_l / G)]
    g        = sigmoid(n W_g)               (S, H_l): float32, precision highest
    x1       = x + concat_h(g[:, h] o[:, h]) W_o
    m        = rmsnorm(x1; norm_post)
    dense layer:   out = x1 + (silu(m W_gate) * (m W_up)) W_down
    sparse layer:
      score  = sigmoid(m W_r)               float32, precision highest
      chosen = top_k(score, k)              ties to the lower index
      w      = score[chosen] / (sum(score[chosen]) + 1e-20) * scale
      out    = x1 + sum over e in chosen that is HELD of w_e expert_e(m)
                  + shared(m)

expert_e and shared are gated as the dense layer is. Then a last rmsnorm
and the untied head over the held rows of the vocabulary; the loss is the
mean next-token cross-entropy over those rows. Every held expert is applied
to every token and weighed by the routing (zero where the token did not
choose it): a loop over the held ones; attention is explicit scores under
the mask, made dense, a head at a time and in blocks of query rows whose
intermediates are recomputed in the backward pass, so that a row of 8192
tokens fits one chip. The router's scores and the gate are float32 at matmul
precision highest in every ``mode``: the control lowers the precision the
configuration states for the other products, not theirs.

Parameters are a nested dict named as the program's flax model names them,
except that an expert's three matrices are leaves of their own
(``h1/experts/gate/e03``).

Departures from the published model, each under ``assumed`` in the
configuration's file (the config gives words, not formulas): the pre-norm
block and the q/k norms are the Qwen3-MoE family's, whose words the config
speaks; ``rotate_half`` pairs and YaRN as the transformers library's
``_compute_yarn_parameters`` has it, truncation on; which half of a head
turns (the first); the window counts the current token; the gate is one
number a head, a sigmoid of a projection of the layer's normed input (what
the published 33.4 B parameters allow; the headwise form of
arXiv:2505.06708); sigmoid scores normalised over the chosen and scaled, no
correction bias; the router reads the second norm; no gate on the shared
expert; SiLU; no auxiliary loss.

``faults``: names of planted faults (tests/unit/test_lm_gated.py,
chipbench/tests/test_gated_lm_cell.py), each one departure from the
equations above that the comparison has to see.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import precision
from chipbench.reference.lm import HEAD_BLOCK, _rms
# every held expert on every token, weighed, and the gated feed-forward: the
# latent-attention reference's, whose expert layer is this one with a bias
from chipbench.reference.mla_lm import _experts, _gated

_HI = jax.lax.Precision.HIGHEST
#: query rows a head's attention is made for at a time
ROW_BLOCK = 512
_ACT = {"relu": jax.nn.relu, "silu": jax.nn.silu}
_GATE = {"sigmoid": jax.nn.sigmoid, "identity": lambda g: g}
FAULTS = ("no_gate", "gate_identity", "plain_for_yarn", "no_attention_factor",
          "whole_head_turned", "window_plus_one", "no_shared", "no_scale",
          "normalise_over_held")


def frequencies(rule: dict, turned: int):
    """(turned / 2,): the angle a position turns each pair by."""
    j = jnp.arange(0, turned, 2, dtype=jnp.float32)
    plain = rule["theta"] ** (-j / turned)
    if rule["yarn"] is None:
        return plain
    scale, original, fast, slow = rule["yarn"]
    pair = lambda turns: turned * math.log(  # noqa: E731
        original / (turns * 2 * math.pi)) / (2 * math.log(rule["theta"]))
    low = max(math.floor(pair(fast)), 0)
    high = min(math.ceil(pair(slow)), turned - 1)
    ramp = jnp.clip((j / 2 - low) / max(high - low, 0.001), 0.0, 1.0)
    return plain * (1 - ramp) + plain / scale * ramp


def rotate(x, rule: dict, faults=()):
    """x (S, H, D) by ``rule`` = {"theta", "turned", "yarn", "factor"}."""
    if "whole_head_turned" in faults:
        rule = {**rule, "turned": x.shape[-1]}
    if "plain_for_yarn" in faults:
        rule = {**rule, "yarn": None}
    if "no_attention_factor" in faults:
        rule = {**rule, "factor": 1.0}
    turned = rule["turned"]
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * frequencies(rule, turned)[None]
    cos = rule["factor"] * jnp.cos(angle)[:, None]
    sin = rule["factor"] * jnp.sin(angle)[:, None]
    a, b, rest = x[..., :turned // 2], x[..., turned // 2:turned], \
        x[..., turned:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def _attention(mode, p, n, layer, cfg, faults):
    """n (S, d) -> (S, d): one row's gated causal self attention."""
    proj = lambda name: precision.einsum(  # noqa: E731
        mode, "sd,dhk->shk", n, p[name]["kernel"])
    rule = cfg["rope"][layer["kind"]]
    q = rotate(_rms(proj("q"), p["q_norm"]["scale"], cfg["rms_eps"]), rule,
               faults)
    k = rotate(_rms(proj("k"), p["k_norm"]["scale"], cfg["rms_eps"]), rule,
               faults)
    v = proj("v")
    s, d_head = q.shape[0], q.shape[-1]
    group = layer["heads"] // cfg["n_kv_heads"]
    window = cfg["window"] if layer["kind"] == "window" else None
    if window is not None and "window_plus_one" in faults:
        window += 1
    blk = math.gcd(s, ROW_BLOCK)

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv                                     # (S, D) each

        @jax.checkpoint
        def rows(args):
            qb, t = args                                     # (blk, D), (blk,)
            scores = precision.einsum(mode, "qk,sk->qs",
                                      qb / math.sqrt(d_head), kh)
            diff = t[:, None] - jnp.arange(s)[None, :]
            seen = diff >= 0
            if window is not None:
                seen &= diff < window
            probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
            return precision.einsum(mode, "qs,sk->qk", probs, vh)

        return jax.lax.map(rows, (qh.reshape(s // blk, blk, -1),
                                  jnp.arange(s).reshape(s // blk, blk))
                           ).reshape(s, -1)

    ctx = jax.lax.map(head, (
        jnp.moveaxis(q, 1, 0),
        jnp.repeat(jnp.moveaxis(k, 1, 0), group, axis=0),
        jnp.repeat(jnp.moveaxis(v, 1, 0), group, axis=0)))   # (H, S, D)
    if cfg["gate"] is not None and "no_gate" not in faults:
        act = _GATE["identity" if "gate_identity" in faults else cfg["gate"]]
        g = act(jnp.einsum("sd,dh->sh", n, p["gate"]["kernel"],
                           precision=_HI))
        ctx = ctx * g.T[:, :, None]
    return precision.einsum(mode, "hqk,hkd->qd", ctx, p["out"]["kernel"])


def routing_weights(logits, cfg, faults=()):
    """(S, E): a token's weight at each of its chosen experts, 0 elsewhere."""
    score = jax.nn.sigmoid(logits)
    chosen, idx = jax.lax.top_k(score, cfg["top_k"])
    if cfg["normalised"]:
        counted = chosen
        if "normalise_over_held" in faults:
            first, count = cfg["experts_held"]
            counted = jnp.where((idx >= first) & (idx < first + count),
                                chosen, 0.0)
        chosen = chosen / (jnp.sum(counted, axis=1, keepdims=True) + 1e-20)
    if "no_scale" not in faults:
        chosen = chosen * cfg["scale"]
    return jnp.zeros_like(score).at[
        jnp.arange(score.shape[0])[:, None], idx].set(chosen)


def _layer(x, p, layer, cfg, mode, faults):
    """One held layer: x (S, d) -> (S, d)."""
    act = _ACT[cfg["activation"]]
    n = _rms(x, p["norm_in"]["scale"], cfg["rms_eps"])
    x = x + _attention(mode, p["attn"], n, layer, cfg, faults)
    m = _rms(x, p["norm_post"]["scale"], cfg["rms_eps"])
    gated = jax.checkpoint(lambda q, y: _gated(mode, q, y, act))
    if layer["ffn"] == "dense":
        return x + gated(p["mlp"], m)
    logits = jnp.einsum("sd,de->se", m, p["router"]["kernel"], precision=_HI)
    e = p["experts"]
    out = x + _experts(mode, {k: e[k] for k in ("gate", "up", "down")}, m,
                       routing_weights(logits, cfg, faults),
                       cfg["experts_held"][0], act)
    return out if "no_shared" in faults else out + gated(e["shared"], m)


def features(params, tokens, cfg, mode="float32", faults=()):
    """tokens (S,) of one row -> the last norm's output (S, d). A layer's
    intermediates are made again in the backward pass: its input is what
    stands."""
    x = params["embed"]["embedding"][tokens - cfg["vocab_held"][0]]
    for i, layer in enumerate(cfg["layers"]):
        x = jax.checkpoint(lambda x, p, layer=layer: _layer(
            x, p, layer, cfg, mode, faults))(x, params[f"h{i}"])
    return _rms(x, params["norm_f"]["scale"], cfg["rms_eps"])


def logits(params, tokens, cfg, mode="float32", faults=()):
    """tokens (S,) of one row -> (S, held rows): the tests' comparison."""
    return precision.einsum(
        mode, "sd,vd->sv", features(params, tokens, cfg, mode, faults),
        params["head"]["embedding"])


def loss(params, rows, cfg, mode="float32", faults=()):
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
        feats = features(params, rows[b, :-1], cfg, mode, faults)
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
    d, kv, k = cfg["d_model"], cfg["n_kv_heads"], cfg["head_dim"]
    f, rows = cfg["expert_d_ff"], cfg["vocab_held"][1]
    each = lambda *s: {f"e{e:02d}": f32(*s)  # noqa: E731
                       for e in range(cfg["experts_held"][1])}
    gated = lambda width: {"gate": {"kernel": f32(d, width)},  # noqa: E731
                           "up": {"kernel": f32(d, width)},
                           "down": {"kernel": f32(width, d)}}
    out = {"embed": {"embedding": f32(rows, d)},
           "head": {"embedding": f32(rows, d)},
           "norm_f": {"scale": f32(d)}}
    for i, spec in enumerate(cfg["layers"]):
        h = spec["heads"]
        layer = out[f"h{i}"] = {
            "norm_in": {"scale": f32(d)}, "norm_post": {"scale": f32(d)},
            "attn": {"q": {"kernel": f32(d, h, k)},
                     "k": {"kernel": f32(d, kv, k)},
                     "v": {"kernel": f32(d, kv, k)},
                     "q_norm": {"scale": f32(k)}, "k_norm": {"scale": f32(k)},
                     "out": {"kernel": f32(h, k, d)}}}
        if cfg["gate"] is not None:
            layer["attn"]["gate"] = {"kernel": f32(d, h)}
        if spec["ffn"] == "dense":
            layer["mlp"] = gated(cfg["d_ff"])
        else:
            layer.update(
                router={"kernel": f32(d, cfg["n_experts"])},
                experts={"gate": each(d, f), "up": each(d, f),
                         "down": each(f, d),
                         "shared": gated(cfg["shared_d_ff"])})
    return out
