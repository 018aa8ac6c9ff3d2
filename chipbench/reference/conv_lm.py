"""Decoder-only language model whose mixers are gated short convolutions
beside grouped attention, with leading dense layers and routed SwiGLU
experts behind them; training loss, float32.

The blocks of LFM2-24B-A2B (``model_type`` lfm2_moe) as ISSUE 53 writes them
down (config: huggingface.co/LiquidAI/LFM2-24B-A2B; LFM2: arXiv:2511.23404),
for the share of a deployment that one chip holds. ``x`` is layer l's
input, (S, d), l the layer's PUBLISHED number:

    x <- x + mix_l(rmsnorm(x; w_in))      two norms a block, pre-norm,
    x <- x + feed_l(rmsnorm(x; w_post))   eps norm_eps, no bias anywhere

``conv`` (the gated short convolution; n the normed input):
    [B | C | X] = n W_in                  d -> 3 d, the thirds in THIS order
    u   = B * X
    c_t = sum_i taps[i] u_{t-(K-1)+i}     depthwise, causal, u = 0 before the
                                          row's start, K = 3 taps: a sum of
                                          K shifts; no bias, NO activation
    out = (C * c) W_out
``full_attention``: q, k, v = n W_q, n W_k, n W_v; q and k each rmsnorm
    over a head's channels with a learned scale (eps norm_eps), THEN rotary
    over the whole head (pairs (j, j + D / 2), theta rope_theta); scores
    q.k / sqrt(D) under the causal mask, query head h on K/V head
    h // (heads / kv heads); softmax; out = concat_h(o) W_o
feed, l < dense layers: (silu(m W_gate) * m W_up) W_down, d_ff wide
feed, else: score = sigmoid(m W_r) (float32, precision highest); chosen =
    the top_k largest of score + b (b the correction bias ``expert_bias``:
    in the choice alone, frozen), ties to the lower index; w = scale
    score[chosen] / (sum(score[chosen]) + 1e-6); out = sum over e in chosen
    that is HELD of w_e (silu(m W_gate_e) * m W_up_e) W_down_e. No shared
    expert; no token dropped.

Then the last rmsnorm (the family's ``embedding_norm``: at the OUTPUT) and
the head, which is the embedding's table (tied), over the held rows of the
vocabulary; the loss is the mean next-token cross-entropy over those rows.

Departures from the published description, each the deployment's share or
a way of computing the same numbers: the experts not held here add nothing
(model-configs guide, section 4), in the program alike; every held expert
is applied to every token and weighed by the routing (zero where the token
did not choose it), a loop over the held ones; attention is explicit scores
under the mask, a head at a time and in blocks of query rows whose
intermediates are made again in the backward pass, so that a row of 8192
tokens fits one chip; the published modelling code computes in bfloat16
throughout, this file in float32 (``mode`` ``fp8``: the control). The
router's scores are float32 at matmul precision highest in every ``mode``.

Parameters are a nested dict named as the program's flax model names them
(a block is ``h<published number>``), except that an expert's three matrices
are leaves of their own (``h2/experts/up/e03``). Nothing of the program is
imported.

``faults``: names of planted faults (tests/unit/test_lm_short_conv.py,
chipbench/tests/test_conv_lm_cell.py), each one departure from the
equations above that the comparison has to see; ``no_routing_eps`` is the
one that is NOT expected to be seen (the four chosen scores sum to 2-3, and
1e-6 beside that is under float32's own rounding of the weights' effect).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import precision
from chipbench.reference.lm import HEAD_BLOCK, _rms, _rope
# every held expert on every token, weighed, the gated feed-forward and the
# frozen leaf's three helpers: the latent-attention reference's, whose
# expert layer is this one with shared experts beside it
from chipbench.reference.mla_lm import (FROZEN, _experts,  # noqa: F401
                                        _gated, frozen, trained, with_frozen)
# a sum of K shifts, zero before the row's start: the Mamba-2 reference's
from chipbench.reference.ssd_lm import short_conv

_HI = jax.lax.Precision.HIGHEST
#: query rows a head's attention is made for at a time
ROW_BLOCK = 512
FAULTS = ("thirds_xbc", "no_gate_c", "gate_b_after_conv", "taps_reversed",
          "silu_after_conv", "conv_bias", "no_qk_norm", "norm_after_rotation",
          "no_rotation", "softmax_scores", "bias_in_weights",
          "normalise_over_held", "no_routing_eps", "no_last_norm",
          "untied_head", "dense_as_expert")


def _mixer(mode, p, n, faults):
    """n (S, d) -> (S, d): one row's gated short convolution."""
    thirds = jnp.split(precision.einsum(mode, "sd,de->se", n,
                                        p["in_proj"]["kernel"]), 3, axis=-1)
    b, c, x = (thirds[i] for i in (
        (1, 2, 0) if "thirds_xbc" in faults else (0, 1, 2)))
    taps = p["conv"][::-1] if "taps_reversed" in faults else p["conv"]
    conv = short_conv(x if "gate_b_after_conv" in faults else b * x, taps)
    if "conv_bias" in faults:
        conv = conv + p["conv"][0]   # a bias a channel, of the taps' size
    if "silu_after_conv" in faults:
        conv = jax.nn.silu(conv)
    if "gate_b_after_conv" in faults:
        conv = b * conv
    y = conv if "no_gate_c" in faults else c * conv
    return precision.einsum(mode, "sk,kd->sd", y, p["out_proj"]["kernel"])


def _attention(mode, p, n, cfg, faults):
    """n (S, d) -> (S, d): one row's causal grouped attention, q and k
    normed a head, then turned."""
    proj = lambda name: precision.einsum(  # noqa: E731
        mode, "sd,dhk->shk", n, p[name]["kernel"])
    normed = lambda y, name: y if "no_qk_norm" in faults else _rms(  # noqa: E731
        y, p[name]["scale"], cfg["rms_eps"])
    turned = lambda y: y if "no_rotation" in faults else _rope(  # noqa: E731
        y, cfg["rope_theta"])
    if "norm_after_rotation" in faults:
        q, k = normed(turned(proj("q")), "q_norm"), \
            normed(turned(proj("k")), "k_norm")
    else:
        q, k = turned(normed(proj("q"), "q_norm")), \
            turned(normed(proj("k"), "k_norm"))
    v = proj("v")
    s, d_head = q.shape[0], q.shape[-1]
    group = cfg["n_heads"] // cfg["n_kv_heads"]
    blk = math.gcd(s, ROW_BLOCK)

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv                                     # (S, D) each

        @jax.checkpoint
        def rows(args):
            qb, t = args                                     # (blk, D), (blk,)
            scores = precision.einsum(mode, "qk,sk->qs",
                                      qb / math.sqrt(d_head), kh)
            seen = t[:, None] >= jnp.arange(s)[None, :]
            probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
            return precision.einsum(mode, "qs,sk->qk", probs, vh)

        return jax.lax.map(rows, (qh.reshape(s // blk, blk, -1),
                                  jnp.arange(s).reshape(s // blk, blk))
                           ).reshape(s, -1)

    ctx = jax.lax.map(head, (
        jnp.moveaxis(q, 1, 0),
        jnp.repeat(jnp.moveaxis(k, 1, 0), group, axis=0),
        jnp.repeat(jnp.moveaxis(v, 1, 0), group, axis=0)))   # (H, S, D)
    return precision.einsum(mode, "hqk,hkd->qd", ctx, p["out"]["kernel"])


def routing_weights(logits, bias, cfg, faults=()):
    """(S, E): a token's weight at each of its chosen experts, 0 elsewhere."""
    score = jax.nn.softmax(logits, axis=-1) if "softmax_scores" in faults \
        else jax.nn.sigmoid(logits)
    biased = score + bias if cfg["use_bias"] else score
    _, idx = jax.lax.top_k(biased, cfg["top_k"])
    chosen = jnp.take_along_axis(
        biased if "bias_in_weights" in faults else score, idx, axis=1)
    if cfg["normalised"]:
        counted = chosen
        if "normalise_over_held" in faults:
            first, count = cfg["experts_held"]
            counted = jnp.where((idx >= first) & (idx < first + count),
                                chosen, 0.0)
        chosen = chosen / (jnp.sum(counted, axis=1, keepdims=True) + (
            0.0 if "no_routing_eps" in faults else cfg["routing_eps"]))
    return jnp.zeros_like(score).at[
        jnp.arange(score.shape[0])[:, None], idx].set(chosen * cfg["scale"])


def _layer(x, p, number, kind, cfg, mode, faults):
    """Published layer ``number``, its mixer of ``kind``: x (S, d) -> (S,
    d)."""
    n = _rms(x, p["norm_in"]["scale"], cfg["rms_eps"])
    x = x + (_mixer(mode, p["conv"], n, faults) if kind == "conv"
             else _attention(mode, p["attn"], n, cfg, faults))
    m = _rms(x, p["norm_post"]["scale"], cfg["rms_eps"])
    if number < cfg["dense_layers"]:
        q = p["mlp"]
        if "dense_as_expert" in faults:   # an expert's width of it alone
            f = cfg["expert_d_ff"]
            q = {"gate": {"kernel": q["gate"]["kernel"][:, :f]},
                 "up": {"kernel": q["up"]["kernel"][:, :f]},
                 "down": {"kernel": q["down"]["kernel"][:f]}}
        return x + jax.checkpoint(lambda q, y: _gated(
            mode, q, y, jax.nn.silu))(q, m)
    logits = jnp.einsum("sd,de->se", m, p["router"]["kernel"], precision=_HI)
    bias = p[FROZEN] if cfg["use_bias"] else 0.0
    return x + _experts(mode, p["experts"], m,
                        routing_weights(logits, bias, cfg, faults),
                        cfg["experts_held"][0], jax.nn.silu)


def features(params, tokens, cfg, mode="float32", faults=()):
    """tokens (S,) of one row -> the last norm's output (S, d). A layer's
    intermediates are made again in the backward pass: its input is what
    stands."""
    x = params["embed"]["embedding"][tokens - cfg["vocab_held"][0]]
    for number, kind in zip(cfg["numbers"], cfg["kinds"]):
        x = jax.checkpoint(
            lambda x, p, number=number, kind=kind: _layer(
                x, p, number, kind, cfg, mode, faults))(
            x, params[f"h{number}"])
    if "no_last_norm" in faults:
        return x
    return _rms(x, params["norm_f"]["scale"], cfg["rms_eps"])


def _head(params, faults):
    """The head's table: the embedding's (tied)."""
    table = params["embed"]["embedding"]
    # the planted untied head: a table of its own sends the embedding no
    # gradient from the logits
    return jax.lax.stop_gradient(table) if "untied_head" in faults else table


def logits(params, tokens, cfg, mode="float32", faults=()):
    """tokens (S,) of one row -> (S, held rows): the tests' comparison."""
    return precision.einsum(
        mode, "sd,vd->sv", features(params, tokens, cfg, mode, faults),
        _head(params, faults))


def loss(params, rows, cfg, mode="float32", faults=()):
    """Mean next-token cross-entropy over ``rows`` (B, S + 1) of ids inside
    the held slice."""
    head = _head(params, faults)
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
    d, rows = cfg["d_model"], cfg["vocab_held"][1]
    h, kv, k = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    f = cfg["expert_d_ff"]
    each = lambda *s: {f"e{e:02d}": f32(*s)  # noqa: E731
                       for e in range(cfg["experts_held"][1])}
    gated = lambda width: {"gate": {"kernel": f32(d, width)},  # noqa: E731
                           "up": {"kernel": f32(d, width)},
                           "down": {"kernel": f32(width, d)}}
    mixers = {
        "conv": lambda: {"conv": {
            "in_proj": {"kernel": f32(d, 3 * d)},
            "conv": f32(cfg["taps"], d),
            "out_proj": {"kernel": f32(d, d)}}},
        "full_attention": lambda: {"attn": {
            "q": {"kernel": f32(d, h, k)}, "k": {"kernel": f32(d, kv, k)},
            "v": {"kernel": f32(d, kv, k)},
            "q_norm": {"scale": f32(k)}, "k_norm": {"scale": f32(k)},
            "out": {"kernel": f32(h, k, d)}}}}
    out = {"embed": {"embedding": f32(rows, d)}, "norm_f": {"scale": f32(d)}}
    for number, kind in zip(cfg["numbers"], cfg["kinds"]):
        layer = out[f"h{number}"] = {
            "norm_in": {"scale": f32(d)}, "norm_post": {"scale": f32(d)},
            **mixers[kind]()}
        if number < cfg["dense_layers"]:
            layer["mlp"] = gated(cfg["d_ff"])
        else:
            layer.update(router={"kernel": f32(d, cfg["n_experts"])},
                         experts={"gate": each(d, f), "up": each(d, f),
                                  "down": each(f, d)})
            if cfg["use_bias"]:
                layer[FROZEN] = f32(cfg["n_experts"])
    return out
