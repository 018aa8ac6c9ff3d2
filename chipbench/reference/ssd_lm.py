"""Decoder-only language model of one sublayer a block: Mamba-2 mixers,
grouped attention without positions, and experts of two matrices under a
squared ReLU beside a shared one; training loss, float32.

The blocks of NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type`` nemotron_h) as
ISSUE 49 writes them down (config: huggingface.co/nvidia/
NVIDIA-Nemotron-3-Nano-30B-A3B-BF16; Mamba-2: arXiv:2405.21060), for the
share of a deployment that one chip holds. ``x`` is block l's input, (S, d):

    x <- x + f_l(rmsnorm(x; w_l))        ONE branch a block, eps norm_eps

with ``f_l`` by the letter of ``hybrid_override_pattern`` at the block's
PUBLISHED number, n the normed input:

``M`` (H heads of P channels, G groups of N states; I = H P):
    [z | xBC | dt] = n W_in              (I | I + 2 G N | H columns), no bias
    xBC   = silu(conv(xBC) + b_conv)     causal, depthwise, K taps: a sum of
                                         K shifts
    x, B, C = split(xBC)                 (S, H, P), (S, G, N) twice
    dt    = softplus(dt + dt_bias)       a number a head; a = -exp(A_log)
    H_t   = exp(dt_t a) H_{t-1} + dt_t x_t B_t^T     (P x N) a head, head h
    y_t   = H_t C_t + D_h x_t            reading group h // (H / G);
                                         TOKEN BY TOKEN, no chunks
    y     = rmsnorm_groups(y * silu(z); w)   the mean square over each group
                                         of I / G channels, the gate BEFORE
    out   = y W_out
``*``: q, k, v = n W_q, n W_k, n W_v, no bias, NO rotation, no q/k norm;
    scores q.k / sqrt(head_dim) under the causal mask, query head h on K/V
    head h // (heads / kv heads); softmax; out = concat_h(o) W_o
``E``: score = sigmoid(n W_r) (float32, precision highest); chosen = the
    top_k largest of score + b (b the correction bias: in the choice
    alone, frozen), ties to the lower index; w = scale score[chosen] /
    (sum(score[chosen]) + 1e-20); out = sum over e in chosen that is HELD of
    w_e relu(n W_up_e)^2 W_down_e + relu(n W_up)^2 W_down (the shared
    expert, weight 1). No gate on any expert.

Then a last rmsnorm and the untied head over the held rows of the
vocabulary; the loss is the mean next-token cross-entropy over those rows.
Every held expert is applied to every token and weighed by the routing
(zero where the token did not choose it): a loop over the held ones;
attention is explicit scores under the mask, a head at a time and in blocks
of query rows whose intermediates are recomputed in the backward pass, so
that a row of 8192 tokens fits one chip. The router's scores and the
steps' projection (dt's H columns of W_in) are float32 at matmul precision
highest in every ``mode``: the control lowers the precision the
configuration states for the other products, not theirs; it rounds the
recurrence's operands C, B and dt x as it rounds a product's.

Parameters are a nested dict named as the program's flax model names them
(a block is ``h<published number>``), except that an expert's two matrices
are leaves of their own (``h1/experts/up/e03``). Nothing of the program is
imported.

``faults``: names of planted faults (tests/unit/test_lm_ssd.py,
chipbench/tests/test_ssd_lm_cell.py), each one departure from the equations
above that the comparison has to see.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import precision
from chipbench.reference.lm import HEAD_BLOCK, _rms, _rope

_HI = jax.lax.Precision.HIGHEST
#: query rows a head's attention is made for at a time, and the tokens of
#: the recurrence whose states the backward pass makes again together
ROW_BLOCK = 512
TOKEN_BLOCK = 128
#: the leaf no gradient reaches
FROZEN = "choice_bias"
FAULTS = ("no_skip", "no_dt_bias", "gate_after_norm", "norm_over_all",
          "group_by_modulo", "decay_sign", "no_conv_bias", "relu_not_squared",
          "gated_experts", "no_shared", "no_scale", "normalise_over_held",
          "rotary_attention")


def short_conv(x, taps):
    """x (S, C), taps (K, C): y_t = sum_i taps[i] x_{t-(K-1)+i}, x before
    the row's start = 0: a sum of K shifts."""
    k, s = taps.shape[0], x.shape[0]
    x = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(taps[i] * x[i:i + s] for i in range(k))


def _mixer(mode, p, n, cfg, faults):
    """n (S, d) -> (S, d): one row's Mamba-2 mixer."""
    h, pd, g, st = cfg["ssd_heads"], cfg["ssd_head_dim"], cfg["ssd_groups"], \
        cfg["ssd_state"]
    inner, bc = h * pd, g * st
    w = p["in_proj"]
    zxbc = precision.einsum(mode, "sd,de->se", n, w[:, :-h])
    dt = jnp.einsum("sd,dh->sh", n, w[:, -h:], precision=_HI)
    z = zxbc[:, :inner]
    xbc = short_conv(zxbc[:, inner:], p["conv"])
    if "no_conv_bias" not in faults:
        xbc = xbc + p["conv_bias"]
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :inner].reshape(-1, h, pd)
    b = xbc[:, inner:inner + bc].reshape(-1, g, st)
    c = xbc[:, inner + bc:].reshape(-1, g, st)
    if "no_dt_bias" not in faults:
        dt = dt + p["dt_bias"]
    dt = jax.nn.softplus(dt)
    a = jnp.exp(p["A_log"])
    if "decay_sign" not in faults:
        a = -a
    heads = jnp.arange(h)
    group_of = heads % g if "group_by_modulo" in faults else heads // (h // g)
    v = dt[:, :, None] * x
    if mode != "float32":
        b, c, v = precision._fp8(b), precision._fp8(c), precision._fp8(v)
    y = recurrence(v, dt, a, b, c, group_of)
    if "no_skip" not in faults:
        y = y + p["D"][:, None] * x
    y, gate = y.reshape(-1, inner), jax.nn.silu(z)
    if "gate_after_norm" not in faults:
        y = y * gate
    grouped = y.reshape(-1, 1 if "norm_over_all" in faults else g,
                        inner if "norm_over_all" in faults else inner // g)
    y = (grouped * jax.lax.rsqrt(jnp.mean(
        jnp.square(grouped), axis=-1, keepdims=True) + cfg["rms_eps"])
    ).reshape(-1, inner) * p["norm"]
    if "gate_after_norm" in faults:
        y = y * gate
    return precision.einsum(mode, "sk,kd->sd", y, p["out_proj"]["kernel"])


def recurrence(v, dt, a, b, c, group_of):
    """y (S, H, P) of H_t = exp(dt_t a) H_{t-1} + v_t B_t^T, y_t = H_t C_t,
    a token at a time, a state (P, N) a head: from v = dt x (S, H, P; the
    step already in the values, which the control rounds as the scan's
    operand they are), dt (S, H), a (H,), b, c (S, G, N) and ``group_of``
    (H,), the group each head reads."""
    s = v.shape[0]
    blk = math.gcd(s, TOKEN_BLOCK)

    def token(h, xs):
        vt, dtt, bt, ct = xs
        h = jnp.exp(dtt * a)[:, None, None] * h \
            + vt[:, :, None] * bt[group_of][:, None, :]
        return h, jnp.sum(h * ct[group_of][:, None, :], axis=-1)

    @jax.checkpoint
    def block(h, xs):
        return jax.lax.scan(token, h, xs)

    _, y = jax.lax.scan(
        block, jnp.zeros(v.shape[1:] + (b.shape[-1],), jnp.float32),
        jax.tree.map(lambda x: x.reshape(s // blk, blk, *x.shape[1:]),
                     (v, dt, b, c)))
    return y.reshape(v.shape)


def _attention(mode, p, n, cfg, faults):
    """n (S, d) -> (S, d): one row's causal grouped attention, no
    positions."""
    proj = lambda name: precision.einsum(  # noqa: E731
        mode, "sd,dhk->shk", n, p[name]["kernel"])
    q, k, v = proj("q"), proj("k"), proj("v")
    if "rotary_attention" in faults:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
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
    score = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(score + bias, cfg["top_k"])
    chosen = jnp.take_along_axis(score, idx, axis=1)
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


def _act(faults):
    if "relu_not_squared" in faults:
        return jax.nn.relu
    return lambda u: jnp.square(jax.nn.relu(u))


def _plain(mode, up, down, m, faults):
    """act(m W_up) W_down: an expert of two matrices, or the shared one."""
    h = precision.einsum(mode, "sd,df->sf", m, up)
    # the planted gate: the up product gating itself, silu(u) * u for u
    h = jax.nn.silu(h) * h if "gated_experts" in faults else _act(faults)(h)
    return precision.einsum(mode, "sf,fd->sd", h, down)


def _experts(mode, p, m, weights, first, faults):
    """Every held expert on every token, weighed: (S, d)."""
    names = sorted(p["up"])                                  # e00, e01, ...
    stack = lambda which: jnp.stack([p[which][e] for e in names])  # noqa: E731

    @jax.checkpoint
    def one(y, expert):
        up, down, w = expert
        return y + w[:, None] * _plain(mode, up, down, m, faults), None

    held = weights[:, first:first + len(names)].T            # (held, S)
    y, _ = jax.lax.scan(one, jnp.zeros_like(m),
                        (stack("up"), stack("down"), held))
    return y


def _block(x, p, letter, cfg, mode, faults):
    """One held block: x (S, d) -> (S, d)."""
    if letter == "E":
        m = _rms(x, p["norm_post"]["scale"], cfg["rms_eps"])
        logits = jnp.einsum("sd,de->se", m, p["router"]["kernel"],
                            precision=_HI)
        e = p["experts"]
        out = x + _experts(
            mode, e, m, routing_weights(logits, p[FROZEN], cfg, faults),
            cfg["experts_held"][0], faults)
        if "no_shared" in faults:
            return out
        return out + jax.checkpoint(lambda s, y: _plain(
            mode, s["up"]["kernel"], s["down"]["kernel"], y, faults))(
                e["shared"], m)
    n = _rms(x, p["norm_in"]["scale"], cfg["rms_eps"])
    if letter == "M":
        return x + _mixer(mode, p["ssd"], n, cfg, faults)
    return x + _attention(mode, p["attn"], n, cfg, faults)


def features(params, tokens, cfg, mode="float32", faults=()):
    """tokens (S,) of one row -> the last norm's output (S, d). A block's
    intermediates are made again in the backward pass: its input is what
    stands."""
    x = params["embed"]["embedding"][tokens - cfg["vocab_held"][0]]
    for number, letter in zip(cfg["numbers"], cfg["letters"]):
        x = jax.checkpoint(lambda x, p, letter=letter: _block(
            x, p, letter, cfg, mode, faults))(x, params[f"h{number}"])
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


def trained(params):
    """``params`` without the correction biases: what the loss sends a
    gradient."""
    return {name: ({k: v for k, v in sub.items() if k != FROZEN}
                   if isinstance(sub, dict) else sub)
            for name, sub in params.items()}


def frozen(params):
    """The correction biases alone, a block that has one."""
    return {name: {FROZEN: sub[FROZEN]} for name, sub in params.items()
            if isinstance(sub, dict) and FROZEN in sub}


def with_frozen(part, biases):
    """``part`` (a tree as ``trained`` gives) with ``biases`` (``frozen``)."""
    return {name: ({**sub, **biases[name]} if name in biases else sub)
            for name, sub in part.items()}


def param_shapes(cfg):
    """The tree of float32 shapes ``loss`` reads."""
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    d, rows = cfg["d_model"], cfg["vocab_held"][1]
    h, pd, g, st = cfg["ssd_heads"], cfg["ssd_head_dim"], cfg["ssd_groups"], \
        cfg["ssd_state"]
    inner, conv = h * pd, h * pd + 2 * g * st
    heads, kv, k = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    f, shared = cfg["expert_d_ff"], cfg["shared_d_ff"]
    each = lambda *s: {f"e{e:02d}": f32(*s)  # noqa: E731
                       for e in range(cfg["experts_held"][1])}
    scale = lambda: {"scale": f32(d)}  # noqa: E731
    blocks = {
        "M": lambda: {"norm_in": scale(), "ssd": {
            "in_proj": f32(d, inner + conv + h),
            "conv": f32(cfg["ssd_conv"], conv), "conv_bias": f32(conv),
            "dt_bias": f32(h), "A_log": f32(h), "D": f32(h),
            "norm": f32(inner), "out_proj": {"kernel": f32(inner, d)}}},
        "*": lambda: {"norm_in": scale(), "attn": {
            "q": {"kernel": f32(d, heads, k)}, "k": {"kernel": f32(d, kv, k)},
            "v": {"kernel": f32(d, kv, k)},
            "out": {"kernel": f32(heads, k, d)}}},
        "E": lambda: {"norm_post": scale(),
                      "router": {"kernel": f32(d, cfg["n_experts"])},
                      FROZEN: f32(cfg["n_experts"]),
                      "experts": {"up": each(d, f), "down": each(f, d),
                                  "shared": {"up": {"kernel": f32(d, shared)},
                                             "down": {"kernel": f32(shared,
                                                                    d)}}}}}
    out = {"embed": {"embedding": f32(rows, d)},
           "head": {"embedding": f32(rows, d)}, "norm_f": scale()}
    for number, letter in zip(cfg["numbers"], cfg["letters"]):
        out[f"h{number}"] = blocks[letter]()
    return out
