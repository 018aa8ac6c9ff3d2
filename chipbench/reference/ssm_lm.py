"""The SambaY decoder-hybrid-decoder with differential attention
(Phi-4-mini-flash-reasoning's: arXiv:2507.06607), training loss, float32.

The model as ISSUE 40 writes it down (config:
huggingface.co/microsoft/Phi-4-mini-flash-reasoning), for the share of a
deployment that one chip holds: some of the published layers (``layers``:
their PUBLISHED numbers l of ``of`` = N), a slice of the vocabulary. ``x``
is a layer's input, (S, d), ``u = LN(x)``:

    h   = x + mixer_l(LN(x; norm_in))
    out = h + (silu(h' W_gate) * (h' W_up)) W_down,   h' = LN(h; norm_post)

LayerNorm with weight and bias, eps ``eps``; no bias in the feed-forward;
a final LayerNorm; logits on the TIED table (the embedding's rows), no head
bias; no positions anywhere. The kind of layer l at the published depth N:
even l is **Mamba** if l <= N/2, else a **gated memory unit**; odd l is
attention: **window** if l < N/2, **full** if l = N/2 + 1, **cross** if l
>= N/2 + 3. Layer N/2 is the Mamba whose scan output is the memory M;
layer N/2 + 1 is the full layer whose K and V the cross layers read.

Mamba-1 (d_inner = 2 d, N_s states, K taps, rank r):

    (x, z) = u W_in ;  x = silu(conv_K(x) + b_conv)       causal, depthwise:
             y_t = sum_i c_i x_{t-(K-1)+i}, x before the row = 0
    (delta, B_t, C_t) = x W_x  split r / N_s / N_s
    Delta = softplus(delta W_dt + b_dt) ;  A = -exp(A_log)   (d_inner, N_s)
    h_0 = 0 ;  h_t = exp(Delta_t A) * h_{t-1} + (Delta_t x_t) B_t^T
    y_t = h_t C_t + D * x_t ;  out = (y * silu(z)) W_out
    in layer N/2:  M = y, BEFORE the gate

computed as written, **token by token** (a ``lax.scan`` over t, blocks of
``TOKEN_BLOCK`` tokens made again in the backward pass: unchecked, the
states alone are 2.7 GB a layer at 8192 tokens), not in the chunks the
program walks. x_proj, dt_proj and the recurrence are float32 in every
``mode``: they are the scan's steps and state, which the configuration
states in float32; the control rounds the scan's input x as it rounds
every other product's operands.

Differential attention (H query heads, H_kv K/V heads, width w): the query
heads are pairs (2p, 2p + 1) = (q1, q2), the K/V heads pairs (2j, 2j + 1)
= (k1, k2) with the pair's two values joined to one v, 2w wide; query pair
p reads K/V pair p // ((H / 2) / (H_kv / 2)).

    a_i = softmax(q_i k_i^T / sqrt(w) + mask) v            i = 1, 2
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
    o = (1 - lambda_init) rmsnorm(a_1 - lambda a_2; subln)   over 2w, eps
    lambda_init = 0.8 - 0.6 exp(-0.3 l)                    the PUBLISHED l
    out = concat_pairs(o) W_o + b_o

with bias on q, k, v and the output projection; the mask is causal, with
the window (key j seen by query i iff 0 <= i - j < window) on the window
layers. Gated memory unit: out = (M * silu(u W_in)) W_out. Cross
attention: q = u W_q + b_q only; K and V are the full layer's, after its
projection; the same differential form, causal, no window.

The loss is the mean next-token cross-entropy over the held rows.
Parameters are a nested dict named as the program's flax model names them
(``h16/ssm/in_proj/kernel``: the blocks carry their published numbers).
Nothing of the program is imported.

``faults``: names of planted faults (chipbench/tests/test_ssm_lm_cell.py)
that change what is computed, for the tests that show the output check
sees them.
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
#: query rows a head's attention is made for at a time
ROW_BLOCK = 512


def kind_of(layer: int, of: int) -> str:
    half = of // 2
    if layer % 2 == 0:
        return "mamba" if layer <= half else "gmu"
    if layer < half:
        return "window"
    if layer == half + 1:
        return "full"
    if layer >= half + 3:
        return "cross"
    raise ValueError(f"layer {layer} of {of} has no kind")


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _ln(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def short_conv(x, taps, shift: int = 0):
    """x (S, D), taps (K, D): y_t = sum_i taps[i] x_{t-(K-1)+i-shift}."""
    k, s = taps.shape[0], x.shape[0]
    x = jnp.concatenate([jnp.zeros((k - 1 + shift, x.shape[1]), x.dtype), x])
    return sum(taps[i] * x[i:i + s] for i in range(k))


def recurrence(x, dt, a, b, c, state_dtype=jnp.float32):
    """y (S, D) of the selective scan, a token at a time, from x, dt (S,
    D), a (D, N), b, c (S, N). The state is held (N, D), the channels
    along an array's last axis: the same numbers as (D, N), laid out for
    the device's registers."""
    s, d = x.shape
    blk = math.gcd(s, TOKEN_BLOCK)
    at = a.T

    def token(h, xs):
        xt, dtt, bt, ct = xs
        h = (jnp.exp(dtt[None, :] * at) * h
             + (dtt * xt)[None, :] * bt[:, None]).astype(state_dtype)
        return h, jnp.sum(h.astype(jnp.float32) * ct[:, None], axis=0)

    @jax.checkpoint
    def block(h, xs):
        return jax.lax.scan(token, h, xs)

    _, y = jax.lax.scan(
        block, jnp.zeros(at.shape, state_dtype),
        jax.tree.map(lambda v: v.reshape(s // blk, blk, *v.shape[1:]),
                     (x, dt, b, c)))
    return y.reshape(s, d)


def _mamba(mode, p, u, cfg, faults):
    """(out (S, d), y (S, d_inner): the scan's output before the gate)."""
    r, n = cfg["dt_rank"], cfg["d_state"]
    xz = precision.einsum(mode, "sd,dgk->sgk", u, p["in_proj"]["kernel"])
    x, z = xz[:, 0], xz[:, 1]
    x = jax.nn.silu(short_conv(x, p["conv"], int("conv_shifted" in faults))
                    + p["conv_bias"])
    dbc = jnp.einsum("sk,kr->sr", x, p["x_proj"]["kernel"], precision=_HI)
    delta, b, c = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    dt = jnp.einsum("sr,rk->sk", delta, p["dt_proj"]["kernel"],
                    precision=_HI)
    if "no_dt_bias" not in faults:
        dt = dt + p["dt_proj"]["bias"]
    dt = jax.nn.softplus(dt)
    xs = x if mode == "float32" else precision._fp8(x)
    y = recurrence(xs, dt, -jnp.exp(p["A_log"]), b, c,
                   jnp.bfloat16 if "state_bfloat16" in faults
                   else jnp.float32)
    if "no_skip" not in faults:
        y = y + p["D"] * x
    gated = y * jax.nn.silu(z)
    out = precision.einsum(mode, "sk,kd->sd", gated, p["out_proj"]["kernel"])
    return out, (gated if "memory_after_gate" in faults else y)


def _gmu(mode, p, u, memory):
    gate = precision.einsum(mode, "sd,dk->sk", u, p["in_proj"]["kernel"])
    return precision.einsum(mode, "sk,kd->sd", memory * jax.nn.silu(gate),
                            p["out_proj"]["kernel"])


def _softmax_attention(mode, q, k, v, window):
    """q (H, S, w) scaled, k (H, S, w), v (H, S, 2w), a key head a query
    head already: (H, S, 2w) under the causal (and window) mask."""
    s = q.shape[1]
    blk = math.gcd(s, ROW_BLOCK)

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv

        @jax.checkpoint
        def rows(args):
            qb, t = args                                     # (blk, w), (blk,)
            scores = precision.einsum(mode, "qk,sk->qs", qb, kh)
            diff = t[:, None] - jnp.arange(s)[None, :]
            seen = diff >= 0
            if window is not None:
                seen &= diff < window
            probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
            return precision.einsum(mode, "qs,sk->qk", probs, vh)

        return jax.lax.map(rows, (qh.reshape(s // blk, blk, -1),
                                  jnp.arange(s).reshape(s // blk, blk))
                           ).reshape(s, -1)

    return jax.lax.map(head, (q, k, v))


def _attention(mode, p, u, layer, window, kv, cfg, faults):
    """(out (S, d), (k, v) (S, H_kv, w) each) of differential attention;
    ``kv``: another layer's, read in place of this layer's own."""
    proj = lambda name: precision.einsum(  # noqa: E731
        mode, "sd,dhk->shk", u, p[name]["kernel"]) + p[name]["bias"]
    q = proj("q")
    if kv is None:
        kv = (proj("k"), proj("v"))
    k, v = kv
    s, h, w = q.shape
    group = (h // 2) // (k.shape[1] // 2)
    joined = v.reshape(s, -1, 2 * w)                         # (S, H_kv/2, 2w)
    per_query_pair = lambda y: jnp.repeat(  # noqa: E731
        jnp.moveaxis(y, 1, 0), group, axis=0)
    q = q / math.sqrt(w)
    a1, a2 = (_softmax_attention(
        mode, jnp.moveaxis(q[:, i::2], 1, 0), per_query_pair(k[:, i::2]),
        per_query_pair(joined), window) for i in (0, 1))     # (H/2, S, 2w)
    at = cfg["layers"].index(layer) if "lambda_init_held_index" in faults \
        else layer
    init = lambda_init(at)
    lam = jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) \
        - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + init
    if "no_second_map" in faults:
        lam = 0.0
    o = _rms(a1 - lam * a2, p["subln"]["scale"], cfg["eps"])
    if "no_one_minus_lambda_init" not in faults:
        o = o * (1.0 - init)
    return precision.einsum(mode, "hsk,hkd->sd", o, p["out"]["kernel"]) \
        + p["out"]["bias"], kv


def _ffn(mode, p, x):
    mm = lambda eq, a, name: precision.einsum(  # noqa: E731
        mode, eq, a, p[name]["kernel"])
    return mm("sf,fd->sd", jax.nn.silu(mm("sd,df->sf", x, "gate"))
              * mm("sd,df->sf", x, "up"), "down")


def _layer(x, p, handed, layer, cfg, mode, faults):
    """Layer ``layer`` (published number): (x (S, d), what it hands on)."""
    kind = kind_of(layer, cfg["of"])
    half = cfg["of"] // 2
    u = _ln(x, p["norm_in"], cfg["eps"])
    on = {}
    if kind == "mamba":
        branch, y = _mamba(mode, p["ssm"], u, cfg, faults)
        if layer == half:
            on["memory"] = y
    elif kind == "gmu":
        branch = _gmu(mode, p["gmu"], u, handed["memory"])
    else:
        window = cfg["window"] if kind == "window" else None
        if window is not None and "window_plus_one" in faults:
            window += 1
        read = handed["kv"] if kind == "cross" else None
        if kind == "cross" and "cross_own_kv" in faults:
            # the faulty cross layer projects its own K and V with the
            # full layer's weights on its own input
            read = _attention(mode, handed["kv_params"], u, layer, None,
                              None, cfg, ())[1]
        branch, kv = _attention(mode, p["attn"], u, layer, window, read,
                                cfg, faults)
        if layer == half + 1:
            on["kv"] = kv
    x = x + branch
    return x + _ffn(mode, p["mlp"], _ln(x, p["norm_post"], cfg["eps"])), on


def features(params, tokens, cfg, mode="float32", faults=()):
    """tokens (S,) of one row -> the last norm's output (S, d). A layer's
    intermediates are made again in the backward pass; its input and what
    it hands on stand."""
    x = params["embed"]["embedding"][tokens - cfg["vocab_held"][0]]
    handed = {}
    if "cross_own_kv" in faults:
        handed["kv_params"] = params[f"h{cfg['of'] // 2 + 1}"]["attn"]
    for layer in cfg["layers"]:
        x, on = jax.checkpoint(
            lambda x, p, handed, layer=layer: _layer(
                x, p, handed, layer, cfg, mode, faults))(
                    x, params[f"h{layer}"], handed)
        handed.update(on)
    return _ln(x, params["norm_f"], cfg["eps"])


def loss(params, rows, cfg, mode="float32", faults=()):
    """Mean next-token cross-entropy over ``rows`` (B, S + 1) of ids inside
    the held slice, the logits on the tied table."""
    table = params["embed"]["embedding"]
    first_id = cfg["vocab_held"][0]

    @jax.checkpoint
    def block(args):
        feats, labels = args
        logp = jax.nn.log_softmax(
            precision.einsum(mode, "sd,vd->sv", feats, table), axis=-1)
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
    d, f, rows = cfg["d_model"], cfg["d_ff"], cfg["vocab_held"][1]
    h, hkv, w = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    di, n, taps, r = cfg["d_inner"], cfg["d_state"], cfg["d_conv"], \
        cfg["dt_rank"]
    norm = lambda: {"scale": f32(d), "bias": f32(d)}  # noqa: E731
    heads = lambda count: {"kernel": f32(d, count, w),  # noqa: E731
                           "bias": f32(count, w)}
    out = {"embed": {"embedding": f32(rows, d)}, "norm_f": norm()}
    for layer in cfg["layers"]:
        kind = kind_of(layer, cfg["of"])
        block = {"norm_in": norm(), "norm_post": norm(),
                 "mlp": {"gate": {"kernel": f32(d, f)},
                         "up": {"kernel": f32(d, f)},
                         "down": {"kernel": f32(f, d)}}}
        if kind == "mamba":
            block["ssm"] = {
                "in_proj": {"kernel": f32(d, 2, di)},
                "conv": f32(taps, di), "conv_bias": f32(di),
                "x_proj": {"kernel": f32(di, r + 2 * n)},
                "dt_proj": {"kernel": f32(r, di), "bias": f32(di)},
                "A_log": f32(di, n), "D": f32(di),
                "out_proj": {"kernel": f32(di, d)}}
        elif kind == "gmu":
            block["gmu"] = {"in_proj": {"kernel": f32(d, di)},
                            "out_proj": {"kernel": f32(di, d)}}
        else:
            attn = {"q": heads(h),
                    "out": {"kernel": f32(h // 2, 2 * w, d), "bias": f32(d)},
                    "subln": {"scale": f32(2 * w)},
                    **{f"lambda_{x}": f32(w)
                       for x in ("q1", "k1", "q2", "k2")}}
            if kind != "cross":
                attn.update(k=heads(hkv), v=heads(hkv))
            block["attn"] = attn
        out[f"h{layer}"] = block
    return out
