"""Operations and bytes of a hybrid linear-attention decoder, counted from
shapes (never from XLA), beside ``flops_lm.py``'s for the pattern decoders.
``cfg`` is ``hybrid_lm_config.reference_cfg``'s dict. A multiply-add is
two operations. Model work only: what a rematerialised block makes again
is not counted.

**The scan's work is the chunked form's products at a chunk of
``COUNTED_CHUNK`` = 64 tokens, whatever chunk or algorithm the program
runs**: a kernel that does more work than this for the same result reads
lower, not higher. A head and a chunk of C tokens, key width k, value
width v, forward:

    K K^T, T K, Q K^T                     3 x 2 C^2 k
    T V, (Q K^T) U                        2 x 2 C^2 v
    W S, Q S, K^T U                       3 x 2 C k v
    the unit triangular solve             C^3

= 6 C^2 k + 4 C^2 v + 6 C k v + C^3 (12 845 056 at C 64, k 96, v 192:
200.7 k a token a head). Backward, by its own products, from the chunks'
entering states (which the forward's contract writes out):

    made again: K K^T, T K, Q K^T, T V, W S, the solve
                                          6 C^2 k + 2 C^2 v + 2 C k v + C^3
    dU = P^T dO + Kd dS', dP = dO U^T, dVb = T^T dU, dT += dU Vb^T
                                          4 x 2 C^2 v (+ 2 C k v in dU)
    dO S^T, Qg^T dO, W^T dU, U dS'^T, dU S^T     5 x 2 C k v
    dKg = T^T dW, dT += dW Kg^T, dQ = dPD K, dK += dPD^T Q, (dG + dG^T) K
                                          5 x 2 C^2 k
    dA = -T^T dT T^T                      4 C^3

= 16 C^2 k + 10 C^2 v + 14 C k v + 5 C^3 (31 981 568 there, 2.49 x the
forward). Bytes, one call: q, k, v, g, beta read and o written once
(bfloat16; g and beta float32), and the chunks' entering states (float32,
k v a chunk and head) written by the forward and read by the backward,
which also reads dO and writes dq, dk, dv, dg, dbeta.
"""

from __future__ import annotations

from chipbench import flops_lm

#: tokens a chunk of the counted form (not the program's: see above)
COUNTED_CHUNK = 64


def scan_fwd_chunk_flops(c: int, k: int, v: int) -> int:
    """One head, one chunk, forward."""
    return 6 * c * c * k + 4 * c * c * v + 6 * c * k * v + c ** 3


def scan_bwd_chunk_flops(c: int, k: int, v: int) -> int:
    """One head, one chunk, backward (the list in the module's docstring)."""
    return 16 * c * c * k + 10 * c * c * v + 14 * c * k * v + 5 * c ** 3


def _chunks(s: int) -> int:
    return -(-s // COUNTED_CHUNK)


def linear_fwd_call(cfg: dict, s: int, batch: int = 1) -> dict:
    """One call of the forward scan over a row of ``s`` tokens."""
    h, k, v = cfg["linear_heads"], cfg["key_dim"], cfg["value_dim"]
    n = _chunks(s)
    return {"flops": batch * h * n * scan_fwd_chunk_flops(COUNTED_CHUNK, k, v),
            "bytes": batch * h * (s * (2 * (2 * k + 2 * v) + 8)
                                  + 4 * n * k * v)}


def linear_bwd_call(cfg: dict, s: int, batch: int = 1) -> dict:
    """One call of the backward scan."""
    h, k, v = cfg["linear_heads"], cfg["key_dim"], cfg["value_dim"]
    n = _chunks(s)
    return {"flops": batch * h * n * scan_bwd_chunk_flops(COUNTED_CHUNK, k, v),
            "bytes": batch * h * (s * (2 * (4 * k + 4 * v) + 16)
                                  + 4 * n * k * v)}


def _as_flops_lm(cfg: dict) -> dict:
    """The full layers in ``flops_lm``'s words: as many K/V heads as query
    heads, no window."""
    return {"n_heads": cfg["n_heads"], "n_kv_heads": cfg["n_heads"],
            "head_dim": cfg["head_dim"]}


def flash_fwd_call(cfg: dict, s: int, batch: int = 1) -> dict:
    return flops_lm.flash_fwd_call(_as_flops_lm(cfg), s, None, batch)


def flash_bwd_call(cfg: dict, s: int, batch: int = 1) -> dict:
    return flops_lm.flash_bwd_call(_as_flops_lm(cfg), s, None, batch)


def forward_flops_per_token(cfg: dict, s: int) -> float:
    """One token's forward pass at row length ``s``, averaged over the row:
    every layer's feed-forward, a linear layer's seven projections and its
    scan (the counted form), a full layer's four projections and the causal
    pairs of its core, and the head over the held rows. Convolutions, norms
    and gates are left out, as element-wise work is everywhere."""
    d, f = cfg["d_model"], cfg["d_ff"]
    h, w = cfg["n_heads"], cfg["head_dim"]
    lh, k, v = cfg["linear_heads"], cfg["key_dim"], cfg["value_dim"]
    ffn = 3 * 2 * d * f
    linear = 2 * d * lh * (2 * k + 3 * v + 2) \
        + lh * scan_fwd_chunk_flops(COUNTED_CHUNK, k, v) / COUNTED_CHUNK
    full = 4 * 2 * d * h * w + 4 * w * h * flops_lm.seen_pairs(s, None) / s
    n_linear = sum(cfg["linear"])
    return len(cfg["linear"]) * ffn + n_linear * linear \
        + (len(cfg["linear"]) - n_linear) * full \
        + 2 * d * cfg["vocab_held"][1]


def train_flops_per_item(cfg: dict, s: int) -> float:
    """Forward plus backward: three times the forward's matrix work, but
    the scans' backward by its own count; recomputed operations do not
    count."""
    lh, k, v = cfg["linear_heads"], cfg["key_dim"], cfg["value_dim"]
    scans = sum(cfg["linear"]) * lh / COUNTED_CHUNK
    fwd = scans * scan_fwd_chunk_flops(COUNTED_CHUNK, k, v)
    bwd = scans * scan_bwd_chunk_flops(COUNTED_CHUNK, k, v)
    return 3.0 * (forward_flops_per_token(cfg, s) - fwd) + fwd + bwd
