"""Operations and bytes a pattern decoder needs, counted from shapes (never
from XLA), beside ``flops.py``'s for the encoder-decoder. ``cfg`` is
``lm_config.reference_cfg``'s dict. A multiply-add is two operations.
"""

from __future__ import annotations


def seen_pairs(s: int, window) -> int:
    """(query, key) pairs a causal mask lets through at length ``s``:
    key j for query i iff 0 <= i - j (< window)."""
    if window is None or window >= s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def layer_windows(cfg: dict) -> list:
    return [cfg["window"] if sliding else None for sliding, _ in cfg["layers"]]


def forward_flops_per_token(cfg: dict, s: int) -> float:
    """One token's forward pass at sequence length ``s``, averaged over the
    row: projections, attention's scores and weighted values over the seen
    pairs only, the router, the experts a token meets HERE on average
    (top_k x held / routed over), and the head over the held rows."""
    d, h, kv, k = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], \
        cfg["head_dim"]
    proj = 2 * d * (h + 2 * kv) * k + 2 * h * k * d
    met = cfg["top_k"] * cfg["experts_held"][1] / cfg["n_experts"]
    experts = met * 3 * 2 * d * cfg["expert_d_ff"]
    router = 2 * d * cfg["n_experts"]
    core = sum(4 * k * h * seen_pairs(s, w) / s for w in layer_windows(cfg))
    return len(cfg["layers"]) * (proj + experts + router) + core \
        + 2 * d * cfg["vocab_held"][1]


def train_flops_per_item(cfg: dict, s: int) -> float:
    """Forward plus backward: three times the forward's matrix work;
    recomputed operations do not count."""
    return 3.0 * forward_flops_per_token(cfg, s)


# -- a kernel's work, one call -------------------------------------------------

def flash_fwd_call(cfg: dict, s: int, window, batch: int = 1) -> dict:
    """One call of the forward attention kernel: scores and weighted values
    of the seen pairs (2 products of 2 k operations a pair and head); reads
    q, k, v and writes the output (2 bytes) and the row statistics (4)."""
    h, kv, k = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    return {"flops": batch * 4 * k * h * seen_pairs(s, window),
            "bytes": batch * s * (2 * k * (2 * h + 2 * kv) + 4 * h)}


def flash_bwd_call(cfg: dict, s: int, window, batch: int = 1) -> dict:
    """One call of the backward kernel: scores again, dP, dV, dK, dQ: 5
    products a seen pair; reads q, k, v, dO, the statistics and delta,
    writes dq (2 bytes) and a query head's dk, dv in float32."""
    h, kv, k = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    return {"flops": batch * 10 * k * h * seen_pairs(s, window),
            "bytes": batch * s * (2 * k * (3 * h + 2 * kv) + 8 * h
                                  + 8 * k * h)}


def experts_pass(cfg: dict, items: float) -> dict:
    """One forward pass of the three grouped products over ``items`` rows
    routed to held experts (the backward pass is twice this): reads each
    held expert's three matrices (bfloat16) and the rows, writes the
    results."""
    d, f, held = cfg["d_model"], cfg["expert_d_ff"], cfg["experts_held"][1]
    return {"flops": 3 * 2 * items * d * f,
            "bytes": 2 * (3 * held * d * f + items * (2 * d + 3 * f + d))}


def roofline_share(work: dict, seconds: float, peak: dict) -> float:
    """% of the least time the chip could take (operations over its bf16
    peak or bytes over its HBM rate, whichever is longer) in ``seconds``."""
    least = max(work["flops"] / peak["bf16_flops_per_s"],
                work["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
