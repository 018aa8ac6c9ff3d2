"""Operations and bytes of a decoder whose full and window layers differ in
head count, with a gate on attention's output and a shared expert beside
the routed ones, counted from shapes (never from XLA), beside
``flops_lm.py``'s for the pattern decoders of one head count. ``cfg`` is
``gated_lm_config.reference_cfg``'s dict. A multiply-add is two
operations. Model work only: every product at its own layer's widths, the
seen pairs once, and nothing that a rematerialised block makes again.
"""

from __future__ import annotations

from chipbench import flops_lm

KINDS = ("window", "full")


def window_of(cfg: dict, layer: dict):
    return cfg["window"] if layer["kind"] == "window" else None


def _heads(cfg: dict, layer: dict) -> dict:
    """``flops_lm``'s words for one layer's attention."""
    return {"n_heads": layer["heads"], "n_kv_heads": cfg["n_kv_heads"],
            "head_dim": cfg["head_dim"]}


def attention_flops_per_token(cfg: dict, layer: dict, s: int) -> float:
    """One layer's attention, forward, a token on average: the four
    projections at the layer's own head count, the gate's (d -> a number a
    head), the scores and weighted values of the seen pairs."""
    d, k, h = cfg["d_model"], cfg["head_dim"], layer["heads"]
    proj = 2 * d * (h + 2 * cfg["n_kv_heads"]) * k + 2 * h * k * d
    gate = 2 * d * h if cfg["gate"] else 0
    core = 4 * k * h * flops_lm.seen_pairs(s, window_of(cfg, layer)) / s
    return proj + gate + core


def forward_flops_per_token(cfg: dict, s: int) -> float:
    """One token's forward pass, averaged over the row: every layer's
    attention; a dense layer's gated feed-forward; a sparse layer's router,
    shared expert and the experts a token meets HERE on average (top_k x
    held / routed over); the head over the held rows."""
    d = cfg["d_model"]
    gated = lambda width: 3 * 2 * d * width  # noqa: E731
    met = cfg["top_k"] * cfg["experts_held"][1] / cfg["n_experts"]
    routed = 2 * d * cfg["n_experts"] + gated(cfg["shared_d_ff"]) \
        + met * gated(cfg["expert_d_ff"])
    return sum(attention_flops_per_token(cfg, layer, s)
               + (gated(cfg["d_ff"]) if layer["ffn"] == "dense" else routed)
               for layer in cfg["layers"]) + 2 * d * cfg["vocab_held"][1]


def train_flops_per_item(cfg: dict, s: int) -> float:
    """Forward plus backward: three times the forward's matrix work;
    recomputed operations do not count."""
    return 3.0 * forward_flops_per_token(cfg, s)


# -- a kernel's work, one call -------------------------------------------------

def flash_fwd_call(cfg: dict, layer: dict, s: int, batch: int = 1) -> dict:
    """One call of the forward kernel on ``layer``: ``flops_lm``'s count at
    the layer's own query heads and mask (a window layer's 64 heads x the
    pairs inside 512, a full layer's 48 x the causal pairs; q, k, v, out
    and lse once a call)."""
    return flops_lm.flash_fwd_call(_heads(cfg, layer), s,
                                   window_of(cfg, layer), batch)


def flash_bwd_call(cfg: dict, layer: dict, s: int, batch: int = 1) -> dict:
    """One call of the backward kernel on ``layer``, likewise."""
    return flops_lm.flash_bwd_call(_heads(cfg, layer), s,
                                   window_of(cfg, layer), batch)
