"""Operations and bytes of a latent-attention MoE decoder, counted from
shapes (never from XLA), beside ``flops_lm.py``'s for the pattern decoders
with a window. ``cfg`` is ``mla_lm_config.reference_cfg``'s dict. A
multiply-add is two operations. Model work only: every product at its own
widths, the causal pairs once, and nothing that a rematerialised block
makes again.
"""

from __future__ import annotations

from chipbench import flops_lm


def causal_pairs(s: int) -> int:
    return flops_lm.seen_pairs(s, None)


def _qk(cfg: dict) -> int:
    return cfg["nope"] + cfg["rope"]


def attention_flops_per_token(cfg: dict, s: int) -> float:
    """One layer's attention, forward, a token on average: q's projection,
    the K/V down-projection, the up-projection from the latent, the scores
    (nope + rope deep) and the weighted values (v wide) of the causal
    pairs, the output projection."""
    d, h = cfg["d_model"], cfg["n_heads"]
    proj = 2 * d * h * _qk(cfg) + 2 * d * (cfg["rank"] + cfg["rope"]) \
        + 2 * cfg["rank"] * h * (cfg["nope"] + cfg["v_dim"]) \
        + 2 * h * cfg["v_dim"] * d
    core = 2 * (_qk(cfg) + cfg["v_dim"]) * h * causal_pairs(s) / s
    return proj + core


def forward_flops_per_token(cfg: dict, s: int) -> float:
    """One token's forward pass, averaged over the row: every layer's
    attention; a dense layer's gated feed-forward; a routed layer's router,
    shared experts and the experts a token meets HERE on average (top_k x
    held / routed over); the head over the held rows."""
    d = cfg["d_model"]
    gated = lambda width: 3 * 2 * d * width  # noqa: E731
    dense = cfg["dense_layers"]
    met = cfg["top_k"] * cfg["experts_held"][1] / cfg["n_experts"]
    routed = 2 * d * cfg["n_experts"] + gated(cfg["shared_d_ff"]) \
        + met * gated(cfg["expert_d_ff"])
    return cfg["n_layers"] * attention_flops_per_token(cfg, s) \
        + dense * gated(cfg["d_ff"]) + (cfg["n_layers"] - dense) * routed \
        + 2 * d * cfg["vocab_held"][1]


def train_flops_per_item(cfg: dict, s: int) -> float:
    """Forward plus backward: three times the forward's matrix work;
    recomputed operations do not count."""
    return 3.0 * forward_flops_per_token(cfg, s)


# -- a kernel's work, one call -------------------------------------------------

def flash_fwd_call(cfg: dict, s: int, batch: int = 1) -> dict:
    """One call of the forward kernel on a latent layer: the scores (nope +
    rope deep) and the weighted values (v wide) of the SEEN pairs; reads q,
    a head's k_nope and v, and the shared rotary key ONCE a layer, whatever
    the program does with it (a kernel that reads a copy a head reads lower
    for it); writes the output (2 bytes) and the row statistics (4)."""
    h = cfg["n_heads"]
    return {"flops": batch * 2 * (_qk(cfg) + cfg["v_dim"]) * h
            * causal_pairs(s),
            "bytes": batch * s * (2 * h * (_qk(cfg) + cfg["nope"]
                                           + 2 * cfg["v_dim"])
                                  + 2 * cfg["rope"] + 4 * h)}


def flash_bwd_call(cfg: dict, s: int, batch: int = 1) -> dict:
    """One call of the backward kernel: the scores again, dQ and dK (nope +
    rope deep each), dP and dV (v deep): five products a seen pair; reads q,
    k_nope, v, dO, the statistics and delta and the shared key once; writes
    dq (2 bytes), a head's dk_nope and dv in float32 and the shared key's
    gradient once."""
    h = cfg["n_heads"]
    return {"flops": batch * 2 * (3 * _qk(cfg) + 2 * cfg["v_dim"]) * h
            * causal_pairs(s),
            "bytes": batch * s * (
                2 * h * (2 * _qk(cfg) + cfg["nope"] + 2 * cfg["v_dim"])
                + 8 * h + 2 * cfg["rope"]
                + 4 * h * (cfg["nope"] + cfg["v_dim"]) + 4 * cfg["rope"])}
