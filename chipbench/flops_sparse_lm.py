"""Operations and bytes of a decoder whose attention runs over selected
keys, counted from shapes (never from XLA), beside ``flops_lm.py``'s for
the pattern decoders with a window. ``cfg`` is
``sparse_lm_config.reference_cfg``'s dict. A multiply-add is two
operations. Model work only: the selected pairs for attention's core (a
masked kernel computes every causal pair and is not credited for it), the
causal pairs for the index scores (every one of them has to be scored to
choose); what a rematerialised block makes again is not counted.
"""

from __future__ import annotations

from chipbench import flops_lm


def selected_pairs(s: int, top_keys: int) -> int:
    """(query, key) pairs attention runs over at length ``s``: query t
    takes min(top_keys, t + 1) keys, which is what a causal window of
    ``top_keys`` lets through."""
    return flops_lm.seen_pairs(s, top_keys)


def causal_pairs(s: int) -> int:
    return flops_lm.seen_pairs(s, None)


def indexer_flops_per_token(cfg: dict, s: int) -> float:
    """The indexer of one layer, forward (it has no backward: no gradient
    reaches it): three projections and the scores of the causal pairs."""
    d, h, k = cfg["d_model"], cfg["index_heads"], cfg["index_dim"]
    return 2 * d * (h * k + k + h) + 2 * h * k * causal_pairs(s) / s


def trained_forward_flops_per_token(cfg: dict, s: int) -> float:
    """One token's forward pass through what is trained, averaged over the
    row: projections, attention's scores and weighted values over the
    selected pairs, the router, the experts a token meets HERE on average
    (top_k x held / routed over), and the head over the held rows."""
    d, h, kv, k = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], \
        cfg["head_dim"]
    proj = 2 * d * (h + 2 * kv) * k + 2 * h * k * d
    met = cfg["top_k"] * cfg["experts_held"][1] / cfg["n_experts"]
    experts = met * 3 * 2 * d * cfg["expert_d_ff"]
    router = 2 * d * cfg["n_experts"]
    core = 4 * k * h * selected_pairs(s, cfg["top_keys"]) / s
    return cfg["n_layers"] * (proj + experts + router + core) \
        + 2 * d * cfg["vocab_held"][1]


def train_flops_per_item(cfg: dict, s: int) -> float:
    """Forward plus backward of what is trained (three times its forward's
    matrix work) and the indexers' forward, once."""
    return 3.0 * trained_forward_flops_per_token(cfg, s) \
        + cfg["n_layers"] * indexer_flops_per_token(cfg, s)


# -- a kernel's work, one call -------------------------------------------------

def _bits_bytes(s: int, batch: int) -> int:
    """The packed selection, a bit a pair of the square, read once."""
    return batch * s * s // 8


def sparse_fwd_call(cfg: dict, s: int, batch: int = 1) -> dict:
    """One call of the forward kernel ``sparse_fwd``: ``flash_fwd``'s work
    over the selected pairs, and the selection's bits."""
    work = flops_lm.flash_fwd_call(cfg, s, cfg["top_keys"], batch)
    return {"flops": work["flops"],
            "bytes": work["bytes"] + _bits_bytes(s, batch)}


def sparse_bwd_call(cfg: dict, s: int, batch: int = 1) -> dict:
    """One call of the backward kernel ``sparse_bwd``, likewise."""
    work = flops_lm.flash_bwd_call(cfg, s, cfg["top_keys"], batch)
    return {"flops": work["flops"],
            "bytes": work["bytes"] + _bits_bytes(s, batch)}
