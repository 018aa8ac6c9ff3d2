"""Operations and bytes of a decoder whose mixers are gated short
convolutions beside grouped attention, with leading dense layers and routed
gated experts, counted from shapes and from the equations (never from XLA,
and not from what a kernel happens to do), beside ``flops_lm.py``'s for the
pattern decoders. ``cfg`` is ``conv_lm_config.reference_cfg``'s dict. A
multiply-add is two operations. Model work only: what a rematerialised
block makes again is not counted.

**The mixer's core is counted from its equations at the operand widths the
configuration's ``precision`` states, 2 bytes a number, whatever a kernel
passes.** A token and channel, forward, K taps: one product B X, K
multiply-adds for the taps (2 K - 1 operations) and one product with C: 2 K
+ 1 operations (7 at K = 3); B, C and X read and y written: 4 numbers.
Backward: B X and c made again (2 K), dy C, dy c, the transpose's K
multiply-adds (2 K - 1), du X and du B, and the taps' gradient (K
multiply-adds): 6 K + 3 operations (21); dy, B, C and X read, dB, dC and dX
written: 7 numbers. The bytes bind by far (14 bytes against 21 operations a
token and channel: the roofline's ridge is at 240 operations a byte).
"""

from __future__ import annotations

from chipbench import flops_lm


def short_conv_fwd_call(cfg: dict, s: int, batch: int = 1) -> dict:
    """One call of the core's forward pass over rows of ``s`` tokens."""
    n, k = batch * s * cfg["d_model"], cfg["taps"]
    return {"flops": (2 * k + 1) * n, "bytes": 2 * 4 * n}


def short_conv_bwd_call(cfg: dict, s: int, batch: int = 1) -> dict:
    """One call of its backward pass (B X and the convolution made again
    among it)."""
    n, k = batch * s * cfg["d_model"], cfg["taps"]
    return {"flops": (6 * k + 3) * n, "bytes": 2 * 7 * n}


def _as_flops_lm(cfg: dict) -> dict:
    return {"n_heads": cfg["n_heads"], "n_kv_heads": cfg["n_kv_heads"],
            "head_dim": cfg["head_dim"]}


def flash_fwd_call(cfg: dict, s: int, batch: int = 1) -> dict:
    return flops_lm.flash_fwd_call(_as_flops_lm(cfg), s, None, batch)


def flash_bwd_call(cfg: dict, s: int, batch: int = 1) -> dict:
    return flops_lm.flash_bwd_call(_as_flops_lm(cfg), s, None, batch)


def forward_flops_per_token(cfg: dict, s: int) -> float:
    """One token's forward pass at row length ``s``, averaged over the row,
    layer by layer: a short-convolution mixer's two projections (d -> 3 d, d
    -> d); an attention mixer's four projections and the causal pairs of its
    core; a dense layer's gated feed-forward; a routed layer's router and the
    experts a token meets HERE on average (top_k x held / routed over), three
    products each; and the head over the held rows. The mixer's core, norms,
    rotary and gates are left out, as element-wise work is everywhere."""
    d = cfg["d_model"]
    heads, kv, k = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    mixer = {
        "conv": 2 * d * 3 * d + 2 * d * d,
        "full_attention": 2 * d * (heads + 2 * kv) * k + 2 * heads * k * d
        + 4 * k * heads * flops_lm.seen_pairs(s, None) / s}
    met = cfg["top_k"] * cfg["experts_held"][1] / cfg["n_experts"]
    routed = 2 * d * cfg["n_experts"] + met * 3 * 2 * d * cfg["expert_d_ff"]
    dense = 3 * 2 * d * cfg["d_ff"]
    return sum(mixer[kind] + (dense if number < cfg["dense_layers"]
                              else routed)
               for number, kind in zip(cfg["numbers"], cfg["kinds"])) \
        + 2 * d * cfg["vocab_held"][1]


def train_flops_per_item(cfg: dict, s: int) -> float:
    """Forward plus backward: three times the forward's matrix work;
    recomputed operations do not count."""
    return 3.0 * forward_flops_per_token(cfg, s)
