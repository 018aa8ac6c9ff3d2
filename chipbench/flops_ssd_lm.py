"""Operations and bytes of a decoder of one sublayer a block (Mamba-2
mixers, attention, experts of two matrices), counted from shapes and from
the equations (never from XLA, and not from what a kernel happens to do),
beside ``flops_lm.py``'s for the pattern decoders. ``cfg`` is
``ssd_lm_config.reference_cfg``'s dict. A multiply-add is two operations.
Model work only: what a rematerialised block makes again is not counted.

**The scan's work is the matmul form's products of a chunk of the
PUBLISHED ``chunk_size`` (128 tokens), whatever chunk or algorithm the
program runs.** A group of Hg heads that share q = C and k = B (width N),
a head's values v = dt x (width P), a chunk of C tokens, forward:

    Q K^T                            once a GROUP          2 C^2 N
    (D^h * Q K^T) V^h                a head                2 C^2 P
    Q S^h, (decayed K)^T V^h         the two state products, a head
                                                           2 x 2 C N P

= 2 C^2 N + Hg (2 C^2 P + 4 C N P) (54.5 M at C 128, N 128, P 64, Hg 8:
6.7 k a token a head). Backward, by its own products, from the chunks'
entering states (which the forward's contract writes out):

    Q K^T made again, dQ = (sum_h dPD^h) K, dK = (sum_h dPD^h)^T Q
                                     once a group          3 x 2 C^2 N
    dP^h = dO V^T, dV^h = P^T dO     a head                2 x 2 C^2 P
    dV^h += Kd dS', dO S^T, V dS'^T, Qg^T dO   a head      4 x 2 C N P

= 6 C^2 N + Hg (4 C^2 P + 8 C N P). Bytes, one call: q, k (bfloat16, a
group's), v read and o written (bfloat16, a head's), g read (float32, a
number a head and token), and the chunks' entering states (float32, N P a
chunk and head) written by the forward and read by the backward, which
also reads dO and writes dq, dk, dv, dg.

The experts are of TWO matrices: a pass is two grouped products f wide
(``flops_lm.experts_pass`` counts three, a gate's among them). The
activation's pass between them moves a filled row's f numbers in and out
(forward: u read, h written; backward: dh and u read, du written), bound
by bytes.
"""

from __future__ import annotations

from chipbench import flops_lm


def scan_fwd_chunk_flops(c: int, n: int, p: int, hg: int) -> int:
    """One group of ``hg`` heads, one chunk, forward."""
    return 2 * c * c * n + hg * (2 * c * c * p + 4 * c * n * p)


def scan_bwd_chunk_flops(c: int, n: int, p: int, hg: int) -> int:
    """One group, one chunk, backward (the list in the module's docstring)."""
    return 6 * c * c * n + hg * (4 * c * c * p + 8 * c * n * p)


def _scan_sizes(cfg: dict, s: int):
    h, g = cfg["ssd_heads"], cfg["ssd_groups"]
    return h, g, cfg["ssd_state"], cfg["ssd_head_dim"], cfg["chunk"], \
        -(-s // cfg["chunk"])


def scan_fwd_call(cfg: dict, s: int, batch: int = 1) -> dict:
    """One call of the forward scan over a row of ``s`` tokens."""
    h, g, n, p, c, chunks = _scan_sizes(cfg, s)
    return {"flops": batch * g * chunks * scan_fwd_chunk_flops(c, n, p,
                                                                h // g),
            "bytes": batch * (s * (2 * 2 * g * n + 2 * 2 * h * p + 4 * h)
                              + 4 * chunks * h * n * p)}


def scan_bwd_call(cfg: dict, s: int, batch: int = 1) -> dict:
    """One call of the backward scan."""
    h, g, n, p, c, chunks = _scan_sizes(cfg, s)
    return {"flops": batch * g * chunks * scan_bwd_chunk_flops(c, n, p,
                                                                h // g),
            "bytes": batch * (s * (2 * 4 * g * n + 2 * 4 * h * p + 8 * h)
                              + 4 * chunks * h * n * p)}


def _as_flops_lm(cfg: dict) -> dict:
    return {"n_heads": cfg["n_heads"], "n_kv_heads": cfg["n_kv_heads"],
            "head_dim": cfg["head_dim"]}


def flash_fwd_call(cfg: dict, s: int, batch: int = 1) -> dict:
    return flops_lm.flash_fwd_call(_as_flops_lm(cfg), s, None, batch)


def flash_bwd_call(cfg: dict, s: int, batch: int = 1) -> dict:
    return flops_lm.flash_bwd_call(_as_flops_lm(cfg), s, None, batch)


def experts_pass(cfg: dict, items: float) -> dict:
    """One forward pass of the TWO grouped products over ``items`` rows
    routed to held experts (the backward pass is twice this): reads each
    held expert's two matrices (bfloat16) and the rows, writes the
    results."""
    d, f, held = cfg["d_model"], cfg["expert_d_ff"], cfg["experts_held"][1]
    return {"flops": 2 * 2 * items * d * f,
            "bytes": 2 * (2 * held * d * f + items * (2 * d + 2 * f))}


def expert_act_call(cfg: dict, items: float, backward: bool) -> dict:
    """One call of the activation's pass over ``items`` filled rows: u read
    and h written, or dh and u read and du written (bfloat16); a compare, a
    product or two an element."""
    f = cfg["expert_d_ff"]
    return {"flops": (3 if backward else 2) * items * f,
            "bytes": 2 * (3 if backward else 2) * items * f}


def forward_flops_per_token(cfg: dict, s: int) -> float:
    """One token's forward pass at row length ``s``, averaged over the row,
    block by block: a Mamba-2 block's two projections and its scan (the
    counted form); an attention block's four projections and the causal
    pairs of its core; an expert block's router, shared expert and the
    experts a token meets HERE on average (top_k x held / routed over), two
    products each; and the head over the held rows. Convolutions, norms and
    gates are left out, as element-wise work is everywhere."""
    d = cfg["d_model"]
    h, g, n, p, c, _ = _scan_sizes(cfg, s)
    inner = h * p
    mixer = 2 * d * (2 * inner + 2 * g * n + h) + 2 * inner * d \
        + g * scan_fwd_chunk_flops(c, n, p, h // g) / c
    heads, kv, k = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    attention = 2 * d * (heads + 2 * kv) * k + 2 * heads * k * d \
        + 4 * k * heads * flops_lm.seen_pairs(s, None) / s
    met = cfg["top_k"] * cfg["experts_held"][1] / cfg["n_experts"]
    experts = 2 * d * cfg["n_experts"] + 2 * 2 * d * cfg["shared_d_ff"] \
        + met * 2 * 2 * d * cfg["expert_d_ff"]
    a_block = {"M": mixer, "*": attention, "E": experts}
    return sum(a_block[letter] for letter in cfg["letters"]) \
        + 2 * d * cfg["vocab_held"][1]


def train_flops_per_item(cfg: dict, s: int) -> float:
    """Forward plus backward: three times the forward's matrix work, but
    the scans' backward by its own count; recomputed operations do not
    count."""
    h, g, n, p, c, _ = _scan_sizes(cfg, s)
    scans = cfg["letters"].count("M") * g / c
    fwd = scans * scan_fwd_chunk_flops(c, n, p, h // g)
    bwd = scans * scan_bwd_chunk_flops(c, n, p, h // g)
    return 3.0 * (forward_flops_per_token(cfg, s) - fwd) + fwd + bwd
