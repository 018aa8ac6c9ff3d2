"""Operations and bytes of a decoder-hybrid-decoder with state-space layers,
counted from the model's shapes (never from XLA, and not from what the
program's kernels happen to do), beside ``flops_lm.py``'s for the pattern
decoders. ``cfg`` is ``ssm_lm_config.reference_cfg``'s dict. A multiply-add
is two operations. Model work only: what a rematerialised block makes
again is not counted.

**The selective scan's work is the recurrence's own, whatever implements
it**: a state update h = exp(Delta A) h + (Delta x) B is a multiply for
Delta A, the exponential, a multiply-add and a multiply for (Delta x) B,
and the read-out y += h C a multiply-add: ``SCAN_FWD_OPS`` = 7 operations
a (token, channel, state), T d_inner N of them a layer. Backward, from the
chunks' entering states (which the forward's contract writes out): the
state made again (5: no read-out), and for each (token, channel, state)
dh += dy C (2), the decay again (2), d(h_prev) (1), dDelta's two terms
(5), dx's (2), dA's (2), dB's and dC's products and sums (4):
``SCAN_BWD_OPS`` = 23. The exponentials are counted as one operation each;
on a chip whose exponentials run on a unit of their own that is the lower
count. Bytes, one call, each operand read once and each result written
once: forward reads x and Delta (float32, T d_inner each), A, B and C and
writes y and the chunks' entering states at a chunk of ``COUNTED_CHUNK``
tokens; backward reads those and dy and writes dx, dDelta, dA, dB, dC.
Against a v5e's peaks the bytes bind (0.5 GB and 0.84 GB a layer at 8192 x
5120: 0.6 and 1.0 ms) and not the 4.7 and 15.4 G operations: the vector
unit's rate is not the bf16 peak the roofline divides by, so a share read
here is of the memory's time, which no walk on the vector unit can pass.

Attention is differential: a layer's two softmaxes run the H / 2 query
pairs each on keys ``head_dim`` wide and the pair's joined value, 2
``head_dim`` wide: 2 (w + 2 w) operations a seen pair, query pair and map,
over the causal pairs or the window's. A call of a kernel is ONE map (q_1
on k_1, or q_2 on k_2): H / 2 query heads on H_kv / 2 key heads and as
many joined values.
"""

from __future__ import annotations

from chipbench import flops_lm

SCAN_FWD_OPS = 7
SCAN_BWD_OPS = 23
#: tokens a chunk of the counted form's entering states
COUNTED_CHUNK = 128


def kinds(cfg: dict) -> list:
    """The kind of each held layer, by the published rule (as
    reference/ssm_lm.py::kind_of has it; written again here because the
    benchmark's counting imports no jax)."""
    half = cfg["of"] // 2

    def kind(layer):
        if layer % 2 == 0:
            return "mamba" if layer <= half else "gmu"
        return "window" if layer < half else \
            "full" if layer == half + 1 else "cross"

    return [kind(layer) for layer in cfg["layers"]]


def scan_fwd_call(cfg: dict, s: int, batch: int = 1) -> dict:
    """One call of the forward scan over rows of ``s`` tokens."""
    di, n = cfg["d_inner"], cfg["d_state"]
    chunks = -(-s // COUNTED_CHUNK)
    return {"flops": batch * SCAN_FWD_OPS * s * di * n,
            "bytes": 4 * (batch * (3 * s * di + 2 * s * n + chunks * di * n)
                          + di * n)}


def scan_bwd_call(cfg: dict, s: int, batch: int = 1) -> dict:
    """One call of the backward scan."""
    di, n = cfg["d_inner"], cfg["d_state"]
    chunks = -(-s // COUNTED_CHUNK)
    return {"flops": batch * SCAN_BWD_OPS * s * di * n,
            "bytes": 4 * (batch * (5 * s * di + 4 * s * n + chunks * di * n)
                          + 2 * di * n)}


def _window(cfg: dict, kind: str):
    return cfg["window"] if kind == "window" else None


def flash_fwd_call(cfg: dict, s: int, kind: str, batch: int = 1) -> dict:
    """One call of the forward attention kernel on a layer of ``kind``:
    one of the two maps. Scores ``w`` deep and weighted values 2 ``w``
    deep over the seen pairs; reads q_i, k_i and the joined v, writes the
    output (2 bytes) and the row statistics (4)."""
    h, kv, w = cfg["n_heads"] // 2, cfg["n_kv_heads"] // 2, cfg["head_dim"]
    return {"flops": batch * 2 * 3 * w * h
            * flops_lm.seen_pairs(s, _window(cfg, kind)),
            "bytes": batch * s * (2 * (h * w + kv * w + kv * 2 * w
                                       + h * 2 * w) + 4 * h)}


def flash_bwd_call(cfg: dict, s: int, kind: str, batch: int = 1) -> dict:
    """One call of the backward kernel: the scores again, dQ and dK (w
    deep each), dP and dV (2 w deep): reads q_i, k_i, v, dO, the statistics
    and delta, writes dq (2 bytes) and a query head's dk and dv in
    float32."""
    h, kv, w = cfg["n_heads"] // 2, cfg["n_kv_heads"] // 2, cfg["head_dim"]
    return {"flops": batch * 2 * (3 * w + 2 * 2 * w) * h
            * flops_lm.seen_pairs(s, _window(cfg, kind)),
            "bytes": batch * s * (2 * (2 * h * w + kv * w + kv * 2 * w
                                       + h * 2 * w) + 8 * h
                                  + 4 * h * 3 * w)}


def forward_flops_per_token(cfg: dict, s: int) -> float:
    """One token's forward pass at row length ``s``, averaged over the row:
    every layer's feed-forward; a Mamba layer's four projections and its
    scan's updates; a memory unit's two projections; an attention layer's
    projections (a cross layer has no k and v) and the seen pairs of its
    two maps at 64 / 128; the tied head over the held rows. Convolutions,
    norms, gates and the differential combination are left out, as
    element-wise work is everywhere."""
    d, f = cfg["d_model"], cfg["d_ff"]
    h, kv, w = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    di, n, r = cfg["d_inner"], cfg["d_state"], cfg["dt_rank"]
    mixer = {
        "mamba": 2 * d * 2 * di + 2 * di * (r + 2 * n) + 2 * r * di
        + 2 * di * d + SCAN_FWD_OPS * di * n,
        "gmu": 2 * d * di + 2 * di * d,
    }
    for kind in ("window", "full", "cross"):
        own_kv = 0 if kind == "cross" else 2 * 2 * d * kv * w
        core = 2 * 2 * 3 * w * (h // 2) \
            * flops_lm.seen_pairs(s, _window(cfg, kind)) / s
        mixer[kind] = 2 * d * h * w + own_kv + 2 * h * w * d + core
    return sum(3 * 2 * d * f + mixer[k] for k in kinds(cfg)) \
        + 2 * d * cfg["vocab_held"][1]


def train_flops_per_item(cfg: dict, s: int) -> float:
    """Forward plus backward: three times the forward's matrix work, but
    the scans' backward by its own count; recomputed operations do not
    count."""
    scans = kinds(cfg).count("mamba") * cfg["d_inner"] * cfg["d_state"]
    fwd, bwd = scans * SCAN_FWD_OPS, scans * SCAN_BWD_OPS
    return 3.0 * (forward_flops_per_token(cfg, s) - fwd) + fwd + bwd
