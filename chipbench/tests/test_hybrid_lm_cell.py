"""The hybrid linear-attention decoder's cell (``olmo-hybrid-7b.steady-8k``)
at sizes a test run can hold: the cut, its FLOP and byte counts against
counts by brute force, its readers on canned records, the planted faults
and the control failing ``correct``, its rehearsal, and a program without
the mechanism refused. ``python3 chipbench/tests/test_hybrid_lm_cell.py
FAULT [SEED]`` reads one planted fault at the cell's own sizes on the
chip."""

import contextlib
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from chipbench import (checks, flops_hybrid_lm, flops_lm, hybrid_lm_config,
                       run as harness)
from chipbench.checks import hybrid_lm_train3
from chipbench.reference import hybrid_lm as reference
from chipbench.run import _reader
from chipbench.runners import hybrid_lm_trial_steps

CELL = "olmo-hybrid-7b.steady-8k"


def context(tmp_path, seed=2 ** 31 + 33, rehearsal=True):
    _, ctx = harness.cell_context(CELL, seed, 1.0, False, rehearsal,
                                  time.time())
    ctx.run_dir = str(tmp_path)
    return ctx


def config():
    with open(os.path.join(harness.ROOT, "chipbench", "configs",
                           "olmo-hybrid-7b-tp2.json")) as f:
        return json.load(f)


# -- the configuration --------------------------------------------------------

def test_the_cut_is_the_issue_s():
    c = config()
    cfg = hybrid_lm_config.reference_cfg(c)
    assert (cfg["d_model"], cfg["d_ff"], cfg["n_heads"], cfg["head_dim"]) \
        == (3840, 11008, 15, 128)
    assert (cfg["linear_heads"], cfg["key_dim"], cfg["value_dim"],
            cfg["conv"]) == (15, 96, 192, 4)
    assert cfg["linear"] == [True, True, True, False] and cfg["neg_eigval"]
    assert cfg["vocab_held"] == [0, 12544] and 12544 * 8 == 100352
    shapes = reference.param_shapes(cfg)
    size = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert size(shapes["h0"]["linear"]) == 44_375_262
    assert size(shapes["h3"]["attn"]) == 29_495_040
    assert size(shapes["h0"]["mlp"]) == 126_812_160
    assert size(shapes) == 766_241_946               # x 16 bytes = 12.26 GB
    assert set(c["reduced"]) == {
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "linear_num_key_heads", "linear_num_value_heads", "vocab_size"}
    assert {k: c["published"][k] for k in c["reduced"]} == {
        "num_hidden_layers": 32, "num_attention_heads": 30,
        "num_key_value_heads": 30, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "vocab_size": 100352}
    desc = hybrid_lm_config.description(c)
    assert desc["num_attention_heads"] == 30       # the layer's, not held
    assert desc["heads_held"] == [0, 15] and desc["linear_num_key_heads"] == 30


def test_every_number_of_the_catalog_s_config_is_kept():
    """Top-level numbers as the catalog has them, but for ``reduced``; the
    nested groups (``layer_types``, ``rope_parameters``) whole."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    c = config()
    assert c["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in c["reduced"]:
            assert c[key] == value, key


# -- operations and bytes -----------------------------------------------------

def chunk_products_by_brute_force(c, k, v):
    """(forward, backward) multiply-adds x 2 of one head's chunk, every
    product of the chunked form written as (rows, depth, columns)."""
    fwd = [(c, k, c), (c, c, k), (c, k, c),          # K K^T, T K, Q K^T
           (c, c, v), (c, c, v),                     # T V, (Q K^T) U
           (c, k, v), (c, k, v), (k, c, v)]          # W S, Q S, K^T U
    again = [(c, k, c), (c, c, k), (c, k, c), (c, c, v), (c, k, v)]
    bwd = again + [
        (c, c, v), (c, k, v), (c, v, c), (c, c, v), (c, v, c),  # dU dP dVb dT
        (c, v, k), (k, c, v), (k, c, v), (c, v, k), (c, v, k),  # 5 x C k v
        (c, c, k), (c, k, c), (c, c, k), (c, c, k),  # dKg, dT, dQ, dK
        (c, c, k)]                                # (dG + dG^T) K, summed first
    count = lambda ps: sum(2 * a * b * d for a, b, d in ps)  # noqa: E731
    return count(fwd) + c ** 3, count(bwd) + c ** 3 + 4 * c ** 3


@pytest.mark.parametrize("c, k, v", [(64, 96, 192), (8, 4, 6), (128, 96, 192)])
def test_the_scan_s_count_is_the_products_of_one_chunk(c, k, v):
    fwd, bwd = chunk_products_by_brute_force(c, k, v)
    assert flops_hybrid_lm.scan_fwd_chunk_flops(c, k, v) == fwd
    assert flops_hybrid_lm.scan_bwd_chunk_flops(c, k, v) == bwd


def test_the_issue_s_count_at_a_chunk_of_64():
    assert flops_hybrid_lm.COUNTED_CHUNK == 64
    assert flops_hybrid_lm.scan_fwd_chunk_flops(64, 96, 192) == 12_845_056
    cfg = hybrid_lm_config.reference_cfg(config())
    call = flops_hybrid_lm.linear_fwd_call(cfg, 8192)
    assert call["flops"] == 15 * 128 * 12_845_056           # 24.7 GFLOP
    assert call["flops"] / 8192 / 15 == pytest.approx(200.7e3, rel=1e-3)
    # q, k, v, o in bfloat16, g and beta in float32, the states in float32
    assert call["bytes"] == 15 * (8192 * (2 * (96 + 96 + 192 + 192) + 8)
                                  + 4 * 128 * 96 * 192)
    back = flops_hybrid_lm.linear_bwd_call(cfg, 8192)
    assert back["flops"] == 15 * 128 * 31_981_568


def test_the_full_layer_s_kernels_count_15_on_15_heads():
    cfg = hybrid_lm_config.reference_cfg(config())
    heads = {"n_heads": 15, "n_kv_heads": 15, "head_dim": 128}
    assert flops_hybrid_lm.flash_fwd_call(cfg, 8192) \
        == flops_lm.flash_fwd_call(heads, 8192, None)


def test_train_flops_by_brute_force_at_a_small_size():
    cfg = dict(d_model=8, d_ff=12, n_heads=2, head_dim=4, linear_heads=2,
               key_dim=3, value_dim=5, conv=4,
               linear=[True, False, True], vocab_held=[0, 10])
    s, c = 128, 64
    fwd_chunk, bwd_chunk = chunk_products_by_brute_force(c, 3, 5)
    dense = scans_f = scans_b = 0
    for linear in cfg["linear"]:
        dense += s * 3 * 2 * 8 * 12                       # the feed-forward
        if linear:
            dense += s * 2 * 8 * 2 * (3 + 3 + 5 + 5 + 1 + 1)  # q k v g a b
            dense += s * 2 * 2 * 5 * 8                    # out
            scans_f += 2 * (s // c) * fwd_chunk
            scans_b += 2 * (s // c) * bwd_chunk
        else:
            dense += s * 4 * 2 * 8 * 2 * 4                # q k v out
            dense += sum(4 * 4 * 2 * (t + 1) for t in range(s))  # the core
    dense += s * 2 * 8 * 10                               # the head
    assert flops_hybrid_lm.forward_flops_per_token(cfg, s) * s \
        == pytest.approx(dense + scans_f)
    assert flops_hybrid_lm.train_flops_per_item(cfg, s) * s \
        == pytest.approx(3 * dense + scans_f + scans_b)


def test_the_issue_s_model_work_a_token():
    cfg = hybrid_lm_config.reference_cfg(config())
    assert flops_hybrid_lm.train_flops_per_item(cfg, 8192) / 1e9 \
        == pytest.approx(4.4, abs=0.1)


# -- the readers --------------------------------------------------------------

def test_the_trace_readers_on_a_few_operations(monkeypatch):
    from chipbench import program_trace

    mixer = "jit(train_step)/h0/linear/linear_attention/"
    back = "jit(train_step)/transpose(jvp(h0))/linear/linear_attention/"
    ops = [(mixer + "q/dot_general", 0.0, 0.1),
           (mixer + "linear_attention.core/jit(_fwd_pallas)/linear_scan_fwd/"
            "pallas_call", 0.1, 0.1),
           (back + "linear_attention.core/jit(_bwd_pallas)/linear_scan_bwd/"
            "pallas_call", 0.2, 0.2),
           (back + "linear_attention.core/cumsum", 0.4, 0.05),
           ("jit(train_step)/h0/mlp/ffn/dot_general", 0.5, 0.3),
           ("jit(train_step)/h3/attn/attention/attention.core/flash_fwd/"
            "pallas_call", 0.8, 0.1)]
    monkeypatch.setattr(program_trace, "load", lambda directory: {
        "ops": {"/device:TPU:0": ops},
        "programs": {"/device:TPU:0": ["jit_train_step"] * 2}})
    monkeypatch.setattr(program_trace, "run_dir", lambda: "unused")
    work = hybrid_lm_trial_steps.kernel_work(config())
    work = {**work, "linear_layers": 1, "layers": 1,
            "linear_scan_fwd": work["linear_scan_fwd"][:1],
            "linear_scan_bwd": work["linear_scan_bwd"][:1]}
    rec = {"step_s": [0.5, 0.5], "trace": {"busy_s": 1.0, "window_s": 1.0},
           "kernel_work": work, "device_kind": "TPU v5 lite"}
    assert _reader("linear_attention_device_ms").read(rec) \
        == pytest.approx(225)
    assert _reader("linear_core_device_ms").read(rec) == pytest.approx(175)
    assert _reader("ffn_device_ms").read(rec) == pytest.approx(150)
    assert _reader("attention_core_device_ms").read(rec) \
        == pytest.approx(50)
    # one call in the slice: 24.7 GFLOP over 0.1 s; its bytes bind (0.2 ms
    # at 819 GB/s against 0.13 ms at 197 TFLOP/s)
    fwd = work["linear_scan_fwd"][0]
    least = max(fwd["flops"] / 197e12, fwd["bytes"] / 819e9)
    assert _reader("linear_fwd_roofline").read(rec) == pytest.approx(
        100 * least / 0.1, rel=1e-6)
    assert 0 < _reader("linear_bwd_roofline").read(rec) < 1
    assert _reader("flash_fwd_roofline").read(rec) > 0
    for name in ("linear_fwd_roofline", "linear_core_device_ms",
                 "ffn_device_ms"):
        assert _reader(name).read({"step_s": [0.5]}) is None  # no trace


def test_the_cell_s_line_names_every_metric_the_issue_lists():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert mine == {
        "linear_attention_device_ms", "linear_core_device_ms",
        "linear_fwd_roofline", "linear_bwd_roofline",
        "ffn_device_ms", "attention_core_device_ms",
        "flash_fwd_roofline", "flash_bwd_roofline",
        "readout_xent_device_ms", "optimizer_device_ms",
        "scoped_device_share", "program_load_s",
        "compile_cache_hit_share", "embed_device_ms",
        "attention_proj_device_ms", "trunk_device_ms", "unnamed_device_ms",
        "forward_again_device_ms", "backward_device_ms"}
    assert all(os.path.exists(os.path.join(
        harness.HERE, "readers", name + ".py")) for name in mine)


# -- correct ------------------------------------------------------------------

def two_sides(ctx, monkeypatch, faulty_loss):
    """``checks.compare`` of the reference with ``faulty_loss`` in its
    loss's place (as the program's side) against the reference."""
    first = hybrid_lm_trial_steps.readings(ctx)
    rows = first.pop("rows")
    with monkeypatch.context() as m:
        m.setattr(reference, "loss", faulty_loss)
        broken = hybrid_lm_train3.reference_readings(ctx.config, ctx.seed,
                                                     rows, "float32")
    sound = checks.run(ctx.config, ctx.seed, rows, first)
    faulty = checks.run(ctx.config, ctx.seed, rows, broken)
    return sound, faulty


REAL_LOSS = reference.loss
REAL = {name: getattr(reference, name)
        for name in ("recurrence", "short_conv", "_unit", "gated_norm")}


@contextlib.contextmanager
def in_place_of(name, stand_in):
    setattr(reference, name, stand_in)
    try:
        yield
    finally:
        setattr(reference, name, REAL[name])


def the_decay_left_out(params, rows, cfg, mode="float32"):
    """alpha = 1: the state forgets nothing."""
    with in_place_of("recurrence", lambda q, k, v, g, beta: REAL[
            "recurrence"](q, k, v, jnp.zeros_like(g), beta)):
        return REAL_LOSS(params, rows, cfg, mode)


def beta_not_doubled(params, rows, cfg, mode="float32"):
    return REAL_LOSS(params, rows, {**cfg, "neg_eigval": False}, mode)


def the_state_not_carried_across_a_chunk_boundary(params, rows, cfg,
                                                  mode="float32"):
    """Every 64 tokens the state starts at zero again."""
    def in_pieces(q, k, v, g, beta):
        s = q.shape[0]
        piece = 64 if s % 64 == 0 else 32
        cut = lambda x: x.reshape(s // piece, piece, *x.shape[1:])  # noqa
        return jax.lax.map(lambda xs: REAL["recurrence"](*xs), tuple(
            map(cut, (q, k, v, g, beta)))).reshape(s, *v.shape[1:])

    with in_place_of("recurrence", in_pieces):
        return REAL_LOSS(params, rows, cfg, mode)


def a_convolution_that_sees_one_token_ahead(params, rows, cfg,
                                            mode="float32"):
    def ahead(x, taps):
        shifted = jnp.concatenate([x[1:], jnp.zeros_like(x[:1])])
        return REAL["short_conv"](shifted, taps)

    with in_place_of("short_conv", ahead):
        return REAL_LOSS(params, rows, cfg, mode)


def q_and_k_not_normalised(params, rows, cfg, mode="float32"):
    with in_place_of("_unit", lambda x: x):
        return REAL_LOSS(params, rows, cfg, mode)


def the_output_gate_left_out(params, rows, cfg, mode="float32"):
    with in_place_of("gated_norm", lambda o, z, scale, eps: REAL[
            "gated_norm"](o, jnp.full_like(z, 1.2785), scale, eps)):
        return REAL_LOSS(params, rows, cfg, mode)   # silu(1.2785) = 1


def the_state_kept_in_bfloat16(params, rows, cfg, mode="float32"):
    """The precision below the file's for the state: rounded to bfloat16
    after every token."""
    def rounded(q, k, v, g, beta):
        s, h, dk = q.shape
        blk = reference.TOKEN_BLOCK if s % reference.TOKEN_BLOCK == 0 else 32

        def token(state, x):
            qt, kt, vt, gt, bt = x
            kept = jnp.exp(gt)[:, None, None] * state
            seen = jnp.einsum("hkv,hk->hv", kept, kt,
                              precision=jax.lax.Precision.HIGHEST)
            state = kept + jnp.einsum("hk,hv->hkv", bt[:, None] * kt,
                                      vt - seen,
                                      precision=jax.lax.Precision.HIGHEST)
            state = state + jax.lax.stop_gradient(
                jax.lax.reduce_precision(state, 8, 7) - state)
            return state, jnp.einsum("hkv,hk->hv", state, qt,
                                     precision=jax.lax.Precision.HIGHEST)

        @jax.checkpoint
        def block(state, xs):
            return jax.lax.scan(token, state, xs)

        return jax.lax.scan(
            block, jnp.zeros((h, dk, v.shape[-1]), jnp.float32),
            jax.tree.map(lambda x: x.reshape(s // blk, blk, *x.shape[1:]),
                         (q, k, v, g, beta)))[1].reshape(s, h, -1)

    with in_place_of("recurrence", rounded):
        return REAL_LOSS(params, rows, cfg, mode)


def one_linear_layer_computed_as_a_full_one(params, rows, cfg,
                                            mode="float32"):
    """The second linear layer's q, k, v go through causal softmax
    attention at d_k^-1/2 in the recurrence's place. The layers are walked
    here, not rematerialised, so that the stand-in is in place while that
    one layer is traced and at no other time."""
    def softmax(q, k, v, g, beta):
        s = q.shape[0]
        seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.einsum("qhk,shk->hqs", q, k,
                            precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return jnp.einsum("hqs,shv->qhv", probs, v,
                          precision=jax.lax.Precision.HIGHEST)

    def features(params, tokens, cfg, mode="float32"):
        rms, eps = reference._rms, cfg["rms_eps"]
        x = params["embed"]["embedding"][tokens - cfg["vocab_held"][0]]
        for i, linear in enumerate(cfg["linear"]):
            p = params[f"h{i}"]
            with in_place_of("recurrence",
                             softmax if i == 1 else REAL["recurrence"]):
                mixer = reference._linear_mixer(mode, p["linear"], x, cfg) \
                    if linear else reference._full_mixer(mode, p["attn"], x,
                                                         cfg)
            x = x + rms(mixer, p["norm_mixer"]["scale"], eps)
            x = x + rms(reference._ffn(mode, p["mlp"], x, jax.nn.silu),
                        p["norm_ffn"]["scale"], eps)
        return rms(x, params["norm_f"]["scale"], eps)

    real = reference.features
    reference.features = features
    try:
        return REAL_LOSS(params, rows, cfg, mode)
    finally:
        reference.features = real


FAULTS = [the_decay_left_out, beta_not_doubled,
          the_state_not_carried_across_a_chunk_boundary,
          a_convolution_that_sees_one_token_ahead, q_and_k_not_normalised,
          the_output_gate_left_out, the_state_kept_in_bfloat16,
          one_linear_layer_computed_as_a_full_one]


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_a_planted_fault_is_read(tmp_path, monkeypatch, fault):
    """At the rehearsal's sizes every fault but the bfloat16 state fails a
    limit; that one is read (PERF.md says what the chip showed)."""
    sound, faulty = two_sides(context(tmp_path), monkeypatch, fault)
    assert sound["correct"], sound["numbers"]
    if fault is the_state_kept_in_bfloat16:
        assert all(np.isfinite(n["value"]) for n in
                   faulty["numbers"].values())
    else:
        assert not faulty["correct"], faulty["numbers"]


def test_the_control_fails_where_the_program_passes(tmp_path):
    ctx = context(tmp_path, seed=11)
    first = hybrid_lm_trial_steps.readings(ctx)
    rows = first.pop("rows")
    program = checks.run(ctx.config, ctx.seed, rows, first)
    control = checks.run(ctx.config, ctx.seed, rows)
    assert program["correct"], program["numbers"]
    assert not control["correct"]


def test_the_check_passes_over_a_row_on_a_short_cycle(tmp_path, capsys):
    """The rehearsal's seed 7 draws a first row on a cycle of 8 tokens of
    the data's permutation (97 tokens long: an eighth is 13): the check
    follows steps 1, 2, 3 and says so (runners/hybrid_lm_trial_steps.py
    says why; tests/unit/test_lm_hybrid.py holds ``judged_steps``)."""
    ctx = context(tmp_path, seed=7)
    first = hybrid_lm_trial_steps.readings(ctx)
    rows = first.pop("rows")
    assert "follows steps [1, 2, 3]; passed over step 0 (8 distinct" \
        in capsys.readouterr().out
    assert [len(np.unique(r)) for r in rows] == [68, 22, 68]
    program = checks.run(ctx.config, ctx.seed, rows, first)
    assert program["correct"], program["numbers"]


def test_the_rehearsal_is_green(tmp_path):
    result = hybrid_lm_trial_steps.run(context(tmp_path))
    assert result["correct"], result["records"]["check"]
    rec = result["records"]
    assert rec["compiles_in_window"] == 0
    assert rec["kernel_work"]["linear_layers"] == 3
    assert rec["kernel_work"]["layers"] == 1
    assert {"step_s", "items_per_s", "flops_per_item", "device_kind", "chips",
            "peak_bytes", "kernel_work"} <= set(rec)


def test_a_program_without_the_mechanism_is_refused_not_crashed(
        tmp_path, monkeypatch):
    import importlib.util

    real = importlib.util.find_spec
    monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: (
        None if name == "metaopt_tpu.ops.linear_attention"
        else real(name, *a)))
    with pytest.raises(harness.Refused, match="linear_attention"):
        hybrid_lm_trial_steps.run(context(tmp_path))


if __name__ == "__main__":
    # one planted fault at the cell's own sizes, on the chip
    class _Patch:
        context = staticmethod(pytest.MonkeyPatch.context)

    _fault = next(f for f in FAULTS if f.__name__ == sys.argv[1])
    _seed = int(sys.argv[2]) if len(sys.argv) > 2 else 2 ** 31 + 33
    _ctx = context(os.path.join(harness.HERE, ".runs", "fault"), _seed,
                   rehearsal=False)
    _ctx.use_steady_cache()
    _sound, _faulty = two_sides(_ctx, _Patch, _fault)
    print("CHIPBENCH_FAULT " + json.dumps({
        "fault": _fault.__name__, "seed": _seed,
        "device": jax.devices()[0].device_kind,
        "sound": {k: v["value"] for k, v in _sound["numbers"].items()},
        "faulty": {k: v["value"] for k, v in _faulty["numbers"].items()},
        "sound_correct": _sound["correct"],
        "faulty_correct": _faulty["correct"]}), flush=True)
