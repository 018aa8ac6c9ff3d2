"""The state-space decoder's cell (``phi-4-mini-flash.steady-8k``) at sizes
a test run can hold: the cut, its FLOP and byte counts against counts by
brute force, its readers on canned records, the planted faults and the
control failing ``correct``, its rehearsal, and a program without the
mechanism refused. ``python3 chipbench/tests/test_ssm_lm_cell.py
FAULT[,FAULT...]|all [SEED]`` reads planted faults at the cell's own sizes
on the chip: the program's first steps and the sound reference once, then
one faulty reference a fault (``reference/ssm_lm.py`` takes the fault's
name)."""

import json
import os
import sys
import time

import jax
import numpy as np
import pytest

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from chipbench import checks, flops_lm, flops_ssm_lm, ssm_lm_config, \
    run as harness
from chipbench.checks import ssm_lm_train3
from chipbench.reference import ssm_lm as reference
from chipbench.run import _reader
from chipbench.runners import ssm_lm_trial_steps

CELL = "phi-4-mini-flash.steady-8k"
#: the planted faults, by the names ``reference/ssm_lm.py`` knows them by
FAULTS = {
    "no_skip": "D * x left out of the scan's output",
    "memory_after_gate": "M tapped after the gate silu(z)",
    "no_dt_bias": "dt_proj's bias left out",
    "conv_shifted": "the convolution shifted by a token",
    "window_plus_one": "the window 513",
    "no_second_map": "lambda a_2 left out",
    "no_one_minus_lambda_init": "(1 - lambda_init) left out",
    "lambda_init_held_index": "lambda_init read at the held index, not the "
                              "published number",
    "cross_own_kv": "a cross layer reading its own K and V",
    "state_bfloat16": "the scan's state in bfloat16",
}


def context(tmp_path, seed=2 ** 31 + 33, rehearsal=True):
    _, ctx = harness.cell_context(CELL, seed, 1.0, False, rehearsal,
                                  time.time())
    ctx.run_dir = str(tmp_path)
    return ctx


def config():
    with open(os.path.join(harness.ROOT, "chipbench", "configs",
                           "phi-4-mini-flash-vp8.json")) as f:
        return json.load(f)


# -- the configuration ---------------------------------------------------------

def test_the_cut_is_the_issue_s():
    c = config()
    cfg = ssm_lm_config.reference_cfg(c)
    assert (cfg["d_model"], cfg["d_ff"], cfg["n_heads"], cfg["n_kv_heads"],
            cfg["head_dim"], cfg["window"], cfg["eps"]) == (
                2560, 10240, 40, 20, 64, 512, 1e-5)
    assert (cfg["d_inner"], cfg["d_state"], cfg["d_conv"],
            cfg["dt_rank"]) == (5120, 16, 4, 160)
    assert cfg["layers"] == [0, 1, 16, 17, 18, 19] and cfg["of"] == 32
    assert flops_ssm_lm.kinds(cfg) == ["mamba", "window", "mamba", "full",
                                       "gmu", "cross"]
    assert cfg["vocab_held"] == [0, 25008]
    shapes = reference.param_shapes(cfg)
    size = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert size(shapes) == 697_094_272                 # x 16 bytes = 11.15 GB
    assert size(shapes["h0"]) == size(shapes["h16"]) == 119_895_040
    assert size(shapes["h1"]) == size(shapes["h17"]) == 98_322_304
    assert size(shapes["h18"]) == 104_867_840
    assert size(shapes["h19"]) == 91_766_144
    assert size(shapes["embed"]) == 25008 * 2560
    assert c["reduced"] == ["num_hidden_layers", "vocab_size"]
    desc = ssm_lm_config.description(c)
    assert desc["num_hidden_layers"] == 32             # published, not held
    assert desc["layers_held"] == [0, 1, 16, 17, 18, 19]
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]


def test_every_number_of_the_catalog_s_config_is_kept():
    """Top-level numbers as the catalog has them, but for ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    c = config()
    assert c["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in c["reduced"]:
            assert c[key] == value, key


@pytest.mark.parametrize("key, value", [
    ("model_type", "phi3"), ("mb_per_layer", 4),
    ("tie_word_embeddings", False), ("mlp_bias", True)])
def test_what_the_reference_does_not_compute_is_refused(key, value):
    c = config()
    c[key] = value
    with pytest.raises(ValueError, match=key):
        ssm_lm_config.reference_cfg(c)


# -- operations and bytes ------------------------------------------------------

def test_train_flops_by_brute_force_at_a_small_size():
    cfg = dict(d_model=8, d_ff=10, n_heads=4, n_kv_heads=2, head_dim=2,
               window=3, eps=1e-5, layers=[0, 1, 4, 5, 6, 7], of=8,
               d_inner=16, d_state=3, d_conv=4, dt_rank=2,
               vocab_held=[0, 50])
    s = 7
    d, f, di, n, r, w = 8, 10, 16, 3, 2, 2
    pairs = {"window": sum(min(i + 1, 3) for i in range(s)),
             "full": s * (s + 1) // 2}
    pairs["cross"] = pairs["full"]
    by_hand = 6 * s * 3 * 2 * d * f + s * 2 * d * 50
    by_hand += 2 * s * (2 * d * 2 * di + 2 * di * (r + 2 * n) + 2 * r * di
                        + 2 * di * d + 7 * di * n)
    by_hand += s * (2 * d * di + 2 * di * d)
    for kind in ("window", "full", "cross"):
        by_hand += s * (2 * d * 4 * w + 2 * 4 * w * d)       # q and out
        if kind != "cross":
            by_hand += s * 2 * 2 * d * 2 * w                 # k and v
        # two maps, two query pairs, scores w deep and values 2 w deep
        by_hand += 2 * 2 * pairs[kind] * 2 * (w + 2 * w)
    assert flops_ssm_lm.forward_flops_per_token(cfg, s) * s \
        == pytest.approx(by_hand)
    scans = 2 * di * n
    assert flops_ssm_lm.train_flops_per_item(cfg, s) * s == pytest.approx(
        3 * (by_hand - s * scans * 7) + s * scans * (7 + 23))


def test_a_kernel_s_call_is_counted_from_the_model_s_shapes():
    cfg = ssm_lm_config.reference_cfg(config())
    t, di, n = 8192, 5120, 16
    fwd = flops_ssm_lm.scan_fwd_call(cfg, t)
    assert fwd["flops"] == 7 * t * di * n                  # 4.7 G
    assert fwd["bytes"] == 4 * (3 * t * di + 2 * t * n + 64 * di * n
                                + di * n)
    bwd = flops_ssm_lm.scan_bwd_call(cfg, t)
    assert bwd["flops"] == 23 * t * di * n
    assert bwd["bytes"] == 4 * (5 * t * di + 4 * t * n + 64 * di * n
                                + 2 * di * n)
    # the bytes bind on a v5e: the share is of the memory's time
    assert fwd["bytes"] / 819e9 > fwd["flops"] / 197e12
    assert bwd["bytes"] / 819e9 > bwd["flops"] / 197e12
    full = flops_ssm_lm.flash_fwd_call(cfg, t, "full")
    assert full["flops"] == 2 * (64 + 128) * 20 * (t * (t + 1) // 2)
    assert flops_ssm_lm.flash_fwd_call(cfg, t, "cross") == full
    window = flops_ssm_lm.flash_fwd_call(cfg, t, "window")
    assert window["flops"] == 2 * 192 * 20 * flops_lm.seen_pairs(t, 512)
    assert window["bytes"] == full["bytes"] == t * (
        2 * (20 * 64 + 10 * 64 + 10 * 128 + 20 * 128) + 4 * 20)
    back = flops_ssm_lm.flash_bwd_call(cfg, t, "full")
    assert back["flops"] == 2 * (3 * 64 + 2 * 128) * 20 * (t * (t + 1) // 2)
    work = ssm_lm_trial_steps.kernel_work(config())
    assert (work["layers"], work["ssm_layers"]) == (6, 2)
    assert [w["flops"] for w in work["flash_fwd"]] == [
        window["flops"]] * 2 + [full["flops"]] * 4


# -- the readers ---------------------------------------------------------------

def canned():
    return {"step_s": [0.4, 0.4]}


def test_the_readers_leave_their_metric_out_without_a_trace():
    rec = canned()
    for name in ("ssm_mixer_device_ms", "ssm_scan_core_device_ms",
                 "ssm_scan_fwd_roofline", "ssm_scan_bwd_roofline",
                 "ssm_gmu_device_ms", "attention_core_device_ms",
                 "flash_fwd_roofline", "flash_bwd_roofline",
                 "ssm_lm_diff_combine_device_ms", "ffn_device_ms",
                 "scoped_device_share", "unnamed_device_ms"):
        assert _reader(name).read(rec) is None    # no trace in it


def test_the_trace_readers_on_a_few_operations(monkeypatch):
    from chipbench import program_trace

    f = "jit(train_step)/jvp(DecoderOnlyLM)/DecoderOnlyLM._hybrid/"
    b = "jit(train_step)/transpose(jvp(DecoderOnlyLM))/"
    ops = [(f + "h0/ssm/ssm/in_proj/dot_general", 0.0, 0.1),
           (f + "h0/ssm/ssm/ssm.core/jit(_fwd_pallas)/selective_scan_fwd/"
            "pallas_call", 0.1, 0.05),
           (b + "h0/ssm/ssm/ssm.core/jit(_bwd_pallas)/selective_scan_bwd/"
            "pallas_call", 0.15, 0.15),
           (f + "h1/attn/attention/q/dot_general", 0.3, 0.1),
           (f + "h1/attn/attention/attention.core/jit(_causal_forward)/"
            "flash_fwd/pallas_call", 0.4, 0.1),
           (b + "h1/attn/attention/attention.core/jit(_causal_backward)/"
            "flash_bwd/pallas_call", 0.5, 0.2),
           (f + "h1/attn/attention/attention.diff/subln/norm/mul", 0.7, 0.05),
           (f + "h18/gmu/gmu/in_proj/dot_general", 0.75, 0.05),
           (f + "h0/mlp/ffn/gate/dot_general", 0.8, 0.2)]
    monkeypatch.setattr(program_trace, "load", lambda directory: {
        "ops": {"/device:TPU:0": ops},
        "programs": {"/device:TPU:0": ["jit_train_step"] * 2}})
    monkeypatch.setattr(program_trace, "run_dir", lambda: "unused")
    cfg = ssm_lm_config.reference_cfg(config())
    work = {"layers": 1, "ssm_layers": 1, "remat": True,
            "flash_fwd": [flops_ssm_lm.flash_fwd_call(cfg, 8192, "full")],
            "flash_bwd": [flops_ssm_lm.flash_bwd_call(cfg, 8192, "full")],
            "selective_scan_fwd": [flops_ssm_lm.scan_fwd_call(cfg, 8192)],
            "selective_scan_bwd": [flops_ssm_lm.scan_bwd_call(cfg, 8192)]}
    rec = dict(canned(), trace={"busy_s": 1.0, "window_s": 1.0},
               kernel_work=work, device_kind="TPU v5 lite")
    read = lambda name: _reader(name).read(rec)  # noqa: E731
    assert read("ssm_mixer_device_ms") == pytest.approx(150)
    assert read("ssm_scan_core_device_ms") == pytest.approx(100)
    assert read("ssm_gmu_device_ms") == pytest.approx(25)
    assert read("attention_core_device_ms") == pytest.approx(150)
    assert read("ssm_lm_diff_combine_device_ms") == pytest.approx(25)
    assert read("attention_proj_device_ms") == pytest.approx(75)
    assert read("ffn_device_ms") == pytest.approx(100)
    # one call in the slice: 0.50 GB over 0.05 s at 819 GB/s
    assert read("ssm_scan_fwd_roofline") == pytest.approx(
        100 * work["selective_scan_fwd"][0]["bytes"] / 819e9 / 0.05,
        rel=1e-6)
    assert 0 < read("ssm_scan_bwd_roofline") < 100
    assert 0 < read("flash_fwd_roofline") < 100
    assert 0 < read("flash_bwd_roofline") < 100
    # the line adds up: the top-level layers and the unnamed are the busy
    layers = sum(read(n) for n in (
        "ssm_mixer_device_ms", "ssm_gmu_device_ms",
        "attention_core_device_ms", "attention_proj_device_ms",
        "ffn_device_ms"))
    assert layers + read("unnamed_device_ms") == pytest.approx(500)


def test_the_cell_s_line_names_every_metric_the_issue_lists():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert len(mine) == 21
    assert all(os.path.exists(os.path.join(
        harness.HERE, "readers", name + ".py")) for name in mine)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["traffic"], cell["chips"]) == ("steady-ssm-lm-8k", 1)
    assert len(cell["why"]) <= 200


# -- correct -------------------------------------------------------------------

def faulty_sides(ctx, faults):
    """(the program against the reference, {fault: the reference with the
    fault planted, as the program's side, against the sound reference}):
    the program's first steps and the sound reference are made once."""
    first = ssm_lm_trial_steps.readings(ctx)
    rows = first.pop("rows")
    spec = ctx.config["check"]
    ref = ssm_lm_train3.reference_readings(ctx.config, ctx.seed, rows,
                                           "float32")
    start = ssm_lm_train3.weights(ctx.config, ctx.seed)
    judged = lambda side: (lambda numbers: {  # noqa: E731
        "numbers": numbers,
        "correct": all(n["ok"] for n in numbers.values())})(
            checks.compare(side, ref, start, spec["limits"]))
    sound, out = judged(first), {}
    del first
    for fault in faults:
        out[fault] = judged(ssm_lm_train3.reference_readings(
            ctx.config, ctx.seed, rows, "float32", faults=(fault,)))
    return sound, out


@pytest.fixture(scope="module")
def planted(tmp_path_factory):
    return faulty_sides(context(tmp_path_factory.mktemp("faults")),
                        list(FAULTS))


#: at the rehearsal's size (96 tokens, a window of 16, a state of 4) these
#: are readings: the row is shorter than the published window, the held
#: index of layer 1 IS its published number, and a bfloat16 state over 96
#: tokens rounds less than the limits made for such rows allow. PERF.md
#: section 4 has the chip's readings at the cell's own sizes.
READINGS_HERE = ("lambda_init_held_index", "state_bfloat16",
                 "window_plus_one")


@pytest.mark.parametrize("fault", [f for f in FAULTS
                                   if f not in READINGS_HERE])
def test_a_planted_fault_fails_correct(planted, fault):
    sound, faulty = planted
    assert sound["correct"], sound["numbers"]
    assert not faulty[fault]["correct"], faulty[fault]["numbers"]


@pytest.mark.parametrize("fault", READINGS_HERE)
def test_the_small_faults_are_readings_at_the_rehearsal_s_size(planted,
                                                               fault):
    _, faulty = planted
    assert faulty[fault]["numbers"]["grad_rms_gap"]["value"] > 0


def test_the_control_fails_where_the_program_passes(tmp_path):
    ctx = context(tmp_path, seed=11)
    first = ssm_lm_trial_steps.readings(ctx)
    rows = first.pop("rows")
    program = checks.run(ctx.config, ctx.seed, rows, first)
    control = checks.run(ctx.config, ctx.seed, rows)
    assert program["correct"], program["numbers"]
    assert not control["correct"]


def test_the_rehearsal_is_green(tmp_path):
    result = ssm_lm_trial_steps.run(context(tmp_path))
    assert result["correct"], result["records"]["check"]
    rec = result["records"]
    assert rec["compiles_in_window"] == 0
    assert rec["kernel_work"]["layers"] == 6
    assert rec["kernel_work"]["ssm_layers"] == 2
    assert {"step_s", "items_per_s", "flops_per_item", "device_kind", "chips",
            "peak_bytes", "kernel_work"} <= set(rec)


def test_a_program_without_the_mechanism_is_refused_not_crashed(
        tmp_path, monkeypatch):
    import importlib.util

    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec", lambda name, *a: None
        if name == "metaopt_tpu.ops.selective_scan" else real(name, *a))
    with pytest.raises(harness.Refused, match="selective_scan"):
        ssm_lm_trial_steps.run(context(tmp_path))


if __name__ == "__main__":
    # planted faults at the cell's own sizes, on the chip
    _names = list(FAULTS) if sys.argv[1] == "all" else sys.argv[1].split(",")
    _seed = int(sys.argv[2]) if len(sys.argv) > 2 else 2 ** 31 + 33
    _ctx = context(os.path.join(harness.HERE, ".runs", "fault"), _seed,
                   rehearsal=False)
    _ctx.use_steady_cache()
    _sound, _faulty = faulty_sides(_ctx, _names)
    _values = lambda side: {k: v["value"]  # noqa: E731
                            for k, v in side["numbers"].items()}
    for _name, _side in _faulty.items():
        print("CHIPBENCH_FAULT " + json.dumps({
            "fault": _name, "what": FAULTS[_name], "seed": _seed,
            "device": jax.devices()[0].device_kind,
            "sound": _values(_sound), "faulty": _values(_side),
            "sound_correct": _sound["correct"],
            "faulty_correct": _side["correct"]}), flush=True)
