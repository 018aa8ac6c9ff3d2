"""The gated mixed-window MoE decoder's cell (``laguna-xs2.steady-8k``) at
sizes a test run can hold: the cut, its FLOP and byte counts against counts
by brute force, its readers on canned records and on a recorded sample of
the chip's trace (how the flash kernels' calls are told apart by kind), the
planted faults and the control failing ``correct``, its rehearsal, and a
program without the family's reader refused. ``python3
chipbench/tests/test_gated_lm_cell.py FAULT[,FAULT...]|all [SEED]`` reads
planted faults at the cell's own sizes on the chip: the program's first
steps and the sound reference once, then one faulty reference a fault
(``reference/gated_lm.py`` takes the fault's name)."""

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

from chipbench import (checks, flops_gated_lm, flops_lm, gated_lm_config,
                       run as harness)
from chipbench.checks import gated_lm_train3
from chipbench.reference import gated_lm as reference
from chipbench.run import _reader
from chipbench.runners import gated_lm_trial_steps

CELL = "laguna-xs2.steady-8k"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
#: the planted faults, by the names ``reference/gated_lm.py`` knows them by
FAULTS = {
    "no_gate": "the gate on attention's output left out",
    "gate_identity": "the gate's sigmoid swapped for the identity",
    "plain_for_yarn": "the plain frequencies for YaRN's on the full layers",
    "no_attention_factor": "the factor 1.4159 on cos and sin left out",
    "whole_head_turned": "the whole head turned on a full layer",
    "window_plus_one": "the window 513",
    "no_shared": "the shared expert left out",
    "no_scale": "the scale 2.5 left out",
    "normalise_over_held": "normalising over the held, not the chosen",
}


def context(tmp_path, seed=2 ** 31 + 45, rehearsal=True):
    _, ctx = harness.cell_context(CELL, seed, 1.0, False, rehearsal,
                                  time.time())
    ctx.run_dir = str(tmp_path)
    return ctx


def config():
    with open(os.path.join(harness.ROOT, "chipbench", "configs",
                           "laguna-xs2-33b-a3b-ep8.json")) as f:
        return json.load(f)


# -- the configuration ---------------------------------------------------------

def test_the_cut_is_the_issue_s():
    c = config()
    cfg = gated_lm_config.reference_cfg(c)
    assert (cfg["d_model"], cfg["head_dim"], cfg["n_kv_heads"],
            cfg["window"], cfg["d_ff"]) == (2048, 128, 8, 512, 8192)
    assert cfg["layers"] == [
        {"kind": "full", "heads": 48, "ffn": "dense"},
        {"kind": "window", "heads": 64, "ffn": "sparse"},
        {"kind": "window", "heads": 64, "ffn": "sparse"},
        {"kind": "window", "heads": 64, "ffn": "sparse"},
        {"kind": "full", "heads": 48, "ffn": "sparse"}]
    assert cfg["rope"]["full"] == {
        "theta": 500000.0, "turned": 64, "yarn": [64.0, 4096, 64.0, 1.0],
        "factor": 1.4158883083359672}
    assert cfg["rope"]["window"] == {"theta": 10000.0, "turned": 128,
                                     "yarn": None, "factor": 1.0}
    assert (cfg["n_experts"], cfg["top_k"], cfg["expert_d_ff"],
            cfg["shared_d_ff"], cfg["scale"]) == (256, 8, 512, 512, 2.5)
    assert cfg["experts_held"] == [0, 32] and cfg["vocab_held"] == [0, 12544]
    size = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert size(reference.param_shapes(cfg)) == 691_625_216  # x 16 = 11.07 GB
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    desc = gated_lm_config.description(c)
    assert desc["num_experts"] == 256                  # routed over, not held
    assert len(desc["layer_types"]) == len(desc["mlp_layer_types"]) \
        == len(desc["num_attention_heads_per_layer"]) == 40
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    assert c["num_experts"] * 8 == c["published"]["num_experts"]


def test_every_number_of_the_catalog_s_config_is_kept():
    """Top-level keys as the catalog has them, but for ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-XS.2")
    c = config()
    assert c["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in c["reduced"]:
            assert c[key] == value, key


@pytest.mark.parametrize("key, value", [
    ("model_type", "qwen3_moe"), ("attention_bias", True),
    ("moe_apply_router_weight_on_input", True), ("gating", False),
    ("tie_word_embeddings", True)])
def test_what_the_reference_does_not_compute_is_refused(key, value):
    c = config()
    c[key] = value
    with pytest.raises(ValueError, match=key):
        gated_lm_config.reference_cfg(c)


# -- operations and bytes ------------------------------------------------------

def test_train_flops_by_brute_force_at_a_small_size():
    cfg = dict(d_model=8, head_dim=2, n_kv_heads=2, window=3, d_ff=10,
               layers=[{"kind": "full", "heads": 4, "ffn": "dense"},
                       {"kind": "window", "heads": 6, "ffn": "sparse"}],
               gate="sigmoid", n_experts=16, top_k=4, expert_d_ff=5,
               shared_d_ff=7, experts_held=[0, 8], vocab_held=[0, 50])
    s, d, k = 7, 8, 2
    pairs = {"window": sum(min(i + 1, 3) for i in range(s)),
             "full": s * (s + 1) // 2}
    by_hand = s * 2 * d * 50
    for kind, h in (("full", 4), ("window", 6)):
        by_hand += s * (2 * d * h * k + 2 * 2 * d * 2 * k + 2 * h * k * d)
        by_hand += s * 2 * d * h                             # the gate
        by_hand += 2 * 2 * k * h * pairs[kind]               # scores, values
    by_hand += s * 3 * 2 * d * 10                            # the dense layer
    by_hand += s * (2 * d * 16 + 3 * 2 * d * 7               # router, shared
                    + 4 * 8 / 16 * 3 * 2 * d * 5)            # 2 experts met
    assert flops_gated_lm.forward_flops_per_token(cfg, s) * s \
        == pytest.approx(by_hand)
    assert flops_gated_lm.train_flops_per_item(cfg, s) * s \
        == pytest.approx(3 * by_hand)


def test_the_issue_s_reckoning_of_a_step():
    """802 MFLOP a token forward, the mixers 74 % of it."""
    cfg = gated_lm_config.reference_cfg(config())
    s = 8192
    whole = flops_gated_lm.forward_flops_per_token(cfg, s)
    mixers = sum(flops_gated_lm.attention_flops_per_token(cfg, layer, s)
                 for layer in cfg["layers"])
    assert whole / 1e6 == pytest.approx(802, abs=8)
    assert mixers / whole == pytest.approx(0.74, abs=0.01)


def test_a_kernel_s_call_is_counted_at_its_own_layer_s_heads():
    cfg = gated_lm_config.reference_cfg(config())
    t = 8192
    full, window = cfg["layers"][0], cfg["layers"][1]
    fwd = flops_gated_lm.flash_fwd_call(cfg, full, t)
    assert fwd["flops"] == 4 * 128 * 48 * (t * (t + 1) // 2)
    assert fwd["bytes"] == t * (2 * 128 * (2 * 48 + 2 * 8) + 4 * 48)
    win = flops_gated_lm.flash_fwd_call(cfg, window, t)
    assert win["flops"] == 4 * 128 * 64 * flops_lm.seen_pairs(t, 512)
    assert win["bytes"] == t * (2 * 128 * (2 * 64 + 2 * 8) + 4 * 64)
    back = flops_gated_lm.flash_bwd_call(cfg, window, t)
    assert back["flops"] == 10 * 128 * 64 * flops_lm.seen_pairs(t, 512)
    # a full layer's call is bound by the operations, a window layer's
    # lies near the ridge
    assert fwd["flops"] / 197e12 > 10 * fwd["bytes"] / 819e9
    counts = {"items": [[256] * 32] * 4}
    work = gated_lm_trial_steps.kernel_work(config(), counts, 1)
    assert (work["layers"], work["routed_layers"]) == (5, 4)
    assert work["blocks"] == {"window": ["h1", "h2", "h3"],
                              "full": ["h0", "h4"]}
    assert [w["flops"] for w in work["flash_fwd"]] == [
        fwd["flops"], win["flops"], win["flops"], win["flops"], fwd["flops"]]
    assert work["experts_pass"] == flops_lm.experts_pass(cfg, 32 * 256)


# -- the readers ---------------------------------------------------------------

def mine():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return bench, [m["name"] for m in bench["per_layer"]
                   if CELL in m.get("workloads", ())]


def test_the_cell_s_line_names_its_metrics():
    bench, names = mine()
    assert len(names) == 25 and len(bench["per_layer"]) <= 128
    assert all(os.path.exists(os.path.join(
        harness.HERE, "readers", name + ".py")) for name in names)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["traffic"], cell["chips"]) == ("steady-gated-lm-8k", 1)
    assert len(cell["why"]) <= 200


def test_the_readers_leave_their_metric_out_without_a_trace():
    rec = {"step_s": [0.3, 0.3]}
    for name in mine()[1]:
        if name not in ("moe_dropped_share", "program_load_s",
                        "compile_cache_hit_share"):
            assert _reader(name).read(rec) is None, name


def test_the_gate_s_reader_finds_nothing_on_a_program_without_the_scope(
        monkeypatch):
    from chipbench import program_trace
    from metaopt_tpu.utils import trace

    monkeypatch.setattr(trace, "SCOPES", tuple(
        s for s in trace.SCOPES if s != "attention.gate"))
    monkeypatch.setattr(program_trace, "load", lambda directory: 1 / 0)
    assert _reader("gated_lm_gate_device_ms").read(
        {"trace": {"busy_s": 1.0, "window_s": 1.0}}) is None


def test_the_trace_readers_on_a_few_operations(monkeypatch):
    from chipbench import program_trace

    f = "jit(train_step)/jvp(DecoderOnlyLM)/DecoderOnlyLM._patterned/"
    b = "jit(train_step)/transpose(jvp(DecoderOnlyLM))/" \
        "DecoderOnlyLM._patterned/checkpoint/"
    core = "/attn/attention/attention.core/"
    ops = [(f + "h0/attn/attention/q/dot_general", 0.0, 0.1),
           (f + "h0" + core + "jit(_causal_forward)/flash_fwd/pallas_call",
            0.1, 0.1),
           (f + "h1" + core + "jit(_causal_forward)/flash_fwd/pallas_call",
            0.2, 0.02),
           (f + "h1/attn/attention/attention.gate/gate/dot_general", 0.22,
            0.03),
           (f + "h1/attn/attention/attention.gate/mul", 0.25, 0.05),
           (b + "h1" + core + "jit(_causal_backward)/flash_bwd/pallas_call",
            0.3, 0.05),
           (b + "h0" + core + "jit(_causal_backward)/flash_bwd/pallas_call",
            0.35, 0.25),
           (f + "h0/mlp/ffn/gate/dot_general", 0.6, 0.1),
           (f + "h1/experts/moe/moe.experts/gmm/pallas_call", 0.7, 0.1),
           (f + "h1/experts/moe/moe.shared/shared/ffn/up/dot_general", 0.8,
            0.1),
           ("copy.7", 0.9, 0.1)]
    monkeypatch.setattr(program_trace, "load", lambda directory: {
        "ops": {"/device:TPU:0": ops},
        "programs": {"/device:TPU:0": ["jit_train_step"] * 2}})
    monkeypatch.setattr(program_trace, "run_dir", lambda: "unused")
    cfg = gated_lm_config.reference_cfg(config())
    layers = cfg["layers"][:2]
    work = {"layers": 2, "routed_layers": 1, "remat": True,
            "block_of_layer": ["h0", "h1"],
            "blocks": {"window": ["h1"], "full": ["h0"]},
            "flash_fwd": [flops_gated_lm.flash_fwd_call(cfg, layer, 8192)
                          for layer in layers],
            "flash_bwd": [flops_gated_lm.flash_bwd_call(cfg, layer, 8192)
                          for layer in layers],
            "experts_pass": flops_lm.experts_pass(cfg, 8192)}
    rec = {"step_s": [0.5, 0.5], "trace": {"busy_s": 1.0, "window_s": 1.0},
           "kernel_work": work, "device_kind": "TPU v5 lite"}
    read = lambda name: _reader(name).read(rec)  # noqa: E731
    assert read("attention_core_device_ms") == pytest.approx(210)
    assert read("attention_proj_device_ms") == pytest.approx(90)
    assert read("gated_lm_gate_device_ms") == pytest.approx(40)
    assert read("ffn_device_ms") == pytest.approx(50)        # not the shared
    assert read("moe_device_ms") == pytest.approx(100)
    assert read("moe_experts_device_ms") == pytest.approx(50)
    assert read("moe_shared_device_ms") == pytest.approx(50)
    assert read("moe_route_device_ms") == pytest.approx(0)   # less both
    assert read("unnamed_device_ms") == pytest.approx(50)
    # one call of each kind in the slice, each against its own work
    peak = 197e12
    assert read("gated_lm_full_flash_fwd_roofline") == pytest.approx(
        100 * work["flash_fwd"][0]["flops"] / peak / 0.1, rel=1e-6)
    assert read("gated_lm_window_flash_fwd_roofline") == pytest.approx(
        100 * work["flash_fwd"][1]["flops"] / peak / 0.02, rel=1e-6)
    assert read("gated_lm_window_flash_bwd_roofline") == pytest.approx(
        100 * work["flash_bwd"][1]["flops"] / peak / 0.05, rel=1e-6)
    assert read("gated_lm_full_flash_bwd_roofline") == pytest.approx(
        100 * work["flash_bwd"][0]["flops"] / peak / 0.25, rel=1e-6)
    assert 0 < read("moe_experts_roofline") < 100
    # the line adds up: the top-level layers and the unnamed are the busy
    named = sum(read(n) for n in (
        "attention_core_device_ms", "attention_proj_device_ms",
        "ffn_device_ms", "moe_device_ms"))
    assert named + read("unnamed_device_ms") == pytest.approx(500)


def test_a_recorded_sample_of_the_chip_s_trace_tells_the_kinds_apart(
        monkeypatch):
    """``data/gated_lm_ops.json``: the first two steps of a traced run of
    the cell on the chip (``record_gated_lm_ops.py``). Every flash call's
    path holds its block's name, each kind's calls are found, and the
    readers read what they read when the sample was made."""
    from chipbench import program_trace

    path = os.path.join(DATA, "gated_lm_ops.json")
    if not os.path.exists(path):
        pytest.skip("no recorded sample here")
    with open(path) as f:
        doc = json.load(f)
    ops = [tuple(e) for e in doc["ops"]]
    monkeypatch.setattr(program_trace, "load", lambda directory: {
        "ops": {"/device:TPU:0": ops},
        "programs": {"/device:TPU:0": doc["programs"]}})
    monkeypatch.setattr(program_trace, "run_dir", lambda: "unused")
    work = doc["kernel_work"]
    steps = len(doc["programs"])
    for kernel in ("flash_fwd", "flash_bwd"):
        calls = [p for p, _, _ in ops if kernel in p.split("/")]
        # once a step a layer (a rematerialised block keeps the forward
        # kernel's out and lse), every layer equally often
        a_layer = len(calls) // (steps * 5)
        assert a_layer >= 1 and len(calls) == steps * 5 * a_layer
        for kind, blocks in work["blocks"].items():
            of_kind = [p for p in calls if set(blocks) & set(p.split("/"))]
            assert len(of_kind) == steps * len(blocks) * a_layer
    rec = {"trace": {"busy_s": 1.0, "window_s": 1.0}, "kernel_work": work,
           "device_kind": doc["device_kind"]}
    for name, value in doc["expected"].items():
        assert _reader(name).read(rec) == pytest.approx(value, rel=1e-9)
        assert 0 < value < 100


# -- correct -------------------------------------------------------------------

def faulty_sides(ctx, faults):
    """(the program against the reference, {fault: the reference with the
    fault planted, as the program's side, against the sound reference}):
    the program's first steps and the sound reference are made once."""
    first = gated_lm_trial_steps.readings(ctx)
    rows = first.pop("rows")
    spec = ctx.config["check"]
    ref = gated_lm_train3.reference_readings(ctx.config, ctx.seed, rows,
                                             "float32")
    start = gated_lm_train3.weights(ctx.config, ctx.seed)
    judged = lambda side: (lambda numbers: {  # noqa: E731
        "numbers": numbers,
        "correct": all(n["ok"] for n in numbers.values())})(
            checks.compare(side, ref, start, spec["limits"]))
    sound, out = judged(first), {}
    del first
    for fault in faults:
        out[fault] = judged(gated_lm_train3.reference_readings(
            ctx.config, ctx.seed, rows, "float32", faults=(fault,)))
    return sound, out


@pytest.fixture(scope="module")
def planted(tmp_path_factory):
    return faulty_sides(context(tmp_path_factory.mktemp("faults")),
                        list(FAULTS))


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_planted_fault_fails_correct(planted, fault):
    assert set(FAULTS) == set(reference.FAULTS)
    sound, faulty = planted
    assert sound["correct"], sound["numbers"]
    assert not faulty[fault]["correct"], faulty[fault]["numbers"]


def test_the_control_fails_where_the_program_passes(tmp_path):
    ctx = context(tmp_path, seed=11)
    first = gated_lm_trial_steps.readings(ctx)
    rows = first.pop("rows")
    program = checks.run(ctx.config, ctx.seed, rows, first)
    control = checks.run(ctx.config, ctx.seed, rows)
    assert program["correct"], program["numbers"]
    assert not control["correct"]


def test_the_rehearsal_is_green(tmp_path):
    result = gated_lm_trial_steps.run(context(tmp_path))
    assert result["correct"], result["records"]["check"]
    rec = result["records"]
    assert rec["compiles_in_window"] == 0
    assert rec["kernel_work"]["layers"] == 5
    assert rec["kernel_work"]["routed_layers"] == 4
    assert rec["moe_counts"]["dropped"] == [0, 0, 0, 0]
    assert {"step_s", "items_per_s", "flops_per_item", "device_kind", "chips",
            "peak_bytes", "kernel_work"} <= set(rec)


def test_a_program_without_the_family_s_reader_is_refused_not_crashed(
        tmp_path, monkeypatch):
    assert gated_lm_trial_steps.has_mechanism()
    monkeypatch.setattr(gated_lm_trial_steps, "FAMILY", "laguna-next")
    assert not gated_lm_trial_steps.has_mechanism()
    with pytest.raises(harness.Refused, match="reader"):
        gated_lm_trial_steps.run(context(tmp_path))


if __name__ == "__main__":
    # planted faults at the cell's own sizes, on the chip
    _names = list(FAULTS) if sys.argv[1] == "all" else sys.argv[1].split(",")
    _seed = int(sys.argv[2]) if len(sys.argv) > 2 else 2 ** 31 + 45
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
