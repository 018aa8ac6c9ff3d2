"""The one-sublayer decoder's cell (``nemotron-3-nano.steady-8k``) at sizes
a test run can hold: the cut, its FLOP and byte counts against counts by
brute force, its readers on canned records, the planted faults and the
control failing ``correct``, its rehearsal, and a program without the
family's reader refused. ``python3 chipbench/tests/test_ssd_lm_cell.py
FAULT[,FAULT...]|all [SEED]`` reads planted faults at the cell's own sizes
on the chip: the program's first steps and the sound reference once, then
one faulty reference a fault (``reference/ssd_lm.py`` takes the fault's
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

from chipbench import checks, flops_lm, flops_ssd_lm, run as harness, \
    ssd_lm_config
from chipbench.checks import ssd_lm_train3
from chipbench.reference import ssd_lm as reference
from chipbench.run import _reader
from chipbench.runners import ssd_lm_trial_steps

CELL = "nemotron-3-nano.steady-8k"
#: the planted faults, by the names ``reference/ssd_lm.py`` knows them by
FAULTS = {
    "no_skip": "D x left out of the mixer's output",
    "no_dt_bias": "dt_bias left out of the steps",
    "gate_after_norm": "the gate silu(z) after the norm, not before it",
    "norm_over_all": "the norm over all 4096 channels, not groups of 512",
    "group_by_modulo": "head h reading group h % 8, not h // 8",
    "decay_sign": "the decay's sign: a = +exp(A_log)",
    "no_conv_bias": "the convolution's bias left out",
    "relu_not_squared": "ReLU for its square in every expert",
    "gated_experts": "a gate on the experts: silu(u) * u for relu(u)^2",
    "no_shared": "the shared expert left out",
    "no_scale": "the scale 2.5 left out",
    "normalise_over_held": "normalising over the held, not the chosen",
    "rotary_attention": "a rotation (theta 10000) on the attention block",
}


def context(tmp_path, seed=2 ** 31 + 49, rehearsal=True):
    _, ctx = harness.cell_context(CELL, seed, 1.0, False, rehearsal,
                                  time.time())
    ctx.run_dir = str(tmp_path)
    return ctx


def config():
    with open(os.path.join(harness.ROOT, "chipbench", "configs",
                           "nemotron-3-nano-30b-a3b-ep16.json")) as f:
        return json.load(f)


# -- the configuration ---------------------------------------------------------

def test_the_cut_is_the_issue_s():
    c = config()
    cfg = ssd_lm_config.reference_cfg(c)
    assert (cfg["d_model"], cfg["letters"], cfg["numbers"]) == (
        2688, "MEMEM*EME", list(range(9)))
    assert (cfg["ssd_heads"], cfg["ssd_head_dim"], cfg["ssd_groups"],
            cfg["ssd_state"], cfg["ssd_conv"], cfg["chunk"]) == (
        64, 64, 8, 128, 4, 128)
    assert (cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]) == (32, 2, 128)
    assert (cfg["n_experts"], cfg["top_k"], cfg["expert_d_ff"],
            cfg["shared_d_ff"], cfg["scale"]) == (128, 6, 1856, 3712, 2.5)
    assert cfg["experts_held"] == [0, 8] and cfg["vocab_held"] == [0, 16384]
    size = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert size(reference.param_shapes(cfg)) == 666_963_456  # x 16 = 10.67 GB
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    desc = ssd_lm_config.description(c)
    assert desc["n_routed_experts"] == 128            # routed over, not held
    assert len(desc["hybrid_override_pattern"]) == 52
    assert desc["layers_held"] == list(range(9))
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    assert c["n_routed_experts"] * 16 == c["published"]["n_routed_experts"]


def test_every_number_of_the_catalog_s_config_is_kept():
    """Top-level keys as the catalog has them, but for ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    c = config()
    assert c["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in c["reduced"]:
            assert c[key] == value, key


@pytest.mark.parametrize("key, value", [
    ("model_type", "deepseek_v3"), ("attention_bias", True),
    ("mlp_bias", True), ("use_conv_bias", False), ("n_group", 2),
    ("mlp_hidden_act", "silu"), ("tie_word_embeddings", True)])
def test_what_the_reference_does_not_compute_is_refused(key, value):
    c = config()
    c[key] = value
    with pytest.raises(ValueError, match=key):
        ssd_lm_config.reference_cfg(c)


# -- operations and bytes ------------------------------------------------------

def test_the_scan_s_products_by_brute_force():
    """A group of 3 heads, a chunk of 5 tokens, state 4, head width 2: the
    products of the module's docstring, counted one by one."""
    c, n, p, hg = 5, 4, 2, 3
    fwd = 2 * c * c * n                                      # Q K^T, once
    fwd += hg * (2 * c * c * p + 2 * c * n * p + 2 * c * n * p)
    assert flops_ssd_lm.scan_fwd_chunk_flops(c, n, p, hg) == fwd
    bwd = 3 * 2 * c * c * n                    # Q K^T again, dQ, dK: a group
    bwd += hg * (2 * 2 * c * c * p + 4 * 2 * c * n * p)
    assert flops_ssd_lm.scan_bwd_chunk_flops(c, n, p, hg) == bwd


def test_a_call_s_work_at_the_cell_s_sizes():
    cfg = ssd_lm_config.reference_cfg(config())
    t = 8192
    fwd = flops_ssd_lm.scan_fwd_call(cfg, t)
    assert fwd["flops"] == 8 * 64 * (2 * 128 * 128 * 128 + 8 * (
        2 * 128 * 128 * 64 + 4 * 128 * 128 * 64))
    assert fwd["bytes"] == t * (2 * 2 * 1024 + 2 * 2 * 4096 + 4 * 64) \
        + 4 * 64 * 64 * 128 * 64
    # bound by bytes: the states are half of them
    assert fwd["bytes"] / 819e9 > 2 * fwd["flops"] / 197e12
    bwd = flops_ssd_lm.scan_bwd_call(cfg, t)
    assert bwd["flops"] > 2 * fwd["flops"] and bwd["bytes"] > fwd["bytes"]
    flash = flops_ssd_lm.flash_fwd_call(cfg, t)
    assert flash["flops"] == 4 * 128 * 32 * (t * (t + 1) // 2)
    two = flops_ssd_lm.experts_pass(cfg, 3072)
    assert two["flops"] == 2 * 2 * 3072 * 2688 * 1856
    assert two["flops"] * 3 == flops_lm.experts_pass(cfg, 3072)["flops"] * 2
    act = flops_ssd_lm.expert_act_call(cfg, 3072, False)
    assert act["bytes"] == 2 * 2 * 3072 * 1856
    counts = {"items": [[384] * 8] * 4}
    work = ssd_lm_trial_steps.kernel_work(config(), counts, 1)
    assert (work["layers"], work["ssd_layers"], work["routed_layers"]) \
        == (1, 4, 4)
    assert work["ssd_scan_fwd"] == [fwd] * 4 and work["flash_fwd"] == [flash]
    assert work["experts_pass"] == two and work["expert_act"][0] == act


def test_train_flops_by_brute_force_at_a_small_size():
    cfg = dict(d_model=8, letters="ME*", numbers=[0, 1, 5], ssd_heads=4,
               ssd_head_dim=2, ssd_groups=2, ssd_state=3, ssd_conv=4, chunk=5,
               n_heads=4, n_kv_heads=2, head_dim=2, n_experts=16, top_k=4,
               expert_d_ff=5, shared_d_ff=7, experts_held=[0, 8],
               vocab_held=[0, 50])
    s, d = 10, 8
    scan_f = 2 * 2 * flops_ssd_lm.scan_fwd_chunk_flops(5, 3, 2, 2)
    scan_b = 2 * 2 * flops_ssd_lm.scan_bwd_chunk_flops(5, 3, 2, 2)
    mixer = s * (2 * d * (2 * 8 + 2 * 6 + 4) + 2 * 8 * d)
    attention = s * (2 * d * (4 + 2 * 2) * 2 + 2 * 4 * 2 * d) \
        + 2 * 2 * 2 * 4 * (s * (s + 1) // 2)
    experts = s * (2 * d * 16 + 2 * 2 * d * 7 + 4 * 8 / 16 * 2 * 2 * d * 5)
    by_hand = mixer + attention + experts + s * 2 * d * 50
    assert flops_ssd_lm.forward_flops_per_token(cfg, s) * s \
        == pytest.approx(by_hand + scan_f)
    assert flops_ssd_lm.train_flops_per_item(cfg, s) * s \
        == pytest.approx(3 * by_hand + scan_f + scan_b)


def test_the_issue_s_reckoning_of_a_step():
    """718 MFLOP a token forward: the four mixers' projections 43 % of it
    (their scans 14 M), the four expert blocks 192 M, attention 114 M, the
    head 88 M."""
    cfg = ssd_lm_config.reference_cfg(config())
    whole = flops_ssd_lm.forward_flops_per_token(cfg, 8192)
    d = 2688
    mixers = 4 * (2 * d * 10304 + 2 * 4096 * d)
    assert mixers / whole == pytest.approx(0.43, abs=0.01)
    assert whole / 1e6 == pytest.approx(718, abs=2)
    assert flops_ssd_lm.train_flops_per_item(cfg, 8192) / 1e6 \
        == pytest.approx(2154, abs=2)


# -- the readers ---------------------------------------------------------------

def mine():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return bench, [m["name"] for m in bench["per_layer"]
                   if CELL in m.get("workloads", ())]


def test_the_cell_s_line_names_its_metrics():
    bench, names = mine()
    assert len(names) == 27 and len(bench["per_layer"]) <= 128
    assert all(os.path.exists(os.path.join(
        harness.HERE, "readers", name + ".py")) for name in names)
    assert all(name.startswith(("ssd_", "ssd_lm_")) for name in names)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["traffic"], cell["chips"]) == ("steady-ssd-lm-8k", 1)
    assert len(cell["why"]) <= 200


def test_a_copied_reader_has_the_accepted_one_s_body():
    """``ssd_lm_<name>`` is ``<name>`` for this cell: the same code under
    another name, until a ``benchmark`` PR folds it into the one entry."""
    import ast

    def body(name):
        with open(os.path.join(harness.HERE, "readers", name + ".py")) as f:
            return ast.dump(ast.Module(body=ast.parse(f.read()).body[1:],
                                       type_ignores=[]))

    own = {"ssd_lm_expert_act_roofline", "ssd_lm_moe_experts_roofline"}
    copies = [n for n in mine()[1] if n.startswith("ssd_lm_")
              and n not in own]
    assert len(copies) == 21
    for name in copies:
        accepted = name[len("ssd_lm_"):]
        if accepted == "moe_choice_bias_share":
            accepted = "mla_lm_" + accepted
        assert body(name) == body(accepted), name


def test_the_readers_leave_their_metric_out_without_a_trace():
    rec = {"step_s": [0.3, 0.3]}
    for name in mine()[1]:
        if name not in ("ssd_lm_program_load_s",
                        "ssd_lm_compile_cache_hit_share"):
            assert _reader(name).read(rec) is None, name


def test_the_new_readers_find_nothing_on_a_program_without_the_scopes(
        monkeypatch):
    from chipbench import program_trace
    from metaopt_tpu.utils import trace

    monkeypatch.setattr(trace, "SCOPES", tuple(
        s for s in trace.SCOPES if not s.startswith("ssd")))
    monkeypatch.setattr(program_trace, "load", lambda directory: 1 / 0)
    rec = {"trace": {"busy_s": 1.0, "window_s": 1.0}}
    assert _reader("ssd_mixer_device_ms").read(rec) is None
    assert _reader("ssd_scan_core_device_ms").read(rec) is None
    assert _reader("ssd_scan_fwd_roofline").read(rec) is None
    assert _reader("ssd_lm_expert_act_roofline").read(rec) is None


def test_the_trace_readers_on_a_few_operations(monkeypatch):
    from chipbench import program_trace

    f = "jit(train_step)/jvp(DecoderOnlyLM)/DecoderOnlyLM._patterned/"
    b = "jit(train_step)/transpose(jvp(DecoderOnlyLM))/" \
        "DecoderOnlyLM._patterned/checkpoint/"
    ops = [(f + "h0/ssd/ssd/dot_general", 0.0, 0.1),
           (f + "h0/ssd/ssd/ssd.core/jit(_decay_fwd_pallas)/ssd_scan_fwd/"
            "pallas_call", 0.1, 0.05),
           (b + "h0/ssd/ssd/ssd.core/jit(_decay_bwd_pallas)/ssd_scan_bwd/"
            "pallas_call", 0.15, 0.15),
           (f + "h1/experts/moe/moe.experts/gmm/pallas_call", 0.3, 0.1),
           (f + "h1/experts/moe/moe.experts/expert_activation/pallas_call",
            0.4, 0.01),
           (b + "h1/experts/moe/moe.experts/expert_activation_bwd/"
            "pallas_call", 0.41, 0.02),
           (f + "h1/experts/moe/moe.shared/shared/ffn/up/dot_general", 0.5,
            0.1),
           (f + "h5/attn/attention/attention.core/jit(_causal_forward)/"
            "flash_fwd/pallas_call", 0.6, 0.1),
           ("copy.7", 0.9, 0.1)]
    monkeypatch.setattr(program_trace, "load", lambda directory: {
        "ops": {"/device:TPU:0": ops},
        "programs": {"/device:TPU:0": ["jit_train_step"] * 2}})
    monkeypatch.setattr(program_trace, "run_dir", lambda: "unused")
    counts = {"items": [[384] * 8]}
    work = ssd_lm_trial_steps.kernel_work(config(), counts, 1)
    work.update(layers=1, ssd_layers=1, routed_layers=1,
                ssd_scan_fwd=work["ssd_scan_fwd"][:1],
                ssd_scan_bwd=work["ssd_scan_bwd"][:1])
    rec = {"step_s": [0.5, 0.5], "trace": {"busy_s": 1.0, "window_s": 1.0},
           "kernel_work": work, "device_kind": "TPU v5 lite"}
    read = lambda name: _reader(name).read(rec)  # noqa: E731
    assert read("ssd_mixer_device_ms") == pytest.approx(150)
    assert read("ssd_scan_core_device_ms") == pytest.approx(100)
    assert read("ssd_lm_moe_device_ms") == pytest.approx(115)
    assert read("ssd_lm_moe_experts_device_ms") == pytest.approx(65)
    assert read("ssd_lm_moe_shared_device_ms") == pytest.approx(50)
    assert read("ssd_lm_moe_route_device_ms") == pytest.approx(0)
    assert read("ssd_lm_attention_core_device_ms") == pytest.approx(50)
    assert read("ssd_lm_unnamed_device_ms") == pytest.approx(50)
    hbm = 819e9
    assert read("ssd_scan_fwd_roofline") == pytest.approx(
        100 * work["ssd_scan_fwd"][0]["bytes"] / hbm / 0.05, rel=1e-6)
    assert read("ssd_scan_bwd_roofline") == pytest.approx(
        100 * work["ssd_scan_bwd"][0]["bytes"] / hbm / 0.15, rel=1e-6)
    act = work["expert_act"]
    assert read("ssd_lm_expert_act_roofline") == pytest.approx(
        100 * (act[0]["bytes"] + act[1]["bytes"]) / hbm / 0.03, rel=1e-6)
    assert 0 < read("ssd_lm_moe_experts_roofline") < 100
    assert 0 < read("ssd_lm_flash_fwd_roofline") < 100


# -- correct -------------------------------------------------------------------

def faulty_sides(ctx, faults):
    """(the program against the reference, {fault: the reference with the
    fault planted, as the program's side, against the sound reference}):
    the program's first steps and the sound reference are made once."""
    first = ssd_lm_trial_steps.readings(ctx)
    rows = first.pop("rows")
    spec = ctx.config["check"]
    ref = ssd_lm_train3.reference_readings(ctx.config, ctx.seed, rows,
                                           "float32")
    start = ssd_lm_train3.weights(ctx.config, ctx.seed)
    judged = lambda side: (lambda numbers: {  # noqa: E731
        "numbers": numbers,
        "correct": all(n["ok"] for n in numbers.values())})(
            checks.compare(side, ref, start, spec["limits"]))
    sound, out = judged(first), {}
    del first
    for fault in faults:
        out[fault] = judged(ssd_lm_train3.reference_readings(
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
    first = ssd_lm_trial_steps.readings(ctx)
    rows = first.pop("rows")
    program = checks.run(ctx.config, ctx.seed, rows, first)
    control = checks.run(ctx.config, ctx.seed, rows)
    assert program["correct"], program["numbers"]
    assert not control["correct"]


def test_the_rehearsal_is_green(tmp_path):
    result = ssd_lm_trial_steps.run(context(tmp_path))
    assert result["correct"], result["records"]["check"]
    rec = result["records"]
    assert rec["compiles_in_window"] == 0
    work = rec["kernel_work"]
    assert (work["layers"], work["ssd_layers"], work["routed_layers"]) \
        == (1, 4, 4)
    assert rec["moe_counts"]["dropped"] == [0, 0, 0, 0]
    assert len(rec["choice_counts"]["bias_moved"]) == 4
    assert {"step_s", "items_per_s", "flops_per_item", "device_kind", "chips",
            "peak_bytes", "kernel_work"} <= set(rec)


def test_a_program_without_the_family_s_reader_is_refused_not_crashed(
        tmp_path, monkeypatch):
    assert ssd_lm_trial_steps.has_mechanism()
    monkeypatch.setattr(ssd_lm_trial_steps, "FAMILY", "nemotron_next")
    assert not ssd_lm_trial_steps.has_mechanism()
    with pytest.raises(harness.Refused, match="reader"):
        ssd_lm_trial_steps.run(context(tmp_path))


if __name__ == "__main__":
    # planted faults at the cell's own sizes, on the chip
    _names = list(FAULTS) if sys.argv[1] == "all" else sys.argv[1].split(",")
    _seed = int(sys.argv[2]) if len(sys.argv) > 2 else 2 ** 31 + 49
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
