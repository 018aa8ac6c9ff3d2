"""The short-convolution decoder's cell (``lfm2-24b.steady-8k``) at sizes a
test run can hold: the cut and its parameter count, its FLOP and byte counts
by hand, its readers on canned records and on the recorded step, the planted
faults and the control failing ``correct``, its rehearsal, and a program
without the family's reader refused. ``python3
chipbench/tests/test_conv_lm_cell.py FAULT[,FAULT...]|all [SEED]`` reads
planted faults at the cell's own sizes on the chip: the program's first
steps and the sound reference once, then one faulty reference a fault
(``reference/conv_lm.py`` takes the fault's name)."""

import ast
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

from chipbench import checks, conv_lm_config, flops_conv_lm, flops_lm, \
    run as harness
from chipbench.checks import conv_lm_train3
from chipbench.reference import conv_lm as reference
from chipbench.run import _reader
from chipbench.runners import conv_lm_trial_steps

CELL = "lfm2-24b.steady-8k"
#: the planted faults, by the names ``reference/conv_lm.py`` knows them by
FAULTS = {
    "thirds_xbc": "the thirds read in another order, X | B | C",
    "no_gate_c": "the gate C left out",
    "gate_b_after_conv": "the gate B applied after the convolution",
    "taps_reversed": "the taps reversed in time",
    "silu_after_conv": "a SiLU after the convolution (the Mamba habit)",
    "conv_bias": "a convolution bias",
    "no_qk_norm": "the q/k norms left out",
    "norm_after_rotation": "the q/k norm after the rotation",
    "no_rotation": "the rotation left out",
    "softmax_scores": "softmax for sigmoid scores",
    "bias_in_weights": "the bias in the weights, not in the choice alone",
    "normalise_over_held": "normalising over the held, not the chosen",
    "no_routing_eps": "the normalisation's 1e-6 left out",
    "no_last_norm": "the last norm left out",
    "untied_head": "an untied head: no gradient from the logits to the table",
    "dense_as_expert": "the dense layer read at an expert's width",
}
#: those the comparison does not see at the rehearsal's sizes, and why: 1e-6
#: beside a sum of three scores of ~0.5 each is under float32's rounding; an
#: RMS norm over a head is the same number before and after a rotation,
#: which turns pairs and keeps their squares' sum, so the fault moves only
#: where the learned scale (1 + 0.1 N) sits; a bias of 0.005 beside scores
#: of ~0.5 moves a weight by a hundredth; and the tied table's gradient is
#: its input side's by nine parts in ten at 96 tokens (the first norm
#: scales the small embeddings up), so the head's part, which the planted
#: untied head loses, reads 0.11 where six routed layers of top 3 of 16,
#: whose last chosen score swaps under bfloat16 for a token in ten, read
#: 0.16-0.26 sound (the rehearsal's limit is 0.4; the fp8 control 0.62)
NOT_SEEN = ("no_routing_eps", "norm_after_rotation", "bias_in_weights",
            "untied_head")


def context(tmp_path, seed=2 ** 31 + 53, rehearsal=True):
    _, ctx = harness.cell_context(CELL, seed, 1.0, False, rehearsal,
                                  time.time())
    ctx.run_dir = str(tmp_path)
    return ctx


def config():
    with open(os.path.join(harness.ROOT, "chipbench", "configs",
                           "lfm2-24b-a2b-ep8.json")) as f:
        return json.load(f)


# -- the configuration ---------------------------------------------------------

def test_the_cut_is_the_issue_s():
    c = config()
    cfg = conv_lm_config.reference_cfg(c)
    assert (cfg["d_model"], cfg["numbers"], cfg["taps"]) == (
        2048, [1, 2, 3, 4, 5, 6, 7], 3)
    assert cfg["kinds"] == ["conv", "full_attention", "conv", "conv", "conv",
                            "full_attention", "conv"]
    assert (cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"],
            cfg["rope_theta"], cfg["rms_eps"]) == (32, 8, 64, 1e6, 1e-5)
    assert (cfg["dense_layers"], cfg["d_ff"]) == (2, 11776)
    assert (cfg["n_experts"], cfg["top_k"], cfg["expert_d_ff"], cfg["scale"],
            cfg["routing_eps"], cfg["use_bias"]) == (64, 4, 1536, 1.0, 1e-6,
                                                     True)
    assert cfg["experts_held"] == [0, 8] and cfg["vocab_held"] == [0, 8192]
    size = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    # x 16 bytes = 10.37 GB; the six frozen biases' 384 among them
    assert size(reference.param_shapes(cfg)) == 647_819_520 + 384
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    desc = conv_lm_config.description(c)
    assert desc["num_experts"] == 64                 # routed over, not held
    assert len(desc["layer_types"]) == 40
    assert desc["layers_held"] == [1, 2, 3, 4, 5, 6, 7]
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    assert c["num_experts"] * 8 == c["published"]["num_experts"]
    assert c["deployment"]["chips_sharing_a_layer"] == 8


def test_the_parameters_by_hand():
    """The issue's arithmetic, layer by layer."""
    d, f, e = 2048, 1536, 8
    conv = d * 3 * d + 3 * d + d * d
    attention = d * 32 * 64 + 2 * d * 8 * 64 + 32 * 64 * d + 2 * 64
    routed = d * 64 + e * 3 * d * f
    dense = 3 * d * 11776
    assert conv + dense + 2 * d == 89_139_200
    assert attention + routed + 2 * d == 86_118_528
    assert conv + routed + 2 * d == 92_416_000
    assert 89_139_200 + 2 * 86_118_528 + 4 * 92_416_000 + 8192 * d + d \
        == 647_819_520


def test_every_number_of_the_catalog_s_config_is_kept():
    """Top-level keys as the catalog has them, but for ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    c = config()
    assert c["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in c["reduced"]:
            assert c[key] == value, key


@pytest.mark.parametrize("key, value", [
    ("model_type", "lfm2"), ("conv_bias", True), ("norm_topk_prob", False),
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"})])
def test_what_the_reference_does_not_compute_is_refused(key, value):
    c = config()
    c[key] = value
    with pytest.raises(ValueError, match=key):
        conv_lm_config.reference_cfg(c)


# -- operations and bytes ------------------------------------------------------

def test_the_core_s_work_by_hand():
    """A token and channel: forward B X, three taps (3 products, 2 sums) and
    the product with C, seven operations, three numbers read and one
    written; backward B X and c again (6), dy C, dy c, the transpose's three
    taps (5), du X, du B and the taps' gradient (3 multiply-adds): 21,
    four numbers read and three written; two bytes a number."""
    cfg = conv_lm_config.reference_cfg(config())
    t, d = 8192, 2048
    fwd = flops_conv_lm.short_conv_fwd_call(cfg, t)
    assert fwd == {"flops": 7 * t * d, "bytes": 2 * 4 * t * d}
    bwd = flops_conv_lm.short_conv_bwd_call(cfg, t)
    assert bwd == {"flops": (1 + 5 + 1 + 1 + 5 + 1 + 1 + 6) * t * d,
                   "bytes": 2 * 7 * t * d}
    # bound by bytes, by far
    assert fwd["bytes"] / 819e9 > 50 * fwd["flops"] / 197e12
    assert flops_conv_lm.short_conv_fwd_call(cfg, t, 2)["bytes"] \
        == 2 * fwd["bytes"]


def test_a_call_s_work_at_the_cell_s_sizes():
    cfg = conv_lm_config.reference_cfg(config())
    t = 8192
    flash = flops_conv_lm.flash_fwd_call(cfg, t)
    assert flash["flops"] == 4 * 64 * 32 * (t * (t + 1) // 2)
    three = flops_lm.experts_pass(cfg, 4096)     # gated experts
    assert three["flops"] == 3 * 2 * 4096 * 2048 * 1536
    counts = {"items": [[512] * 8] * 6}
    work = conv_lm_trial_steps.kernel_work(config(), counts, 1)
    assert (work["layers"], work["conv_layers"], work["routed_layers"]) \
        == (2, 5, 6)
    assert work["flash_fwd"] == [flash] * 2
    assert work["short_conv_fwd"] == [
        flops_conv_lm.short_conv_fwd_call(cfg, t)] * 5
    assert work["experts_pass"] == three and work["remat"] is True


def test_train_flops_by_brute_force_at_a_small_size():
    cfg = dict(d_model=8, numbers=[1, 2, 3], dense_layers=2, d_ff=11,
               kinds=["conv", "full_attention", "conv"], taps=3, n_heads=4,
               n_kv_heads=2, head_dim=2, n_experts=16, top_k=4,
               expert_d_ff=5, experts_held=[0, 8], vocab_held=[0, 50])
    s, d = 10, 8
    conv = s * (2 * d * 3 * d + 2 * d * d)
    attention = s * (2 * d * (4 + 2 * 2) * 2 + 2 * 4 * 2 * d) \
        + 2 * 2 * 2 * 4 * (s * (s + 1) // 2)
    dense = s * 3 * 2 * d * 11
    routed = s * (2 * d * 16 + 4 * 8 / 16 * 3 * 2 * d * 5)
    by_hand = (conv + dense) + (attention + routed) + (conv + routed) \
        + s * 2 * d * 50
    assert flops_conv_lm.forward_flops_per_token(cfg, s) * s \
        == pytest.approx(by_hand)
    assert flops_conv_lm.train_flops_per_item(cfg, s) * s \
        == pytest.approx(3 * by_hand)


def test_the_issue_s_reckoning_of_a_step():
    """~12.5 TFLOP a step: 515 MFLOP a token forward, of which the five
    mixers' projections 168 M, the dense layer 145 M, the six routed layers'
    held experts 57 M, the two attention layers 101 M, the head 34 M."""
    cfg = conv_lm_config.reference_cfg(config())
    whole = flops_conv_lm.forward_flops_per_token(cfg, 8192)
    d = 2048
    assert 5 * (2 * d * 3 * d + 2 * d * d) / 1e6 == pytest.approx(168, abs=1)
    assert 3 * 2 * d * 11776 / 1e6 == pytest.approx(145, abs=1)
    assert 6 * 0.5 * 3 * 2 * d * 1536 / 1e6 == pytest.approx(57, abs=1)
    assert whole / 1e6 == pytest.approx(515, abs=3)
    step = flops_conv_lm.train_flops_per_item(cfg, 8192) * 8192
    assert step / 1e12 == pytest.approx(12.66, abs=0.05)


# -- the readers ---------------------------------------------------------------

def mine():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return bench, [m["name"] for m in bench["per_layer"]
                   if CELL in m.get("workloads", ())]


OWN = {"short_conv_mixer_device_ms", "short_conv_core_device_ms",
       "short_conv_fwd_roofline", "short_conv_bwd_roofline",
       "conv_lm_compiler_for_mixer_device_ms"}


def test_the_cell_s_line_names_its_metrics():
    bench, names = mine()
    assert len(names) == 29 and len(bench["per_layer"]) == 119 <= 128
    assert all(os.path.exists(os.path.join(
        harness.HERE, "readers", name + ".py")) for name in names)
    assert all(name.startswith(("short_conv_", "conv_lm_")) for name in names)
    assert all(m["workloads"] == [CELL] for m in bench["per_layer"]
               if m["name"] in names)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-24b-a2b-ep8", "steady-conv-lm-8k", 1)
    assert len(cell["why"]) <= 200
    assert bench["workloads"][-1] == cell and len(bench["workloads"]) == 9


def _body(name):
    with open(os.path.join(harness.HERE, "readers", name + ".py")) as f:
        return ast.dump(ast.Module(body=ast.parse(f.read()).body[1:],
                                   type_ignores=[]))


def test_a_copied_reader_has_the_accepted_one_s_body():
    """``conv_lm_<name>`` is ``<name>`` for this cell: the same code under
    another name, until a ``benchmark`` PR folds it into the one entry."""
    copies = [n for n in mine()[1] if n not in OWN]
    assert len(copies) == 24
    for name in copies:
        accepted = name[len("conv_lm_"):]
        if accepted == "moe_choice_bias_share":
            accepted = "mla_lm_" + accepted
        assert _body(name) == _body(accepted), name


def test_no_two_entries_share_a_reader_s_body_and_a_cell():
    bench, names = mine()
    lists = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    bodies = {name: _body(name) for name in lists}
    for name in names:
        for other, body in bodies.items():
            if other != name and body == bodies[name]:
                assert lists[other] is not None \
                    and CELL not in lists[other], (name, other)


def test_the_readers_leave_their_metric_out_without_a_trace():
    rec = {"step_s": [0.3, 0.3]}
    for name in mine()[1]:
        if name not in ("conv_lm_program_load_s",
                        "conv_lm_compile_cache_hit_share"):
            assert _reader(name).read(rec) is None, name


def test_the_new_readers_find_nothing_on_a_program_without_the_scopes(
        monkeypatch):
    """As the parent of this cell's PR is: no ``short_conv`` among the
    program's scopes; the readers return None and do not raise."""
    from chipbench import program_trace
    from metaopt_tpu.utils import trace

    monkeypatch.setattr(trace, "SCOPES", tuple(
        s for s in trace.SCOPES if not s.startswith("short_conv")))
    monkeypatch.setattr(program_trace, "load", lambda directory: 1 / 0)
    rec = {"trace": {"busy_s": 1.0, "window_s": 1.0}}
    for name in ("short_conv_mixer_device_ms", "short_conv_core_device_ms",
                 "short_conv_fwd_roofline", "short_conv_bwd_roofline"):
        assert _reader(name).read(rec) is None, name


def test_the_trace_readers_on_a_few_operations(monkeypatch):
    from chipbench import program_trace

    f = "jit(train_step)/jvp(DecoderOnlyLM)/DecoderOnlyLM._patterned/"
    b = "jit(train_step)/transpose(jvp(DecoderOnlyLM))/" \
        "DecoderOnlyLM._patterned/checkpoint/"
    ops = [(f + "h1/conv/short_conv/in_proj/dot_general", 0.0, 0.1),
           (f + "h1/conv/short_conv/short_conv.core/jit(_forward)/"
            "short_conv_fwd/pallas_call", 0.1, 0.05),
           (b + "h1/conv/short_conv/short_conv.core/jit(_backward)/"
            "short_conv_bwd/pallas_call", 0.15, 0.15),
           (f + "h1/mlp/ffn/up/dot_general", 0.3, 0.05),
           (f + "h2/experts/moe/moe.experts/gmm/pallas_call", 0.35, 0.1),
           (f + "h2/router/moe/moe.router/dot_general", 0.45, 0.05),
           (f + "h2/attn/attention/attention.core/jit(_causal_forward)/"
            "flash_fwd/pallas_call", 0.6, 0.1),
           ("copy.7", 0.9, 0.1)]
    monkeypatch.setattr(program_trace, "load", lambda directory: {
        "ops": {"/device:TPU:0": ops},
        "programs": {"/device:TPU:0": ["jit_train_step"] * 2}})
    monkeypatch.setattr(program_trace, "run_dir", lambda: "unused")
    counts = {"items": [[512] * 8]}
    work = conv_lm_trial_steps.kernel_work(config(), counts, 1)
    work.update(layers=1, conv_layers=1, routed_layers=1,
                short_conv_fwd=work["short_conv_fwd"][:1],
                short_conv_bwd=work["short_conv_bwd"][:1],
                flash_fwd=work["flash_fwd"][:1])
    rec = {"step_s": [0.5, 0.5], "trace": {"busy_s": 1.0, "window_s": 1.0},
           "kernel_work": work, "device_kind": "TPU v5 lite"}
    read = lambda name: _reader(name).read(rec)  # noqa: E731
    assert read("short_conv_mixer_device_ms") == pytest.approx(150)
    assert read("short_conv_core_device_ms") == pytest.approx(100)
    assert read("conv_lm_ffn_device_ms") == pytest.approx(25)
    assert read("conv_lm_moe_device_ms") == pytest.approx(75)
    assert read("conv_lm_moe_experts_device_ms") == pytest.approx(50)
    assert read("conv_lm_moe_route_device_ms") == pytest.approx(25)
    assert read("conv_lm_attention_core_device_ms") == pytest.approx(50)
    assert read("conv_lm_unnamed_device_ms") == pytest.approx(50)
    hbm = 819e9
    assert read("short_conv_fwd_roofline") == pytest.approx(
        100 * 2 * 4 * 8192 * 2048 / hbm / 0.05, rel=1e-6)
    assert read("short_conv_bwd_roofline") == pytest.approx(
        100 * 2 * 7 * 8192 * 2048 / hbm / 0.15, rel=1e-6)
    assert 0 < read("conv_lm_moe_experts_roofline") < 100
    assert 0 < read("conv_lm_flash_fwd_roofline") < 100


def test_every_reader_on_the_recorded_step(monkeypatch):
    """``data/step_lfm2-24b.steady-8k.json``: one step of a traced run on the
    chip as its readers were handed it (tests/record_cell_step.py); every
    metric that lists the cell, and the four that list none, reads what it
    read there, and none but the compiler's three reads None."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import record_cell_step

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        f"step_{CELL}.json")
    with open(path) as f:
        doc = json.load(f)
    bench, names = mine()
    assert doc["cell"] == CELL
    assert {"step_ms_p50", "mfu", "device_idle_share.train",
            "peak_hbm_gb"} <= set(doc["expected"])
    # the compiler's three read the trace FILE's programs, which a recorded
    # step does not hold (tests/test_compiler_trace.py has their sample):
    # they are not among ``expected`` and read None here
    of_the_file = set(names) - set(doc["expected"])
    assert of_the_file == {"conv_lm_compiler_copy_device_ms",
                           "conv_lm_compiler_for_mixer_device_ms",
                           "conv_lm_compiler_owned_share"}
    record_cell_step.hand_out(doc, monkeypatch.setattr)
    for name in of_the_file:
        assert _reader(name).read(doc["records"]) is None, name
    for name, want in doc["expected"].items():
        value = _reader(name).read(doc["records"])
        assert value is not None and value == want, name
    line = harness.per_layer_metrics(bench, CELL, doc["records"])
    assert set(line) == set(doc["expected"])
    got = {name: m["value"] for name, m in line.items()}
    assert got["conv_lm_moe_route_device_ms"] == pytest.approx(
        got["conv_lm_moe_device_ms"] - got["conv_lm_moe_experts_device_ms"])
    assert 0 < got["short_conv_core_device_ms"] \
        < got["short_conv_mixer_device_ms"]
    for name in ("short_conv_fwd_roofline", "short_conv_bwd_roofline",
                 "conv_lm_flash_fwd_roofline", "conv_lm_flash_bwd_roofline",
                 "conv_lm_moe_experts_roofline", "mfu"):
        assert 0 < got[name] < 100, name
    assert got["conv_lm_moe_dropped_share"] == 0


# -- correct -------------------------------------------------------------------

def faulty_sides(ctx, faults):
    """(the program against the reference, {fault: the reference with the
    fault planted, as the program's side, against the sound reference}):
    the program's first steps and the sound reference are made once."""
    first = conv_lm_trial_steps.readings(ctx)
    rows = first.pop("rows")
    spec = ctx.config["check"]
    ref = conv_lm_train3.reference_readings(ctx.config, ctx.seed, rows,
                                            "float32")
    start = conv_lm_train3.weights(ctx.config, ctx.seed)
    judged = lambda side: (lambda numbers: {  # noqa: E731
        "numbers": numbers,
        "correct": all(n["ok"] for n in numbers.values())})(
            checks.compare(side, ref, start, spec["limits"]))
    sound, out = judged(first), {}
    del first
    for fault in faults:
        out[fault] = judged(conv_lm_train3.reference_readings(
            ctx.config, ctx.seed, rows, "float32", faults=(fault,)))
    return sound, out


@pytest.fixture(scope="module")
def planted(tmp_path_factory):
    return faulty_sides(context(tmp_path_factory.mktemp("faults")),
                        list(FAULTS))


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_planted_fault_fails_correct(planted, fault):
    """Twelve of the sixteen fail a limit at the rehearsal's sizes; the
    four of ``NOT_SEEN`` pass, each for the reason written there."""
    assert set(FAULTS) == set(reference.FAULTS)
    sound, faulty = planted
    assert sound["correct"], sound["numbers"]
    assert faulty[fault]["correct"] == (fault in NOT_SEEN), \
        faulty[fault]["numbers"]


def test_the_control_fails_where_the_program_passes(tmp_path):
    ctx = context(tmp_path, seed=11)
    first = conv_lm_trial_steps.readings(ctx)
    rows = first.pop("rows")
    program = checks.run(ctx.config, ctx.seed, rows, first)
    control = checks.run(ctx.config, ctx.seed, rows)
    assert program["correct"], program["numbers"]
    assert not control["correct"]


def test_the_rehearsal_is_green(tmp_path):
    result = conv_lm_trial_steps.run(context(tmp_path))
    assert result["correct"], result["records"]["check"]
    rec = result["records"]
    assert rec["compiles_in_window"] == 0
    work = rec["kernel_work"]
    assert (work["layers"], work["conv_layers"], work["routed_layers"]) \
        == (2, 5, 6)
    assert rec["moe_counts"]["dropped"] == [0] * 6
    assert len(rec["choice_counts"]["bias_moved"]) == 6
    assert {"step_s", "items_per_s", "flops_per_item", "device_kind", "chips",
            "peak_bytes", "kernel_work"} <= set(rec)


def test_a_program_without_the_family_s_reader_is_refused_not_crashed(
        tmp_path, monkeypatch):
    assert conv_lm_trial_steps.has_mechanism()
    monkeypatch.setattr(conv_lm_trial_steps, "FAMILY", "lfm3_moe")
    assert not conv_lm_trial_steps.has_mechanism()
    with pytest.raises(harness.Refused, match="reader"):
        conv_lm_trial_steps.run(context(tmp_path))


if __name__ == "__main__":
    # planted faults at the cell's own sizes, on the chip
    _names = list(FAULTS) if sys.argv[1] == "all" else sys.argv[1].split(",")
    _seed = int(sys.argv[2]) if len(sys.argv) > 2 else 2 ** 31 + 53
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
