"""The pattern decoder's cell (``smallthinker-21b.steady-8k``) at sizes a
test run can hold: its FLOP and byte counts against counts by brute force,
its readers on canned records and a recorded trace's operations, the
planted faults and the control failing ``correct``, and its rehearsal."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import (checks, flops_lm, kernel_trace, lm_config,
                       program_trace, run as harness)
from chipbench.checks import lm_train3
from chipbench.reference import lm as reference
from chipbench.run import _reader
from chipbench.runners import lm_trial_steps

CELL = "smallthinker-21b.steady-8k"
HERE = os.path.dirname(__file__)


def context(tmp_path, seed=2 ** 31 + 33):
    _, ctx = harness.cell_context(CELL, seed, 1.0, False, True, time.time())
    ctx.run_dir = str(tmp_path)
    return ctx


def config():
    with open(os.path.join(harness.ROOT, "chipbench", "configs",
                           "smallthinker-21b-a3b-ep4.json")) as f:
        return json.load(f)


# -- the configuration ---------------------------------------------------------

def test_the_cut_is_the_issue_s():
    c = config()
    cfg = lm_config.reference_cfg(c)
    assert cfg["layers"] == [(False, False)] + 3 * [(True, True)]
    assert (cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]) == (28, 4, 128)
    assert (cfg["n_experts"], cfg["top_k"], cfg["expert_d_ff"]) == (64, 6, 768)
    assert cfg["experts_held"] == [0, 16] and cfg["vocab_held"] == [0, 37984]
    shapes = reference.param_shapes(cfg)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert round(n / 1e6, 1) == 656.5          # x 16 bytes = 10.5 GB
    assert set(c["reduced"]) == {"num_hidden_layers",
                                 "moe_num_primary_experts", "vocab_size"}
    desc = lm_config.description(c)
    assert desc["moe_num_primary_experts"] == 64   # routed over, not held
    assert desc["experts_held"] == [0, 16]


# -- operations and bytes ------------------------------------------------------

@pytest.mark.parametrize("s, window", [(16, None), (16, 5), (16, 16),
                                       (16, 40), (33, 8), (8192, 4096)])
def test_seen_pairs_by_brute_force(s, window):
    if s > 100:
        i = np.arange(s)
        brute = int(np.minimum(i + 1, window).sum())
    else:
        brute = sum(1 for i in range(s) for j in range(s)
                    if 0 <= i - j and (window is None or i - j < window))
    assert flops_lm.seen_pairs(s, window) == brute


def test_forward_flops_by_brute_force_at_a_small_size():
    """Every product of reference/lm.py counted pair by pair and item by
    item over one row, against the closed form."""
    cfg = dict(d_model=8, n_heads=4, n_kv_heads=2, head_dim=4,
               layers=[(False, False), (True, True)], window=3, n_experts=8,
               top_k=2, expert_d_ff=6, experts_held=[2, 4],
               vocab_held=[0, 10])
    s = 7
    total = 0
    for sliding, _ in cfg["layers"]:
        for i in range(s):
            total += 2 * 8 * (4 + 2 + 2) * 4      # q, k, v
            total += 2 * 4 * 4 * 8                # out
            total += 2 * 8 * 8                    # router
            seen = [j for j in range(s) if 0 <= i - j
                    and (not sliding or i - j < 3)]
            total += 4 * len(seen) * 2 * 4 * 2    # heads x (scores, values)
            total += (2 * 4 / 8) * 3 * 2 * 8 * 6  # experts met here
    total += s * 2 * 8 * 10                       # the head
    assert flops_lm.forward_flops_per_token(cfg, s) * s \
        == pytest.approx(total)
    assert flops_lm.train_flops_per_item(cfg, s) * s == pytest.approx(3 * total)


def test_the_issue_s_count_of_the_work_a_token():
    cfg = lm_config.reference_cfg(config())
    per = flops_lm.forward_flops_per_token(cfg, 8192)
    assert per == pytest.approx(624.9e6, rel=2e-3)
    fwd = flops_lm.flash_fwd_call(cfg, 8192, None)
    win = flops_lm.flash_fwd_call(cfg, 8192, 4096)
    assert fwd["flops"] / 8192 == pytest.approx(58.7e6, rel=1e-3)
    assert win["flops"] / 8192 == pytest.approx(44.0e6, rel=1e-3)
    assert flops_lm.flash_bwd_call(cfg, 8192, None)["flops"] \
        == 2.5 * fwd["flops"]
    assert flops_lm.experts_pass(cfg, 12288)["flops"] \
        == 3 * 2 * 12288 * 2560 * 768


def test_a_roofline_share_is_the_longer_bound_over_the_time():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops_lm.roofline_share({"flops": 50, "bytes": 1}, 1.0, peak) == 50
    assert flops_lm.roofline_share({"flops": 50, "bytes": 8}, 1.0, peak) == 80


# -- the readers ---------------------------------------------------------------

def canned():
    with open(os.path.join(HERE, "data", "records_lm_trial_steps.json")) as f:
        return json.load(f)


def test_the_counters_readers_on_a_canned_run():
    rec = canned()
    assert _reader("moe_dropped_share").read(rec) == 0.0
    # layer 0's fullest expert: 30 of 4 experts' 80 items
    assert _reader("moe_held_load_max_over_mean").read(rec) \
        == pytest.approx(30 * 4 / 80)
    assert _reader("step_ms_p50").read(rec) == 250.0
    for name in ("attention_core_device_ms", "moe_device_ms",
                 "moe_route_device_ms", "flash_fwd_roofline",
                 "moe_experts_roofline"):
        assert _reader(name).read(rec) is None    # no trace in it


@pytest.fixture
def recorded_ops(monkeypatch):
    """``program_trace.load`` handing out ``data/lm_ops.json``: the device
    operations of two traced steps of the cell (op_name, start, seconds),
    recorded on the chip."""
    with open(os.path.join(HERE, "data", "lm_ops.json")) as f:
        doc = json.load(f)
    loaded = {"ops": {"/device:TPU:0": [tuple(e) for e in doc["ops"]]},
              "programs": {"/device:TPU:0": doc["programs"]}}
    monkeypatch.setattr(program_trace, "load", lambda directory: loaded)
    monkeypatch.setattr(program_trace, "run_dir", lambda: "unused")
    return doc


def test_the_trace_readers_on_recorded_operations(recorded_ops):
    rec = dict(canned(), trace={"busy_s": 1.0, "window_s": 1.0},
               kernel_work=recorded_ops["kernel_work"])
    want = recorded_ops["expected"]
    got = {name: _reader(name).read(rec) for name in want}
    for name, value in want.items():
        assert got[name] == pytest.approx(value, rel=1e-6), name
    assert got["moe_route_device_ms"] == pytest.approx(
        got["moe_device_ms"] - got["moe_experts_device_ms"])
    for name in ("flash_fwd_roofline", "flash_bwd_roofline",
                 "moe_experts_roofline"):
        assert 0 < got[name] <= 100, name
    seconds, calls = kernel_trace.kernel_seconds(rec, "flash_fwd")
    assert calls == 2 * 2 * 4      # two steps, forward and its second run


ACCEPTED_DEVICE = ("attention_core_device_ms", "readout_xent_device_ms",
                   "optimizer_device_ms", "scoped_device_share")
ACCEPTED_HOST = ("trial_data_s", "trial_init_s", "program_load_s",
                 "compile_cache_hit_share")


@pytest.mark.parametrize("accepted", ACCEPTED_DEVICE)
def test_an_accepted_device_metric_in_this_cell(recorded_ops, accepted):
    """The cell reports the accepted metrics of the layers it runs under
    their own names: one entry a metric, the cell in its list."""
    rec = dict(canned(), trace={"busy_s": 1.0, "window_s": 1.0})
    value = _reader(accepted).read(rec)
    assert value is not None and value > 0
    assert _reader(accepted).read(canned()) is None   # no trace


@pytest.mark.parametrize("accepted", ACCEPTED_HOST)
def test_an_accepted_set_up_metric_in_this_cell(monkeypatch, accepted):
    from metaopt_tpu.utils import trace

    with open(os.path.join(HERE, "data", "ring_spans.jsonl")) as f:
        ring = [r for r in map(json.loads, f) if "name" in r]
    monkeypatch.setattr(trace, "_ring", ring)
    assert _reader(accepted).read({}) is not None
    monkeypatch.setattr(trace, "_ring", [])        # no set-up in the ring
    assert _reader(accepted).read({}) is None


def test_the_cell_s_line_names_every_layer_it_runs():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(ACCEPTED_DEVICE + ACCEPTED_HOST) <= mine


def test_the_trace_readers_read_nothing_without_the_kernels(monkeypatch):
    rec = dict(canned(), trace={"busy_s": 1.0, "window_s": 1.0})
    monkeypatch.setattr(program_trace, "load", lambda directory: {
        "ops": {"/device:TPU:0": [("jit(train_step)/ffn/dot", 0.0, 1.0)]},
        "programs": {"/device:TPU:0": ["jit_train_step"]}})
    monkeypatch.setattr(program_trace, "run_dir", lambda: "unused")
    assert kernel_trace.kernel_seconds(rec, "flash_fwd") is None
    assert _reader("flash_fwd_roofline").read(rec) is None
    assert _reader("moe_experts_roofline").read(rec) is None


# -- correct -------------------------------------------------------------------

def two_sides(ctx, monkeypatch, faulty_loss):
    """``checks.compare`` of the reference with ``faulty_loss`` in its
    loss's place (as the program's side) against the reference."""
    first = lm_trial_steps.readings(ctx)
    rows = first.pop("rows")
    with monkeypatch.context() as m:
        m.setattr(reference, "loss", faulty_loss)
        broken = lm_train3.reference_readings(ctx.config, ctx.seed, rows,
                                              "float32")
    sound = checks.run(ctx.config, ctx.seed, rows, first)
    faulty = checks.run(ctx.config, ctx.seed, rows, broken)
    return sound, faulty


REAL_LOSS = reference.loss


def an_expert_left_out(params, rows, cfg, mode="float32"):
    p = jax.tree.map(lambda x: x, params)
    for which in ("gate", "up", "down"):
        p["h1"]["experts"][which]["e02"] = 0.0 * p["h1"]["experts"][which]["e02"]
    return REAL_LOSS(p, rows, cfg, mode)


def window_one_too_long(params, rows, cfg, mode="float32"):
    return REAL_LOSS(params, rows, {**cfg, "window": cfg["window"] + 1},
                     mode)


def wrong_kv_head(params, rows, cfg, mode="float32"):
    """Query head h reads K/V head h % kv_heads, not h // group."""
    h, kv = cfg["n_heads"], cfg["n_kv_heads"]
    perm = jnp.asarray(sorted(range(h), key=lambda j: (j % kv, j)))
    p = jax.tree.map(lambda x: x, params)
    for i in range(len(cfg["layers"])):
        attn = dict(p[f"h{i}"]["attn"])
        attn["q"] = {"kernel": attn["q"]["kernel"][:, perm]}
        attn["out"] = {"kernel": attn["out"]["kernel"][perm]}
        p[f"h{i}"] = {**p[f"h{i}"], "attn": attn}
    return REAL_LOSS(p, rows, cfg, mode)


def rotary_on_the_global_layer(params, rows, cfg, mode="float32"):
    layers = [(s, True) for s, _ in cfg["layers"]]
    return REAL_LOSS(params, rows, {**cfg, "layers": layers}, mode)


@pytest.mark.parametrize("fault", [
    an_expert_left_out, window_one_too_long, wrong_kv_head,
    rotary_on_the_global_layer], ids=lambda f: f.__name__)
def test_a_planted_fault_fails_correct(tmp_path, monkeypatch, fault):
    sound, faulty = two_sides(context(tmp_path), monkeypatch, fault)
    assert sound["correct"], sound["numbers"]
    assert not faulty["correct"], faulty["numbers"]


def test_the_control_fails_where_the_program_passes(tmp_path):
    ctx = context(tmp_path, seed=11)
    first = lm_trial_steps.readings(ctx)
    rows = first.pop("rows")
    program = checks.run(ctx.config, ctx.seed, rows, first)
    control = checks.run(ctx.config, ctx.seed, rows)
    assert program["correct"], program["numbers"]
    assert not control["correct"]


def test_the_rehearsal_is_green(tmp_path):
    result = lm_trial_steps.run(context(tmp_path))
    assert result["correct"], result["records"]["check"]
    rec = result["records"]
    assert rec["moe_counts"]["dropped"] == [0, 0, 0, 0]
    assert rec["compiles_in_window"] == 0
    assert {"step_s", "items_per_s", "flops_per_item", "device_kind", "chips",
            "peak_bytes"} <= set(rec)


def test_a_program_without_the_trial_is_refused_not_crashed(tmp_path,
                                                            monkeypatch):
    import metaopt_tpu.models.lm as lm

    monkeypatch.delattr(lm, "LMTrial")
    with pytest.raises(harness.Refused, match="LMTrial"):
        lm_trial_steps.run(context(tmp_path))
