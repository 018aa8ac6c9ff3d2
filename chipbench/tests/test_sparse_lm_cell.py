"""The selected-attention decoder's cell (``keye-vl2-30b.steady-16k``) at
sizes a test run can hold: the cut, its FLOP and byte counts against counts
by brute force, its readers on canned records, the planted faults and the
control failing ``correct``, its rehearsal, and a program without the
mechanism refused. ``python3 chipbench/tests/test_sparse_lm_cell.py FAULT
[SEED]`` reads one planted fault at the cell's own sizes on the chip."""

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

from chipbench import (checks, flops_lm, flops_sparse_lm, run as harness,
                       sparse_lm_config)
from chipbench.checks import sparse_lm_train3
from chipbench.reference import sparse_lm as reference
from chipbench.run import _reader
from chipbench.runners import sparse_lm_trial_steps

CELL = "keye-vl2-30b.steady-16k"


def context(tmp_path, seed=2 ** 31 + 33, rehearsal=True):
    _, ctx = harness.cell_context(CELL, seed, 1.0, False, rehearsal,
                                  time.time())
    ctx.run_dir = str(tmp_path)
    return ctx


def config():
    with open(os.path.join(harness.ROOT, "chipbench", "configs",
                           "keye-vl2-30b-a3b-ep8.json")) as f:
        return json.load(f)


# -- the configuration ---------------------------------------------------------

def test_the_cut_is_the_issue_s():
    c = config()
    cfg = sparse_lm_config.reference_cfg(c)
    assert (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
            cfg["head_dim"]) == (2048, 32, 4, 128)
    assert (cfg["n_experts"], cfg["top_k"], cfg["expert_d_ff"]) == (128, 8,
                                                                    768)
    assert (cfg["index_heads"], cfg["index_dim"], cfg["top_keys"]) == (
        16, 64, 2048)
    assert cfg["rope_theta"] == 1e7 and cfg["activation"] == "silu"
    assert cfg["n_layers"] == 4
    assert cfg["experts_held"] == [0, 16] and cfg["vocab_held"] == [0, 18992]
    shapes = reference.param_shapes(cfg)
    size = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    trained = size(reference.trained(shapes))
    assert round(trained / 1e6, 1) == 456.3          # x 16 bytes = 7.30 GB
    assert size(shapes) - trained == 4 * 2_261_120   # the indexers, x 4 bytes
    assert set(c["reduced"]) == {"num_hidden_layers", "num_experts",
                                 "vocab_size"}
    desc = sparse_lm_config.description(c)
    assert desc["num_experts"] == 128              # routed over, not held
    assert desc["experts_held"] == [0, 16]


def test_every_number_of_the_catalog_s_config_is_kept():
    """Top-level numbers as the catalog has them, but for ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    c = config()
    assert c["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in c["reduced"]:
            assert c[key] == value, key


# -- operations and bytes ------------------------------------------------------

@pytest.mark.parametrize("s, keys", [(16, 5), (16, 16), (16, 40), (33, 8),
                                     (16384, 2048)])
def test_selected_pairs_by_brute_force(s, keys):
    t = np.arange(s)
    assert flops_sparse_lm.selected_pairs(s, keys) \
        == int(np.minimum(t + 1, keys).sum())
    assert flops_sparse_lm.causal_pairs(s) == s * (s + 1) // 2


def test_the_issue_s_share_of_the_causal_pairs():
    share = lambda s: (flops_sparse_lm.selected_pairs(s, 2048)  # noqa: E731
                       / flops_sparse_lm.causal_pairs(s))
    assert share(16384) == pytest.approx(0.234, abs=5e-4)
    assert share(8192) == pytest.approx(0.44, abs=5e-3)
    assert share(2048) == 1.0


def test_train_flops_by_brute_force_at_a_small_size():
    cfg = dict(d_model=8, n_heads=4, n_kv_heads=2, head_dim=4, n_layers=2,
               index_heads=2, index_dim=4, top_keys=3, n_experts=8, top_k=2,
               expert_d_ff=6, experts_held=[2, 4], vocab_held=[0, 10])
    s = 7
    trained = index = 0
    for _ in range(cfg["n_layers"]):
        for t in range(s):
            trained += 2 * 8 * (4 + 2 + 2) * 4      # q, k, v
            trained += 2 * 4 * 4 * 8                # out
            trained += 2 * 8 * 8                    # router
            trained += 4 * min(t + 1, 3) * 4 * 4    # scores, values: selected
            trained += (2 * 4 / 8) * 3 * 2 * 8 * 6  # experts met here
            index += 2 * 8 * (2 * 4 + 4 + 2)        # qI, kI, w
            index += 2 * 2 * 4 * (t + 1)            # scores: causal pairs
    trained += s * 2 * 8 * 10                       # the head
    assert flops_sparse_lm.train_flops_per_item(cfg, s) * s \
        == pytest.approx(3 * trained + index)


def test_a_kernel_s_call_counts_the_selected_pairs_and_the_bits():
    cfg = sparse_lm_config.reference_cfg(config())
    fwd = flops_sparse_lm.sparse_fwd_call(cfg, 16384)
    bwd = flops_sparse_lm.sparse_bwd_call(cfg, 16384)
    assert fwd["flops"] == 4 * 128 * 32 * 31_458_304   # 23.4 % of causal
    assert bwd["flops"] == 2.5 * fwd["flops"]
    plain = flops_lm.flash_fwd_call(cfg, 16384, 2048)
    assert fwd["bytes"] - plain["bytes"] == 16384 * 16384 // 8


# -- the readers ---------------------------------------------------------------

def canned():
    return {"step_s": [0.7, 0.7], "moe_counts": {
        "items": [[30, 10]], "dropped": [0]},
        "selection_counts": {"selected_pairs": [10, 30],
                             "causal_pairs": [40, 120]}}


def test_the_counters_readers_on_a_canned_run():
    rec = canned()
    assert _reader("sparse_selected_share").read(rec) == 25.0
    assert _reader("moe_dropped_share").read(rec) == 0.0
    assert _reader("sparse_selected_share").read({}) is None
    for name in ("sparse_index_device_ms", "sparse_select_device_ms",
                 "attention_core_device_ms", "sparse_fwd_roofline",
                 "sparse_bwd_roofline", "moe_device_ms",
                 "moe_route_device_ms",
                 "moe_experts_roofline",
                 "scoped_device_share"):
        assert _reader(name).read(rec) is None    # no trace in it


def test_the_trace_readers_on_a_few_operations(monkeypatch):
    from chipbench import program_trace

    ops = [("jit(train_step)/h0/attn/attention/indexer/attention.index/dot",
            0.0, 0.2),
           ("jit(train_step)/h0/attn/attention/indexer/attention.select/"
            "while", 0.2, 0.1),
           ("jit(train_step)/h0/attn/attention/attention.core/sparse_fwd/"
            "pallas_call", 0.3, 0.1),
           ("jit(train_step)/transpose(jvp(h0))/attn/attention/"
            "attention.core/sparse_bwd/pallas_call", 0.4, 0.2)]
    monkeypatch.setattr(program_trace, "load", lambda directory: {
        "ops": {"/device:TPU:0": ops},
        "programs": {"/device:TPU:0": ["jit_train_step"] * 2}})
    monkeypatch.setattr(program_trace, "run_dir", lambda: "unused")
    cfg = sparse_lm_config.reference_cfg(config())
    work = {"layers": 1,
            "sparse_fwd": [flops_sparse_lm.sparse_fwd_call(cfg, 16384)],
            "sparse_bwd": [flops_sparse_lm.sparse_bwd_call(cfg, 16384)]}
    rec = dict(canned(), trace={"busy_s": 1.0, "window_s": 1.0},
               kernel_work=work, device_kind="TPU v5 lite")
    assert _reader("sparse_index_device_ms").read(rec) == pytest.approx(100)
    assert _reader("sparse_select_device_ms").read(rec) == pytest.approx(50)
    assert _reader("attention_core_device_ms").read(rec) \
        == pytest.approx(150)
    # one call in the slice: 16.1 TFLOP over 0.1 s at 197 TFLOP/s
    assert _reader("sparse_fwd_roofline").read(rec) == pytest.approx(
        100 * work["sparse_fwd"][0]["flops"] / 197e12 / 0.1, rel=1e-6)
    assert 0 < _reader("sparse_bwd_roofline").read(rec) < 100


def test_the_cell_s_line_names_every_metric_the_issue_lists():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert len(mine) == 22
    assert all(os.path.exists(os.path.join(
        harness.HERE, "readers", name + ".py")) for name in mine)


# -- correct -------------------------------------------------------------------

def two_sides(ctx, monkeypatch, faulty_loss):
    """``checks.compare`` of the reference with ``faulty_loss`` in its
    loss's place (as the program's side) against the reference."""
    first = sparse_lm_trial_steps.readings(ctx)
    rows = first.pop("rows")
    with monkeypatch.context() as m:
        m.setattr(reference, "loss", faulty_loss)
        broken = sparse_lm_train3.reference_readings(ctx.config, ctx.seed,
                                                     rows, "float32")
    sound = checks.run(ctx.config, ctx.seed, rows, first)
    faulty = checks.run(ctx.config, ctx.seed, rows, broken)
    return sound, faulty


REAL_LOSS = reference.loss
REAL = {name: getattr(reference, name)
        for name in ("index_scores", "select_keys", "_rms")}


@contextlib.contextmanager
def in_place_of(name, stand_in):
    setattr(reference, name, stand_in)
    try:
        yield
    finally:
        setattr(reference, name, REAL[name])


def index_scores_from_bfloat16_products(params, rows, cfg, mode="float32"):
    """The precision below the file's for the index scores: operands
    rounded to bfloat16, float32 sums."""
    def scores(q, k, w):
        s = jnp.einsum("rhk,sk->rhs", q.astype(jnp.bfloat16),
                       k.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        return jnp.einsum("rh,rhs->rs", w, jax.nn.relu(s),
                          precision=jax.lax.Precision.HIGHEST)

    with in_place_of("index_scores", scores):
        return REAL_LOSS(params, rows, cfg, mode)


def half_the_keys_selected(params, rows, cfg, mode="float32"):
    return REAL_LOSS(params, rows, {**cfg, "top_keys": cfg["top_keys"] // 2},
                     mode)


def the_selection_not_causal(params, rows, cfg, mode="float32"):
    """Every query chooses among all the row's keys, later ones included."""
    def select(scores, t, top_keys):
        return REAL["select_keys"](scores, jnp.full_like(
            t, scores.shape[1] - 1), top_keys)

    with in_place_of("select_keys", select):
        return REAL_LOSS(params, rows, cfg, mode)


def the_head_weights_left_out(params, rows, cfg, mode="float32"):
    with in_place_of("index_scores", lambda q, k, w: REAL["index_scores"](
            q, k, jnp.ones_like(w))):
        return REAL_LOSS(params, rows, cfg, mode)


def the_relu_left_out(params, rows, cfg, mode="float32"):
    def scores(q, k, w):
        s = jnp.einsum("rhk,sk->rhs", q, k,
                       precision=jax.lax.Precision.HIGHEST)
        return jnp.einsum("rh,rhs->rs", w, s,
                          precision=jax.lax.Precision.HIGHEST)

    with in_place_of("index_scores", scores):
        return REAL_LOSS(params, rows, cfg, mode)


def an_expert_left_out(params, rows, cfg, mode="float32"):
    p = jax.tree.map(lambda x: x, params)
    for which in ("gate", "up", "down"):
        p["h1"]["experts"][which]["e02"] = 0.0 * p["h1"]["experts"][which]["e02"]
    return REAL_LOSS(p, rows, cfg, mode)


def relu_in_silu_s_place(params, rows, cfg, mode="float32"):
    return REAL_LOSS(params, rows, {**cfg, "activation": "relu"}, mode)


def the_qk_norms_left_out(params, rows, cfg, mode="float32"):
    """q and k (S, heads, width) go on as projected; the residual stream's
    norms (S, d) stay."""
    with in_place_of("_rms", lambda x, scale, eps: x if x.ndim == 3
                     else REAL["_rms"](x, scale, eps)):
        return REAL_LOSS(params, rows, cfg, mode)


FAULTS = [index_scores_from_bfloat16_products, half_the_keys_selected,
          the_selection_not_causal, the_head_weights_left_out,
          the_relu_left_out, an_expert_left_out, relu_in_silu_s_place,
          the_qk_norms_left_out]


@pytest.mark.parametrize("fault", FAULTS[1:], ids=lambda f: f.__name__)
def test_a_planted_fault_fails_correct(tmp_path, monkeypatch, fault):
    sound, faulty = two_sides(context(tmp_path), monkeypatch, fault)
    assert sound["correct"], sound["numbers"]
    assert not faulty["correct"], faulty["numbers"]


def test_index_scores_from_bfloat16_products_are_not_seen(tmp_path,
                                                           monkeypatch):
    """The one planted fault the check cannot see, here and at the cell's
    own sizes on the chip (PR 30: grad_rms_gap 0.0170 beside the sound
    run's 0.0191, every number inside its limit): the program's hidden
    state carries bfloat16 rounding already, which moves as many boundary
    keys as the scores' own rounding does. Kept as a reading, so that a
    check that learns to see it shows here first."""
    sound, faulty = two_sides(context(tmp_path), monkeypatch, FAULTS[0])
    assert sound["correct"], sound["numbers"]
    assert faulty["numbers"]["grad_rms_gap"]["value"] \
        < 2 * sound["numbers"]["grad_rms_gap"]["value"]


def test_the_control_fails_where_the_program_passes(tmp_path):
    ctx = context(tmp_path, seed=11)
    first = sparse_lm_trial_steps.readings(ctx)
    rows = first.pop("rows")
    program = checks.run(ctx.config, ctx.seed, rows, first)
    control = checks.run(ctx.config, ctx.seed, rows)
    assert program["correct"], program["numbers"]
    assert not control["correct"]


def test_the_rehearsal_is_green(tmp_path):
    result = sparse_lm_trial_steps.run(context(tmp_path))
    assert result["correct"], result["records"]["check"]
    rec = result["records"]
    assert rec["moe_counts"]["dropped"] == [0, 0, 0, 0]
    assert rec["compiles_in_window"] == 0
    chosen, causal = (rec["selection_counts"][k] for k in (
        "selected_pairs", "causal_pairs"))
    assert all(0 < c < s for c, s in zip(chosen, causal))
    assert {"step_s", "items_per_s", "flops_per_item", "device_kind", "chips",
            "peak_bytes", "kernel_work"} <= set(rec)


def test_a_program_without_the_mechanism_is_refused_not_crashed(
        tmp_path, monkeypatch):
    import importlib.util

    real = importlib.util.find_spec
    monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: (
        None if name == "metaopt_tpu.ops.sparse_index" else real(name, *a)))
    with pytest.raises(harness.Refused, match="sparse_index"):
        sparse_lm_trial_steps.run(context(tmp_path))


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
