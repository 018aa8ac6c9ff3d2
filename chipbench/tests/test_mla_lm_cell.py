"""The latent-attention MoE decoder's cell (``kanana-2-30b.steady-16k``)
at sizes a test run can hold: the cut, its FLOP and byte counts against
counts by brute force, its readers on canned records, the planted faults
and the control failing ``correct``, its rehearsal, and a program without
the mechanism refused. ``python3 chipbench/tests/test_mla_lm_cell.py
FAULT[,FAULT...]|all [SEED]`` reads planted faults at the cell's own sizes
on the chip: the program's first steps and the sound reference once, then
one faulty reference a fault."""

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

from chipbench import (checks, flops_lm, flops_mla_lm, mla_lm_config,
                       run as harness)
from chipbench.checks import mla_lm_train3
from chipbench.reference import lm as reference_lm, mla_lm as reference
from chipbench.run import _reader
from chipbench.runners import mla_lm_trial_steps

CELL = "kanana-2-30b.steady-16k"


def context(tmp_path, seed=2 ** 31 + 33, rehearsal=True):
    _, ctx = harness.cell_context(CELL, seed, 1.0, False, rehearsal,
                                  time.time())
    ctx.run_dir = str(tmp_path)
    return ctx


def config():
    with open(os.path.join(harness.ROOT, "chipbench", "configs",
                           "kanana-2-30b-a3b-ep8.json")) as f:
        return json.load(f)


# -- the configuration ---------------------------------------------------------

def test_the_cut_is_the_issue_s():
    c = config()
    cfg = mla_lm_config.reference_cfg(c)
    assert (cfg["d_model"], cfg["n_heads"], cfg["rank"], cfg["nope"],
            cfg["rope"], cfg["v_dim"]) == (2048, 32, 512, 128, 64, 128)
    assert (cfg["n_experts"], cfg["top_k"], cfg["expert_d_ff"],
            cfg["shared_d_ff"], cfg["d_ff"]) == (128, 6, 768, 1536, 6144)
    assert (cfg["rope_theta"], cfg["scale"], cfg["normalised"],
            cfg["activation"]) == (1e6, 2.448, True, "silu")
    assert (cfg["n_layers"], cfg["dense_layers"]) == (5, 1)
    assert cfg["experts_held"] == [0, 16] and cfg["vocab_held"] == [0, 16032]
    shapes = reference.param_shapes(cfg)
    size = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    trained = size(reference.trained(shapes))
    assert trained == 575_955_456                    # x 16 bytes = 9.22 GB
    assert size(shapes) - trained == 4 * 128         # the biases
    assert size(shapes["h0"]) == 64_098_816
    assert size(reference.trained(shapes)["h1"]) == 111_546_880
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    desc = mla_lm_config.description(c)
    assert desc["n_routed_experts"] == 128           # routed over, not held
    assert desc["experts_held"] == [0, 16]
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]


def test_every_number_of_the_catalog_s_config_is_kept():
    """Top-level numbers as the catalog has them, but for ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    c = config()
    assert c["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in c["reduced"]:
            assert c[key] == value, key


@pytest.mark.parametrize("key, value", [
    ("q_lora_rank", 1536), ("n_group", 8), ("scoring_func", "softmax"),
    ("rope_interleave", False)])
def test_what_the_reference_does_not_compute_is_refused(key, value):
    c = config()
    c[key] = value
    with pytest.raises(ValueError, match=key):
        mla_lm_config.reference_cfg(c)


# -- operations and bytes ------------------------------------------------------

def test_train_flops_by_brute_force_at_a_small_size():
    cfg = dict(d_model=8, n_heads=4, n_layers=3, rank=6, nope=4, rope=2,
               v_dim=3, dense_layers=1, d_ff=10, n_experts=8, top_k=2,
               expert_d_ff=6, shared_d_ff=5, experts_held=[2, 4],
               vocab_held=[0, 10])
    s = 7
    total = 0
    for layer in range(cfg["n_layers"]):
        for t in range(s):
            total += 2 * 8 * 4 * 6                  # q: 4 heads of 4 + 2
            total += 2 * 8 * (6 + 2)                # the K/V down-projection
            total += 2 * 6 * 4 * (4 + 3)            # the up-projection
            total += 2 * 4 * 3 * 8                  # out
            total += (t + 1) * 4 * 2 * (6 + 3)      # scores 6 deep, values 3
            if layer == 0:
                total += 3 * 2 * 8 * 10             # the dense layer
            else:
                total += 2 * 8 * 8                  # router
                total += 3 * 2 * 8 * 5              # shared experts
                total += (2 * 4 / 8) * 3 * 2 * 8 * 6  # experts met here
    total += s * 2 * 8 * 10                         # the head
    assert flops_mla_lm.train_flops_per_item(cfg, s) * s \
        == pytest.approx(3 * total)


def test_a_kernel_s_call_counts_each_product_at_its_own_width():
    cfg = mla_lm_config.reference_cfg(config())
    pairs = 16384 * 16385 // 2
    fwd = flops_mla_lm.flash_fwd_call(cfg, 16384)
    bwd = flops_mla_lm.flash_bwd_call(cfg, 16384)
    assert fwd["flops"] == 32 * pairs * 2 * (192 + 128)
    assert bwd["flops"] == 32 * pairs * 2 * (3 * 192 + 2 * 128)
    assert bwd["flops"] / fwd["flops"] == 832 / 320      # 2.6 x
    # q, k_nope, v, out a head, lse, and the shared key ONCE a layer
    assert fwd["bytes"] == 16384 * (2 * 32 * (192 + 128 + 128 + 128)
                                    + 4 * 32 + 2 * 64)
    # the equal-width kernels' count is what the accepted cells' is
    equal = dict(n_heads=32, n_kv_heads=32, head_dim=128)
    assert flops_lm.flash_fwd_call(equal, 16384, None)["flops"] \
        == 32 * pairs * 4 * 128
    # the whole step: five layers' cores are three quarters of the work
    step = flops_mla_lm.train_flops_per_item(cfg, 16384) * 16384
    assert 5 * (fwd["flops"] + bwd["flops"]) / step \
        == pytest.approx(0.746, abs=2e-3)


# -- the readers ---------------------------------------------------------------

def canned():
    return {"step_s": [0.7, 0.7],
            "moe_counts": {"items": [[30, 10], [20, 20]], "dropped": [0, 0]},
            "choice_counts": {"bias_moved": [10, 30], "tokens": 100}}


def test_the_counters_readers_on_a_canned_run():
    rec = canned()
    assert _reader("mla_lm_moe_choice_bias_share").read(rec) == 20.0
    assert _reader("moe_dropped_share").read(rec) == 0.0
    assert _reader("moe_held_load_max_over_mean").read(rec) == 1.5
    assert _reader("mla_lm_moe_choice_bias_share").read({}) is None
    assert _reader("mla_lm_moe_choice_bias_share").read(
        {"choice_counts": {"bias_moved": [], "tokens": 0}}) is None
    for name in ("attention_core_device_ms", "mla_latent_device_ms",
                 "flash_fwd_roofline", "flash_bwd_roofline",
                 "moe_shared_device_ms", "moe_route_device_ms",
                 "moe_experts_roofline", "ffn_device_ms",
                 "scoped_device_share", "unnamed_device_ms"):
        assert _reader(name).read(rec) is None    # no trace in it


def test_the_trace_readers_on_a_few_operations(monkeypatch):
    from chipbench import program_trace

    f = "jit(train_step)/jvp(DecoderOnlyLM)/DecoderOnlyLM._patterned/"
    b = "jit(train_step)/transpose(jvp(DecoderOnlyLM))/"
    ops = [(f + "h0/attn/attention/q/dot_general", 0.0, 0.1),
           (f + "h0/attn/attention/attention.latent/kv_b/dot_general", 0.1,
            0.2),
           (f + "h0/attn/attention/attention.core/jit(_causal_forward)/"
            "flash_fwd/pallas_call", 0.3, 0.1),
           (b + "h0/attn/attention/attention.core/jit(_causal_backward)/"
            "flash_bwd/pallas_call", 0.4, 0.2),
           (f + "h0/mlp/ffn/gate/dot_general", 0.6, 0.1),
           (f + "h1/moe/moe.router/router/dot_general", 0.7, 0.05),
           (f + "h1/experts/moe/moe.experts/gmm/pallas_call", 0.75, 0.1),
           (f + "h1/experts/moe/moe.shared/shared/ffn/up/dot_general", 0.85,
            0.15)]
    monkeypatch.setattr(program_trace, "load", lambda directory: {
        "ops": {"/device:TPU:0": ops},
        "programs": {"/device:TPU:0": ["jit_train_step"] * 2}})
    monkeypatch.setattr(program_trace, "run_dir", lambda: "unused")
    cfg = mla_lm_config.reference_cfg(config())
    work = {"layers": 1, "routed_layers": 1, "remat": True,
            "flash_fwd": [flops_mla_lm.flash_fwd_call(cfg, 16384)],
            "flash_bwd": [flops_mla_lm.flash_bwd_call(cfg, 16384)],
            "experts_pass": flops_lm.experts_pass(cfg, 98304.0)}
    rec = dict(canned(), trace={"busy_s": 1.0, "window_s": 1.0},
               kernel_work=work, device_kind="TPU v5 lite")
    read = lambda name: _reader(name).read(rec)  # noqa: E731
    assert read("attention_core_device_ms") == pytest.approx(150)
    assert read("mla_latent_device_ms") == pytest.approx(100)
    assert read("attention_proj_device_ms") == pytest.approx(150)
    assert read("moe_device_ms") == pytest.approx(150)
    assert read("moe_shared_device_ms") == pytest.approx(75)
    assert read("moe_experts_device_ms") == pytest.approx(50)
    assert read("moe_route_device_ms") == pytest.approx(25)
    # the dense layer's alone: the shared experts' ``ffn`` is the moe's
    assert read("ffn_device_ms") == pytest.approx(50)
    # one call in the slice: 2.75 TFLOP over 0.1 s at 197 TFLOP/s
    assert read("flash_fwd_roofline") == pytest.approx(
        100 * work["flash_fwd"][0]["flops"] / 197e12 / 0.1, rel=1e-6)
    assert 0 < read("flash_bwd_roofline") < 100
    assert 0 < read("moe_experts_roofline") < 100
    # the line adds up: the top-level layers and the unnamed are the busy
    layers = sum(read(n) for n in (
        "attention_core_device_ms", "attention_proj_device_ms",
        "ffn_device_ms", "moe_device_ms"))
    assert layers + read("unnamed_device_ms") == pytest.approx(500)


def test_the_cell_s_line_names_every_metric_the_issue_lists():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert len(mine) == 24
    assert all(os.path.exists(os.path.join(
        harness.HERE, "readers", name + ".py")) for name in mine)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["traffic"], cell["chips"]) == ("steady-mla-lm-16k", 1)


# -- correct -------------------------------------------------------------------

REAL_LOSS = reference.loss
REAL = {name: getattr(reference, name)
        for name in ("rope_adjacent", "_rms", "routing_weights")}


@contextlib.contextmanager
def in_place_of(name, stand_in):
    setattr(reference, name, stand_in)
    try:
        yield
    finally:
        setattr(reference, name, REAL[name])


def _scaled(params, path, factor):
    """``params`` with the leaf at ``path`` times ``factor`` in every layer
    that has it (the gradient passes through the product)."""
    out = dict(params)
    for name, layer in params.items():
        node = layer
        for part in path[:-1]:
            node = node.get(part) if isinstance(node, dict) else None
        if isinstance(node, dict) and path[-1] in node:
            out[name] = _replaced(layer, path, node[path[-1]] * factor)
    return out


def _replaced(tree, path, leaf):
    if not path:
        return leaf
    return {**tree, path[0]: _replaced(tree[path[0]], path[1:], leaf)}


def the_rotary_part_of_the_scores_left_out(params, rows, cfg,
                                           mode="float32"):
    with in_place_of("rope_adjacent", lambda x, theta: 0.0 * x):
        return REAL_LOSS(params, rows, cfg, mode)


def the_shared_key_rotated_on_halves(params, rows, cfg, mode="float32"):
    """q's rotary part on adjacent pairs, the one shared key's on a head's
    two halves."""
    with in_place_of("rope_adjacent", lambda x, theta: (
            reference_lm._rope(x, theta) if x.shape[1] == 1
            else REAL["rope_adjacent"](x, theta))):
        return REAL_LOSS(params, rows, cfg, mode)


def the_scale_of_the_nope_width(params, rows, cfg, mode="float32"):
    """nope^-1/2 where the scores take (nope + rope)^-1/2: q times the
    ratio."""
    factor = ((cfg["nope"] + cfg["rope"]) / cfg["nope"]) ** 0.5
    return REAL_LOSS(_scaled(params, ("attn", "q", "kernel"), factor), rows,
                     cfg, mode)


def the_latent_s_norm_left_out(params, rows, cfg, mode="float32"):
    with in_place_of("_rms", lambda x, scale, eps: (
            x if x.shape[-1] == cfg["rank"]
            else REAL["_rms"](x, scale, eps))):
        return REAL_LOSS(params, rows, cfg, mode)


def the_bias_added_to_the_weights(params, rows, cfg, mode="float32"):
    def weights(logits, bias, cfg):
        score = jax.nn.sigmoid(logits) + bias
        _, idx = jax.lax.top_k(score, cfg["top_k"])
        chosen = jnp.take_along_axis(score, idx, axis=1)
        chosen = chosen / (jnp.sum(chosen, axis=1, keepdims=True) + 1e-20)
        return jnp.zeros_like(score).at[
            jnp.arange(score.shape[0])[:, None], idx].set(
                chosen * cfg["scale"])

    with in_place_of("routing_weights", weights):
        return REAL_LOSS(params, rows, cfg, mode)


def the_bias_left_out_of_the_choice(params, rows, cfg, mode="float32"):
    with in_place_of("routing_weights", lambda logits, bias, cfg: REAL[
            "routing_weights"](logits, 0.0 * bias, cfg)):
        return REAL_LOSS(params, rows, cfg, mode)


def the_weights_not_normalised(params, rows, cfg, mode="float32"):
    return REAL_LOSS(params, rows, {**cfg, "normalised": False}, mode)


def the_shared_branch_left_out(params, rows, cfg, mode="float32"):
    return REAL_LOSS(_scaled(params, ("experts", "shared", "down", "kernel"),
                             0.0), rows, cfg, mode)


def an_expert_left_out(params, rows, cfg, mode="float32"):
    last = f"h{cfg['n_layers'] - 1}"
    return REAL_LOSS({**params, last: _replaced(
        params[last], ("experts", "down", "e02"),
        0.0 * params[last]["experts"]["down"]["e02"])}, rows, cfg, mode)


FAULTS = [the_rotary_part_of_the_scores_left_out,
          the_shared_key_rotated_on_halves, the_scale_of_the_nope_width,
          the_latent_s_norm_left_out, the_bias_added_to_the_weights,
          the_bias_left_out_of_the_choice, the_weights_not_normalised,
          the_shared_branch_left_out, an_expert_left_out]


def faulty_sides(ctx, monkeypatch, faults):
    """(the program against the reference, {fault: the reference with the
    fault in its loss's place, as the program's side, against the
    reference}): the program's first steps and the sound reference are
    made once."""
    first = mla_lm_trial_steps.readings(ctx)
    rows = first.pop("rows")
    spec = ctx.config["check"]
    ref = mla_lm_train3.reference_readings(ctx.config, ctx.seed, rows,
                                           "float32")
    start = mla_lm_train3.weights(ctx.config, ctx.seed)
    judged = lambda side: (lambda numbers: {  # noqa: E731
        "numbers": numbers,
        "correct": all(n["ok"] for n in numbers.values())})(
            checks.compare(side, ref, start, spec["limits"]))
    sound, out = judged(first), {}
    del first
    for fault in faults:
        with monkeypatch.context() as m:
            m.setattr(reference, "loss", fault)
            out[fault.__name__] = judged(mla_lm_train3.reference_readings(
                ctx.config, ctx.seed, rows, "float32"))
    return sound, out


@pytest.fixture(scope="module")
def planted(tmp_path_factory):
    return faulty_sides(context(tmp_path_factory.mktemp("faults")),
                        pytest.MonkeyPatch, FAULTS)


#: the two faults of the bias: at the rehearsal's size it moves the choice
#: of a token in twenty (of one in two at the cell's) and is 0.005 beside
#: scores of 0.9, so neither shows under limits made for 96-token rows
BIAS_FAULTS = (the_bias_added_to_the_weights, the_bias_left_out_of_the_choice)


@pytest.mark.parametrize("fault", [f for f in FAULTS if f not in BIAS_FAULTS],
                         ids=lambda f: f.__name__)
def test_a_planted_fault_fails_correct(planted, fault):
    sound, faulty = planted
    assert sound["correct"], sound["numbers"]
    assert not faulty[fault.__name__]["correct"], \
        faulty[fault.__name__]["numbers"]


def test_the_bias_s_faults_are_readings_at_the_rehearsal_s_size(planted):
    """Kept as readings (PERF.md section 4 has the chip's at the cell's own
    sizes): leaving the bias out of the choice moves the first gradient
    several times as far as adding it to the weights does, and both stay
    inside the rehearsal's limits."""
    _, faulty = planted
    added, left_out = (faulty[f.__name__] for f in BIAS_FAULTS)
    assert added["correct"] and left_out["correct"]
    assert left_out["numbers"]["grad_rms_gap"]["value"] \
        > 3 * added["numbers"]["grad_rms_gap"]["value"] > 0


def test_layer_0_routed_instead_of_dense_is_another_tree(tmp_path):
    """A program that routes layer 0 has a router and experts there and no
    ``mlp``: the comparison stops on the leaves before any number."""
    ctx = context(tmp_path)
    cfg = mla_lm_config.reference_cfg(ctx.config)
    as_built = reference.trained(reference.param_shapes(cfg))
    routed = reference.trained(reference.param_shapes(
        {**cfg, "dense_layers": 0}))
    zeros = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: np.zeros(x.shape, np.float32), tree)
    side = lambda tree: {"losses": [1.0], "grad": zeros(tree),  # noqa: E731
                         "params": zeros(tree)}
    with pytest.raises(ValueError, match="disagree on the leaves"):
        checks.compare(side(routed), side(as_built), zeros(as_built),
                       ctx.config["check"]["limits"])


def test_the_control_fails_where_the_program_passes(tmp_path):
    ctx = context(tmp_path, seed=11)
    first = mla_lm_trial_steps.readings(ctx)
    rows = first.pop("rows")
    program = checks.run(ctx.config, ctx.seed, rows, first)
    control = checks.run(ctx.config, ctx.seed, rows)
    assert program["correct"], program["numbers"]
    assert not control["correct"]


def test_the_rehearsal_is_green(tmp_path):
    result = mla_lm_trial_steps.run(context(tmp_path))
    assert result["correct"], result["records"]["check"]
    rec = result["records"]
    assert rec["moe_counts"]["dropped"] == [0, 0, 0, 0]   # the routed four
    assert len(rec["moe_counts"]["items"]) == 4
    assert rec["compiles_in_window"] == 0
    moved, tokens = (rec["choice_counts"][k] for k in ("bias_moved",
                                                       "tokens"))
    assert all(0 < m < tokens for m in moved)
    assert rec["kernel_work"]["layers"] == 5
    assert rec["kernel_work"]["routed_layers"] == 4
    assert {"step_s", "items_per_s", "flops_per_item", "device_kind", "chips",
            "peak_bytes", "kernel_work"} <= set(rec)


def test_a_program_without_the_mechanism_is_refused_not_crashed(
        tmp_path, monkeypatch):
    from metaopt_tpu.models import lm

    monkeypatch.delattr(lm, "LatentAttention")
    with pytest.raises(harness.Refused, match="LatentAttention"):
        mla_lm_trial_steps.run(context(tmp_path))


if __name__ == "__main__":
    # planted faults at the cell's own sizes, on the chip
    _names = [f.__name__ for f in FAULTS] if sys.argv[1] == "all" \
        else sys.argv[1].split(",")
    _seed = int(sys.argv[2]) if len(sys.argv) > 2 else 2 ** 31 + 33
    _ctx = context(os.path.join(harness.HERE, ".runs", "fault"), _seed,
                   rehearsal=False)
    _ctx.use_steady_cache()
    _sound, _faulty = faulty_sides(
        _ctx, pytest.MonkeyPatch,
        [f for f in FAULTS if f.__name__ in _names])
    _values = lambda side: {k: v["value"]  # noqa: E731
                            for k, v in side["numbers"].items()}
    for _name, _side in _faulty.items():
        print("CHIPBENCH_FAULT " + json.dumps({
            "fault": _name, "seed": _seed,
            "device": jax.devices()[0].device_kind,
            "sound": _values(_sound), "faulty": _values(_side),
            "sound_correct": _sound["correct"],
            "faulty_correct": _side["correct"]}), flush=True)
