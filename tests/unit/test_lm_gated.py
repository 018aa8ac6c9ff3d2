"""The ``laguna`` family's layer in the pattern decoder (models/lm_layers.py,
models/lm_description.py): full and window layers that differ in head count
and rotary rule (YaRN on half a head, the plain rule on the whole), a
sigmoid gate a head on attention's output, a leading dense layer and
sigmoid routing beside one shared expert, against a reference that shares
no code with what it tests (chipbench/reference/gated_lm.py) and numbers
written here, at small sizes on the CPU.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metaopt_tpu.models import lm, lm_description, lm_layers, moe
from metaopt_tpu.utils import trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
D, HD, KV, FULL, WINDOWED = 64, 16, 2, 6, 8
DFF, F, E, TOPK, V, S, W = 96, 32, 16, 4, 128, 64, 16
HELD = (8, 8)          # a strict share of the 16 routed experts
SCALE = 2.5
TYPES = ["full_attention", "sliding_attention", "sliding_attention",
         "sliding_attention", "full_attention", "sliding_attention"]
ROPES = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1}}
#: bfloat16 products move a token's fourth and fifth score past each other
#: now and then, and such a token then differs by a whole expert's output;
#: on this seed the tolerances below are rounding's
WEIGHTS_SEED = 5
#: the benchmark's comparison at these sizes (the configuration's
#: ``rehearsal_limits``)
LIMITS = {"loss_gap": 0.004, "grad_norm_gap": 0.3, "grad_rms_gap": 0.2,
          "update_norm_gap": 0.3}


def description(layers=5, held=HELD, **over):
    said = dict(
        model_type="laguna", hidden_size=D, head_dim=HD,
        num_attention_heads=FULL, num_key_value_heads=KV,
        num_hidden_layers=layers, vocab_size=V, intermediate_size=DFF,
        moe_intermediate_size=F, shared_expert_intermediate_size=F,
        num_experts=E, num_experts_per_tok=TOPK, rms_norm_eps=1e-6,
        attention_bias=False, gating=True, sliding_window=W,
        moe_apply_router_weight_on_input=False,
        moe_routed_scaling_factor=SCALE, rope_parameters=ROPES,
        layer_types=TYPES, mlp_layer_types=["dense"] + ["sparse"] * 5,
        num_attention_heads_per_layer=[
            FULL if t == "full_attention" else WINDOWED for t in TYPES],
        experts_held=held, vocab_held=(0, V))
    said.update(over)
    return said


def reference_cfg(layers=5, held=HELD):
    return {
        "d_model": D, "head_dim": HD, "n_kv_heads": KV, "window": W,
        "rms_eps": 1e-6,
        "layers": [{"kind": "full" if t == "full_attention" else "window",
                    "heads": FULL if t == "full_attention" else WINDOWED,
                    "ffn": "dense" if i == 0 else "sparse"}
                   for i, t in enumerate(TYPES[:layers])],
        "rope": {"full": {"theta": 500000.0, "turned": HD // 2,
                          "yarn": [64.0, 4096, 64.0, 1.0],
                          "factor": 1.4158883083359672},
                 "window": {"theta": 10000.0, "turned": HD, "yarn": None,
                            "factor": 1.0}},
        "gate": "sigmoid", "d_ff": DFF, "n_experts": E, "top_k": TOPK,
        "expert_d_ff": F, "shared_d_ff": F, "normalised": True,
        "scale": SCALE, "activation": "silu",
        "experts_held": list(held), "vocab_held": [0, V]}


def leaf(tree, path):
    for part in path.split("/"):
        tree = tree[part]
    return np.asarray(tree, np.float32)


def close(p, r, share):
    return np.linalg.norm(p - r) <= share * np.linalg.norm(r)


# -- the decoder against the plain reference -----------------------------------

def _reference_side(whole, tokens, cfg, faults=()):
    """The reference's loss, first gradient and parameters after one AdamW
    step from ``whole``, with ``faults`` planted."""
    from chipbench.reference import gated_lm as reference, optim

    loss, grads = jax.value_and_grad(
        lambda p: reference.loss(p, tokens, cfg, "float32", faults))(whole)
    moved, _ = optim.adamw(whole, optim.adamw_init(whole), grads, lr=1e-3,
                           weight_decay=0.0)
    return {"losses": [float(loss)], "grad": grads, "params": moved}


@pytest.fixture(scope="module")
def both_sides():
    """The program's and the reference's logits, loss, first gradient and
    parameters after one AdamW step, from the same seeded weights and rows;
    the program's trees in the reference's form (an expert a leaf)."""
    import optax

    from chipbench import weights_gated_lm, weights_lm
    from chipbench.reference import gated_lm as reference

    cfg = reference_cfg()
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, S + 1), 2, V)
    whole = weights_gated_lm.make_weights(
        WEIGHTS_SEED, reference.param_shapes(cfg))
    model = lm.make_lm(description())
    params = weights_lm.stacked(whole)
    loss, grads = jax.value_and_grad(lambda p: lm.lm_loss_fn(
        model, p, tokens, jax.random.PRNGKey(0)))(params)
    tx = optax.adamw(1e-3, weight_decay=0.0)
    updates, _ = tx.update(grads, tx.init(params), params)
    logits = model.apply({"params": params}, tokens[:, :-1], train=False)
    prog = {"losses": [float(loss)], "grad": weights_lm.split(grads),
            "params": weights_lm.split(optax.apply_updates(params, updates)),
            "logits": logits}
    ref = _reference_side(whole, tokens, cfg)
    ref["logits"] = jnp.stack([reference.logits(whole, row[:-1], cfg)
                               for row in tokens])
    return prog, ref, whole, tokens


def test_logits_match_the_plain_reference(both_sides):
    prog, ref = both_sides[:2]
    assert prog["logits"].shape == ref["logits"].shape == (2, S, V)
    p, r = np.asarray(prog["logits"]), np.asarray(ref["logits"])
    assert np.linalg.norm(p - r) <= 0.05 * np.linalg.norm(r), (
        np.linalg.norm(p - r) / np.linalg.norm(r))


def test_the_first_step_passes_the_benchmark_s_own_comparison(both_sides):
    from chipbench import checks

    prog, ref, whole, _ = both_sides
    assert abs(prog["losses"][0] - ref["losses"][0]) \
        <= 2e-3 * ref["losses"][0]
    numbers = checks.compare(prog, ref, whole, LIMITS)
    assert all(n["ok"] for n in numbers.values()), numbers


_ATTN = ["q/kernel", "k/kernel", "v/kernel", "out/kernel", "gate/kernel",
         "q_norm/scale", "k_norm/scale"]
LEAVES = (["embed/embedding", "head/embedding", "norm_f/scale"]
          + [f"h{i}/attn/{name}" for i in (0, 1, 4) for name in _ATTN]
          + [f"h0/mlp/{name}/kernel" for name in ("gate", "up", "down")]
          + [f"h{i}/{name}" for i in (1, 4) for name in (
              "norm_in/scale", "norm_post/scale", "router/kernel",
              "experts/shared/gate/kernel", "experts/shared/up/kernel",
              "experts/shared/down/kernel")])


@pytest.mark.parametrize("path", LEAVES)
def test_every_gradient_leaf_matches_the_plain_reference(both_sides, path):
    """A full layer with the dense feed-forward, a window layer and a full
    layer with experts: every leaf of theirs, the gate's projection and the
    q and k norms among them. A sixth is rounding's (a leaf read through
    bfloat16 products five layers deep); a router's gradient and the norm
    it reads take a third, since it comes through the chosen experts'
    weights alone and a token whose fourth and fifth score swap under
    rounding changes it by a whole expert's (five tokens of 128 here); a
    leaf left out or wired wrongly reads 1 or more."""
    prog, ref = both_sides[:2]
    p, r = leaf(prog["grad"], path), leaf(ref["grad"], path)
    assert p.shape == r.shape
    share = 0.33 if "router" in path or "norm_post" in path else 0.17
    assert close(p, r, share), np.linalg.norm(p - r) / np.linalg.norm(r)


@pytest.mark.parametrize("layer", [1, 2, 3, 4])
@pytest.mark.parametrize("which", ["gate", "up", "down"])
def test_the_held_experts_gradients_match_all_experts_together(both_sides,
                                                               layer, which):
    prog, ref = both_sides[:2]
    stack = lambda side: np.stack([  # noqa: E731
        leaf(side["grad"], f"h{layer}/experts/{which}/e{e:02d}")
        for e in range(HELD[1])])
    p, r = stack(prog), stack(ref)
    # a swapped choice moves a token from one expert's gradient to another's
    assert close(p, r, 0.25), np.linalg.norm(p - r) / np.linalg.norm(r)


def test_the_two_sides_name_the_same_leaves(both_sides):
    from chipbench import checks

    prog, ref = both_sides[:2]
    names = set(checks.named_leaves(ref["grad"]))
    assert names == set(checks.named_leaves(prog["grad"]))
    assert "h0/attn/gate/kernel" in names and "h0/router/kernel" not in names
    assert "h1/experts/shared/down/kernel" in names
    assert not any("choice_bias" in name for name in names)


# -- q and k from their products in one pass ----------------------------------

@pytest.fixture(scope="module")
def handed_over(both_sides):
    """{form: (loss, gradients)} of the program on the Pallas route,
    interpreted, from ``both_sides``' weights and rows: q and k by the one
    Pallas call a direction (ops/grouped_hand_over.py: the norm a head,
    YaRN on half a head or the plain rule on the whole, q's scale) and by
    XLA's passes, every other line the same; and what the span says of
    each kind either way."""
    from lm_pattern_cases import _on_the_kernels

    from chipbench import weights_lm
    from metaopt_tpu.ops import grouped_hand_over

    _, _, whole, tokens = both_sides
    model, params = lm.make_lm(description()), weights_lm.stacked(whole)
    run = lambda: jax.value_and_grad(lambda p: lm.lm_loss_fn(  # noqa: E731
        model, p, tokens, jax.random.PRNGKey(0)))(params)
    said = lambda: {kind: how["hand_over"] for kind, how in (  # noqa: E731
        lm_description.describe_pattern(
            description(), "pallas", tokens=2 * S,
            seq_len=S)["attention_layers"].items())}
    out, patch = {}, pytest.MonkeyPatch()
    try:
        _on_the_kernels(patch)
        out["one pass"], out["said"] = run(), said()
        patch.setattr(grouped_hand_over, "hand_over", lambda *a: "passes")
        out["passes"], out["said otherwise"] = run(), said()
    finally:
        patch.undo()
    return out


def test_both_kinds_of_layer_hand_over_in_one_pass(handed_over):
    assert handed_over["said"] == {"global-rope": "one pass",
                                   "window-rope": "one pass"}
    assert set(handed_over["said otherwise"].values()) == {"passes"}


def test_the_hand_over_in_one_pass_changes_no_loss(handed_over):
    one, passes = handed_over["one pass"][0], handed_over["passes"][0]
    assert abs(float(one) - float(passes)) <= 2e-3 * abs(float(passes))


@pytest.mark.parametrize("path", LEAVES)
def test_the_hand_over_in_one_pass_changes_no_gradient(handed_over, path):
    """To the shares the plain reference is held to above, the q and k
    norms' scales, whose gradient the backward call sums, among them."""
    from chipbench import weights_lm

    p, r = (leaf(weights_lm.split(handed_over[form][1]), path)
            for form in ("one pass", "passes"))
    share = 0.33 if "router" in path or "norm_post" in path else 0.17
    assert close(p, r, share), np.linalg.norm(p - r) / np.linalg.norm(r)


# -- planted faults ------------------------------------------------------------

@pytest.mark.parametrize("fault", [
    "no_gate", "gate_identity", "plain_for_yarn", "no_attention_factor",
    "whole_head_turned", "window_plus_one", "no_shared", "no_scale",
    "normalise_over_held"])
def test_a_planted_fault_fails_the_comparison(both_sides, fault):
    """The faulty float32 reference in the program's place (as the cell's
    chip test plants them): the gate left out, its sigmoid swapped for the
    identity, the plain frequencies for YaRN's, the factor on cos and sin
    left out, the whole head turned on a full layer, the window one token
    too long, the shared expert left out, the scale 2.5 left out,
    normalising over the held instead of the chosen. Each fails a limit the
    sound program passes."""
    from chipbench import checks
    from chipbench.reference import gated_lm as reference

    _, ref, whole, tokens = both_sides
    assert fault in reference.FAULTS
    faulty = _reference_side(whole, tokens, reference_cfg(), (fault,))
    numbers = checks.compare(faulty, ref, whole, LIMITS)
    assert not all(n["ok"] for n in numbers.values()), numbers


def test_full_layers_given_the_window_layers_heads_stop_on_the_shapes(
        both_sides):
    """A program whose full layers have 8 query heads for 6 cannot take the
    seeded weights: the comparison stops before a number is read."""
    from flax.errors import ScopeParamShapeError

    from chipbench import weights_lm

    _, _, whole, tokens = both_sides
    model = lm.make_lm(description(
        num_attention_heads_per_layer=[WINDOWED] * len(TYPES)))
    with pytest.raises(ScopeParamShapeError):
        model.apply({"params": weights_lm.stacked(whole)}, tokens[:, :-1],
                    train=False)


# -- the rotary rule -----------------------------------------------------------

YARN = lm_layers.Rotary(500000.0, 64, (64.0, 4096, 64.0, 1.0),
                        1.4158883083359672)


def test_yarn_s_frequencies_by_value():
    """dim 64, base 500000, factor 64 over 4096 original positions,
    beta_fast 64, beta_slow 1, truncation on: low = floor(5.66) = 5, high =
    ceil(15.80) = 16; pairs 0-4 keep the plain frequency, pairs 16-31 take
    it over 64, pair 10 lies 5/11 of the way."""
    got = np.asarray(YARN.frequencies(64), np.float64)
    plain = 500000.0 ** (-np.arange(32) / 32.0)
    pair = lambda turns: 64 * math.log(  # noqa: E731
        4096 / (turns * 2 * math.pi)) / (2 * math.log(500000.0))
    assert (round(pair(64), 2), round(pair(1), 2)) == (5.66, 15.80)
    np.testing.assert_allclose(got[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(got[16:], plain[16:] / 64, rtol=1e-6)
    np.testing.assert_allclose(
        got[10], plain[10] * (1 - 5 / 11) + plain[10] / 64 * 5 / 11,
        rtol=1e-6)
    # written out: pair 0, pair 10, pair 31
    np.testing.assert_allclose(got[[0, 10, 31]],
                               [1.0, 9.150584e-03, 4.709153e-08], rtol=1e-5)
    assert np.all(np.diff(got) < 0)
    assert YARN.factor == pytest.approx(0.1 * math.log(64) + 1, abs=1e-12)
    assert YARN.factor == pytest.approx(1.4158883, abs=1e-7)


def test_the_reference_s_frequencies_are_the_same_numbers():
    from chipbench.reference import gated_lm as reference

    rule = {"theta": 500000.0, "turned": 64, "yarn": [64.0, 4096, 64.0, 1.0],
            "factor": 1.4158883083359672}
    np.testing.assert_allclose(reference.frequencies(rule, 64),
                               YARN.frequencies(64), rtol=1e-6)
    np.testing.assert_allclose(
        reference.frequencies({**rule, "yarn": None}, 64),
        lm_layers.Rotary(500000.0).frequencies(64), rtol=1e-6)


def test_the_unturned_half_of_a_head_is_bit_equal_to_its_input():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 3, 128))
    out = np.asarray(lm_layers.rope(x, YARN))
    assert out.shape == x.shape
    np.testing.assert_array_equal(out[..., 64:], np.asarray(x)[..., 64:])
    # position 0 turns nothing: the turned half is its input times the factor
    np.testing.assert_allclose(out[:, 0, :, :64],
                               YARN.factor * np.asarray(x)[:, 0, :, :64],
                               rtol=1e-6)
    assert not np.allclose(out[:, 1:, :, :64], np.asarray(x)[:, 1:, :, :64])


def test_the_turned_half_is_a_complex_rotation_by_yarn_s_angles():
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (1, 40, 2, 128)))
    out = np.asarray(lm_layers.rope(jnp.asarray(x), YARN), np.float64)
    freq = np.asarray(YARN.frequencies(64), np.float64)
    z = (x[..., :32] + 1j * x[..., 32:64]) * YARN.factor * np.exp(
        1j * np.arange(40)[None, :, None, None] * freq)
    np.testing.assert_allclose(out[..., :32], z.real, atol=2e-5)
    np.testing.assert_allclose(out[..., 32:64], z.imag, atol=2e-5)


def test_a_bare_base_is_the_plain_rule_over_the_whole_head():
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 2, 32))
    np.testing.assert_array_equal(
        lm_layers.rope(x, 1e4), lm_layers.rope(x, lm_layers.Rotary(1e4)))
    assert lm_layers.Rotary(1e4).said(32) == "plain 10000, 32 of 32"
    assert YARN.said(128) == ("yarn 500000 x64 over 4096, 64 of 128, cos "
                              "and sin x 1.4159")


# -- the description -----------------------------------------------------------

def test_the_family_names_itself_before_the_key_probes():
    said = description()
    assert "layer_types" in said and "num_experts" in said
    assert lm_description.family_of(said) == "laguna"
    assert lm_description.family_of(
        {**said, "model_type": "olmo"}) == "olmo_hybrid"


def test_the_pattern_is_the_description_s():
    p = lm_description.pattern_of(lm_description._own_names(description()))
    full, window = p.layers[0].mixer, p.layers[1].mixer
    assert [layer.mixer.kind for layer in p.layers] == [
        "global-rope", "window-rope", "window-rope", "window-rope",
        "global-rope"]
    assert (full.heads, full.kv_heads, full.head_dim, full.window) \
        == (FULL, KV, HD, None)
    assert (window.heads, window.kv_heads, window.window) \
        == (WINDOWED, KV, W)
    assert full.rule == lm_layers.Rotary(
        500000.0, HD // 2, (64.0, 4096, 64.0, 1.0), 1.4158883083359672)
    assert window.rule == lm_layers.Rotary(10000.0)
    assert full.gate == window.gate == "sigmoid"
    assert full.qk_norm == window.qk_norm == "head"
    assert p.layers[0].ffn == lm_layers.GatedSpec(DFF, "silu")
    assert p.layers[1].ffn == moe.RoutedSpec(
        n_experts=E, top_k=TOPK, d_ff=F, held=HELD, activation="silu",
        shared_d_ff=F,
        rule=moe.RoutingRule("sigmoid", False, True, SCALE),
        router_after_mixer=True)
    assert p.heads_held is None and not p.tied and p.vocab_held == (0, V)
    assert lm_description.pattern_of(lm_description._own_names(
        description(gating=False))).layers[0].mixer.gate is None


@pytest.mark.parametrize("over, message", [
    ({"rope_parameters": {**ROPES, "full_attention": {
        **ROPES["full_attention"], "rope_type": "longrope"}}},
     "rope_parameters.full_attention.rope_type 'longrope'"),
    ({"layer_types": ["full_attention", "chunked_attention"] * 3},
     r"layer_types names \['chunked_attention'\]"),
    ({"mlp_layer_types": ["dense", "moe", "moe", "moe", "moe"]},
     r"mlp_layer_types names \['moe'\]"),
    ({"layer_types": TYPES[:3]}, "layer_types names 3 layers, the model "
                                 "has 5"),
    ({"mlp_layer_types": ["dense"]}, "mlp_layer_types names 1 layers"),
    ({"num_attention_heads_per_layer": [6, 8]},
     "num_attention_heads_per_layer names 2 layers"),
    ({"moe_apply_router_weight_on_input": True},
     "moe_apply_router_weight_on_input True"),
    ({"attention_bias": True}, "attention_bias True"),
    ({"num_attention_heads_per_layer": [6, 8, 7, 8, 6]},
     r"num_attention_heads_per_layer\[2\] 7: 2 K/V heads do not divide"),
    ({"heads_held": (0, 3)}, "heads_held .*shares no heads"),
    ({"gating": "elementwise"}, "gating 'elementwise'"),
])
def test_what_has_no_layer_here_is_refused_by_name(over, message):
    with pytest.raises(ValueError, match=message):
        lm.make_lm(description(**over))


def _published():
    from chipbench import gated_lm_config

    with open(os.path.join(ROOT, "chipbench", "configs",
                           "laguna-xs2-33b-a3b-ep8.json")) as f:
        config = json.load(f)
    return config, gated_lm_config


def test_the_published_description_counts_33_44_b():
    """From the specs' shapes at the published depth, experts and
    vocabulary: 33.44 B +- 0.05 against the published 33.4 B; a gate of a
    head's full width would read 34.07."""
    config, module = _published()
    said = module.description(config)
    said.update({k: config["published"][k] for k in (
        "num_hidden_layers", "num_experts", "vocab_size")})
    said.update(experts_held=None, vocab_held=None, remat=False)
    model = lm.make_lm(said)
    assert len(model.pattern.layers) == 40
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        train=False)["params"])
    count = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))
    assert abs(count / 1e9 - 33.44) < 0.05, count
    gates = sum(math.prod(x.shape) for path, x in
                jax.tree_util.tree_flatten_with_path(shapes)[0]
                if any(getattr(k, "key", None) == "gate" for k in path)
                and any(getattr(k, "key", None) == "attn" for k in path))
    assert gates == 2048 * (10 * 48 + 30 * 64) == 4_915_200


def test_the_held_share_counts_what_the_file_says():
    from chipbench.reference import gated_lm as reference

    config, module = _published()
    cfg = module.reference_cfg(config)
    shapes = reference.param_shapes(cfg)
    size = lambda tree: sum(  # noqa: E731
        math.prod(x.shape) for x in jax.tree.leaves(tree))
    assert size(shapes["h0"]["attn"]) == 29_458_688
    assert size(shapes["h1"]["attn"]) == 37_880_064
    assert size(shapes["h0"]) == 79_794_432
    assert size(shapes["h1"]) == size(shapes["h3"]) == 142_217_472
    assert size(shapes["h4"]) == 133_796_096
    assert size(shapes) == 691_625_216             # x 16 bytes = 11.07 GB
    # the program's tree is the same leaves
    model = lm.make_lm(module.description(config))
    ours = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        train=False)["params"])
    assert size(ours) == 691_625_216


# -- a trial -------------------------------------------------------------------

@pytest.fixture(scope="module")
def trial():
    """The configuration's own description at its rehearsal sizes, through
    ``LMTrial``: what ``examples/lm_causal.py --model`` builds."""
    from jax.sharding import Mesh

    from chipbench import run as harness

    config, module = _published()
    harness.rehearsal_sizes(config)
    a = config["script_args"]
    one = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
    before = len(trace.spans("trial.setup"))
    t = lm.LMTrial(module.description(config), mesh=one,
                   n_train=a["n_train"], batch_size=a["batch_size"],
                   seq_len=a["seq_len"], steps=8, seed=0)
    setup = trace.spans("trial.setup")[before]
    with t:
        losses = [float(t.step(i)) for i in range(4)]
    return t, losses, config, setup


def test_a_trial_trains_and_drops_nothing(trial):
    t, losses, config, _ = trial
    assert all(np.isfinite(losses))
    counts = t.read_counts()
    held = config["script_args"]["share"]["experts_held"][1]
    assert np.asarray(counts["items"]).shape == (4, held)
    assert counts["dropped"] == [0, 0, 0, 0]
    assert "bias_moved" not in counts


def test_the_setup_span_says_heads_rotary_rule_and_gate_a_kind(trial, capsys):
    setup = trial[3]
    said = setup["attrs"]["attention_layers"]
    assert list(said) == ["global-rope", "window-rope"]
    assert said["global-rope"]["layers"] == [0, 4]
    assert said["window-rope"]["layers"] == [1, 2, 3]
    assert (said["global-rope"]["heads"], said["window-rope"]["heads"]) \
        == (6, 8)
    assert said["global-rope"]["kv_heads"] == 2
    assert said["global-rope"]["rotary"] == (
        "yarn 500000 x64 over 4096, 8 of 16, cos and sin x 1.4159")
    assert said["window-rope"]["rotary"] == "plain 10000, 16 of 16"
    assert said["window-rope"]["gate"] == "sigmoid a head"
    # off the TPU the reference takes q and k as XLA's passes make them
    assert {how["hand_over"] for how in said.values()} == {"passes"}
    held = setup["attrs"]["moe"]
    assert (held["routed_over"], held["top_k"], held["held"]) \
        == (16, 4, [8, 8])
    assert (held["scoring"], held["bias"], held["scale"],
            held["shared_d_ff"], held["dense_layers"]) \
        == ("sigmoid", False, 2.5, 32, 1)
    trace.print_routes([setup])
    out = capsys.readouterr().out
    assert "global-rope layers" in out and "layers 0, 4: 6 query heads on 2 " \
        "K/V heads, rotary yarn 500000 x64 over 4096, 8 of 16" in out
    assert "layers 1-3: 8 query heads on 2 K/V heads, rotary plain 10000, " \
        "16 of 16, gate sigmoid a head" in out


@pytest.mark.parametrize("direction", ["forward", "forward.again",
                                       "backward"])
def test_the_gate_s_scope_names_the_step_s_operations(trial, direction):
    """``attention.gate`` is in ``SCOPES``, a part of the layer
    ``attention``, and the lowered step has operations under it in every
    direction a rematerialised block runs."""
    import re

    t = trial[0]
    assert "attention.gate" in trace.SCOPES
    assert trace.layer_of("h1/attn/attention/attention.gate/mul") \
        == "attention"
    with t:
        text = t._step_fn.lower(
            t.params, t.opt_state, t.counts, t.rows(0),
            jax.random.PRNGKey(0)).as_text(debug_info=True)
    at = re.compile(r"(?:^|[/(])attention\.gate(?:$|[/)])")
    gate = [n for n in set(re.findall(r'loc\("([^"]+)"', text))
            if at.search(n)]
    assert any(trace.direction(n) == direction for n in gate), direction
    assert {trace.layer_of(n) for n in gate} == {"attention"}
    # the projection is the gate's, the rotation is not
    assert any("gate" in n.split("/") for n in gate)
    assert not any("cos" in n.split("/")[-1] for n in gate)


def test_the_blocks_can_keep_the_gate_s_product():
    """The gate's product is one of the q, k, v candidate's names, at four
    bytes a head and token, on a gated layer alone."""
    from metaopt_tpu.models import lm_remat

    p = lm_description.pattern_of(lm_description._own_names(description()))
    full = p.layers[0].mixer
    (width, sizes), _ = full.products(D)
    assert width == D and sizes["attention.gate_proj"] == 4 * FULL
    plain = lm_layers.GroupedSpec(FULL, KV, HD, None, 1e4, None, None)
    assert "attention.gate_proj" not in plain.products(D)[0][1]
    said = lm_remat.remat_keeps(p, tokens=S, d_model=D, parameters=0,
                                bytes_limit=2 ** 30)
    assert said["bytes"]["attention.gate_proj"] \
        == 4 * S * (2 * FULL + 3 * WINDOWED)
    assert said["bytes"]["attention.q_proj"] \
        == 2 * S * HD * (2 * FULL + 3 * WINDOWED)
    assert "attention.gate_proj" in said["keeps"]
