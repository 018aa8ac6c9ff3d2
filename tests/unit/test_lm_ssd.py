"""The ``nemotron_h`` family's blocks in the pattern decoder
(models/lm_layers.py, models/moe.py, models/lm_description.py): ONE sublayer
a block, a Mamba-2 mixer (the scalar decay rule, B and C shared by groups of
heads, a convolution with a bias, a norm gated over groups), grouped
attention without positions, or experts of two matrices under a squared
ReLU beside a shared one, against a reference that shares no code with what
it tests (chipbench/reference/ssd_lm.py: the recurrence token by token) and
numbers written here, at small sizes on the CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metaopt_tpu.models import lm, lm_description, lm_layers, moe

D, HD, HEADS, KV = 64, 16, 4, 2
SH, SP, SG, SN, CONV = 8, 8, 2, 16, 4          # the mixer's sizes
F, SHARED, E, TOPK, V, S = 24, 48, 16, 3, 128, 96
HELD = (8, 8)          # a strict share of the 16 routed experts
PATTERN = "MEMEM*EMEMEM*EME"
NUMBERS = list(range(9))                       # MEMEM*EME: all three letters
#: on this seed the tolerances below are rounding's (a token whose third
#: and fourth score swap under bfloat16 moves a whole expert's output)
WEIGHTS_SEED = 5
#: the benchmark's comparison at these sizes (the configuration's
#: ``rehearsal_limits``)
LIMITS = {"loss_gap": 0.004, "grad_norm_gap": 0.3, "grad_rms_gap": 0.2,
          "update_norm_gap": 0.3}


def description(**over):
    said = dict(
        model_type="nemotron_h", hybrid_override_pattern=PATTERN,
        hidden_size=D, head_dim=HD, num_attention_heads=HEADS,
        num_key_value_heads=KV, mamba_num_heads=SH, mamba_head_dim=SP,
        n_groups=SG, ssm_state_size=SN, conv_kernel=CONV, chunk_size=128,
        num_hidden_layers=len(NUMBERS), vocab_size=V,
        moe_intermediate_size=F, moe_shared_expert_intermediate_size=SHARED,
        n_routed_experts=E, n_shared_experts=1, num_experts_per_tok=TOPK,
        n_group=1, topk_group=1, norm_topk_prob=True,
        routed_scaling_factor=2.5, norm_eps=1e-5, mlp_hidden_act="relu2",
        mamba_hidden_act="silu", attention_bias=False, mlp_bias=False,
        use_bias=False, mamba_proj_bias=False, use_conv_bias=True,
        rope_theta=10000, experts_held=HELD, vocab_held=(0, V),
        layers_held=NUMBERS)
    said.update(over)
    return said


def reference_cfg():
    return {
        "d_model": D, "rms_eps": 1e-5, "numbers": NUMBERS,
        "letters": "".join(PATTERN[n] for n in NUMBERS),
        "ssd_heads": SH, "ssd_head_dim": SP, "ssd_groups": SG,
        "ssd_state": SN, "ssd_conv": CONV, "chunk": 128,
        "n_heads": HEADS, "n_kv_heads": KV, "head_dim": HD,
        "rope_theta": 10000.0, "n_experts": E, "top_k": TOPK,
        "expert_d_ff": F, "shared_d_ff": SHARED, "normalised": True,
        "scale": 2.5, "time_step": [0.001, 0.1, 1e-4],
        "experts_held": list(HELD), "vocab_held": [0, V]}


def leaf(tree, path):
    for part in path.split("/"):
        tree = tree[part]
    return np.asarray(tree, np.float32)


def close(p, r, share):
    return np.linalg.norm(p - r) <= share * np.linalg.norm(r)


# -- the decoder against the plain reference -----------------------------------

def _reference_side(whole, tokens, cfg, faults=()):
    """The reference's loss, first gradient (the trained leaves) and
    parameters after one AdamW step from ``whole``, ``faults`` planted."""
    from chipbench.reference import optim, ssd_lm as reference

    biases, trained = reference.frozen(whole), reference.trained(whole)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: reference.loss(
        reference.with_frozen(p, biases), tokens, cfg, "float32",
        faults)))(trained)
    moved, _ = optim.adamw(trained, optim.adamw_init(trained), grads,
                           lr=1e-3, weight_decay=0.0)
    return {"losses": [float(loss)], "grad": grads,
            "params": reference.with_frozen(moved, biases)}


@pytest.fixture(scope="module")
def both_sides():
    """The program's and the reference's logits, loss, first gradient and
    parameters after one AdamW step, from the same seeded weights and rows;
    the program's trees in the reference's form (an expert a leaf)."""
    import optax

    from chipbench import weights_lm, weights_ssd_lm
    from chipbench.reference import ssd_lm as reference

    cfg = reference_cfg()
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, S + 1), 2, V)
    whole = weights_ssd_lm.make_weights(
        WEIGHTS_SEED, reference.param_shapes(cfg))
    model = lm.make_lm(description())
    trained, frozen = lm.split_frozen(weights_lm.stacked(whole))
    loss, grads = jax.jit(jax.value_and_grad(lambda p: lm.lm_loss_fn(
        model, lm.merge_frozen(p, frozen), tokens,
        jax.random.PRNGKey(0))))(trained)
    tx = optax.adamw(1e-3, weight_decay=0.0)
    updates, _ = tx.update(grads, tx.init(trained), trained)
    moved = lm.merge_frozen(optax.apply_updates(trained, updates), frozen)
    logits = model.apply({"params": lm.merge_frozen(trained, frozen)},
                         tokens[:, :-1], train=False)
    prog = {"losses": [float(loss)], "grad": weights_lm.split(grads),
            "params": weights_lm.split(moved), "logits": logits}
    ref = _reference_side(whole, tokens, cfg)
    ref["logits"] = jnp.stack([reference.logits(whole, row[:-1], cfg)
                               for row in tokens])
    return prog, ref, whole, tokens


def test_logits_match_the_plain_reference(both_sides):
    prog, ref = both_sides[:2]
    assert prog["logits"].shape == ref["logits"].shape == (2, S, V)
    p, r = np.asarray(prog["logits"]), np.asarray(ref["logits"])
    # a token's own gap: bfloat16's rounding at the median (0.011; the fp8
    # reference reads 0.24 there); a token whose third and fourth score
    # swap differs by a whole expert's output, and the mixers behind carry
    # that to the row's later tokens, so the whole reads 0.076
    token = np.linalg.norm(p - r, axis=-1) / np.linalg.norm(r, axis=-1)
    assert np.median(token) <= 0.03, np.median(token)
    assert np.linalg.norm(p - r) <= 0.15 * np.linalg.norm(r), (
        np.linalg.norm(p - r) / np.linalg.norm(r))


def test_the_first_step_passes_the_benchmark_s_own_comparison(both_sides):
    """Loss, every gradient leaf's norm, the gradients' difference and one
    AdamW update, as the cell's check compares them."""
    from chipbench import checks

    prog, ref, whole, _ = both_sides
    assert abs(prog["losses"][0] - ref["losses"][0]) \
        <= 2e-3 * ref["losses"][0]
    numbers = checks.compare(prog, ref, whole, LIMITS)
    assert all(n["ok"] for n in numbers.values()), numbers


_SSD = ["in_proj", "conv", "conv_bias", "dt_bias", "A_log", "D", "norm",
        "out_proj/kernel"]
LEAVES = (["embed/embedding", "head/embedding", "norm_f/scale"]
          + [f"h{i}/ssd/{name}" for i in (0, 7) for name in _SSD]
          + [f"h{i}/norm_in/scale" for i in (0, 5)]
          + [f"h5/attn/{name}/kernel" for name in ("q", "k", "v", "out")]
          + [f"h{i}/{name}" for i in (1, 8) for name in (
              "norm_post/scale", "router/kernel",
              "experts/shared/up/kernel", "experts/shared/down/kernel")])


@pytest.mark.parametrize("path", LEAVES)
def test_every_gradient_leaf_matches_the_plain_reference(both_sides, path):
    """The first and the last Mamba-2 block, the attention block, the first
    and the last expert block: every leaf of theirs. A sixth is rounding's
    (a leaf read through bfloat16 products nine blocks deep); a router's
    gradient and the norm it reads take a third (it comes through the
    chosen experts' weights alone); a leaf left out or wired wrongly reads
    1 or more; so do a mixer's leaves of a number a head (8 numbers, each
    a sum over every token of the rows)."""
    prog, ref = both_sides[:2]
    p, r = leaf(prog["grad"], path), leaf(ref["grad"], path)
    assert p.shape == r.shape
    loose = ("router", "norm_post", "A_log", "dt_bias", "ssd/D")
    share = 0.33 if any(name in path for name in loose) else 0.17
    assert close(p, r, share), np.linalg.norm(p - r) / np.linalg.norm(r)


@pytest.mark.parametrize("layer", [1, 3, 6, 8])
@pytest.mark.parametrize("which", ["up", "down"])
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
    """One norm a block, no gate among an expert's matrices, no second
    sublayer's leaves, and the frozen bias outside the gradient."""
    from chipbench import checks

    prog, ref = both_sides[:2]
    names = set(checks.named_leaves(ref["grad"]))
    assert names == set(checks.named_leaves(prog["grad"]))
    assert {"h0/norm_in/scale", "h1/norm_post/scale", "h5/attn/q/kernel",
            "h1/experts/shared/up/kernel", "h1/experts/up/e00"} <= names
    assert not any(n.startswith(("h0/norm_post", "h1/norm_in", "h0/router",
                                 "h1/ssd", "h5/mlp")) for n in names)
    assert not any("gate" in n or "choice_bias" in n for n in names)
    assert "h1/choice_bias" in checks.named_leaves(prog["params"])


def test_a_step_leaves_the_correction_bias_alone(both_sides):
    prog, _, whole, _ = both_sides
    for block in ("h1", "h3", "h6", "h8"):
        np.testing.assert_array_equal(leaf(prog["params"],
                                           f"{block}/choice_bias"),
                                      leaf(whole, f"{block}/choice_bias"))
    assert np.abs(leaf(whole, "h1/choice_bias")).max() > 0


# -- planted faults ------------------------------------------------------------

@pytest.mark.parametrize("fault", [
    "no_skip", "gate_after_norm", "group_by_modulo", "relu_not_squared"])
def test_a_planted_fault_fails_the_comparison(both_sides, fault):
    """The faulty float32 reference in the program's place (as the cell's
    chip test plants all thirteen, chipbench/tests/test_ssd_lm_cell.py): D
    left out, the gate after the norm, head h reading group h % 2, ReLU for
    its square. Each fails a limit the sound program passes."""
    from chipbench import checks
    from chipbench.reference import ssd_lm as reference

    _, ref, whole, tokens = both_sides
    assert fault in reference.FAULTS
    faulty = _reference_side(whole, tokens, reference_cfg(), (fault,))
    numbers = checks.compare(faulty, ref, whole, LIMITS)
    assert not all(n["ok"] for n in numbers.values()), numbers


# -- the reader ----------------------------------------------------------------

def test_the_pattern_is_read_at_the_published_numbers():
    p = lm_description.pattern_of(lm_description._own_names(description(
        layers_held=[5, 6, 7])))
    assert [layer.number for layer in p.layers] == [5, 6, 7]
    attention, experts, mixer = p.layers
    absent = lambda spec: isinstance(spec, lm_layers.Absent)  # noqa: E731
    assert attention.mixer.kind == "global-nope" and absent(attention.ffn)
    assert attention.mixer.theta is None and attention.mixer.qk_norm is None
    assert absent(experts.mixer) and experts.ffn == moe.RoutedSpec(
        n_experts=E, top_k=TOPK, d_ff=F, held=HELD, activation="relu2",
        shared_d_ff=SHARED, rule=moe.RoutingRule("sigmoid", True, True, 2.5),
        router_after_mixer=True, gated=False)
    assert mixer.mixer == lm_layers.ScalarDecaySpec(SH, SP, SG, SN, CONV) \
        and absent(mixer.ffn)
    assert p.norm == "rms" and p.eps == 1e-5 and not p.tied
    assert [kind for kind, _ in p.by_kind()] == ["global-nope", "ssd"]
    assert lm_description.family_of(description()) == "nemotron_h"


def test_without_layers_held_every_block_of_the_pattern_is_built():
    p = lm_description.pattern_of(lm_description._own_names(description(
        layers_held=None)))
    assert len(p.layers) == len(PATTERN)
    assert lm.make_lm(description(layers_held=None)).n_layers == len(PATTERN)


@pytest.mark.parametrize("over, named", [
    ({"hybrid_override_pattern": "MEM-E"}, r"hybrid_override_pattern.*'-'"),
    ({"n_group": 2}, "n_group 2"),
    ({"topk_group": 2}, "topk_group 2"),
    ({"mlp_bias": True}, "mlp_bias True"),
    ({"attention_bias": True}, "attention_bias True"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias True"),
    ({"use_bias": True}, "use_bias True"),
    ({"use_conv_bias": False}, "use_conv_bias false"),
    ({"n_groups": 3}, "n_groups 3 does not divide mamba_num_heads 8"),
    ({"mlp_hidden_act": "silu"}, "mlp_hidden_act 'silu'"),
    ({"layers_held": [3, 2]}, r"layers_held \[3, 2\]"),
    ({"layers_held": [99]}, r"layers_held \[99\]"),
    ({"heads_held": [0, 2]}, "heads_held"),
    ({"scoring_func": "softmax"}, "scoring_func 'softmax'"),
], ids=lambda x: x if isinstance(x, str) else "-".join(x))
def test_what_has_no_layer_here_is_refused_by_its_name(over, named):
    with pytest.raises(ValueError, match=named):
        lm.make_lm(description(**over))


def test_the_parameters_at_the_published_sizes():
    """From the program's own tree shapes (``jax.eval_shape``): 31.58 B
    whole, 666.96 M for the benchmark's cut (published blocks 0-8, 8 of 128
    experts, 16384 of 131072 rows)."""
    import json
    import os

    from chipbench import ssd_lm_config
    from metaopt_tpu.models.lm_remat import param_init

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "chipbench", "configs",
                           "nemotron-3-nano-30b-a3b-ep16.json")) as f:
        config = json.load(f)
    count = lambda model: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree.leaves(jax.eval_shape(
            param_init(model, (1, 128)),
            jax.ShapeDtypeStruct((2,), jnp.uint32))))
    cut = ssd_lm_config.description(config)
    assert count(lm.make_lm(cut)) == 666_963_456
    whole = {k: v for k, v in cut.items() if k not in (
        "experts_held", "vocab_held", "layers_held")}
    whole.update(config["published"], parameters=None)
    assert count(lm.make_lm(whole)) == 31_577_940_288


def test_what_trial_setup_says_of_the_blocks():
    said = lm_description.describe_pattern(description(), "reference",
                                           tokens=2 * S, seq_len=S)
    assert said["attention_layers"]["ssd"] == {
        "route": "xla", "chunk": 128, "hand_over": "passes",
        "layers": [0, 2, 4, 7], "heads": SH,
        "head_dim": SP, "groups": SG, "state": SN, "conv": CONV,
        "norm_group": SH * SP // SG,
        "program": "group of 4 heads and chunk",
        "remat_keeps": ["ssd.out", "ssd.states"]}
    assert "absent" not in said["attention_layers"]
    experts = said["moe"]
    assert experts["gated"] is False and experts["layers"] == [1, 3, 6, 8]
    assert experts["dense_layers"] == 0 and experts["bias"] is True
    assert experts["experts"]["gate_up"] == "no gate: one product of 24 " \
        "columns"


def test_a_width_of_no_whole_lanes_is_one_block():
    """1856 = 29 x 64: the whole width as one block on whichever axis it
    lies, the other axis cut to fit; a width of whole lanes tiles as
    before."""
    n, d, f = 49152, 2688, 1856
    assert moe._gmm_tiling(n, d, f) == (256, 896, 1856)
    assert moe._gmm_tiling(n, f, d) == (256, 1856, 896)
    assert moe._tgmm_tiling(n, d, f) == (256, 384, 1856)
    assert moe._tgmm_tiling(n, f, d) == (256, 1856, 384)
    assert moe._gmm_tiling(49152, 2560, 768) == (256, 2560, 768)
    assert moe._gmm_tiling(100, d, f)[0] is None     # no row tile: not here
    for tiling in (moe._gmm_tiling, moe._tgmm_tiling):
        assert all(tiling(n, d, f)) and all(tiling(n, f, d))
