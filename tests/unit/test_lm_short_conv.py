"""The ``lfm2_moe`` family's layers in the pattern decoder
(models/lm_layers.py, models/moe.py, models/lm_description.py): gated short
convolutions as mixers beside grouped attention with q/k norms and rotary
positions, leading dense layers, then SwiGLU experts chosen by sigmoid
scores with a correction bias, a tied head: the mixer against the equations
written out here, the whole description against a reference that shares no
code with what it tests (chipbench/reference/conv_lm.py), and the reader's
words, at small sizes on the CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from metaopt_tpu.models import lm, lm_description, lm_layers, moe

D, HEADS, KV, TAPS = 64, 4, 2, 3
DENSE, F, E, TOPK, V, S = 96, 24, 16, 8, 128, 96
HELD = (8, 8)          # a strict share of the 16 routed experts
TYPES = ["conv", "conv", "full_attention", "conv"] * 3
NUMBERS = [1, 2, 3, 4, 5, 6, 7]     # conv+dense, attn, conv x 3, attn, conv
#: on this seed the tolerances below are rounding's (a token whose third
#: and fourth score swap under bfloat16 moves a whole expert's output)
WEIGHTS_SEED = 5
#: the benchmark's comparison at these sizes (the configuration's
#: ``rehearsal_limits``)
LIMITS = {"loss_gap": 0.008, "grad_norm_gap": 0.3, "grad_rms_gap": 0.2,
          "update_norm_gap": 0.3}


def description(**over):
    said = dict(
        model_type="lfm2_moe", layer_types=TYPES, hidden_size=D,
        num_attention_heads=HEADS, num_key_value_heads=KV,
        num_hidden_layers=len(NUMBERS), num_dense_layers=2,
        intermediate_size=DENSE, moe_intermediate_size=F, num_experts=E,
        num_experts_per_tok=TOPK, norm_topk_prob=True,
        routed_scaling_factor=1, use_expert_bias=True, conv_L_cache=TAPS,
        conv_bias=False, norm_eps=1e-5, vocab_size=V,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
        experts_held=HELD, vocab_held=(0, V), layers_held=NUMBERS)
    said.update(over)
    return said


def reference_cfg():
    return {
        "d_model": D, "rms_eps": 1e-5, "numbers": NUMBERS,
        "kinds": [TYPES[n] for n in NUMBERS], "dense_layers": 2,
        "d_ff": DENSE, "taps": TAPS, "n_heads": HEADS, "n_kv_heads": KV,
        "head_dim": D // HEADS, "rope_theta": 1e6, "n_experts": E,
        "top_k": TOPK, "expert_d_ff": F, "normalised": True, "scale": 1.0,
        "routing_eps": 1e-6, "use_bias": True, "experts_held": list(HELD),
        "vocab_held": [0, V]}


def leaf(tree, path):
    for part in path.split("/"):
        tree = tree[part]
    return np.asarray(tree, np.float32)


def close(p, r, share):
    return np.linalg.norm(p - r) <= share * np.linalg.norm(r)


# -- the mixer against the equations -------------------------------------------

def equations(u, w_in, taps, w_out):
    """The gated short convolution, float32, a token at a time: [B | C | X]
    = u W_in; c_t = sum_i taps[i] (B X)_{t-2+i}, zero before the row's
    start; out = (C c) W_out."""
    hi = jax.lax.Precision.HIGHEST
    b, c, x = jnp.split(jnp.einsum("btd,de->bte", u, w_in, precision=hi), 3,
                        axis=-1)
    gated = b * x
    rows = []
    for t in range(u.shape[1]):
        conv = sum(taps[i] * gated[:, t - 2 + i] for i in range(3)
                   if t - 2 + i >= 0)
        rows.append(c[:, t] * conv)
    return jnp.einsum("btk,kd->btd", jnp.stack(rows, axis=1), w_out,
                      precision=hi)


@pytest.fixture(scope="module")
def mixer_sides():
    """(the module's output and gradients, the equations') on one input."""
    mixer = lm_layers.ShortConvMixer(D, lm_layers.ShortConvSpec(D, TAPS))
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 24, D))
    cot = jax.random.normal(jax.random.PRNGKey(2), (2, 24, D))
    params = nn.meta.unbox(mixer.init(jax.random.PRNGKey(1), u)["params"])
    assert {k: jax.tree.map(jnp.shape, v) for k, v in params.items()} == {
        "in_proj": {"kernel": (D, 3 * D)}, "conv": (TAPS, D),
        "out_proj": {"kernel": (D, D)}}

    def module(p, v):
        return jnp.sum(mixer.apply({"params": p}, v).astype(jnp.float32)
                       * cot)

    def plain(p, v):
        return jnp.sum(equations(v, p["in_proj"]["kernel"], p["conv"],
                                 p["out_proj"]["kernel"]) * cot)

    out = mixer.apply({"params": params}, u)
    want = equations(u, params["in_proj"]["kernel"], params["conv"],
                     params["out_proj"]["kernel"])
    return (out, jax.grad(module, argnums=(0, 1))(params, u),
            want, jax.grad(plain, argnums=(0, 1))(params, u))


def test_the_mixer_s_output_is_the_equations(mixer_sides):
    out, _, want, _ = mixer_sides
    assert out.dtype == jnp.bfloat16 and out.shape == want.shape
    # two bfloat16 products and one rounding between them
    assert close(np.asarray(out, np.float32), np.asarray(want), 0.02)


@pytest.mark.parametrize("path", ["in_proj/kernel", "conv",
                                  "out_proj/kernel", "input"])
def test_the_mixer_s_gradients_are_the_equations(mixer_sides, path):
    _, (gp, gu), _, (wp, wu) = mixer_sides
    ours, want = (gu, wu) if path == "input" else (leaf(gp, path),
                                                   leaf(wp, path))
    assert close(np.asarray(ours, np.float32), np.asarray(want), 0.03), \
        np.linalg.norm(ours - want) / np.linalg.norm(want)


def test_the_thirds_are_b_c_x_in_this_order():
    """With the C third's columns of W_in zero the output is zero; with the
    B third's or the X third's zero too; and swapping B's and X's columns
    leaves the output, swapping B's and C's does not."""
    mixer = lm_layers.ShortConvMixer(D, lm_layers.ShortConvSpec(D, TAPS))
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 16, D))
    params = nn.meta.unbox(mixer.init(jax.random.PRNGKey(1), u)["params"])
    w = params["in_proj"]["kernel"]
    run = lambda w: np.asarray(mixer.apply(  # noqa: E731
        {"params": {**params, "in_proj": {"kernel": w}}}, u), np.float32)
    b, c, x = w[:, :D], w[:, D:2 * D], w[:, 2 * D:]
    join = lambda *parts: jnp.concatenate(parts, axis=1)  # noqa: E731
    for third in range(3):
        assert not run(w.at[:, third * D:(third + 1) * D].set(0.0)).any()
    np.testing.assert_allclose(run(join(x, c, b)), run(w), rtol=0.02,
                               atol=1e-3)
    assert not close(run(join(c, b, x)), run(w), 0.5)


# -- the decoder against the plain reference -----------------------------------

def _reference_side(whole, tokens, cfg, faults=()):
    """The reference's loss, first gradient (the trained leaves) and
    parameters after one AdamW step from ``whole``, ``faults`` planted."""
    from chipbench.reference import conv_lm as reference, optim

    biases, trained = reference.frozen(whole), reference.trained(whole)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: reference.loss(
        reference.with_frozen(p, biases), tokens, cfg, "float32",
        faults)))(trained)
    moved, _ = optim.adamw(trained, optim.adamw_init(trained), grads,
                           lr=1e-3, weight_decay=0.0)
    return {"losses": [float(loss)], "grad": grads,
            "params": reference.with_frozen(moved, biases)}


def _deciding_bias(number: int):
    chosen = [(6 + number + i) % E for i in range(TOPK)]
    return jnp.full((E,), -1.0).at[jnp.asarray(chosen)].set(1.0)


@pytest.fixture(scope="module")
def both_sides():
    """The program's and the reference's logits, loss, first gradient and
    parameters after one AdamW step, from the same seeded weights and rows;
    the program's trees in the reference's form (an expert a leaf)."""
    import optax

    from chipbench import weights_conv_lm, weights_lm
    from chipbench.reference import conv_lm as reference

    cfg = reference_cfg()
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, S + 1), 2, V)
    whole = weights_conv_lm.make_weights(
        WEIGHTS_SEED, reference.param_shapes(cfg))
    # six routed layers of seven at 16 experts: on seeded biases a token in
    # ten has its last chosen and first unchosen score closer than
    # bfloat16's rounding of the stream moves them, and each swap is a whole
    # expert's output. Here the correction bias decides the choice (+1 on
    # eight experts in a row a layer, from 6 + the layer's number on: eight
    # of them held in layer 2, three in layer 7; -1 on the others; scores
    # lie in (0, 1)),
    # so both sides choose alike, the weights are still the sigmoid scores'
    # over their sum, and what is compared is the layers' arithmetic; the
    # seeded biases' comparison is the cell's rehearsal
    # (chipbench/tests/test_conv_lm_cell.py)
    whole = {name: ({**sub, "choice_bias": _deciding_bias(int(name[1:]))}
                    if "choice_bias" in sub else sub)
             for name, sub in whole.items()}
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
    # bfloat16's rounding through seven layers of two sublayers each (a
    # layer alone reads 0.009; the fp8 reference 0.2 and more)
    token = np.linalg.norm(p - r, axis=-1) / np.linalg.norm(r, axis=-1)
    assert np.median(token) <= 0.05, np.median(token)
    assert close(p, r, 0.08), np.linalg.norm(p - r) / np.linalg.norm(r)


def test_the_first_step_passes_the_benchmark_s_own_comparison(both_sides):
    """Loss, every gradient leaf's norm, the gradients' difference and one
    AdamW update, as the cell's check compares them."""
    from chipbench import checks

    prog, ref, whole, _ = both_sides
    assert abs(prog["losses"][0] - ref["losses"][0]) \
        <= 2e-3 * ref["losses"][0]
    numbers = checks.compare(prog, ref, whole, LIMITS)
    assert all(n["ok"] for n in numbers.values()), numbers


_CONV = ["in_proj/kernel", "conv", "out_proj/kernel"]
LEAVES = (["embed/embedding", "norm_f/scale"]
          + [f"h{i}/conv/{name}" for i in (1, 7) for name in _CONV]
          + [f"h{i}/attn/{name}" for i in (2, 6) for name in (
              "q/kernel", "k/kernel", "v/kernel", "out/kernel",
              "q_norm/scale", "k_norm/scale")]
          + [f"h1/mlp/{name}/kernel" for name in ("gate", "up", "down")]
          + [f"h{i}/{name}" for i in (2, 7) for name in (
              "norm_in/scale", "norm_post/scale", "router/kernel")])


@pytest.mark.parametrize("path", LEAVES)
def test_every_gradient_leaf_matches_the_plain_reference(both_sides, path):
    """The first and the last short-convolution mixer, both attention
    layers, the dense layer, the first and the last routed layer: every
    leaf of theirs. A sixth is rounding's (a leaf read through bfloat16
    products seven layers deep); a router's gradient and the norm it reads
    take a third (it comes through the chosen experts' weights alone); a
    leaf left out or wired wrongly reads 1 or more."""
    prog, ref = both_sides[:2]
    p, r = leaf(prog["grad"], path), leaf(ref["grad"], path)
    assert p.shape == r.shape
    loose = ("router", "norm_post", "q_norm", "k_norm")
    share = 0.33 if any(name in path for name in loose) else 0.17
    assert close(p, r, share), np.linalg.norm(p - r) / np.linalg.norm(r)


@pytest.mark.parametrize("layer", [2, 5, 7])
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
    """Two norms a block, no head of its own (tied), no shared expert, no
    bias on the convolution, and the frozen bias outside the gradient."""
    from chipbench import checks

    prog, ref = both_sides[:2]
    names = set(checks.named_leaves(ref["grad"]))
    assert names == set(checks.named_leaves(prog["grad"]))
    assert {"h1/norm_in/scale", "h1/norm_post/scale", "h1/conv/conv",
            "h1/mlp/gate/kernel", "h2/attn/q_norm/scale",
            "h2/experts/gate/e00"} <= names
    assert not any(n.startswith(("head", "h1/router", "h1/experts",
                                 "h2/mlp", "h2/conv", "h3/attn"))
                   for n in names)
    assert not any("shared" in n or "bias" in n for n in names)
    assert "h2/choice_bias" in checks.named_leaves(prog["params"])
    assert "h1/choice_bias" not in checks.named_leaves(prog["params"])


def test_a_step_leaves_the_correction_bias_alone(both_sides):
    prog, _, whole, _ = both_sides
    for block in ("h2", "h5", "h7"):
        np.testing.assert_array_equal(leaf(prog["params"],
                                           f"{block}/choice_bias"),
                                      leaf(whole, f"{block}/choice_bias"))
    assert np.abs(leaf(whole, "h2/choice_bias")).max() > 0
    assert not np.array_equal(leaf(whole, "h2/choice_bias"),
                              leaf(whole, "h5/choice_bias"))


# -- planted faults ------------------------------------------------------------

@pytest.mark.parametrize("fault", [
    "thirds_xbc", "silu_after_conv", "no_qk_norm", "bias_in_weights"])
def test_a_planted_fault_fails_the_comparison(both_sides, fault):
    """The faulty float32 reference in the program's place (as the cell's
    chip test plants all sixteen, chipbench/tests/test_conv_lm_cell.py): the
    thirds read as X | B | C, a SiLU behind the convolution, the q/k norms
    left out, the bias in the weights. Each fails a limit the
    sound program passes."""
    from chipbench import checks
    from chipbench.reference import conv_lm as reference

    _, ref, whole, tokens = both_sides
    assert fault in reference.FAULTS
    faulty = _reference_side(whole, tokens, reference_cfg(), (fault,))
    numbers = checks.compare(faulty, ref, whole, LIMITS)
    assert not all(n["ok"] for n in numbers.values()), numbers


# -- the reader ----------------------------------------------------------------

def test_the_pattern_is_read_at_the_published_numbers():
    p = lm_description.pattern_of(lm_description._own_names(description(
        layers_held=[1, 2, 3])))
    assert [layer.number for layer in p.layers] == [1, 2, 3]
    dense, attention, routed = p.layers
    conv = lm_layers.ShortConvSpec(channels=D, taps=TAPS)
    assert dense.mixer == conv and dense.ffn == lm_layers.GatedSpec(
        DENSE, "silu")
    assert attention.mixer == lm_layers.GroupedSpec(
        heads=HEADS, kv_heads=KV, head_dim=D // HEADS, window=None,
        theta=1e6, qk_norm="head", selection=None)
    assert attention.mixer.kind == "global-rope"
    assert routed.mixer == conv and routed.ffn == attention.ffn \
        == moe.RoutedSpec(
            n_experts=E, top_k=TOPK, d_ff=F, held=HELD, activation="silu",
            shared_d_ff=0, rule=moe.RoutingRule("sigmoid", True, True, 1.0,
                                                1e-6),
            router_after_mixer=True)
    assert p.norm == "rms" and p.eps == 1e-5 and p.tied
    assert p.heads_held is None and p.vocab_held == (0, V)
    assert [kind for kind, _ in p.by_kind()] == ["global-rope", "short-conv"]


def test_model_type_is_asked_before_the_keys():
    """The description speaks the Olmo hybrid family's ``layer_types`` and
    the Qwen3-MoE family's ``num_experts`` at once."""
    said = description()
    assert {"layer_types", "num_experts"} <= set(said)
    assert lm_description.family_of(said) == "lfm2_moe"
    del said["model_type"]
    assert lm_description.family_of(said) == "olmo_hybrid"
    with pytest.raises(ValueError, match=r"layer_types names \['conv'\]"):
        lm.make_lm(said)


def test_without_layers_held_every_layer_is_built():
    model = lm.make_lm(description(layers_held=None))
    assert model.n_layers == len(TYPES)
    kinds = [(layer.mixer.kind, layer.ffn.kind)
             for layer in model.pattern.layers]
    assert kinds[:3] == [("short-conv", "gated"), ("short-conv", "gated"),
                         ("global-rope", "routed")]
    assert sum(kind == "global-rope" for kind, _ in kinds) == 3


@pytest.mark.parametrize("over, named", [
    ({"conv_bias": True}, "conv_bias True"),
    ({"layer_types": TYPES[:3] + ["linear_attention"] + TYPES[4:]},
     r"layer_types names \['linear_attention'\]"),
    ({"layer_types": []}, "layer_types names no layer"),
    ({"heads_held": [0, 2]}, "heads_held"),
    ({"num_dense_layers": 13}, "num_dense_layers 13: the model has 12"),
    ({"num_dense_layers": -1}, "num_dense_layers -1"),
    ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}},
     "rope_type 'yarn'"),
    ({"num_experts": 0}, "num_experts 0"),
    ({"layers_held": [3, 2]}, r"layers_held \[3, 2\]"),
    ({"layers_held": [12]}, r"layers_held \[12\]"),
    ({"experts_held": [12, 8]}, "experts"),
], ids=lambda x: x if isinstance(x, str) else "-".join(x))
def test_what_has_no_layer_here_is_refused_by_its_name(over, named):
    with pytest.raises(ValueError, match=named):
        model = lm.make_lm(description(**over))
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                   train=False)


def test_the_parameters_at_the_published_sizes():
    """From the program's own tree shapes (``jax.eval_shape``): 23.84 B
    whole and tied, 647.82 M for the benchmark's cut (published layers 1-7,
    8 of 64 experts, 8192 of 65536 rows)."""
    import json
    import os

    from chipbench import conv_lm_config
    from metaopt_tpu.models.lm_remat import param_init

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "chipbench", "configs",
                           "lfm2-24b-a2b-ep8.json")) as f:
        config = json.load(f)
    count = lambda model: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree.leaves(jax.eval_shape(
            param_init(model, (1, 128)),
            jax.ShapeDtypeStruct((2,), jnp.uint32))))
    cut = conv_lm_config.description(config)
    assert count(lm.make_lm(cut)) == 647_819_520 + 6 * 64
    whole = {k: v for k, v in cut.items() if k not in (
        "experts_held", "vocab_held", "layers_held")}
    whole.update(config["published"], parameters=None)
    assert count(lm.make_lm(whole)) == 23_843_661_440


def test_what_trial_setup_says_of_the_layers():
    said = lm_description.describe_pattern(description(), "reference",
                                           tokens=2 * S, seq_len=S)
    assert said["attention_layers"]["short-conv"] == {
        "route": "plain", "layers": [1, 3, 4, 5, 7], "channels": D,
        "taps": TAPS, "tp": "whole on every device"}
    assert list(said["attention_layers"]) == ["global-rope", "short-conv"]
    assert said["embed"]["tied"] is True
    experts = said["moe"]
    assert experts["bias"] is True and experts["scoring"] == "sigmoid"
    assert experts["dense_layers"] == 1 and experts["shared_d_ff"] == 0


def test_on_a_tp_axis_the_mixer_is_whole_on_every_device():
    spec = lm_layers.ShortConvSpec(channels=D, taps=TAPS)
    assert spec.under_tp(2) == spec and spec.kernel_keeps() == ()
    assert not spec.attends
    assert spec.products(D) == [
        (D, {"short_conv.in_proj": 2 * 3 * D}),
        (D, {"short_conv.out_proj": 2 * D})]


def test_the_remat_rule_takes_the_mixer_s_products_where_they_fit():
    from metaopt_tpu.models.lm_remat import remat_keeps

    p = lm_description.pattern_of(lm_description._own_names(description()))
    roomy = remat_keeps(p, tokens=S, d_model=D, parameters=10 ** 5,
                        bytes_limit=10 ** 9)
    assert {"short_conv.in_proj", "short_conv.out_proj"} <= set(
        roomy["keeps"])
    assert roomy["bytes"]["short_conv.in_proj"] == 5 * S * 6 * D
    none = remat_keeps(p, tokens=S, d_model=D, parameters=10 ** 5,
                       bytes_limit=None)
    assert not any(n.startswith("short_conv") for n in none["keeps"])
