"""The DeepSeek-V3 family's layer in the pattern decoder (models/lm.py,
models/moe.py): latent attention, a leading dense layer, sigmoid routing
with a correction bias and shared experts, against references that share
no code with what they test (chipbench/reference/mla_lm.py, numpy, a
complex-number rotary), at small sizes on the CPU.
"""

import dataclasses
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metaopt_tpu.models import (lm, lm_description, lm_layers, lm_remat,
                                moe)
from metaopt_tpu.utils import trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
D, H, NOPE, ROPE, VD, RANK = 64, 4, 16, 8, 16, 32
DFF, F, E, TOPK, SHARED = 96, 32, 16, 3, 2
V, S = 128, 48
HELD = (4, 8)          # a strict share of the 16 routed experts
SCALE = 2.448
#: bfloat16 products move a token's third and fourth score past each other
#: now and then, and such a token then differs by a whole expert's output:
#: on 12 seeds tried, 1 to 6 of the 96 tokens did. On this one no token of
#: the first routed layer does and one of the second: the tolerances below
#: are rounding's, a twentieth, but for the second routed layer's experts
WEIGHTS_SEED = 5


def description(layers=3, held=HELD, **over):
    return dict(
        hidden_size=D, num_attention_heads=H, num_key_value_heads=H,
        num_hidden_layers=layers, vocab_size=V, head_dim=8,
        kv_lora_rank=RANK, q_lora_rank=None, qk_nope_head_dim=NOPE,
        qk_rope_head_dim=ROPE, v_head_dim=VD, rope_theta=1e6,
        rope_interleave=True, rope_scaling=None, first_k_dense_replace=1,
        intermediate_size=DFF, moe_intermediate_size=F, n_routed_experts=E,
        num_experts_per_tok=TOPK, n_shared_experts=SHARED, n_group=1,
        topk_group=1, moe_layer_freq=1, scoring_func="sigmoid",
        topk_method="noaux_tc", norm_topk_prob=True,
        routed_scaling_factor=SCALE, hidden_act="silu", rms_norm_eps=1e-6,
        experts_held=held, vocab_held=(0, V), **over)


def reference_cfg(layers=3, held=HELD):
    return {"d_model": D, "n_heads": H, "n_layers": layers, "rank": RANK,
            "nope": NOPE, "rope": ROPE, "v_dim": VD, "rope_theta": 1e6,
            "rms_eps": 1e-6, "dense_layers": 1, "d_ff": DFF, "n_experts": E,
            "top_k": TOPK, "expert_d_ff": F, "shared_d_ff": SHARED * F,
            "normalised": True, "scale": SCALE, "activation": "silu",
            "experts_held": list(held), "vocab_held": [0, V]}


def leaf(tree, path):
    for part in path.split("/"):
        tree = tree[part]
    return np.asarray(tree, np.float32)


def close(p, r, share):
    return np.linalg.norm(p - r) <= share * np.linalg.norm(r)


# -- the decoder against the plain reference -----------------------------------

@pytest.fixture(scope="module")
def both_sides():
    """The program's and the reference's logits, loss, first gradient and
    parameters after one AdamW step, from the same seeded weights and rows;
    the program's trees in the reference's form (an expert a leaf)."""
    import optax

    from chipbench import weights_lm, weights_mla_lm
    from chipbench.reference import mla_lm as reference, optim

    cfg = reference_cfg()
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, S + 1), 2, V)
    whole = weights_mla_lm.make_weights(WEIGHTS_SEED, reference.param_shapes(cfg))
    model = lm.make_lm(description())
    trained, frozen = lm.split_frozen(weights_lm.stacked(whole))
    loss, grads = jax.value_and_grad(lambda p: lm.lm_loss_fn(
        model, lm.merge_frozen(p, frozen), tokens,
        jax.random.PRNGKey(0)))(trained)
    tx = optax.adamw(1e-3, weight_decay=0.0)
    updates, _ = tx.update(grads, tx.init(trained), trained)
    after = lm.merge_frozen(optax.apply_updates(trained, updates), frozen)
    logits = model.apply({"params": weights_lm.stacked(whole)},
                         tokens[:, :-1], train=False)
    prog = {"losses": [float(loss)], "grad": weights_lm.split(grads),
            "params": weights_lm.split(after), "logits": logits}

    part, biases = reference.trained(whole), reference.frozen(whole)
    ref_loss, ref_grads = jax.value_and_grad(lambda p: reference.loss(
        reference.with_frozen(p, biases), tokens, cfg))(part)
    moved, _ = optim.adamw(part, optim.adamw_init(part), ref_grads, lr=1e-3,
                           weight_decay=0.0)
    ref_logits = jnp.stack([jnp.einsum(
        "sd,vd->sv", reference.features(whole, row[:-1], cfg),
        whole["head"]["embedding"], precision="highest") for row in tokens])
    ref = {"losses": [float(ref_loss)], "grad": ref_grads,
           "params": reference.with_frozen(moved, biases),
           "logits": ref_logits}
    return prog, ref, whole


def test_logits_match_the_plain_reference(both_sides):
    prog, ref, _ = both_sides
    assert prog["logits"].shape == ref["logits"].shape == (2, S, V)
    p, r = np.asarray(prog["logits"]), np.asarray(ref["logits"])
    assert np.linalg.norm(p - r) <= 0.05 * np.linalg.norm(r), (
        np.linalg.norm(p - r) / np.linalg.norm(r))


def test_the_first_step_passes_the_benchmark_s_own_comparison(both_sides):
    """Loss, the first gradient by its worst leaf and all leaves together,
    and the parameters after one AdamW step: ``chipbench.checks.compare``
    with the configuration's rehearsal limits, the correction bias among
    the compared parameters (a moved bias is an update the reference has
    not)."""
    from chipbench import checks

    prog, ref, whole = both_sides
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "kanana-2-30b-a3b-ep8.json")) as f:
        limits = json.load(f)["check"]["rehearsal_limits"]
    numbers = checks.compare(prog, ref, whole, limits)
    assert all(n["ok"] for n in numbers.values()), numbers
    for name in ("h1", "h2"):
        np.testing.assert_array_equal(
            np.asarray(prog["params"][name]["choice_bias"]),
            np.asarray(whole[name]["choice_bias"]))


LEAVES = ["embed/embedding", "head/embedding", "norm_f/scale",
          "h0/norm_in/scale", "h0/norm_post/scale", "h0/attn/q/kernel",
          "h0/attn/kv_a/kernel", "h0/attn/kv_a_norm/scale",
          "h0/attn/kv_b/kernel", "h0/attn/out/kernel", "h0/mlp/gate/kernel",
          "h0/mlp/up/kernel", "h0/mlp/down/kernel", "h1/norm_post/scale",
          "h1/attn/q/kernel", "h1/attn/kv_a/kernel", "h1/attn/kv_b/kernel",
          "h1/router/kernel", "h1/experts/shared/gate/kernel",
          "h1/experts/shared/up/kernel", "h1/experts/shared/down/kernel"]


@pytest.mark.parametrize("path", LEAVES)
def test_every_gradient_leaf_matches_the_plain_reference(both_sides, path):
    """bfloat16 products against float32: the difference's norm stays under
    a twelfth of the leaf's (the readings: 0.021-0.055)."""
    prog, ref, _ = both_sides
    p, r = leaf(prog["grad"], path), leaf(ref["grad"], path)
    assert np.linalg.norm(r) > 0
    assert close(p, r, 0.08), path


@pytest.mark.parametrize("path, share", [
    ("h1/experts/gate", 0.08), ("h1/experts/up", 0.08),
    ("h1/experts/down", 0.08), ("h2/experts/gate", 0.16),
    ("h2/experts/up", 0.16), ("h2/experts/down", 0.16)])
def test_the_held_experts_gradients_match_all_experts_together(both_sides,
                                                               path, share):
    """An expert sees a few of the 96 tokens: its matrices are compared all
    held experts together (readings 0.035-0.037, and 0.087-0.114 in the
    layer where a token changed an expert)."""
    prog, ref, _ = both_sides
    stack = lambda tree: np.stack([  # noqa: E731
        leaf(tree, f"{path}/{e}") for e in sorted(_sub(tree, path))])
    p, r = stack(prog["grad"]), stack(ref["grad"])
    assert p.shape[0] == HELD[1]
    assert close(p, r, share), path


def _sub(tree, path):
    for part in path.split("/"):
        tree = tree[part]
    return tree


def test_the_gradient_tree_names_no_bias_and_the_dense_layer_no_router(
        both_sides):
    prog, ref, _ = both_sides
    names = lambda tree: sorted(  # noqa: E731
        "/".join(str(p.key) for p in path)
        for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0])
    assert names(prog["grad"]) == names(ref["grad"])
    assert not any("choice_bias" in n for n in names(prog["grad"]))
    assert not any(n.startswith(("h0/router", "h0/experts"))
                   for n in names(prog["params"]))
    assert any(n.startswith("h0/mlp/") for n in names(prog["params"]))
    assert "h1/choice_bias" in names(prog["params"])


# -- rotary on adjacent pairs ---------------------------------------------------

def test_rotary_on_adjacent_pairs_is_a_complex_rotation():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 3, ROPE))
    got = np.asarray(lm_layers.rope(x, 1e6, adjacent=True))
    z = np.asarray(x, np.float64)
    z = z[..., 0::2] + 1j * z[..., 1::2]
    angle = np.arange(9)[:, None] * 1e6 ** (-np.arange(0, ROPE, 2) / ROPE)
    turned = z * np.exp(1j * angle)[None, :, None]
    want = np.stack([turned.real, turned.imag], axis=-1).reshape(x.shape)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_halves_form_is_another_function():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 9, 1, ROPE))
    halves = lm_layers.rope(x, 1e6)
    pairs = lm_layers.rope(x, 1e6, adjacent=True)
    np.testing.assert_allclose(halves[:, 0], pairs[:, 0], atol=1e-6)  # pos 0
    assert float(jnp.max(jnp.abs(halves[:, 1:] - pairs[:, 1:]))) > 0.1
    # the same rotation on a permuted head: halves(x[perm]) == pairs(x)[perm]
    perm = np.concatenate([np.arange(0, ROPE, 2), np.arange(1, ROPE, 2)])
    np.testing.assert_allclose(lm_layers.rope(x[..., perm], 1e6),
                               pairs[..., perm], atol=2e-6)


# -- the routing rule -----------------------------------------------------------

RULE = moe.RoutingRule("sigmoid", bias=True, normalised=True, scale=SCALE)


def _logits(t=64, key=3):
    return 2.0 * jax.random.normal(jax.random.PRNGKey(key), (t, E))


def test_the_bias_moves_the_choice_and_never_a_weight():
    logits = _logits()
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (E,))
    w0, e0 = moe.route_top_k(logits, TOPK, RULE, jnp.zeros((E,)))
    w1, e1 = moe.route_top_k(logits, TOPK, RULE, bias)
    moved = np.any(np.sort(e0, 1) != np.sort(e1, 1), axis=1)
    assert 0 < moved.sum() < len(moved)
    # a weight is the chosen expert's own score over the chosen scores' sum
    scores = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    chosen = np.take_along_axis(scores, np.asarray(e1), axis=1)
    np.testing.assert_allclose(
        w1, chosen / chosen.sum(1, keepdims=True) * SCALE, rtol=2e-6)
    # where the choice stood the weights stood: the bias is in none of them
    same = ~moved & np.all(np.asarray(e0) == np.asarray(e1), axis=1)
    np.testing.assert_array_equal(np.asarray(w0)[same], np.asarray(w1)[same])
    assert int(moe.bias_moved_tokens(logits, e1)) == moved.sum()
    assert int(moe.bias_moved_tokens(logits, e0)) == 0


def test_the_weights_sum_to_the_scale_or_are_the_bare_scores():
    logits = _logits()
    bias = jnp.zeros((E,))
    w, e = moe.route_top_k(logits, TOPK, RULE, bias)
    np.testing.assert_allclose(np.asarray(w).sum(1), SCALE, rtol=1e-6)
    bare, _ = moe.route_top_k(
        logits, TOPK, dataclasses.replace(RULE, normalised=False, scale=1.0),
        bias)
    np.testing.assert_allclose(
        bare, np.take_along_axis(np.asarray(jax.nn.sigmoid(logits)),
                                 np.asarray(e), axis=1), rtol=1e-6)


def test_a_planted_bias_forces_an_expert_in_and_sends_it_no_gradient():
    logits = _logits()
    bias = jnp.zeros((E,)).at[11].set(10.0)
    w, e = moe.route_top_k(logits, TOPK, RULE, bias)
    assert np.all(np.any(np.asarray(e) == 11, axis=1))
    assert float(jnp.max(w)) <= SCALE          # a score, never 10
    grad = jax.grad(lambda b: jnp.sum(
        moe.route_top_k(logits, TOPK, RULE, b)[0] ** 2))(bias)
    np.testing.assert_array_equal(np.asarray(grad), 0.0)


def test_ties_go_to_the_lower_index_under_both_rules():
    logits = jnp.zeros((5, E))
    for rule, bias in ((RULE, jnp.zeros((E,))), (moe.RoutingRule(), None)):
        _, e = moe.route_top_k(logits, TOPK, rule, bias)
        np.testing.assert_array_equal(np.asarray(e),
                                      np.tile(np.arange(TOPK), (5, 1)))


def test_the_softmax_rule_is_what_it_was():
    logits = _logits()
    w, e = moe.route_top_k(logits, TOPK)
    top = np.sort(np.asarray(logits), axis=1)[:, ::-1][:, :TOPK]
    want = np.exp(top - top.max(1, keepdims=True))
    np.testing.assert_allclose(w, want / want.sum(1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_array_equal(
        e, np.argsort(-np.asarray(logits), axis=1, kind="stable")[:, :TOPK])


@pytest.mark.parametrize("over, message", [
    ({"n_group": 2}, "n_group"), ({"topk_group": 2}, "topk_group"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling")])
def test_what_has_no_layer_here_is_refused_by_name(over, message):
    with pytest.raises(ValueError, match=message):
        lm.make_lm({**description(), **over})


# -- the shares add up -----------------------------------------------------------

def _layer(held, shared=True):
    return moe.DroplessMoE(D, F, E, TOPK, held, "silu", RULE,
                           SHARED * F if shared else 0)


@pytest.fixture(scope="module")
def expert_layer_operands():
    from chipbench import weights_lm, weights_mla_lm
    from chipbench.reference import mla_lm as reference

    cfg = reference_cfg(layers=2, held=(0, E))
    whole = weights_mla_lm.make_weights(3, reference.param_shapes(cfg))["h1"]
    x = jax.random.normal(jax.random.PRNGKey(8), (1, S, D))
    w = jax.random.normal(jax.random.PRNGKey(9), (1, S, D))
    logits = jnp.einsum("bsd,de->bse", x, whole["router"]["kernel"],
                        precision="highest")
    return cfg, whole, weights_lm.stacked(whole["experts"]), x, w, logits


def _run(held, params, x, w, logits, bias):
    """(output, input gradient) of the layer holding ``held``."""
    first, count = held
    mine = {k: (v[first:first + count] if k != "shared" else v)
            for k, v in params.items()}

    def f(x):
        return _layer(held).apply({"params": mine}, x, logits, bias)

    return f(x), jax.grad(lambda x: jnp.sum(f(x) * w))(x)


def test_eight_shares_add_up_to_the_uncut_layer(expert_layer_operands):
    """The guide's section 4 test: the parts that all the shares give, with
    what every chip computes alike (the shared experts) counted once, are
    the uncut layer's output and input gradient."""
    cfg, whole, params, x, w, logits = expert_layer_operands
    bias = whole["choice_bias"]
    uncut, uncut_dx = _run((0, E), params, x, w, logits, bias)
    shared = lm_layers.GatedFeedForward(D, SHARED * F, "silu")
    alike = lambda x: shared.apply(  # noqa: E731
        {"params": params["shared"]}, x).astype(jnp.float32)
    alike_dx = jax.grad(lambda x: jnp.sum(alike(x) * w))(x)
    parts = [_run((first, E // 8), params, x, w, logits, bias)
             for first in range(0, E, E // 8)]
    total = sum(y for y, _ in parts) - 7 * alike(x)
    total_dx = sum(dx for _, dx in parts) - 7 * alike_dx
    np.testing.assert_allclose(total, uncut, atol=2e-2 * float(
        jnp.max(jnp.abs(uncut))))
    np.testing.assert_allclose(total_dx, uncut_dx, atol=2e-2 * float(
        jnp.max(jnp.abs(uncut_dx))))
    # every share holds something, and none the whole
    assert all(0 < float(jnp.linalg.norm(y - alike(x)))
               < float(jnp.linalg.norm(uncut)) for y, _ in parts)


def test_the_uncut_layer_is_the_plain_reference_s(expert_layer_operands):
    from chipbench.reference import mla_lm as reference

    cfg, whole, params, x, w, logits = expert_layer_operands
    uncut, _ = _run((0, E), params, x, w, logits, whole["choice_bias"])
    e = whole["experts"]
    weights = reference.routing_weights(logits[0], whole["choice_bias"], cfg)
    want = reference._experts(
        "float32", {k: e[k] for k in ("gate", "up", "down")}, x[0], weights,
        0, jax.nn.silu) + reference._gated("float32", e["shared"], x[0],
                                           jax.nn.silu)
    assert close(np.asarray(uncut[0]), np.asarray(want), 0.02)
    assert float(jnp.sum(weights > 0)) == S * TOPK


def test_a_share_normalises_over_all_the_chosen_not_the_held(
        expert_layer_operands):
    """Under ``experts_held`` the weights are those of the uncut layer: a
    share without the shared branch is the uncut routed part's terms of
    the held experts, whatever else the token chose."""
    cfg, whole, params, x, w, logits = expert_layer_operands
    bias = whole["choice_bias"]
    routed = {k: v for k, v in params.items() if k != "shared"}
    run = lambda held: _layer(held, shared=False).apply(  # noqa: E731
        {"params": {k: v[held[0]:held[0] + held[1]]
                    for k, v in routed.items()}}, x, logits, bias)
    np.testing.assert_allclose(
        run((0, 4)) + run((4, 12)), run((0, E)),
        atol=2e-2 * float(jnp.max(jnp.abs(run((0, E))))))


# -- what a rematerialised block keeps -------------------------------------------

def _cell(name, module):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        config = json.load(f)
    desc = importlib.import_module("chipbench." + module).description(config)
    a = config["script_args"]
    return desc, a["seq_len"] * a["batch_size"]


def _asked(desc, tokens, parameters, bytes_limit):
    model = lm.make_lm(desc)
    return lm_remat.remat_keeps(
        model.pattern, tokens=tokens, d_model=model.d_model,
        parameters=parameters, bytes_limit=bytes_limit)


#: a described 16 GB device: what a v5e's ``memory_stats`` states
LIMIT = 16_910_000_000
PARAMETERS = 575_955_968     # trained 575 955 456 + 4 x 128 biases


def test_what_the_cell_s_blocks_keep_on_a_16_gb_device():
    desc, tokens = _cell("kanana-2-30b-a3b-ep8", "mla_lm_config")
    ans = _asked(desc, tokens, PARAMETERS, LIMIT)
    assert ans["room"] == (LIMIT - 16 * PARAMETERS) // 2 == 3_847_352_256
    mb = {k: round(v / 2 ** 20) for k, v in ans["bytes"].items()}
    assert mb == {"ffn.down": 320, "ffn.gate": 384, "ffn.up": 384,
                  "attention.q_proj": 960, "attention.kv_latent": 90,
                  "attention.kv_up": 1280, "attention.out_proj": 320}
    assert ans["keeps"] == [
        "attention.out", "attention.lse", "attention.selected",
        "attention.out_proj", "ffn.down", "ffn.gate", "ffn.up",
        "attention.q_proj", "attention.kv_latent"]
    kept = sum(v for k, v in ans["bytes"].items() if k in ans["keeps"])
    assert kept <= ans["room"] < kept + ans["bytes"]["attention.kv_up"]


def test_with_less_room_the_up_projection_goes_first():
    """The up-projection's product is the dearest a byte (a 512-deep
    matmul makes it again from the kept latent): no room keeps it where
    q's product or the latent is declined."""
    desc, tokens = _cell("kanana-2-30b-a3b-ep8", "mla_lm_config")
    seen = set()
    for limit in range(9_300_000_000, 19_000_000_000, 100_000_000):
        keeps = _asked(desc, tokens, PARAMETERS, limit)["keeps"]
        kept = tuple(n in keeps for n in (
            "attention.kv_up", "attention.q_proj", "attention.kv_latent"))
        seen.add(kept)
        if kept[0]:
            assert kept[1] and kept[2], limit
    assert {(False, False, False), (False, False, True),
            (False, True, True), (True, True, True)} <= seen


with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "lm_goldens.json")) as _f:
    GOLDEN = json.load(_f)
ACCEPTED = [("smallthinker-21b-a3b-ep4", "lm_config"),
            ("keye-vl2-30b-a3b-ep8", "sparse_lm_config"),
            ("olmo-hybrid-7b-tp2", "hybrid_lm_config")]


@pytest.mark.parametrize("name, module", ACCEPTED)
def test_an_accepted_description_gets_the_answer_it_got(name, module):
    """``remat_keeps`` of the three accepted decoder cells at their sizes:
    the names, the bytes and the room PR 35's rule gave them."""
    desc, tokens = _cell(name, module)
    ans = _asked(desc, tokens, GOLDEN[name]["parameters"], LIMIT)
    assert json.loads(json.dumps(ans)) == GOLDEN[name]["remat"]


def _in_the_words_that_stood(p):
    """A pattern's specs in the words of the flat ``Pattern`` that the
    goldens were written from (PR 35's): what the specs still say of them
    (a window no layer has, a rotary base no layer turns by, are not
    said)."""
    grouped = [layer.mixer for layer in p.layers
               if isinstance(layer.mixer, lm_layers.GroupedSpec)]
    linear = [layer.mixer for layer in p.layers
              if isinstance(layer.mixer, lm_layers.LinearSpec)]
    ffn, attention = p.layers[-1].ffn, grouped[0]
    routed = isinstance(ffn, moe.RoutedSpec)
    said = {
        "activation": ffn.activation, "expert_d_ff": ffn.d_ff,
        "experts_held": list(ffn.held) if routed else [0, 0],
        "n_experts": ffn.n_experts if routed else 0,
        "top_k": ffn.top_k if routed else 1,
        "router_after_attention": routed and ffn.router_after_mixer,
        "head_dim": attention.head_dim, "n_kv_heads": attention.kv_heads,
        "qk_norm": attention.qk_norm is not None,
        "qk_norm_whole": attention.qk_norm == "whole",
        "selection": attention.selection and list(attention.selection),
        "layers": [[bool(getattr(layer.mixer, "window", None)),
                    getattr(layer.mixer, "theta", None) is not None]
                   for layer in p.layers],
        "linear": dataclasses.asdict(linear[0]) if linear else None,
        "linear_layers": [layer.mixer.kind == "linear" for layer in p.layers]
        if linear else [],
        "norm_after": p.norm == "rms on the branches", "rms_eps": p.eps,
        "heads_held": p.heads_held and list(p.heads_held),
        "vocab_held": list(p.vocab_held)}
    said.update({"window": g.window for g in grouped if g.window})
    said.update({"rope_theta": g.theta for g in grouped if g.theta})
    return said, (ffn.rule, ffn.shared_d_ff) if routed else None


@pytest.mark.parametrize("name, module", ACCEPTED)
def test_an_accepted_description_builds_the_pattern_it_built(name, module):
    """q/k norms and the router's place are facts of a family, not of a
    key's spelling: the three accepted descriptions' ``Pattern`` says,
    field by field, what ``pattern_of`` built before a third family's
    words."""
    desc, _ = _cell(name, module)
    p = lm.make_lm(desc).pattern
    got, routing = _in_the_words_that_stood(p)
    want = GOLDEN[name]["pattern"]
    assert set(want) - set(got) <= {"window", "rope_theta"}
    for field, value in got.items():
        assert value == want[field], field
    assert not any(isinstance(layer.mixer, lm_layers.LatentSpec)
                   for layer in p.layers)
    assert len({layer.ffn for layer in p.layers}) == 1  # no dense layers
    assert routing in (None, (moe.RoutingRule(), 0))


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


@pytest.mark.parametrize("kind, window, theta", [
    ("global-nope", None, None), ("window-rope", 384, 1e6)])
def test_a_grouped_layer_traces_to_the_program_it_traced_to(monkeypatch, kind,
                                                            window, theta):
    """The 8k decoder's and the hybrid's layer (``GroupedAttention`` through
    ``attend`` under a ``CausalMask``: equal widths, grouped K/V heads, a
    window and none) on the Pallas route: the gradient's equations, kernel
    bodies and index maps included (primitive, a call's name, grid and
    compiler parameters, the results' types) are the ones PR 37's tree
    traced, by their count and hash: a latent layer's hand-over is another
    entry, not another form of this one. The window is wider than the
    rows' one tile of 256, as the 8k decoder's 4096 is than its tiles of
    512: the walk's kernels, whose trace no window's width changes (128
    until PR 46, which gave a window no wider than the tile kernels of its
    own: ``tests/unit/test_window_kernels.py``). q and k reach the kernels
    by XLA's passes here, the form PR 37's tree had and a layer with a norm
    over the whole width still takes; the rotary layer's one pass (PR 47)
    is ``tests/unit/test_grouped_hand_over.py``'s."""
    import hashlib

    from metaopt_tpu.ops import grouped_hand_over

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(grouped_hand_over, "hand_over",
                        lambda *a: "passes")
    layer = lm_layers.GroupedAttention(64, lm_layers.GroupedSpec(
        4, 2, 32, window, theta, None, None), 1e-6)
    x = jnp.zeros((2, 256, 64))
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0),
                                               x)["params"])
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(layer.apply(
        {"params": p}, x).astype(jnp.float32)), argnums=(0, 1)))(params, x)
    lines = [" ".join([
        e.primitive.name, str(e.params.get("name", "")),
        str(getattr(e.params.get("grid_mapping"), "grid", "")),
        str(e.params.get("compiler_params", "")),
        *(str(v.aval) for v in e.outvars)]) for e in _equations(jaxpr.jaxpr)]
    assert sum("flash_fwd" in line or "flash_bwd" in line
               for line in lines) == 2
    assert [len(lines), hashlib.sha256("\n".join(lines).encode()).hexdigest()
            ] == GOLDEN["causal_kernels"][kind]


@pytest.mark.parametrize("keys, family", [
    ({"kv_lora_rank": 4}, "deepseek_v3"), ({"layer_types": []},
                                           "olmo_hybrid"),
    ({"num_experts": 4}, "qwen3_moe"),
    ({"num_experts": 4, "sa_config": {}}, "qwen3_moe"),
    ({"rope_layout": [1]}, "layouts"), ({"sa_config": {}}, "layouts"),
    ({"hidden_size": 8}, None)])
def test_a_description_s_family(keys, family):
    assert lm_description.family_of(keys) == family


# -- the trial, its counts and its spans -------------------------------------------

@pytest.fixture(scope="module")
def trial():
    from jax.sharding import Mesh

    one = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
    before = len(trace.spans("trial.setup"))
    t = lm.LMTrial({**description(), "remat": True, "lr": 1e-2, "warmup": 1},
                   mesh=one, n_train=8, batch_size=2, seq_len=32, steps=8)
    return t, trace.spans("trial.setup")[before]


def test_a_trial_trains_and_leaves_the_bias_where_it_was(trial):
    t, _ = trial
    bias = lambda: [np.asarray(jax.device_get(  # noqa: E731
        t.params[f"h{i}"]["choice_bias"].value)) for i in (1, 2)]
    before = bias()
    planted = jax.tree.map(lambda x: x, t.params)
    with t:
        losses = [float(t.step(i)) for i in range(4)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    for b, a in zip(before, bias()):
        np.testing.assert_array_equal(a, b)
    assert "choice_bias" not in str(jax.tree_util.tree_structure(
        t.opt_state[0].mu))
    del planted
    counts = t.read_counts()
    assert np.asarray(counts["items"]).shape == (2, HELD[1])  # routed layers
    assert counts["dropped"] == [0, 0]
    assert counts["bias_moved"] == [0, 0]     # the init's bias is zero


def test_the_setup_span_says_the_kind_and_the_routing(trial, capsys):
    _, setup = trial
    said = setup["attrs"]["attention_layers"]["latent-rope"]
    assert said == {"route": "reference", "mask": "dense: causal",
                    "layers": [0, 1, 2], "heads": H, "nope": NOPE,
                    "rope": ROPE, "v": VD, "rank": RANK,
                    "hand_over": "copies"}
    routing = setup["attrs"]["moe"]
    assert (routing["scoring"], routing["bias"], routing["scale"],
            routing["shared_d_ff"], routing["dense_layers"],
            routing["held"]) == ("sigmoid", True, SCALE, SHARED * F, 1,
                                 list(HELD))
    assert set(setup["attrs"]["remat"]["bytes"]) == {
        "ffn.down", "ffn.gate", "ffn.up",
        *lm_layers.LatentSpec.KEPT.values()}
    trace.print_routes([{**setup, "trial": "t"}])
    out = capsys.readouterr().out
    assert ("layers 0-2: latent attention, 4 heads, q·k 16 + 8 rotary "
            "on one shared key, v 16, K/V rank 32, by reference") in out
    assert ("experts: sigmoid scores, 3 of 16 on score + bias, weights "
            "scaled 2.448, 8 held, shared as one of 64; layers 0 dense 96"
            ) in out
    # on the Pallas route of one device the line says the hand-over's form
    chip = lm_description.describe_pattern(
        description(), "pallas", tokens=64)["attention_layers"]["latent-rope"]
    assert (chip["hand_over"], chip["mask"]) == ("in place",
                                                 "structure: causal")
    trace.print_routes([{**setup, "trial": "t", "attrs": {
        **setup["attrs"], "attention_layers": {"latent-rope": chip}}}])
    assert ("K/V rank 32, by pallas, the kernels reading q after one pass, "
            "K, V and out where the matmuls leave them, the shared key "
            "joined in VMEM") in capsys.readouterr().out


@pytest.fixture(scope="module")
def lowered_op_names(trial):
    import re

    t, _ = trial
    with t:
        text = t._step_fn.lower(
            t.params, t.opt_state, t.counts, t.rows(0),
            jax.random.PRNGKey(0)).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', text))


@pytest.mark.parametrize("scope, word", [
    ("attention.latent", "kv_a"), ("attention.latent", "kv_b"),
    ("attention.latent", "kv_a_norm"), ("moe.shared", "shared"),
    ("ffn", "mlp")])
@pytest.mark.parametrize("direction", ["forward", "forward.again",
                                       "backward"])
def test_the_two_scopes_name_the_step_s_operations(lowered_op_names, scope,
                                                   word, direction):
    import re

    at = re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:$|[/)])")
    under = [n for n in lowered_op_names
             if at.search(n) and word in n.split("/")
             and trace.direction(n) == direction]
    assert under, (scope, word, direction)
    layer = {"attention.latent": "attention", "moe.shared": "moe",
             "ffn": "ffn"}[scope]
    assert {trace.layer_of(n) for n in under} == {layer}


def test_the_shared_key_s_rotary_is_the_latent_s_and_q_s_is_attention_s(
        lowered_op_names):
    cos = [n for n in lowered_op_names if n.endswith("/cos")
           and trace.direction(n) == "forward"]
    assert any("attention.latent" in n for n in cos)
    assert any("attention.latent" not in n and "/attention/" in n
               for n in cos)


# -- the hand-over to the kernels -----------------------------------------------

def _on_the_kernels():
    """The Pallas route as the chip takes it, interpreted here, until the
    returned patch is undone: the backend reads as the TPU, every Pallas
    call runs the interpreter, and the causal kernels take float32 operands
    (inside their loops this CPU's dot takes no pair of bfloat16), widened
    under the core's own scope."""
    from jax.experimental import pallas as pl

    from metaopt_tpu.ops import attention, latent_attention

    patch = pytest.MonkeyPatch()
    call, flash = pl.pallas_call, latent_attention.flash_latent

    @trace.scope("attention.core")
    def wide(q, k, interpret=False):
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
        return flash(f32(q), attention.LatentKV(f32(k.kv), f32(k.shared),
                                                k.nope), True).astype(q.dtype)

    patch.setattr(jax, "default_backend", lambda: "tpu")
    patch.setattr(pl, "pallas_call", lambda *a, **kw: call(
        *a, **{**kw, "interpret": True}))
    patch.setattr(latent_attention, "flash_latent", wide)
    return patch


@pytest.fixture(scope="module")
def layer_both_ways():
    """{form: (output, {leaf: gradient}, input's gradient)} of one latent
    layer on seeded weights: ``copies`` as the reference route takes it,
    ``in place`` through the kernels, interpreted."""
    layer = lm_layers.LatentAttention(D, lm_layers.LatentSpec(
        H, RANK, NOPE, ROPE, VD, True, 1e6), 1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, S, D))
    w = jax.random.normal(jax.random.PRNGKey(1), (2, S, D))
    params = jax.tree.map(lambda p: 3.0 * p, layer.init(
        jax.random.PRNGKey(2), x)["params"])

    def run():
        out = layer.apply({"params": params}, x)
        dp, dx = jax.grad(lambda p, x: jnp.sum(layer.apply(
            {"params": p}, x).astype(jnp.float32) * w), argnums=(0, 1))(
                params, x)
        return out, {"/".join(str(k.key) for k in path
                              if hasattr(k, "key")): g for path, g in
                     jax.tree_util.tree_flatten_with_path(dp)[0]}, dx

    got = {"copies": run()}
    patch = _on_the_kernels()
    try:
        got["in place"] = run()
    finally:
        patch.undo()
    return got


@pytest.mark.parametrize("what", ["out", "x", "q/kernel", "kv_a/kernel",
                                  "kv_a_norm/scale", "kv_b/kernel",
                                  "out/kernel"])
def test_the_kernels_read_in_place_what_the_reference_form_copies(
        layer_both_ways, what):
    """The layer's output, its input's gradient and all five parameters':
    bfloat16 against bfloat16, one rounding of q either way (readings
    0.008-0.032)."""
    def pick(form):
        out, grads, dx = layer_both_ways[form]
        return np.asarray({"out": out, "x": dx, **grads}[what], np.float32)

    a, b = pick("in place"), pick("copies")
    assert a.shape == b.shape and np.linalg.norm(b) > 0
    assert close(a, b, 0.08), np.linalg.norm(a - b) / np.linalg.norm(b)


def test_the_rule_for_the_hand_over():
    from jax.sharding import Mesh

    from metaopt_tpu.ops.latent_attention import hand_over

    one = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
    two = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    assert hand_over("pallas", None, 128, 128) == "in place"
    assert hand_over("pallas", one, 128, 128) == "in place"
    assert hand_over("pallas", two, 128, 128) == "copies"
    assert hand_over("pallas", one, 128, 64) == "copies"
    for route in ("reference", "chunked", "ring"):
        assert hand_over(route, one, 128, 128) == "copies"


LOWERED_S = 96   # a q-sized array then holds more than any weight or stream


@pytest.fixture(scope="module")
def lowered_steps():
    """{form: [(operation, [(shape, dtype) of its results], name)]} of a
    rematerialised three-layer model's gradient, lowered for the reference
    route (``copies``) and for the Pallas route, interpreted (``in
    place``)."""
    import re

    tokens = jnp.zeros((2, LOWERED_S + 1), jnp.int32)
    model = lm.make_lm({**description(), "remat": True})
    keeps = lm_remat.remat_keeps(model.pattern)["keeps"] + [
        n for n in lm_layers.LatentSpec.KEPT.values()
        if n != "attention.kv_up"]
    model = model.clone(keeps=tuple(keeps))
    trained, frozen = lm.split_frozen(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), tokens[:, :-1], train=False)["params"]))

    def lowered():
        text = jax.jit(jax.grad(lambda p, fr, t: lm.lm_loss_fn(
            model, lm.merge_frozen(p, fr), t, jax.random.PRNGKey(0)))).lower(
                trained, frozen, tokens).as_text(debug_info=True)
        names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]+)"', text, re.M))
        ops = []
        for line in text.splitlines():
            m = re.search(r'= "?(stablehlo\.\w+|call @\w+?)(?:_\d+)?"?[ (].*'
                          r' loc\((#loc\d+)\)$', line)
            if m and m.group(2) in names:
                results = line.rsplit("->", 1)[-1] if "->" in line \
                    else line.rsplit(" : ", 1)[-1]
                ops.append((m.group(1), [
                    (tuple(int(d) for d in dims.split("x") if d), dtype)
                    for dims, dtype in re.findall(
                        r"tensor<((?:\d+x)*)(\w+)>", results)],
                    names[m.group(2)]))
        return ops

    got = {"copies": lowered()}
    patch = _on_the_kernels()
    try:
        got["in place"] = lowered()
    finally:
        patch.undo()
    return got


def _glue(ops, direction):
    """What stands between a latent layer's matmuls and the kernels, of
    the operations named under ``attention`` and outside ``attention.core``
    in one direction: joins nope + rope wide, float32 arrays as large as
    q, and transposes that move the last axis of an array as large as a
    head's keys or values."""
    import re

    core = re.compile(r"(?:^|[/(])attention\.core(?:$|[/)])")
    q_sized = 2 * LOWERED_S * H * (NOPE + ROPE)
    kv_sized = 2 * LOWERED_S * H * min(NOPE, VD)
    found = []
    for op, results, name in ops:
        if trace.layer_of(name) != "attention" or core.search(name) \
                or trace.direction(name) != direction:
            continue
        for shape, dtype in results:
            size = int(np.prod(shape))
            if op == "stablehlo.concatenate" and size >= kv_sized and (
                    NOPE + ROPE in shape or H * (NOPE + ROPE) in shape):
                found.append(("join", shape, name))
            if dtype == "f32" and size >= q_sized:
                found.append(("float32", shape, name))
            if op == "stablehlo.transpose" and size >= kv_sized \
                    and name.endswith("/transpose") and shape[-1] not in (
                        LOWERED_S,):
                found.append(("transpose", shape, name))
    return found


@pytest.mark.parametrize("direction", ["forward", "forward.again",
                                       "backward"])
def test_nothing_stands_between_the_matmuls_and_the_kernels(lowered_steps,
                                                            direction):
    """In place: no join of width nope + rope, no float32 array of q's
    full width and no transposed copy of a q-, K- or V-sized array under
    ``attention`` outside the core, in any direction (the sequence is every
    such array's last axis and stays it). The reference form has them
    (backward a join is slices): the check can see them."""
    assert _glue(lowered_steps["in place"], direction) == []
    kinds = {kind for kind, _, _ in _glue(lowered_steps["copies"], direction)}
    assert kinds >= ({"float32"} if direction == "backward"
                     else {"join", "float32"}), kinds


def test_the_kernels_are_called_once_a_layer_under_their_names(lowered_steps):
    """How often the mechanism engages, off the chip: the jitted entries
    that hold ``flash_fwd`` and ``flash_bwd`` are called under
    ``attention.core``, once a layer each, the forward one never in a
    block's second run; q's pass once a layer in each direction; the
    reference form calls none."""
    import collections

    calls = collections.Counter(
        (op.removeprefix("call @"), trace.direction(name),
         "attention.core" in name)
        for op, _, name in lowered_steps["in place"]
        if op in ("call @_forward", "call @_backward", "call @_q_pass")
        and trace.layer_of(name) == "attention")
    assert calls == {("_forward", "forward", True): 3,
                     ("_backward", "backward", True): 3,
                     ("_q_pass", "forward", False): 3,
                     ("_q_pass", "forward.again", False): 3,
                     ("_q_pass", "backward", False): 3}
    names = {name for _, _, name in lowered_steps["in place"]}
    assert {"flash_fwd/pallas_call", "flash_bwd/pallas_call",
            "latent_q/pallas_call"} <= names
    assert not [op for op, _, name in lowered_steps["copies"]
                if op.startswith("call @_forward")]
