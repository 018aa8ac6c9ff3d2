"""The decoder whose attention runs over the keys an indexer selects
(models/lm.py, a description with ``sa_config``), as a trial: an indexer
that a step leaves to the bit, remat to the bit, the family's words, the
description that stood before still building the tree it built, the counts
and the spans. The selection rule and its kernels are
test_lm_selected_index.py's, the comparison with the plain reference
test_lm_selected_reference.py's; what the three share is
lm_selected_cases.py's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from lm_selected_cases import (D, E, F, IH, IK, KEYS, LEAVES, S, TOPK, V,
                               description, leaf)


# -- the frozen indexer ----------------------------------------------------------

INDEXER = ["q/kernel", "k/kernel", "w/kernel", "k_norm/scale", "k_norm/bias"]


@pytest.fixture(scope="module")
def stepped():
    """(parameters before, after one AdamW step, the optimizer's state) of
    a two-layer trial."""
    from lm_pattern_cases import one_device

    from metaopt_tpu.models.lm import LMTrial

    trial = LMTrial({**description(2), "lr": 1e-2, "warmup": 1},
                    mesh=one_device(), n_train=8, batch_size=2, seq_len=S,
                    steps=4, seed=2)
    before = jax.device_get(nn.meta.unbox(trial.params))
    with trial:
        trial.step(0)
        trial.step(1)
    return (before, jax.device_get(nn.meta.unbox(trial.params)),
            nn.meta.unbox(trial.opt_state), trial.read_counts())


@pytest.mark.parametrize("layer", ["h0", "h1"])
@pytest.mark.parametrize("path", INDEXER)
def test_a_step_leaves_an_indexer_leaf_to_the_bit(stepped, layer, path):
    before, after, _, _ = stepped
    path = f"{layer}/attn/indexer/{path}"
    np.testing.assert_array_equal(leaf(after, path), leaf(before, path))
    assert path.endswith("bias") or np.abs(leaf(before, path)).max() > 0


def test_a_step_moves_what_is_trained(stepped):
    before, after, _, _ = stepped
    for path in ("h0/attn/q/kernel", "h1/attn/k_norm/scale",
                 "h1/router/kernel", "h0/experts/gate"):
        assert np.abs(leaf(after, path) - leaf(before, path)).max() > 0, path


def test_adamw_holds_no_moment_for_an_indexer(stepped):
    _, after, opt_state, _ = stepped
    for moments in (opt_state[0].mu, opt_state[0].nu):
        assert "indexer" not in moments["h0"]["attn"]
        assert set(moments["h0"]["attn"]) == set(after["h0"]["attn"]) \
            - {"indexer"}
    held = sum(x.size for x in jax.tree.leaves(opt_state[0].mu))
    whole = sum(x.size for x in jax.tree.leaves(after))
    assert whole - held == 2 * (D * IH * IK + D * IK + D * IH + 2 * IK)


def test_the_trial_counts_the_selected_and_the_causal_pairs(stepped):
    counts = stepped[3]
    row = KEYS * (KEYS + 1) // 2 + (S - KEYS) * KEYS
    assert counts["selected_pairs"] == [2 * 2 * row] * 2   # steps x batch
    assert counts["causal_pairs"] == [2 * 2 * S * (S + 1) // 2] * 2
    assert counts["dropped"] == [0, 0]


def test_the_pairs_counts_carry_past_an_int32():
    """31 M pairs a step a layer at 16 384 tokens: a plain int32 sum would
    wrap after 68 steps."""
    from metaopt_tpu.models import lm

    total = {"selected_pairs": jnp.zeros((2, 2), jnp.int32),
             "items": jnp.zeros((2, 3), jnp.int32)}
    step = {"selected_pairs": jnp.asarray([31_500_000, 2 ** 31 - 1],
                                          jnp.int32),
            "items": jnp.ones((2, 3), jnp.int32)}
    for _ in range(200):
        total = lm._add_counts(total, step)
    hi, lo = np.asarray(total["selected_pairs"]).T.astype(object)
    assert ((hi << lm._LIMB) + lo).tolist() == [200 * 31_500_000,
                                                200 * (2 ** 31 - 1)]
    assert int(total["items"][0, 0]) == 200


# -- remat ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def with_and_without_remat():
    """(loss, gradients, parameters after a step) of a two-layer stack,
    rematerialised and not."""
    import optax

    from metaopt_tpu.models import lm, lm_layers, lm_remat

    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, S + 1), 2, V)
    out = {}
    for remat in (False, True):
        model = lm.make_lm(description(2, remat=remat))
        if remat:  # every name the rule can say: the projections' too
            model = model.clone(keeps=(
                *lm_remat.remat_keeps(model.pattern)["keeps"],
                *lm_layers.GroupedSpec.KEPT.values()))
        params = nn.meta.unbox(model.init(
            jax.random.PRNGKey(0), tokens[:, :-1], train=False)["params"])
        tx = optax.adamw(1e-2)
        step = jax.jit(lm.make_lm_train_step(model, tx))
        trained, frozen = lm.split_frozen(params)
        loss, grads = jax.value_and_grad(lambda p: lm.lm_loss_fn(
            model, lm.merge_frozen(p, frozen), tokens,
            jax.random.PRNGKey(0)))(trained)
        counts = {k: jnp.zeros((2,) + shape, jnp.int32) for k, shape in (
            ("items", (E,)), ("dropped", ()), ("chunks", ()),
            ("selected_pairs", (2,)), ("causal_pairs", (2,)))}
        after, *_ = step(params, tx.init(trained), counts, tokens,
                         jax.random.PRNGKey(0))
        out[remat] = (loss, grads, after)
    return out


@pytest.mark.parametrize("what", ["gradient", "update"])
@pytest.mark.parametrize("path", ["loss"] + [p for p in LEAVES
                                             if "/e" not in p] + [
    "h1/attn/q/kernel", "h1/experts/down", "h0/experts/gate",
    "h1/attn/k/kernel", "h1/attn/out/kernel", "h1/attn/k_norm/scale"])
def test_remat_changes_nothing_to_the_last_bit(with_and_without_remat, what,
                                               path):
    (loss, grads, after), (r_loss, r_grads, r_after) = (
        with_and_without_remat[False], with_and_without_remat[True])
    if path == "loss":
        assert np.isfinite(float(loss)) and float(loss) == float(r_loss)
        return
    got, want = ((leaf(r_grads, path), leaf(grads, path))
                 if what == "gradient"
                 else (leaf(r_after, path), leaf(after, path)))
    assert np.abs(want).max() > 0
    if (what, path) == ("gradient", "h0/router/kernel"):
        # the one float32 product at precision highest whose operand the
        # backward pass makes again: this CPU sums it in another order
        # inside the recomputed block (the update still rounds alike)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-9)
        return
    if (what, path) == ("gradient", "h0/norm_post/scale"):
        # a float32 sum over the tokens of what the expert layer and the
        # router hand the norm. The routing's ``_sum_to_tokens`` (``old +
        # rows * weight`` in float32) rounds otherwise compiled inside a
        # block than operation by operation on this CPU: ``_combine``'s
        # output differs in its last bit in a fifth of its numbers, with
        # PR 41's tree as with this one, and so does the cotangent that
        # reaches this norm (21-37 of its 2560 numbers). Whether the sum
        # over the tokens then rounds alike hangs on the values: it did
        # for these tokens until PR 43 rounded the experts' ``d_rows`` once
        # where it was rounded three times (PR 41's tree fails this leaf
        # with the tokens of key 6 or 7). The held experts' part itself is
        # the same to the bit compiled or not: test_lm_pattern_experts.py holds it.
        np.testing.assert_array_max_ulp(got, want, maxulp=1)
        return
    np.testing.assert_array_equal(got, want)


def test_a_rematerialised_block_keeps_the_selection(monkeypatch):
    """On the kernels' route the gradient of a rematerialised two-layer
    stack holds one ``sparse_fwd`` a layer and makes the selection once
    (two bit casts a block of rows: the scores' order, the packed words):
    the policy keeps ``out``, ``lse`` and the packed bits."""
    from lm_pattern_cases import _equations, _on_the_kernels

    from metaopt_tpu.models import lm

    _on_the_kernels(monkeypatch)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, S + 1), 2, V)
    counted = {}
    for how in ("kept", "bare"):
        if how == "bare":
            monkeypatch.setattr(lm, "rematerialised",
                                lambda cls, keeps: nn.remat(cls))
        model = lm.make_lm(description(2, remat=True))
        trained, frozen = lm.split_frozen(nn.meta.unbox(model.init(
            jax.random.PRNGKey(0), tokens[:, :-1], train=False)["params"]))
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: lm.lm_loss_fn(
            model, lm.merge_frozen(p, frozen), tokens,
            jax.random.PRNGKey(0))))(trained)
        eqs = list(_equations(jaxpr.jaxpr))
        names = [e.params["name"] for e in eqs
                 if e.primitive.name == "pallas_call"]
        counted[how] = (sum("sparse_fwd" in n for n in names),
                        sum("sparse_bwd" in n for n in names),
                        sum(e.primitive.name == "bitcast_convert_type"
                            for e in eqs))
    assert counted["kept"] == (2, 2, 4)
    assert counted["bare"] == (4, 2, 8)


# -- the description -------------------------------------------------------------

def test_the_family_s_words_make_the_selected_pattern():
    from metaopt_tpu.models.lm import make_lm

    model = make_lm(description(3, held=(4, 8), vocab_held=(16, 32)))
    p = model.pattern
    assert len(p.layers) == 3 and len(set(p.layers[i].mixer
                                          for i in range(3))) == 1
    attention, experts = p.layers[0].mixer, p.layers[0].ffn
    assert (attention.window, attention.theta) == (None, 1e7)
    assert p.kinds() == ["selected-rope"]
    assert (experts.n_experts, experts.top_k, experts.d_ff) == (E, TOPK, F)
    assert attention.qk_norm == "head" and experts.router_after_mixer
    assert experts.activation == "silu" \
        and attention.selection == (IH, IK, KEYS)
    assert experts.held == (4, 8) and p.vocab_held == (16, 32)


def test_an_indexer_of_several_key_heads_is_refused():
    from metaopt_tpu.models.lm import make_lm

    sa = dict(description()["sa_config"], indexer_num_kv_heads=2)
    with pytest.raises(ValueError, match="one key head"):
        make_lm(description(sa_config=sa))


#: SmallThinker's description as PR 26 to 29 built it: every parameter's
#: path and shape at the small sizes of test_lm_pattern.py. A checkpoint
#: written then restores into what the description builds now.
SMALLTHINKER_TREE = {
    "embed/embedding": (64, 32), "head/embedding": (64, 32),
    "norm_f/scale": (32,),
    **{f"h{i}/{path}": shape for i in range(2) for path, shape in {
        "attn/k/kernel": (32, 2, 16), "attn/out/kernel": (4, 16, 32),
        "attn/q/kernel": (32, 4, 16), "attn/v/kernel": (32, 2, 16),
        "experts/down": (16, 24, 32), "experts/gate": (16, 32, 24),
        "experts/up": (16, 32, 24), "norm_in/scale": (32,),
        "norm_post/scale": (32,), "router/kernel": (32, 16)}.items()}}


def test_the_description_that_stood_builds_the_tree_it_built():
    from lm_pattern_cases import description as smallthinker

    from metaopt_tpu.models import lm

    model = lm.make_lm(smallthinker([(0, 0), (1, 1)]))
    attention, experts = (model.pattern.layers[0].mixer,
                          model.pattern.layers[0].ffn)
    assert not attention.qk_norm and not experts.router_after_mixer
    assert experts.activation == "relu" and attention.selection is None
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(0), tokens,
                                      train=False)["params"])
    tree = {"/".join(str(k.key) for k in path): leaf.shape for path, leaf
            in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert tree == SMALLTHINKER_TREE
    trained, frozen = lm.split_frozen(params)
    assert frozen == {} and jax.tree.structure(trained) \
        == jax.tree.structure(params)


def test_a_checkpoint_of_the_description_that_stood_restores(tmp_path):
    """Saved as a trial saves (the whole tree), restored into what the
    description builds now."""
    from lm_pattern_cases import description as smallthinker, one_device

    from metaopt_tpu.models.lm import LMTrial, train_lm

    hp = smallthinker([(0, 0), (1, 1)], lr=1e-3)
    kw = dict(mesh=one_device(), n_train=8, batch_size=2, seq_len=24, seed=4)
    train_lm(hp, steps=2, save_dir=str(tmp_path), **kw)
    trial = LMTrial(hp, steps=2, restore_dir=str(tmp_path), **kw)
    with trial:
        assert np.isfinite(float(trial.step(2)))


# -- what the trace says ----------------------------------------------------------

def test_train_lm_says_which_layers_select_and_counts_their_pairs():
    from lm_pattern_cases import one_device

    from metaopt_tpu.models.lm import train_lm
    from metaopt_tpu.utils import trace

    loss = train_lm({**description(2, held=(0, 8)), "lr": 1e-3, "remat": True},
                    mesh=one_device(), n_train=8, batch_size=2, seq_len=S,
                    steps=3)
    assert np.isfinite(loss)
    setup = trace.spans("trial.setup")[-1]["attrs"]
    assert setup["attention_layers"] == {"selected-rope": {
        "route": "reference",
        "mask": f"selected: causal, top {KEYS} of the index scores, "
                f"{IH} index heads",
        "hand_over": "passes", "index_scores": {"route": "xla"}}}
    assert setup["remat"]["keeps"] == ["attention.out", "attention.lse",
                                       "attention.selected"]
    assert setup["moe"]["top_k"] == TOPK
    train = trace.spans("trial.train")[-1]["attrs"]
    row = KEYS * (KEYS + 1) // 2 + (S - KEYS) * KEYS
    assert train["selection"] == {
        "selected_pairs": [3 * 2 * row] * 2,
        "causal_pairs": [3 * 2 * S * (S + 1) // 2] * 2}
    assert train["moe"]["dropped"] == [0, 0]
    assert "selected_pairs" not in train["moe"]


@pytest.mark.parametrize("scope", ["attention.index", "attention.select",
                                   "attention.core", "moe.router"])
def test_the_selection_s_scopes_name_ops_of_the_train_step(scope):
    """The indexer's projections and scores, the top-k and the attention
    under it carry their scopes' names into the lowered step, forward and
    (the core, the router) backward."""
    import re

    from metaopt_tpu.models import lm

    model = lm.make_lm(description(1, remat=True))
    tokens = jnp.zeros((1, S + 1), jnp.int32)
    trained, frozen = lm.split_frozen(nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), tokens[:, :-1], train=False)["params"]))
    text = jax.jit(jax.grad(lambda p: lm.lm_loss_fn(
        model, lm.merge_frozen(p, frozen), tokens,
        jax.random.PRNGKey(0)))).lower(trained).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    at = re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:$|[/)])")
    assert [n for n in names if at.search(n)], scope


@pytest.mark.parametrize("said, words", [
    ({"route": "pallas", "tiles": [512, 512], "depth": 128},
     ", index scores by pallas, tiles 512 x 512, passes 128 deep"),
    ({"route": "xla"}, ", index scores by xla"),
    (None, "")], ids=["pallas", "xla", "a-trace-from-before"])
def test_the_reader_prints_a_line_a_layer_that_selects(capsys, said, words):
    from metaopt_tpu.utils import trace

    setup = {"name": "trial.setup", "trial": "T-2", "attrs": {
        "attention": {"dropout": 0.0, "train": "pallas", "eval": "pallas"},
        "attention_layers": {"selected-rope": {
            "route": "pallas",
            "mask": "selected: causal, top 2048 of the index scores, 16 "
                    "index heads",
            **({"index_scores": said} if said else {})}}}}
    train = {"name": "trial.train", "trial": "T-2", "attrs": {
        "steps": 1, "selection": {"selected_pairs": [31458304],
                                  "causal_pairs": [134225920]}}}
    trace.print_routes([setup, train])
    assert capsys.readouterr().out.splitlines() == [
        "trial T-2: attention pallas in training (dropout 0.0), pallas in "
        "evaluation",
        "trial T-2: selected-rope layers: pallas, mask by selected: causal, "
        "top 2048 of the index scores, 16 index heads" + words,
        "trial T-2: layer 0: attention over 31458304 selected of 134225920 "
        "causal pairs, 23.4 %"]


def test_the_benchmark_prints_this_family_s_description_too():
    import json
    import os
    import subprocess
    import sys

    from metaopt_tpu.models.lm import make_lm

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.sparse_lm_config", os.path.join(
            "chipbench", "configs", "keye-vl2-30b-a3b-ep8.json")],
        cwd=root, check=True, capture_output=True, text=True).stdout
    model = make_lm(json.loads(out))
    p = model.pattern
    assert model.n_layers == 4 and model.remat is True
    attention, experts = p.layers[0].mixer, p.layers[0].ffn
    assert (model.d_model, model.n_heads, attention.kv_heads,
            attention.head_dim) == (2048, 32, 4, 128)
    assert (experts.n_experts, experts.top_k, experts.d_ff) == (128, 8, 768)
    assert experts.held == (0, 16) and p.vocab_held == (0, 18992)
    assert attention.selection == (16, 64, 2048) and attention.theta == 1e7
    assert experts.activation == "silu" and p.kinds() == ["selected-rope"]
