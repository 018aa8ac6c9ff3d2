"""A decoder with linear-attention layers (the Olmo hybrid family's words).

The program's model against the plain reference of the benchmark
(chipbench/reference/hybrid_lm.py, whose linear layers are the recurrence
token by token) on seeded weights, two periods deep: the loss and every
gradient leaf, with the program's products in float32 (the mathematics,
to float32's rounding) and as the models run them (bfloat16, to its);
remat changes nothing and keeps what the rule says; the family's words
build the pattern and the descriptions that stood build the trees they
built; a checkpoint restores; the chip's share of heads adds up to the
uncut layer; the scopes name operations of the train step and the trace's
reader prints the linear kind's line.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

D, HEADS, HELD, HD = 64, 4, 2, 16
KD, VD, F, V, S = 8, 16, 96, 128, 70
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
#: a linear and a full layer: the least that has both kinds
PAIR = dict(layers=2, layer_types=["linear_attention", "full_attention"])


def description(layers=8, held=(0, HELD), **over):
    h = dict(hidden_size=D, intermediate_size=F, num_hidden_layers=layers,
             num_attention_heads=HEADS, num_key_value_heads=HEADS,
             hidden_act="silu", rms_norm_eps=1e-6, layer_types=PERIOD * 8,
             linear_num_key_heads=HEADS, linear_num_value_heads=HEADS,
             linear_key_head_dim=KD, linear_value_head_dim=VD,
             linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
             rope_parameters={"rope_theta": None}, vocab_size=V,
             vocab_held=[0, V], heads_held=list(held))
    h.update(over)
    return h


def reference_cfg(layers=8, heads=HELD):
    return {"d_model": D, "d_ff": F, "n_heads": heads, "head_dim": HD,
            "linear": [t == "linear_attention" for t in (PERIOD * 8)[:layers]],
            "linear_heads": heads, "key_dim": KD, "value_dim": VD, "conv": 4,
            "neg_eigval": True, "activation": "silu", "rms_eps": 1e-6,
            "vocab_held": [0, V]}


def leaf(tree, path):
    for part in path.split("/"):
        tree = tree[part]
    return np.asarray(tree, np.float32)


def paths(tree):
    return ["/".join(str(p.key) for p in path) for path, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


@pytest.fixture(scope="module")
def both_sides():
    """{"float32" | "bfloat16": (program's (loss, grads), reference's)} on
    seeded weights. "float32": two periods deep, the program with every
    bfloat16 of its own read as float32, so that only the mathematics can
    differ; "bfloat16": one period, as the models run it."""
    from chipbench import weights_hybrid_lm
    from chipbench.reference import hybrid_lm as reference
    from metaopt_tpu.models import lm

    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, S + 1), 2, V)
    out = {}
    for how, layers in (("bfloat16", 4), ("float32", 8)):
        cfg = reference_cfg(layers)
        weights = weights_hybrid_lm.make_weights(
            7, reference.param_shapes(cfg))
        ref = jax.jit(jax.value_and_grad(
            lambda p: reference.loss(p, tokens, cfg)))(weights)
        with pytest.MonkeyPatch.context() as patch:
            if how == "float32":
                patch.setattr(jnp, "bfloat16", jnp.float32)
            model = lm.make_lm(description(layers))
            out[how] = (jax.jit(jax.value_and_grad(lambda p: lm.lm_loss_fn(
                model, p, tokens, jax.random.PRNGKey(0))))(weights), ref)
    return out


LINEAR = ["q/kernel", "k/kernel", "v/kernel", "g/kernel", "a/kernel",
          "b/kernel", "conv_q", "conv_k", "conv_v", "A_log", "dt_bias",
          "norm/scale", "out/kernel"]
BLOCK = ["norm_mixer/scale", "norm_ffn/scale", "mlp/gate/kernel",
         "mlp/up/kernel", "mlp/down/kernel"]
FULL = ["q/kernel", "k/kernel", "v/kernel", "out/kernel", "q_norm/scale",
        "k_norm/scale"]
LEAVES = ["embed/embedding", "head/embedding", "norm_f/scale"] \
    + [f"h0/linear/{p}" for p in LINEAR] + [f"h0/{p}" for p in BLOCK] \
    + [f"h3/attn/{p}" for p in FULL] + [f"h3/{p}" for p in BLOCK] \
    + [f"h6/linear/{p}" for p in LINEAR] + [f"h7/attn/{p}" for p in FULL]


def test_both_sides_name_the_same_leaves(both_sides):
    (_, prog), (_, ref) = both_sides["float32"]
    assert sorted(paths(prog)) == sorted(paths(ref))
    assert set(LEAVES) <= set(paths(ref))
    # nn.Embed holds the two tables whatever looks their rows up
    assert {p for p in paths(prog) if p.endswith("embedding")} == {
        "embed/embedding", "head/embedding"}


@pytest.mark.parametrize("how, tol", [("float32", 1e-5), ("bfloat16", 3e-3)])
def test_loss_matches_the_plain_reference(both_sides, how, tol):
    (prog, _), (ref, _) = both_sides[how]
    assert abs(float(prog) - float(ref)) <= tol * abs(float(ref))


@pytest.mark.parametrize("path", LEAVES)
def test_every_gradient_leaf_matches_the_plain_reference(both_sides, path):
    """The chunked scan, the convolutions, norms and gates, the block's
    norm placement and the q/k norms over the projected width are the
    reference's mathematics: with float32 products a leaf's gradient
    differs from the token-by-token reference's by float32 rounding."""
    (_, prog), (_, ref) = both_sides["float32"]
    p, r = leaf(prog, path), leaf(ref, path)
    assert np.linalg.norm(r) > 0
    assert np.linalg.norm(p - r) <= 2e-3 * np.linalg.norm(r), path


def test_the_models_own_products_stay_near_it(both_sides):
    """bfloat16 products, one period, all leaves together: at these widths
    (8-wide keys) rounding is a seventh of the gradient; the benchmark
    reads it at the published ones."""
    (_, prog), (_, ref) = both_sides["bfloat16"]
    gap = np.sqrt(sum(np.sum((leaf(prog, p) - leaf(ref, p)) ** 2)
                      for p in paths(ref))
                  / sum(np.sum(leaf(ref, p) ** 2) for p in paths(ref)))
    assert gap <= 0.3


# -- remat ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def with_and_without_remat():
    import optax

    from metaopt_tpu.models import lm, lm_layers, lm_remat

    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, S + 1), 2, V)
    out = {}
    for remat in (False, True):
        model = lm.make_lm(description(**PAIR, remat=remat))
        if remat:  # every name the rule can say: the feed-forward's and
            # both kinds of mixer's projections too
            model = model.clone(keeps=tuple(
                lm_remat.remat_keeps(model.pattern)["keeps"]) + tuple(
                name for spec in (lm_layers.GatedSpec, lm_layers.GroupedSpec,
                                  lm_layers.LinearSpec)
                for name in spec.KEPT.values()))
        params = nn.meta.unbox(model.init(
            jax.random.PRNGKey(0), tokens[:, :-1], train=False)["params"])
        tx = optax.adamw(1e-2)
        loss, grads = jax.jit(jax.value_and_grad(lambda p: lm.lm_loss_fn(
            model, p, tokens, jax.random.PRNGKey(0))))(params)
        after, *_ = jax.jit(lm.make_lm_train_step(model, tx))(
            params, tx.init(params), {}, tokens, jax.random.PRNGKey(0))
        out[remat] = (loss, grads, after)
    return out


@pytest.mark.parametrize("what", ["gradient", "update"])
@pytest.mark.parametrize("path", ["loss", "embed/embedding", "h0/linear/A_log",
                                  "h0/linear/conv_k", "h0/linear/q/kernel",
                                  "h0/linear/b/kernel", "h1/attn/k/kernel",
                                  "h1/attn/q_norm/scale", "h1/mlp/up/kernel",
                                  "h0/linear/g/kernel", "h0/linear/a/kernel",
                                  "h0/linear/out/kernel", "h0/linear/norm/scale",
                                  "h1/attn/q/kernel", "h1/attn/out/kernel"])
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
    if what == "gradient" and path.split("/")[-2] in ("a", "b", "norm"):
        # float32 products at precision highest, and the gated norm's
        # float32 sum over the tokens, whose operand the backward pass
        # makes again: this CPU sums them in another order there (with
        # and without the projections' names kept)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-9)
        return
    np.testing.assert_array_equal(got, want)


def test_a_rematerialised_block_keeps_what_the_scan_made(monkeypatch):
    """On the kernels' route the gradient of a rematerialised period holds
    one ``linear_scan_fwd`` a linear layer and one ``flash_fwd`` for the
    full one; a bare ``nn.remat`` walks each forward a second time."""
    from lm_pattern_cases import _equations

    from metaopt_tpu.models import lm, lm_remat
    from metaopt_tpu.ops import linear_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert lm_remat.remat_keeps(lm.make_lm(description(4)).pattern)[
        "keeps"][-2:] == list(linear_attention.REMAT_KEEPS)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, S + 1), 2, V)
    counted = {}
    for how in ("kept", "bare"):
        if how == "bare":
            monkeypatch.setattr(lm, "rematerialised",
                                lambda cls, keeps: nn.remat(cls))
        model = lm.make_lm(description(4, remat=True))
        params = jax.eval_shape(lambda: nn.meta.unbox(model.init(
            jax.random.PRNGKey(0), tokens[:, :-1], train=False)["params"]))
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: lm.lm_loss_fn(
            model, p, tokens, jax.random.PRNGKey(0))))(params)
        names = [e.params["name"] for e in _equations(jaxpr.jaxpr)
                 if e.primitive.name == "pallas_call"]
        counted[how] = tuple(sum(k in n for n in names) for k in (
            "linear_scan_fwd", "linear_scan_bwd", "flash_fwd", "flash_bwd"))
    assert counted["kept"] == (3, 3, 1, 1)
    assert counted["bare"] == (6, 3, 2, 1)



# -- what a rematerialised block keeps of its feed-forward ----------------------

def _ffn_products(jaxpr):
    """The ``dot_general``s of a jaxpr one of whose sides is ``F`` wide:
    the feed-forward's (no other width of these sizes is 96)."""
    from lm_pattern_cases import _equations

    return sum(
        e.primitive.name == "dot_general" and any(
            F in v.aval.shape for v in (*e.invars, *e.outvars))
        for e in _equations(jaxpr.jaxpr))


@pytest.mark.parametrize("how, a_layer", [("kept", 9), ("down", 11),
                                          ("today", 12), ("bare", 12)])
def test_a_rematerialised_block_keeps_what_its_feed_forward_made(
        monkeypatch, how, a_layer):
    """The gradient of a rematerialised period holds nine products of the
    feed-forward's shapes a layer with the three names kept (three forward,
    six backward) and twelve under a bare ``nn.remat`` or today's list; with
    the down product's name alone the gate and up products run again."""
    from metaopt_tpu.models import lm, lm_layers, lm_remat

    model = lm.make_lm(description(4, remat=True))
    today = tuple(lm_remat.remat_keeps(model.pattern)["keeps"])
    if how == "bare":
        monkeypatch.setattr(lm, "rematerialised",
                            lambda cls, keeps: nn.remat(cls))
    else:
        model = model.clone(keeps=today + {
            "kept": tuple(lm_layers.GatedSpec.KEPT.values()),
            "down": (lm_layers.GatedSpec.KEPT["down"],), "today": ()}[how])
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, S + 1), 2, V)
    params = jax.eval_shape(lambda: nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), tokens[:, :-1], train=False)["params"]))
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: lm.lm_loss_fn(
        model, p, tokens, jax.random.PRNGKey(0))))(params)
    assert _ffn_products(jaxpr) == 4 * a_layer


def test_without_remat_the_feed_forward_s_names_are_identities(monkeypatch):
    """Not rematerialised, the gradient is the one without the names but
    for three ``name`` equations a layer and one a mixer's projection."""
    from lm_pattern_cases import _equations

    from metaopt_tpu.models import lm, lm_layers

    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, S + 1), 2, V)
    primitives = {}
    for how in ("named", "unnamed"):
        if how == "unnamed":
            monkeypatch.setattr(lm_layers, "checkpoint_name",
                                lambda x, name: x)
        model = lm.make_lm(description(**PAIR))
        params = jax.eval_shape(lambda: nn.meta.unbox(model.init(
            jax.random.PRNGKey(0), tokens[:, :-1], train=False)["params"]))
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: lm.lm_loss_fn(
            model, p, tokens, jax.random.PRNGKey(0))))(params)
        primitives[how] = [e.primitive.name
                           for e in _equations(jaxpr.jaxpr)]
    named, unnamed = primitives["named"], primitives["unnamed"]
    assert "checkpoint" not in named
    # the gating stands apart from the matmuls around it, forward and (the
    # barrier's transpose) backward, named or not
    # (and so does the head's bfloat16 input from its two matmuls)
    assert named.count("optimization_barrier") == 2 * 2 + 2 \
        == unnamed.count("optimization_barrier")
    # the feed-forward's three a layer, a linear mixer's seven, a full
    # layer's four
    assert named.count("name") - unnamed.count("name") == 3 * 2 + 7 + 4
    assert [p for p in named if p != "name"] \
        == [p for p in unnamed if p != "name"]


#: the benchmark cell's sizes (chipbench/configs/olmo-hybrid-7b-tp2.json):
#: one row of 8192 tokens, 3840 x 11 008, 15 heads of each kind, 766.2 M
#: parameters on a device that states 15.75 GiB
LIMIT = int(15.75 * 2 ** 30)
CELL = dict(tokens=8192, d_model=3840, parameters=766_200_000,
            bytes_limit=LIMIT)
#: family -> (its cell's configuration, the chipbench module that turns it
#: into the program's description)
CELLS = {"smallthinker": ("smallthinker-21b-a3b-ep4", "lm_config"),
         "keye": ("keye-vl2-30b-a3b-ep8", "sparse_lm_config"),
         "hybrid": ("olmo-hybrid-7b-tp2", "hybrid_lm_config")}
FFN = ["ffn.down", "ffn.gate", "ffn.up"]
ATT_IN = ["attention.q_proj", "attention.k_proj", "attention.v_proj"]
ATT_OUT = ["attention.out_proj"]
LIN_IN = [f"linear_attention.{n}_proj" for n in "qkvgab"]
LIN_OUT = ["linear_attention.out_proj"]


@functools.lru_cache(maxsize=None)
def cell_model(family, rehearsal=False, **over):
    """A family's cell as the benchmark describes it to the program, at
    its own sizes or its rehearsal's, and what ``remat_on`` would tell the
    rule of it on one device that states ``LIMIT`` (traced once a case)."""
    import importlib
    import json
    import os

    from chipbench.run import rehearsal_sizes
    from metaopt_tpu.models import lm, lm_remat

    name, module = CELLS[family]
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "chipbench", "configs", name + ".json")) as f:
        config = json.load(f)
    if rehearsal:
        rehearsal_sizes(config)
    model = lm.make_lm({**importlib.import_module(
        "chipbench." + module).description(config), **over})
    a = config["script_args"]
    return model, dict(
        tokens=a["batch_size"] * a["seq_len"], d_model=model.d_model,
        parameters=sum(x.size for x in jax.tree.leaves(nn.meta.unbox(
            jax.eval_shape(lm_remat.param_init(model, (1, 8)),
                           jax.random.PRNGKey(0))))), bytes_limit=LIMIT)


def contracting_width(name, p, sizes):
    """FLOPs of a kept product over the bytes of its output."""
    mixers = {layer.mixer.kind.split("-")[0]: layer.mixer
              for layer in p.layers}
    full, linear = mixers.get("global", mixers.get("selected")), \
        mixers.get("linear")
    return {"ffn.down": getattr(p.layers[0].ffn, "d_ff", None),
            "attention.out_proj": full.heads * full.head_dim,
            "linear_attention.out_proj": linear and linear.heads
            * linear.value_dim}.get(name, sizes["d_model"])


@pytest.mark.parametrize("family, layers, over, kept, room", [
    ("hybrid", 4, {}, FFN + ATT_IN + LIN_IN + ATT_OUT, 2_326_116_864),
    # a row twice as long: the gate and up products (2.89 GB) do not fit,
    # every other name (2.05 GB) does
    ("hybrid", 4, {"tokens": 16384},
     FFN[:1] + ATT_IN + LIN_IN + LIN_OUT + ATT_OUT, 2_326_116_864),
    # so long that the down products alone pass the room: only the full
    # layer's q, k, v (1.51 GB) are small enough
    ("hybrid", 4, {"tokens": 16 * 8192}, ATT_IN, 2_326_116_864),
    # the published depth on one device: the state alone passes the limit
    ("hybrid", 32, {"parameters": 6_129_600_000}, [], 0),
    # 8 layers whose state leaves 4.6 GB: 3.39 GB of the feed-forward's
    # products against 2.3; the mixers' 1.55 GB fit beside the down ones
    ("hybrid", 8, {}, FFN[:1] + ATT_IN + LIN_IN + LIN_OUT + ATT_OUT,
     2_326_116_864),
    # a backend that states no limit
    ("hybrid", 4, {"bytes_limit": None}, [], None),
    # the two MoE cells: every name fits (0.47 and 0.94 GB), the output
    # projection's first (28 and 32 heads x 128 against 2560 and 2048)
    ("smallthinker", 4, None, ATT_OUT + ATT_IN, 3_203_477_504),
    ("smallthinker", 4, {"bytes_limit": None}, [], None),
    ("keye", 4, None, ATT_OUT + ATT_IN, 4_732_588_032),
    ("keye", 4, {"bytes_limit": None}, [], None)])
def test_the_rule_keeps_the_feed_forward_s_products_where_they_fit(
        family, layers, over, kept, room):
    """At the cells' sizes, candidates in order of contracting width, each
    held against what the ones before it left of the room."""
    from metaopt_tpu.models import lm_remat
    from metaopt_tpu.ops import linear_attention
    from metaopt_tpu.ops.attention import REMAT_KEEPS

    model, sizes = cell_model(family, num_hidden_layers=layers)
    p = model.pattern
    if family == "hybrid":      # the counts these cases were written with
        assert layers != 4 or sizes == {**CELL, "parameters": 766_241_946}
        sizes = {**CELL, **over}
    else:
        sizes = {**sizes, **(over or {})}
    said = lm_remat.remat_keeps(p, **sizes)
    today = list(REMAT_KEEPS + (linear_attention.REMAT_KEEPS
                                if family == "hybrid" else ()))
    assert said["keeps"] == today + kept
    assert said["room"] == room
    t = sizes["tokens"]
    candidates = (FFN + ATT_IN + ATT_OUT + LIN_IN + LIN_OUT
                  if family == "hybrid" else ATT_IN + ATT_OUT)
    assert list(said["bytes"]) == candidates
    widths = [contracting_width(n, p, sizes) for n in kept]
    assert widths == sorted(widths, reverse=True)
    if family == "hybrid":
        full = layers // 4
        assert {n: said["bytes"][n] for n in FFN + ATT_OUT + LIN_OUT
                + LIN_IN[3:5]} == {
            "ffn.down": layers * 2 * t * 3840,
            "ffn.gate": layers * 2 * t * 11008,
            "ffn.up": layers * 2 * t * 11008,
            "attention.out_proj": full * 2 * t * 3840,
            "linear_attention.out_proj": 3 * full * 2 * t * 3840,
            "linear_attention.g_proj": 3 * full * 2 * t * 15 * 192,
            "linear_attention.a_proj": 3 * full * 4 * t * 15}
    else:
        heads = {"smallthinker": 28, "keye": 32}[family]
        assert said["bytes"] == {
            "attention.q_proj": 4 * 2 * t * heads * 128,
            "attention.k_proj": 4 * 2 * t * 4 * 128,
            "attention.v_proj": 4 * 2 * t * 4 * 128,
            "attention.out_proj": 4 * 2 * t * sizes["d_model"]}
    standing = sum(said["bytes"][n] for n in kept)
    if room is not None:
        assert standing <= room
        declined = set(candidates) - set(kept)      # they did not fit
        assert not declined or sum(
            said["bytes"][n] for n in declined) > room - standing
    if (family, layers, over) == ("hybrid", 4, {}):
        # 4 x 423.6 MB of the feed-forward, 0.52 GB of the four mixers'
        # input projections, the full layer's output projection
        assert standing == 1_694_498_816 + 521_994_240 + 62_914_560
    if over is None:
        assert standing == {"smallthinker": 469_762_048,
                            "keye": 939_524_096}[family]


@pytest.mark.parametrize("limit", [LIMIT, None])
@pytest.mark.parametrize("family", list(CELLS))
def test_at_a_rehearsal_s_sizes_every_name_fits_or_none_is_kept(family,
                                                                limit):
    from metaopt_tpu.models import lm_remat

    model, sizes = cell_model(family, rehearsal=True)
    said = lm_remat.remat_keeps(model.pattern,
                                **{**sizes, "bytes_limit": limit})
    new = [n for n in said["keeps"] if n in said["bytes"]]
    assert sorted(new) == (sorted(said["bytes"]) if limit else [])
    assert len(said["bytes"]) == (14 if family == "hybrid" else 4)
    widths = [contracting_width(n, model.pattern, sizes) for n in new]
    assert widths == sorted(widths, reverse=True)
    assert said["room"] == (limit and (limit - 16 * sizes["parameters"]) // 2)


@pytest.mark.parametrize("family, limit, kept", [
    ("hybrid", None, ()), ("hybrid", 2 ** 20, ()),
    ("hybrid", 2 ** 34, tuple(FFN + ATT_IN + LIN_IN + ATT_OUT + LIN_OUT)),
    ("smallthinker", None, ()),
    ("smallthinker", 2 ** 34, tuple(ATT_OUT + ATT_IN)),
    ("keye", None, ()), ("keye", 2 ** 34, tuple(ATT_OUT + ATT_IN))])
def test_the_model_and_the_span_are_told_the_same_once(monkeypatch, family,
                                                       limit, kept):
    """``LMTrial`` asks the rule once, inside ``trial.setup`` (the mesh
    exists there), and hands the one answer to the model's blocks and to
    the span; a device's limit is pinned in ``device_bytes_limit``'s
    place. The model is traced once: the rule's count and the init share
    the trace of one ``param_init``."""
    import lm_pattern_cases
    import lm_selected_cases

    from metaopt_tpu.models import lm, lm_remat
    from metaopt_tpu.utils import trace

    asked, traced = [], []
    real, real_init = lm_remat.remat_keeps, lm.DecoderOnlyLM.init

    def counting(p, **sizes):
        if sizes:  # not the bare model's own say of its pattern alone
            asked.append(sizes)
        return real(p, **sizes)

    monkeypatch.setattr(lm_remat, "remat_keeps", counting)
    monkeypatch.setattr(lm.DecoderOnlyLM, "init", lambda *a, **kw: (
        traced.append(a) or real_init(*a, **kw)))
    monkeypatch.setattr(lm_remat, "device_bytes_limit", lambda mesh: limit)
    described = {"hybrid": lambda: description(**PAIR),
                 "smallthinker": lambda: lm_pattern_cases.description(
                     [(0, 0), (1, 1)]),
                 "keye": lambda: lm_selected_cases.description(2)}[family]()
    trial = lm.LMTrial({**described, "remat": True},
                       mesh=lm_pattern_cases.one_device(), n_train=4,
                       batch_size=2, seq_len=S)
    said = trace.spans("trial.setup")[-1]["attrs"]["remat"]
    assert tuple(said["keeps"]) == trial.model.keeps
    assert trial.model.keeps[-len(kept):] == kept if kept \
        else not set(trial.model.keeps) & set(said["bytes"])
    assert set(kept) <= set(said["bytes"])
    assert said["blocks"] == 2
    assert said["room"] == (None if limit is None else max(
        0, limit - 16 * asked[0]["parameters"]) // 2)
    params = jax.tree.leaves(nn.meta.unbox(trial.params))
    model = trial.model
    assert asked == [dict(
        tokens=2 * S, d_model=model.d_model,
        parameters=sum(x.size for x in params), bytes_limit=limit)]
    assert len(traced) == 1


def test_the_init_given_the_rule_s_function_places_the_same_trees():
    """``init_sharded_lm`` given the ``param_init`` whose shapes the rule
    counted places the same trees as one that makes its own."""
    import optax
    from lm_pattern_cases import one_device

    from metaopt_tpu.models import lm, lm_remat
    from metaopt_tpu.parallel.mesh import use_mesh

    model, tx, mesh = lm.make_lm(description(**PAIR)), optax.adamw(1e-3), \
        one_device()
    init_params = lm_remat.param_init(model, (2, S))
    assert lm_remat.remat_on(model, mesh, (2, S), init_params) \
        == lm_remat.remat_on(model, mesh, (2, S))
    with use_mesh(mesh):
        own = lm.init_sharded_lm(model, mesh, tx, (2, S), 3)
        given = lm.init_sharded_lm(model, mesh, tx, (2, S), 3, init_params)
    assert jax.tree.structure(own) == jax.tree.structure(given)
    for a, b in zip(jax.tree.leaves(own), jax.tree.leaves(given)):
        if isinstance(a, jax.Array):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            assert a == b


def test_a_mesh_s_axes_divide_what_a_device_holds():
    """Two devices over ``tp`` hold half of the feed-forward's width, of
    every kind of head and of the partitioned kernels; two over ``dp`` half
    of the step's tokens."""
    from jax.sharding import Mesh

    from metaopt_tpu.models import lm, lm_remat

    model = lm.make_lm(description(**PAIR, remat=True))
    said = {}
    for shape in ((1, 1), (2, 1), (1, 2)):
        mesh = Mesh(np.array(jax.devices()[:shape[0] * shape[1]]).reshape(
            shape), ("dp", "tp"))
        said[shape] = lm_remat.remat_on(model, mesh, (2, S))["bytes"]
    t = 2 * S
    assert said[1, 1] == {                      # a linear and a full layer
        "ffn.down": 2 * 2 * t * D, "ffn.gate": 2 * 2 * t * F,
        "ffn.up": 2 * 2 * t * F,
        **{n: 2 * t * HELD * HD for n in ATT_IN}, "attention.out_proj": 2 * t * D,
        **{n: 2 * t * HELD * w for n, w in zip(LIN_IN, (KD, KD, VD, VD))},
        **{n: 4 * t * HELD for n in LIN_IN[4:]},
        "linear_attention.out_proj": 2 * t * D}
    assert said[2, 1] == {k: v // 2 for k, v in said[1, 1].items()}
    whole = FFN[:1] + ATT_OUT + LIN_OUT         # as wide as the stream
    assert said[1, 2] == {k: v if k in whole else v // 2
                          for k, v in said[1, 1].items()}


# -- the description -------------------------------------------------------------

def test_the_family_s_words_make_the_pattern():
    from metaopt_tpu.models import lm, lm_layers

    model = lm.make_lm(description(8))
    p = model.pattern
    assert [layer.number for layer in p.layers] == list(range(8))
    assert [p.kind(i) for i in range(8)] == [
        "linear", "linear", "linear", "global-nope"] * 2
    assert p.kinds() == ["linear", "global-nope"]
    assert p.layers[2].mixer == lm_layers.LinearSpec(
        heads=HELD, of=HEADS, key_dim=KD, value_dim=VD, conv=4,
        neg_eigval=True)
    # no window, no positions, q/k norms over the projected width
    assert p.layers[3].mixer == lm_layers.GroupedSpec(
        heads=HELD, kv_heads=HELD, head_dim=HD, window=None, theta=None,
        qk_norm="whole", selection=None)
    assert (model.n_heads, p.heads_held) == (HEADS, (0, HELD))
    assert p.norm == "rms on the branches" and not p.tied
    assert {layer.ffn for layer in p.layers} == {
        lm_layers.GatedSpec(F, "silu")} and model.d_ff == F
    params = jax.eval_shape(lambda: nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        train=False)["params"]))
    tree = {k: v.shape for k, v in zip(paths(params),
                                       jax.tree.leaves(params))}
    assert tree["h0/linear/q/kernel"] == (D, HELD, KD)
    assert tree["h0/linear/conv_v"] == (4, HELD, VD)
    assert tree["h0/linear/out/kernel"] == (HELD, VD, D)
    assert tree["h3/attn/k/kernel"] == (D, HELD, HD)
    assert tree["h3/attn/q_norm/scale"] == (HELD * HD,)
    assert tree["h3/mlp/gate/kernel"] == (D, F)
    assert "h3/linear/q/kernel" not in tree and "h0/attn/q/kernel" not in tree


def test_whole_heads_are_the_default():
    from metaopt_tpu.models import lm

    h = description(4)
    del h["heads_held"]
    p = lm.make_lm(h).pattern
    linear, full = p.layers[0].mixer, p.layers[3].mixer
    assert p.heads_held is None and full.heads == full.kv_heads == HEADS
    assert linear.heads == linear.of == HEADS


@pytest.mark.parametrize("sliding, rotary, selected, linear, name", [
    (False, False, False, True, "linear"), (True, True, True, True, "linear"),
    (False, True, True, False, "selected-rope"),
    (True, True, False, False, "window-rope"),
    (False, False, False, False, "global-nope")])
def test_a_layer_s_kind_comes_from_one_list(sliding, rotary, selected,
                                            linear, name):
    from metaopt_tpu.models import lm_layers

    spec = lm_layers.LinearSpec(2, 2, 8, 8, 4, True) if linear \
        else lm_layers.GroupedSpec(
            4, 2, 8, 16 if sliding else None, 1e4 if rotary else None, None,
            (2, 4, 8) if selected else None)
    assert spec.kind == name
    # the kinds that say a route and a mask say their positions too
    assert spec.attends == ("-" in name)


def test_a_layer_type_it_does_not_know_is_refused_by_name():
    from metaopt_tpu.models import lm

    with pytest.raises(ValueError, match="mamba"):
        lm.make_lm(description(4, layer_types=["linear_attention", "mamba",
                                               "full_attention"] * 2))


def test_a_layer_types_list_shorter_than_the_model_is_refused():
    from metaopt_tpu.models import lm

    with pytest.raises(ValueError, match="the model has 4"):
        lm.make_lm(description(4, layer_types=PERIOD[:3]))


def test_unequal_key_and_value_heads_are_refused():
    from metaopt_tpu.models import lm

    with pytest.raises(ValueError, match="as many key heads"):
        lm.make_lm(description(4, linear_num_key_heads=2))


@pytest.mark.parametrize("activation", ["relu", "silu"])
def test_the_feed_forward_s_activation_is_the_named_one(activation):
    from metaopt_tpu.models import lm_layers

    ffn = lm_layers.GatedFeedForward(8, 16, activation)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 8))
    params = nn.meta.unbox(ffn.init(jax.random.PRNGKey(1), x))
    k = {n: params["params"][n]["kernel"].astype(jnp.bfloat16)
         for n in ("gate", "up", "down")}
    xb = x.astype(jnp.bfloat16)
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[activation]
    want = (act(xb @ k["gate"]) * (xb @ k["up"])) @ k["down"]
    np.testing.assert_allclose(np.asarray(ffn.apply(params, x), np.float32),
                               np.asarray(want, np.float32), rtol=2e-2,
                               atol=1e-3)
    assert lm_layers.GatedFeedForward(8, 16).activation == "relu"


@pytest.mark.parametrize("family", ["smallthinker", "keye"])
def test_a_description_that_stood_builds_the_pattern_it_built(family):
    """None of this family's fields is set by another family's words."""
    import lm_pattern_cases
    import lm_selected_cases
    import test_lm_selected

    from metaopt_tpu.models import lm, lm_layers, lm_remat

    h = lm_pattern_cases.description([(0, 0), (1, 1)]) \
        if family == "smallthinker" else lm_selected_cases.description(2)
    p = lm.make_lm(h).pattern
    assert all(isinstance(layer.mixer, lm_layers.GroupedSpec)
               and layer.mixer.qk_norm != "whole" for layer in p.layers)
    assert p.norm == "rms" and p.heads_held is None
    assert p.kinds() == (["global-nope", "window-rope"]
                         if family == "smallthinker" else ["selected-rope"])
    said = lm_remat.remat_keeps(p, tokens=8192, d_model=64,
                                parameters=10 ** 6, bytes_limit=2 ** 34)
    assert said["keeps"] == ["attention.out", "attention.lse",
                             "attention.selected"] + ATT_IN + ATT_OUT
    assert list(said["bytes"]) == ATT_IN + ATT_OUT
    model = lm.make_lm(h)
    params = jax.eval_shape(lambda: nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        train=False)["params"]))
    names = set(paths(params))
    assert {"h0/norm_in/scale", "h0/norm_post/scale",
            "h1/attn/out/kernel"} <= names
    assert not any("linear" in n or "norm_mixer" in n or "mlp" in n
                   for n in names)
    if family == "smallthinker":
        tree = {k: v.shape for k, v in zip(paths(params),
                                           jax.tree.leaves(params))}
        assert tree == test_lm_selected.SMALLTHINKER_TREE


def test_a_checkpoint_restores(tmp_path):
    from lm_pattern_cases import one_device

    from metaopt_tpu.models.lm import LMTrial, train_lm

    hp = {**description(**PAIR), "lr": 1e-3}
    kw = dict(mesh=one_device(), n_train=8, batch_size=2, seq_len=24, seed=4)
    train_lm(hp, steps=2, save_dir=str(tmp_path), **kw)
    trial = LMTrial(hp, steps=2, restore_dir=str(tmp_path), **kw)
    with trial:
        assert np.isfinite(float(trial.step(2)))


# -- the share adds up ------------------------------------------------------------

@pytest.fixture(scope="module")
def uncut():
    """An uncut period's weights (all HEADS heads), an input, and the
    reference's own functions."""
    from chipbench import weights_hybrid_lm
    from chipbench.reference import hybrid_lm as reference

    cfg = reference_cfg(4, heads=HEADS)
    weights = weights_hybrid_lm.make_weights(3, reference.param_shapes(cfg))
    x = jax.random.normal(jax.random.PRNGKey(2), (S, D))
    return cfg, weights, x, reference


def _heads(tree, first, count, axes):
    """The sub-tree of ``count`` heads from ``first`` on, ``axes`` naming
    each leaf's head axis (None: every chip holds the leaf whole)."""
    return {k: (_heads(v, first, count, axes[k]) if isinstance(v, dict)
                else v if axes[k] is None
                else jax.lax.slice_in_dim(v, first, first + count,
                                          axis=axes[k]))
            for k, v in tree.items()}


LINEAR_HEAD_AXIS = {
    **{n: {"kernel": 1} for n in ("q", "k", "v", "g", "a", "b")},
    "conv_q": 1, "conv_k": 1, "conv_v": 1, "A_log": 0, "dt_bias": 0,
    "norm": {"scale": None}, "out": {"kernel": 0}}


def test_two_head_shares_of_a_linear_layer_add_up_to_the_uncut_layer(uncut):
    """Convolutions, norms, gates, decays and states are a head's or a
    channel's own: the two chips' mixers (the program's, each told its
    heads alone) sum to the uncut reference's, and with the block's norm
    and the feed-forward counted ONCE give the uncut layer."""
    from chipbench.reference.lm import _rms
    from metaopt_tpu.models import lm_layers

    cfg, weights, x, reference = uncut
    p = weights["h0"]
    with jax.default_matmul_precision("highest"), \
            pytest.MonkeyPatch.context() as patch:
        patch.setattr(jnp, "bfloat16", jnp.float32)
        whole = reference._linear_mixer("float32", p["linear"], x, cfg)
        spec = lm_layers.LinearSpec(HELD, HEADS, KD, VD, 4, True)
        shares = [lm_layers.LinearAttention(D, spec, 1e-6).apply(
            {"params": _heads(p["linear"], first, HELD, LINEAR_HEAD_AXIS)},
            x[None])[0] for first in (0, HELD)]
        np.testing.assert_allclose(np.asarray(shares[0] + shares[1]),
                                   np.asarray(whole), rtol=1e-4, atol=1e-5)
        assert float(jnp.abs(shares[0]).max()) > 1e-2 \
            and float(jnp.abs(shares[0] - shares[1]).max()) > 1e-2
        x1 = x + _rms(shares[0] + shares[1], p["norm_mixer"]["scale"], 1e-6)
        once = lm_layers.GatedFeedForward(D, F, "silu").apply(
            {"params": p["mlp"]}, x1[None])[0]
        layer = x1 + _rms(once, p["norm_ffn"]["scale"], 1e-6)
        want = x + _rms(whole, p["norm_mixer"]["scale"], 1e-6)
        want = want + _rms(reference._ffn("float32", p["mlp"], want,
                                          jax.nn.silu),
                           p["norm_ffn"]["scale"], 1e-6)
        np.testing.assert_allclose(np.asarray(layer), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_two_head_shares_of_the_full_layer_add_up_after_the_norms(uncut):
    """Given the uncut layer's normed q and k (the one scalar a token that
    two chips would exchange), each chip's heads through the program's
    attention and its rows of the output projection sum to the uncut
    reference's mixer."""
    from chipbench.reference.lm import _rms
    from metaopt_tpu.ops.attention import CausalMask, attend

    cfg, weights, x, reference = uncut
    p = weights["h3"]["attn"]
    with jax.default_matmul_precision("highest"):
        whole = reference._full_mixer("float32", p, x, cfg)
        proj = lambda n: jnp.einsum("sd,dhk->shk", x, p[n]["kernel"])  # noqa
        normed = lambda y, n: _rms(  # noqa: E731
            y.reshape(S, -1), p[n]["scale"], 1e-6).reshape(S, HEADS, HD)
        q, k, v = normed(proj("q"), "q_norm"), normed(proj("k"), "k_norm"), \
            proj("v")
        total = 0.0
        for first in (0, HELD):
            mine = slice(first, first + HELD)
            out = attend((q[None, :, mine] / np.sqrt(HD)), k[None, :, mine],
                         v[None, :, mine], CausalMask(None))
            total = total + jnp.einsum("shk,hkd->sd", out[0],
                                       p["out"]["kernel"][mine])
        np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                                   rtol=1e-4, atol=1e-5)


# -- what the trace says ----------------------------------------------------------

def test_train_lm_says_which_layers_are_linear_and_what_a_block_keeps():
    from lm_pattern_cases import one_device

    from metaopt_tpu.models.lm import train_lm
    from metaopt_tpu.ops.linear_attention import CHUNK
    from metaopt_tpu.utils import trace

    loss = train_lm({**description(**PAIR), "lr": 1e-3, "remat": True},
                    mesh=one_device(), n_train=8, batch_size=2, seq_len=S,
                    steps=2)
    assert np.isfinite(loss)
    setup = trace.spans("trial.setup")[-1]["attrs"]
    assert setup["attention_layers"] == {
        "global-nope": {"route": "reference", "mask": "dense: causal",
                        "hand_over": "passes"},
        "linear": {"route": "xla", "chunk": CHUNK, "layers": [0],
                   "hand_over": "passes",
                   "heads": [HELD, HEADS], "key_dim": KD, "value_dim": VD,
                   "conv": 4}}
    # this backend states no limit: the products are sized and not kept
    sizes = setup["remat"].pop("bytes")
    assert setup["remat"] == {"blocks": 2, "keeps": [
        "attention.out", "attention.lse", "attention.selected",
        "linear_attention.out", "linear_attention.states"], "room": None}
    assert list(sizes) == FFN + ATT_IN + ATT_OUT + LIN_IN + LIN_OUT
    assert {n: sizes[n] for n in FFN} == {
        "ffn.down": 2 * 2 * 2 * S * D, "ffn.gate": 2 * 2 * 2 * S * F,
        "ffn.up": 2 * 2 * 2 * S * F}
    assert "moe" not in setup
    # the untied table and the route its gradient takes off the chip
    assert setup["embed"] == {"gradient": "take", "rows": V, "width": D,
                              "tokens": 2 * S, "tied": False}


@pytest.mark.parametrize("scope", ["linear_attention", "linear_attention.core",
                                   "ffn", "attention.core"])
def test_the_mixer_s_scopes_name_ops_of_the_train_step(scope):
    """Forward and backward: the names reach the lowered step."""
    import re

    from metaopt_tpu.models import lm

    model = lm.make_lm(description(**PAIR, remat=True))
    tokens = jnp.zeros((1, S + 1), jnp.int32)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), tokens[:, :-1], train=False)["params"])
    text = jax.jit(jax.grad(lambda p: lm.lm_loss_fn(
        model, p, tokens, jax.random.PRNGKey(0)))).lower(params).as_text(
            debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    at = re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:$|[/)])")
    found = [n for n in names if at.search(n)]
    assert found, scope
    assert any("transpose" in n for n in found), "no backward op under it"


@pytest.mark.parametrize("layers, said", [([0, 1, 2], "0-2"),
                                          ([0, 1, 2, 4, 5, 6], "0-2, 4-6"),
                                          ([5], "5")])
def test_the_reader_prints_the_linear_kind_s_line(capsys, layers, said):
    from metaopt_tpu.utils import trace

    setup = {"name": "trial.setup", "trial": "T-3", "attrs": {
        "attention": {"dropout": 0.0, "train": "pallas", "eval": "pallas"},
        "attention_layers": {
            "linear": {"route": "pallas", "chunk": 128, "layers": layers,
                       "heads": [15, 30], "key_dim": 96, "value_dim": 192,
                       "conv": 4},
            "global-nope": {"route": "pallas", "mask": "structure: causal"}},
        "remat": {"blocks": 4, "keeps": ["attention.out",
                                         "linear_attention.states"]}}}
    trace.print_routes([setup])
    assert capsys.readouterr().out.splitlines() == [
        "trial T-3: attention pallas in training (dropout 0.0), pallas in "
        "evaluation",
        f"trial T-3: linear layers {said}: gated delta rule, 15 of 30 heads, "
        "keys 96, values 192, convolutions of 4, chunks of 128 by pallas",
        "trial T-3: global-nope layers: pallas, mask by structure: causal",
        "trial T-3: remat: 4 blocks keep attention.out, "
        "linear_attention.states"]


def test_the_benchmark_prints_this_family_s_description_too():
    import json
    import os
    import subprocess
    import sys

    from metaopt_tpu.models import lm, lm_layers

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.hybrid_lm_config", os.path.join(
            "chipbench", "configs", "olmo-hybrid-7b-tp2.json")],
        cwd=root, check=True, capture_output=True, text=True).stdout
    model = lm.make_lm(json.loads(out))
    p = model.pattern
    assert model.n_layers == 4 and model.remat is True
    full = p.layers[3].mixer
    assert (model.d_model, model.d_ff, model.n_heads, full.head_dim) == (
        3840, 11008, 30, 128)
    assert p.heads_held == (0, 15) and full.kv_heads == 15
    assert p.vocab_held == (0, 12544)
    assert {layer.ffn for layer in p.layers} == {
        lm_layers.GatedSpec(11008, "silu")}
    assert p.layers[0].mixer == lm_layers.LinearSpec(
        heads=15, of=30, key_dim=96, value_dim=192, conv=4, neg_eigval=True)
    assert p.kinds() == ["linear", "global-nope"]
    assert [p.kind(i) for i in range(4)] == ["linear"] * 3 + ["global-nope"]
    assert full.theta is None
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        train=False)["params"])
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
        nn.meta.unbox(params))) == 766_241_946


# -- which steps the benchmark's output check follows --------------------------

class _Rows:
    """What ``judged_steps`` asks of a trial: ``rows(i)`` as ``LMTrial``
    slices them."""

    def __init__(self, tokens, batch_size=1):
        self.tokens, self.batch_size = np.asarray(tokens), batch_size
        self.n_train = len(self.tokens)

    def rows(self, i):
        lo = (i * self.batch_size) % (self.n_train - self.batch_size + 1)
        return self.tokens[lo:lo + self.batch_size]


def _walk(cycle, length=64):
    """A row that walks a cycle of ``cycle`` tokens, as ``synthetic_lm``'s
    rows walk their permutation's."""
    return 2 + np.arange(length) % cycle


@pytest.mark.parametrize("cycles, batch, steps, passed_over", [
    ([64, 64, 64, 64], 1, [0, 1, 2], []),
    ([64, 5, 64, 64], 1, [0, 2, 3], [1]),
    ([3, 64, 7, 1, 64, 64], 1, [1, 4, 5], [0, 2, 3]),
    ([8, 64, 64, 64], 1, [0, 1, 2], []),        # an eighth is enough
    ([7, 64, 64, 64], 1, [1, 2, 3], [0]),
    ([64, 64, 64, 2, 64, 64, 64, 64], 2, [0, 2, 3], [1]),  # a step's worst
])
def test_the_check_follows_the_steps_whose_rows_it_can_judge(
        capsys, cycles, batch, steps, passed_over):
    """A row on a short cycle of the data's permutation is the same few
    tokens over and over: its loss is a mean over that few predictions and
    bfloat16's rounding no longer averages out, so the benchmark's check
    follows the first steps whose rows hold an eighth of their length in
    distinct tokens and says which it passed over."""
    from chipbench.runners import hybrid_lm_trial_steps as runner

    trial = _Rows([_walk(c) for c in cycles], batch)
    assert runner.judged_steps(trial, 3, 0.125) == steps
    said = capsys.readouterr().out
    assert all(f"step {i} (" in said for i in passed_over)
    assert bool(said) == bool(passed_over)


def test_the_check_refuses_data_it_cannot_judge():
    from chipbench.runners import hybrid_lm_trial_steps as runner

    with pytest.raises(ValueError, match="distinct tokens"):
        runner.judged_steps(_Rows([_walk(3)] * 4 + [_walk(64)] * 2), 3,
                            0.125)


def test_the_cell_s_data_has_the_short_cycles_the_check_passes_over():
    """``synthetic_lm`` walks one permutation of the held vocabulary; the
    cell's (12 542 tokens) has cycles of 6582, 4319, 1201 and, below the
    traffic mix's eighth of a row (1024 tokens), of 385 and shorter: 440
    tokens, 3.5 % of the rows' starts."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "chipbench", "traffic",
                           "steady-hybrid-lm-8k.json")) as f:
        share = json.load(f)["check_row_distinct_share"]
    vocab = 12544
    perm = np.asarray(2 + jax.random.permutation(
        jax.random.PRNGKey(7), vocab - 2))     # synthetic_lm's teacher_seed
    seen, lengths = np.zeros(vocab, bool), []
    for start in range(2, vocab):
        n, tok = 0, start
        while not seen[tok]:
            seen[tok], n, tok = True, n + 1, perm[tok - 2]
        if n:
            lengths.append(n)
    assert sorted(lengths, reverse=True) == [
        6582, 4319, 1201, 385, 16, 14, 12, 9, 2, 1, 1]
    floor = share * 8193
    assert sum(n for n in lengths if n < floor) == 440
    assert min(n for n in lengths if n >= floor) == 1201
