"""A decoder-hybrid-decoder with state-space layers (the ``phi4flash``
family's words: Phi-4-mini-flash-reasoning's SambaY with differential
attention).

The program's model against the plain reference of the benchmark
(chipbench/reference/ssm_lm.py, whose scans are the recurrence token by
token) on seeded weights, at hidden 64, d_state 4, the published layers 0,
1, 4, 5, 6, 7 of N = 8 (every kind, Mamba twice) and a row of 96 tokens in
chunks of 32: each new mixer alone, the whole model's loss and every
gradient leaf with the program's products in float32 (the mathematics, to
float32's rounding) and as the models run them; what a layer hands on
reaches its readers and their gradients come back; remat changes nothing;
three ``LMTrial`` steps; the tied head over a held slice; the family's
words build the pattern; and the reference's shapes at the PUBLISHED sizes
count 3.85 B parameters in 9 / 8 / 1 / 7 / 7 layers, 697 M in the cell's
cut.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

D, H, HKV, W, F, V, S = 64, 4, 2, 16, 96, 128, 96
DI, N, R, WINDOW = 128, 4, 4, 16
OF, HELD = 8, [0, 1, 4, 5, 6, 7]
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def description(held=HELD, vocab_held=(0, V), **over):
    h = dict(model_type="phi4flash", hidden_size=D, intermediate_size=F,
             num_hidden_layers=OF, num_attention_heads=H,
             num_key_value_heads=HKV, hidden_act="silu", layer_norm_eps=1e-5,
             mb_per_layer=2, sliding_window=WINDOW, tie_word_embeddings=True,
             mamba_d_state=N, vocab_size=V,
             layers_held=held and list(held), vocab_held=list(vocab_held))
    h.update(over)
    return h


def reference_cfg(held=HELD, vocab_held=(0, V)):
    return {"d_model": D, "d_ff": F, "n_heads": H, "n_kv_heads": HKV,
            "head_dim": W, "window": WINDOW, "eps": 1e-5,
            "layers": list(held), "of": OF, "d_inner": DI, "d_state": N,
            "d_conv": 4, "dt_rank": R, "vocab_held": list(vocab_held)}


def leaf(tree, path):
    for part in path.split("/"):
        tree = tree[part]
    return np.asarray(tree, np.float32)


def paths(tree):
    return ["/".join(str(p.key) for p in path) for path, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape and np.linalg.norm(want) > 0
    return np.linalg.norm(got - want) <= tol * np.linalg.norm(want)


def seeded(cfg, seed=7):
    from chipbench import weights_ssm_lm
    from chipbench.reference import ssm_lm as reference

    return weights_ssm_lm.make_weights(seed, reference.param_shapes(cfg))


# -- each new mixer alone -------------------------------------------------------

def _mixers():
    """{mixer: (program's function, reference's function, its seeded
    parameters)}: each maps (parameters, u, and what the mixer reads of an
    earlier layer) to its output, for one row (the reference's) or a batch
    of one (the program's)."""
    from chipbench.reference import ssm_lm as reference
    from metaopt_tpu.models import lm_description, lm_layers

    cfg = reference_cfg()
    weights = seeded(cfg)
    spec = lm_description.pattern_of(lm_description._own_names(
        description())).layers[0].mixer
    row = lambda fn: (lambda p, u, *read: jax.tree.map(  # noqa: E731
        lambda y: y[0], fn(p, u[None], *jax.tree.map(
            lambda v: v[None], read))))
    attn = lambda layer, window: lm_layers.DifferentialAttention(  # noqa: E731
        D, lm_layers.DifferentialSpec(
            H, HKV, W, window, lm_description.lambda_init(layer), False),
        1e-5)
    apply = lambda module: row(  # noqa: E731
        lambda p, *args: module.apply({"params": p}, *args))
    return {
        "ssm": (apply(lm_layers.StateSpaceMixer(D, spec)),
                lambda p, u: reference._mamba("float32", p, u, cfg, ()),
                weights["h4"]["ssm"]),
        "gmu": (apply(lm_layers.GatedMemoryUnit(D, DI)),
                lambda p, u, m: reference._gmu("float32", p, u, m),
                weights["h6"]["gmu"]),
        "window": (apply(attn(1, WINDOW)),
                   lambda p, u: reference._attention(
                       "float32", p, u, 1, WINDOW, None, cfg, ()),
                   weights["h1"]["attn"]),
        "full": (apply(attn(5, None)),
                 lambda p, u: reference._attention(
                     "float32", p, u, 5, None, None, cfg, ()),
                 weights["h5"]["attn"]),
        "cross": (apply(attn(7, None)),
                  lambda p, u, kv: reference._attention(
                      "float32", p, u, 7, None, kv, cfg, ()),
                  weights["h7"]["attn"]),
    }


@pytest.fixture(scope="module")
def mixers_both_ways():
    """{mixer: {side: {"out" | "x" | "read" | leaf: array}}}: the output,
    the gradient of a weighted sum of it by the input, by what is read and
    by every leaf, the program's products in float32."""
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    u = jax.random.normal(k[0], (S, D))
    reads = {"gmu": (jax.random.normal(k[1], (S, DI)),),
             "cross": ((jax.random.normal(k[2], (S, HKV, W)),
                        jax.random.normal(k[3], (S, HKV, W))),)}
    out = {}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jnp, "bfloat16", jnp.float32)
        for name, (prog, ref, params) in _mixers().items():
            read = reads.get(name, ())
            out[name] = {}
            w = jax.random.normal(k[4], (S, D))
            for side, fn in (("program", prog), ("reference", ref)):
                def weighed(p, u, *r, fn=fn):
                    made = fn(p, u, *r)
                    made = made if isinstance(made, tuple) else (made, ())
                    return jnp.sum(made[0] * w), made

                (_, (y, handed)), grads = jax.jit(jax.value_and_grad(
                    weighed, argnums=tuple(range(2 + len(read))),
                    has_aux=True))(params, u, *read)
                said = {"out": y, "x": grads[1],
                        **{path: leaf(grads[0], path)
                           for path in paths(params)}}
                flat = lambda tree: jnp.concatenate(  # noqa: E731
                    [g.reshape(S, -1) for g in jax.tree.leaves(tree)], -1)
                if read:
                    said["read"] = flat(grads[2])
                if name in ("ssm", "full"):  # what the layer hands on
                    said["handed"] = flat(handed)
                out[name][side] = said
    return out


SSM = ["in_proj/kernel", "conv", "conv_bias", "x_proj/kernel",
       "dt_proj/kernel", "dt_proj/bias", "A_log", "D", "out_proj/kernel"]
ATTN = ["q/kernel", "q/bias", "k/kernel", "v/kernel", "v/bias",
        "out/kernel", "out/bias", "subln/scale", "lambda_q1", "lambda_k1",
        "lambda_q2", "lambda_k2"]
CROSS = [p for p in ATTN if p[0] not in "kv"]
GMU = ["in_proj/kernel", "out_proj/kernel"]
ALONE = [("ssm", w) for w in ["out", "x", "handed"] + SSM] \
    + [("gmu", w) for w in ["out", "x", "read"] + GMU] \
    + [("window", w) for w in ["out", "x"] + ATTN] \
    + [("full", w) for w in ["out", "x", "handed"] + ATTN] \
    + [("cross", w) for w in ["out", "x", "read"] + CROSS]


@pytest.mark.parametrize("mixer, what", ALONE)
def test_each_mixer_alone_is_the_references(mixers_both_ways, mixer, what):
    """Output, what it hands on, and the gradients of every leaf, of the
    input and of what it reads: float32 against float32."""
    sides = mixers_both_ways[mixer]
    assert close(sides["program"][what], sides["reference"][what], 2e-4), \
        (mixer, what)


def test_a_key_bias_moves_no_softmax(mixers_both_ways):
    """k's bias shifts a query's scores by one number: its gradient is
    rounding on both sides (the check's ``dead_leaves`` leaves it out)."""
    for side in ("program", "reference"):
        said = mixers_both_ways["full"][side]
        assert np.abs(said["k/bias"]).max() \
            < 1e-4 * np.abs(said["v/bias"]).max()


# -- the whole model ------------------------------------------------------------

@pytest.fixture(scope="module")
def both_sides():
    """{"float32" | "bfloat16" | "cut": (program's (loss, grads),
    reference's)} on seeded weights; "cut": the self-decoder alone, without
    the layers that read what layers 4 and 5 hand on."""
    from chipbench.reference import ssm_lm as reference
    from metaopt_tpu.models import lm

    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, S + 1), 2, V)
    out, refs = {}, {}
    for how, held in (("bfloat16", HELD), ("float32", HELD),
                      ("cut", HELD[:4])):
        cfg = reference_cfg(held)
        weights = seeded(cfg)
        if len(held) not in refs:   # one reference for both arithmetics
            refs[len(held)] = jax.jit(jax.value_and_grad(
                lambda p: reference.loss(p, tokens, cfg)))(weights)
        ref = refs[len(held)]
        with pytest.MonkeyPatch.context() as patch:
            if how != "bfloat16":
                patch.setattr(jnp, "bfloat16", jnp.float32)
            model = lm.make_lm(description(held))
            out[how] = (jax.jit(jax.value_and_grad(lambda p: lm.lm_loss_fn(
                model, p, tokens, jax.random.PRNGKey(0))))(weights), ref)
    return out


BLOCK = ["norm_in/scale", "norm_in/bias", "norm_post/scale",
         "norm_post/bias", "mlp/gate/kernel", "mlp/up/kernel",
         "mlp/down/kernel"]
LEAVES = ["embed/embedding", "norm_f/scale", "norm_f/bias"] \
    + [f"h0/ssm/{p}" for p in SSM] + [f"h0/{p}" for p in BLOCK] \
    + [f"h1/attn/{p}" for p in ATTN] + [f"h4/ssm/{p}" for p in SSM] \
    + [f"h5/attn/{p}" for p in ATTN] + [f"h6/gmu/{p}" for p in GMU] \
    + [f"h7/attn/{p}" for p in CROSS] + [f"h7/{p}" for p in BLOCK]


def test_both_sides_name_the_same_leaves(both_sides):
    (_, prog), (_, ref) = both_sides["float32"]
    assert sorted(paths(prog)) == sorted(paths(ref))
    assert set(LEAVES) <= set(paths(ref))
    assert "head" not in prog           # the head is the embedding's table
    assert [p for p in paths(prog) if p.endswith("embedding")] == [
        "embed/embedding"]


@pytest.mark.parametrize("how, tol", [("float32", 1e-5), ("bfloat16", 3e-3)])
def test_loss_matches_the_plain_reference(both_sides, how, tol):
    (prog, _), (ref, _) = both_sides[how]
    assert abs(float(prog) - float(ref)) <= tol * abs(float(ref))


@pytest.mark.parametrize("path", LEAVES)
def test_every_gradient_leaf_matches_the_plain_reference(both_sides, path):
    """The chunked scan, the convolution, the differential pairs through
    two calls of attention, the memory and K/V handed on with every
    reader's gradient summed, LayerNorm with bias and the tied head are
    the reference's mathematics."""
    (_, prog), (_, ref) = both_sides["float32"]
    assert close(leaf(prog, path), leaf(ref, path), 2e-3), path


def test_the_models_own_products_stay_near_it(both_sides):
    (_, prog), (_, ref) = both_sides["bfloat16"]
    gap = np.sqrt(sum(np.sum((leaf(prog, p) - leaf(ref, p)) ** 2)
                      for p in paths(ref))
                  / sum(np.sum(leaf(ref, p) ** 2) for p in paths(ref)))
    assert gap <= 0.3


@pytest.mark.parametrize("path", ["h4/ssm/in_proj/kernel", "h4/ssm/A_log",
                                  "h4/ssm/D", "h5/attn/k/kernel",
                                  "h5/attn/v/kernel", "h5/attn/v/bias"])
def test_a_dropped_reader_would_show(both_sides, path):
    """The gradient into the layers that hand on holds the readers' part:
    with the cross-decoder cut (layers 6 and 7: the memory's and the K/V's
    readers) it is another gradient, on both sides alike; so a reader
    whose contribution the trunk dropped fails the test above."""
    (_, whole), (_, ref_whole) = both_sides["float32"]
    (_, cut), (_, ref_cut) = both_sides["cut"]
    assert close(leaf(cut, path), leaf(ref_cut, path), 2e-3)
    assert not close(leaf(cut, path), leaf(whole, path), 0.05)
    assert not close(leaf(ref_cut, path), leaf(ref_whole, path), 0.05)


# -- remat, and the trial's steps -----------------------------------------------

@pytest.fixture(scope="module")
def with_and_without_remat():
    import optax

    from metaopt_tpu.models import lm, lm_layers, lm_remat

    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, S + 1), 2, V)
    out = {}
    for remat in (False, True):
        model = lm.make_lm(description(remat=remat))
        if remat:  # every name the rule can say
            model = model.clone(keeps=tuple(
                lm_remat.remat_keeps(model.pattern)["keeps"]) + tuple(
                name for spec in (
                    lm_layers.GatedSpec, lm_layers.DifferentialSpec,
                    lm_layers.StateSpaceSpec, lm_layers.MemoryUnitSpec)
                for name in spec.KEPT.values()))
        params = nn.meta.unbox(model.init(
            jax.random.PRNGKey(0), tokens[:, :-1], train=False)["params"])
        tx = optax.sgd(1.0)   # the step IS the gradient: one program a case
        after, _, _, loss = jax.jit(lm.make_lm_train_step(model, tx))(
            params, tx.init(params), {}, tokens, jax.random.PRNGKey(0))
        out[remat] = (loss, jax.tree.map(lambda a, b: a - b, params, after))
    return out


@pytest.mark.parametrize("path", [
    "loss", "embed/embedding", "h0/ssm/A_log", "h0/ssm/conv",
    "h0/ssm/in_proj/kernel", "h4/ssm/dt_proj/bias", "h4/ssm/out_proj/kernel",
    "h1/attn/k/kernel", "h5/attn/v/kernel", "h5/attn/lambda_q1",
    "h6/gmu/in_proj/kernel", "h7/attn/q/kernel", "h7/attn/subln/scale",
    "h7/mlp/up/kernel"])
def test_remat_changes_nothing(with_and_without_remat, path):
    """A rematerialised block keeps what was handed to it (its inputs) and
    makes the rest again: the same numbers, to the order this CPU sums a
    float32 product's terms in when it makes the operand again."""
    (loss, grads), (r_loss, r_grads) = (
        with_and_without_remat[False], with_and_without_remat[True])
    if path == "loss":
        assert np.isfinite(float(loss)) and float(loss) == float(r_loss)
        return
    got, want = leaf(r_grads, path), leaf(grads, path)
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=1e-6 * np.abs(want).max())


def test_a_rematerialised_block_keeps_what_the_scan_made(monkeypatch):
    """On the kernels' route the gradient of the rematerialised model holds
    one ``selective_scan_fwd`` a Mamba layer and two ``flash_fwd`` an
    attention layer (the two maps); a bare ``nn.remat`` walks each forward a
    second time."""
    from lm_pattern_cases import _equations

    from metaopt_tpu.models import lm, lm_remat
    from metaopt_tpu.ops import selective_scan

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert lm_remat.remat_keeps(lm.make_lm(description()).pattern)[
        "keeps"][-2:] == list(selective_scan.REMAT_KEEPS)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 128 + 1), 2, V)
    counted = {}
    for how in ("kept", "bare"):
        if how == "bare":
            monkeypatch.setattr(lm, "rematerialised",
                                lambda cls, keeps: nn.remat(cls))
        model = lm.make_lm(description(remat=True))
        params = jax.eval_shape(lambda: nn.meta.unbox(model.init(
            jax.random.PRNGKey(0), tokens[:, :-1], train=False)["params"]))
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: lm.lm_loss_fn(
            model, p, tokens, jax.random.PRNGKey(0))))(params)
        names = [e.params["name"] for e in _equations(jaxpr.jaxpr)
                 if e.primitive.name == "pallas_call"]
        counted[how] = tuple(sum(k in n for n in names) for k in (
            "selective_scan_fwd", "selective_scan_bwd", "flash_fwd",
            "flash_bwd"))
    assert counted["kept"] == (2, 2, 6, 6)
    assert counted["bare"] == (4, 2, 12, 6)


@pytest.mark.parametrize("remat", [False, True])
def test_three_steps_of_the_trial(remat, capsys):
    """``LMTrial`` over the description, as ``examples/lm_causal.py
    --model`` and the benchmark's runner drive it: three steps, finite
    losses that fall, and the span says what the layers are."""
    from jax.sharding import Mesh

    from metaopt_tpu.models import lm
    from metaopt_tpu.utils import trace

    one = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
    trial = lm.LMTrial(
        {**description(remat=remat), "lr": 3e-3, "warmup": 1}, mesh=one,
        n_train=8, batch_size=2, seq_len=S, steps=3, seed=3)
    with trial:
        losses = [float(trial.step(i)) for i in (0, 0, 0)]
    assert all(np.isfinite(losses)) and losses[2] < losses[0]
    said = trace.spans("trial.setup")[-1]["attrs"]
    layers = said["attention_layers"]
    assert layers["ssm"] == {
        "route": "xla", "chunk": 32, "state": "float32", "layers": [0, 4],
        "d_inner": DI, "d_state": N, "conv": 4, "dt_rank": R, "hands_on": 4}
    assert layers["gmu"] == {"layers": [6], "reads": 4, "d_inner": DI}
    assert layers["cross-nope"]["reads"] == 5
    assert layers["window-nope"]["mask"].endswith(f"window {WINDOW}")
    assert bool(said.get("remat")) == remat
    if remat:
        assert said["remat"]["blocks"] == len(HELD)
    trace.print_routes([dict(trace.spans("trial.setup")[-1], trial="T-9")])
    lines = capsys.readouterr().out.splitlines()
    assert ("trial T-9: state-space layers 0, 4: selective scan over 128 "
            "channels x 4 states (float32), convolutions of 4, steps of "
            "rank 4, chunks of 32 by xla; layer 4 hands on its scan "
            "output") in lines
    assert ("trial T-9: gated memory units 6: 128 wide, reading layer 4's "
            "scan output") in lines
    assert ("trial T-9: cross-nope layers: reference, mask by dense: causal; "
            "layers 7 differential: 2 query pairs on 1 K/V pairs, "
            "q\u00b7k 16, v 32, reading layer 5's K and V") in lines


# -- the tied head over a held slice --------------------------------------------

def test_the_tied_head_reads_out_over_the_held_slice():
    """Ids inside rows 64..127 of a vocabulary of 128: the table has 64
    rows, logits and loss are over them, and the loss and the table's
    gradient are the reference's (which embeds and reads out with the one
    table)."""
    from chipbench.reference import ssm_lm as reference
    from metaopt_tpu.models import lm

    held, short = (64, 64), 32
    cfg = reference_cfg([1], held)
    weights = seeded(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, short + 1), 64, V)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jnp, "bfloat16", jnp.float32)
        model = lm.make_lm(description([1], held))
        assert model.held_vocab() == held
        logits = jax.jit(lambda p: model.apply(
            {"params": p}, tokens[:, :-1], train=False))(weights)
        prog = jax.jit(jax.value_and_grad(lambda p: lm.lm_loss_fn(
            model, p, tokens, jax.random.PRNGKey(0))))(weights)
    ref = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, tokens, cfg)))(weights)
    assert logits.shape == (1, short, 64)
    assert weights["embed"]["embedding"].shape == (64, D)
    assert abs(float(prog[0]) - float(ref[0])) <= 1e-5 * float(ref[0])
    assert close(leaf(prog[1], "embed/embedding"),
                 leaf(ref[1], "embed/embedding"), 2e-3)


# -- the family's words ---------------------------------------------------------

def test_the_published_rule_names_the_kinds_at_the_published_depth():
    from chipbench.reference import ssm_lm as reference
    from metaopt_tpu.models import lm_description

    kinds = [lm_description.hybrid_kind(n, 32) for n in range(32)]
    assert [kinds.count(k) for k in ("ssm", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert [kinds[n] for n in (0, 1, 16, 17, 18, 19)] == [
        "ssm", "window", "ssm", "full", "gmu", "cross"]
    assert [reference.kind_of(n, 32) for n in range(32)] == [
        {"ssm": "mamba"}.get(k, k) for k in kinds]
    # read at the cut's depth of 6 the same rule names no full layer (N/2
    # + 1 = 4 is even) and no cross layer: the kinds are the published
    # numbers', which is why the cut lists them
    for n in (3, 5):
        with pytest.raises(ValueError, match=f"layer {n} of 6 has no kind"):
            lm_description.hybrid_kind(n, 6)
    assert lm_description.lambda_init(17) == pytest.approx(
        0.8 - 0.6 * math.exp(-5.1)) == pytest.approx(reference.lambda_init(17))


def test_the_description_builds_the_pattern():
    from metaopt_tpu.models import lm, lm_description, lm_layers

    h = lm_description._own_names(description())
    assert lm_description.family_of(h) == "phi4flash"
    p = lm_description.pattern_of(h)
    assert [layer.number for layer in p.layers] == list(HELD)
    assert [p.kind(i) for i in range(len(HELD))] == [
        "ssm", "window-nope", "ssm", "global-nope", "gmu", "cross-nope"]
    assert p.layers[0].mixer == p.layers[2].mixer \
        == lm_layers.StateSpaceSpec(DI, N, 4, R)
    assert p.layers[4].mixer == lm_layers.MemoryUnitSpec(DI)
    # layer 4 hands its scan output to the memory unit, layer 5 its K and
    # V to the cross layer
    assert [(layer.reads, layer.hands_on) for layer in p.layers] == [
        ((), ()), ((), ()), ((), ("memory",)), ((), ("kv",)),
        (("memory",), ()), (("kv",), ())]
    assert p.kinds() == ["ssm", "window-nope", "global-nope", "gmu",
                         "cross-nope"]
    assert [p.layers[i].mixer for i in (1, 3, 5)] == [
        lm_layers.DifferentialSpec(H, HKV, W, window,
                                   lm_description.lambda_init(n), cross)
        for n, window, cross in ((1, WINDOW, False), (5, None, False),
                                 (7, None, True))]
    assert (p.norm, p.eps, p.tied) == ("layer", 1e-5, True)
    assert {layer.ffn for layer in p.layers} == {
        lm_layers.GatedSpec(F, "silu")}
    assert lm.make_lm(description()).n_layers == len(HELD)
    # the one table, tied, and the route its lookup's gradient takes here
    assert lm_description.describe_pattern(
        description(), "reference", tokens=S)["embed"] == {"gradient": "take", "rows": V, "width": D, "tokens": S,
                     "tied": True}
    # without ``layers_held`` every published layer is held
    assert [layer.number for layer in lm_description.pattern_of(
        lm_description._own_names(description(None))).layers] \
        == list(range(OF))


@pytest.mark.parametrize("held, message", [
    ([0, 1, 6], "layer 6 reads the memory of layer 4, which is not among"),
    ([0, 1, 4, 7], "layer 7 reads K and V of layer 5, which is not among"),
    ([1, 0], "the published numbers of layers 0..7, ascending"),
    ([0, 9], "the published numbers of layers 0..7, ascending")])
def test_a_reader_without_its_source_is_refused_by_name(held, message):
    from metaopt_tpu.models import lm

    with pytest.raises(ValueError, match=message):
        lm.make_lm(description(held))


# -- the published sizes --------------------------------------------------------

def _cell():
    from chipbench import ssm_lm_config

    with open(os.path.join(ROOT, "chipbench", "configs",
                           "phi-4-mini-flash-vp8.json")) as f:
        config = json.load(f)
    return config, ssm_lm_config


def _count(shapes) -> int:
    return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))


def test_the_published_model_counts_3_85_billion_parameters():
    """The reference's shapes at the PUBLISHED sizes (every layer, the whole
    vocabulary; shapes alone, no memory): 3.85 B against the published
    3.8 B, which is what ties the reading of the layers to the model."""
    from chipbench.reference import ssm_lm as reference

    config, ssm_lm_config = _cell()
    cfg = {**ssm_lm_config.reference_cfg(config), "layers": list(range(32)),
           "vocab_held": [0, config["published"]["vocab_size"]]}
    assert config["published"]["num_hidden_layers"] == cfg["of"] == 32
    shapes = reference.param_shapes(cfg)
    assert abs(_count(shapes) - 3.85e9) <= 0.01 * 3.85e9
    kinds = [reference.kind_of(n, 32) for n in range(32)]
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    by_kind = {k: _count(shapes[f"h{kinds.index(k)}"]) for k in set(kinds)}
    ffn, norms = 3 * 2560 * 10240, 4 * 2560
    assert by_kind["mamba"] == ffn + norms + 2560 * 10240 + 5120 * 4 + 5120 \
        + 5120 * 192 + 160 * 5120 + 5120 + 5120 * 16 + 5120 + 5120 * 2560
    assert by_kind["gmu"] == ffn + norms + 2 * 2560 * 5120
    assert by_kind["cross"] < by_kind["full"] == by_kind["window"]


def test_the_cell_holds_697_million_parameters_on_both_sides():
    """The cut's count from the reference's shapes and from the program's
    (``jax.eval_shape`` of its init), leaf for leaf: 697 M x 16 bytes =
    11.15 GB of the chip's 16."""
    from chipbench.reference import ssm_lm as reference
    from metaopt_tpu.models import lm, lm_remat

    config, ssm_lm_config = _cell()
    shapes = reference.param_shapes(ssm_lm_config.reference_cfg(config))
    assert _count(shapes) == 697_094_272
    assert 16 * _count(shapes) / 1e9 == pytest.approx(11.15, abs=0.01)
    model = lm.make_lm(ssm_lm_config.description(config))
    assert [layer.number for layer in model.pattern.layers] == [
        0, 1, 16, 17, 18, 19]
    prog = nn.meta.unbox(jax.eval_shape(
        lm_remat.param_init(model, (1, 128)),
        jax.ShapeDtypeStruct((2,), jnp.uint32)))
    assert {p: x.shape for p, x in zip(paths(prog), jax.tree.leaves(prog))} \
        == {p: x.shape for p, x in zip(paths(shapes),
                                       jax.tree.leaves(shapes))}
    # every number of the catalog's config is in the file under its key,
    # but for the two that ``reduced`` names
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["vocab_size"]) == (6, 25008)
    assert config["script_args"]["share"]["layers_held"] == [
        0, 1, 16, 17, 18, 19]


def test_the_rule_keeps_what_fits_the_cell():
    """``remat_keeps`` at the cell's sizes on a device that states 15.75
    GiB (2.88 GB of room beside 11.15 GB of state): the scan's and the
    kernels' names, the feed-forward's three products (2.27 GB over six
    layers), attention's and the memory unit's projections; the Mamba
    layers' input projection and dt_proj (336 MB each) are declined."""
    from metaopt_tpu.models import lm, lm_layers, lm_remat

    config, ssm_lm_config = _cell()
    model = lm.make_lm(ssm_lm_config.description(config))
    said = lm_remat.remat_keeps(
        model.pattern, tokens=8192, d_model=2560,
        parameters=697_094_272, bytes_limit=int(15.75 * 2 ** 30))
    assert said["bytes"]["ffn.gate"] == 6 * 8192 * 2 * 10240
    assert said["bytes"]["attention.q_proj"] == 3 * 8192 * 2 * 2560
    assert said["bytes"]["attention.k_proj"] == 2 * 8192 * 2 * 1280
    assert said["bytes"]["ssm.in_proj"] == 2 * 8192 * 2 * 2 * 5120
    assert said["bytes"]["gmu.in_proj"] == 8192 * 2 * 5120
    kept = set(said["keeps"])
    assert {"selective_scan.out", "selective_scan.states", "attention.out",
            *lm_layers.GatedSpec.KEPT.values(), "ssm.x_proj",
            "ssm.out_proj", *lm_layers.MemoryUnitSpec.KEPT.values(),
            *lm_layers.DifferentialSpec.KEPT.values()} <= kept
    assert not {"ssm.in_proj", "ssm.dt_proj"} & kept
    assert sum(said["bytes"][n] for n in kept if n in said["bytes"]) \
        <= said["room"] == (int(15.75 * 2 ** 30) - 16 * 697_094_272) // 2
