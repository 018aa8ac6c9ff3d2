"""The pattern decoder (models/lm.py) against a plain float32 reference
written here, in the test tree's own words: block by block, loss and every
gradient leaf; and its description: the published names and the share, the
trial handed out step by step, what the reader prints. The dropless expert
layer is test_lm_pattern_experts.py's, what a rematerialised block keeps
test_lm_pattern_remat.py's (one file with this one until PR 44: split by
topic, a file under 300 s of one worker); what the three share is
lm_pattern_cases.py's."""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from lm_pattern_cases import (D, E, F, H, HD, HI, KINDS, KV, LEAVES, ROOT, S,
                              TOPK, V, WINDOW, description, leaf, one_device,
                              ref_moe, seeded)


# -- the reference, in this file's own words ---------------------------------

def ref_rms(x, g):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * g


def ref_rope(x):
    half = HD // 2
    inv = 1.5e6 ** (-np.arange(half) * 2.0 / HD)
    ang = np.arange(x.shape[0])[:, None] * inv[None]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, a * s + b * c], -1)


def ref_block(p, x, sliding, rotary, held):
    n = ref_rms(x, p["norm_in"]["scale"])
    logits = jnp.dot(n, p["router"]["kernel"], precision=HI)
    q = jnp.einsum("sd,dhk->shk", n, p["attn"]["q"]["kernel"], precision=HI)
    k = jnp.einsum("sd,dhk->shk", n, p["attn"]["k"]["kernel"], precision=HI)
    v = jnp.einsum("sd,dhk->shk", n, p["attn"]["v"]["kernel"], precision=HI)
    if rotary:
        q, k = ref_rope(q), ref_rope(k)
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    seen = (i - j >= 0) & ((i - j < WINDOW) if sliding else True)
    heads = []
    for hh in range(H):
        sc = jnp.dot(q[:, hh], k[:, hh // (H // KV)].T,
                     precision=HI) / math.sqrt(HD)
        pr = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
        heads.append(jnp.dot(pr, v[:, hh // (H // KV)], precision=HI))
    a = jnp.einsum("shk,hkd->sd", jnp.stack(heads, 1),
                   p["attn"]["out"]["kernel"], precision=HI)
    x1 = x + a
    m = ref_rms(x1, p["norm_post"]["scale"])
    return x1 + ref_moe(m, logits, p["experts"], held)


def ref_loss(params, tokens, layers, held):
    total = 0.0
    for row in tokens:
        x = params["embed"]["embedding"][row[:-1]]
        for i, (sliding, rotary) in enumerate(layers):
            x = ref_block(params[f"h{i}"], x, sliding, rotary, held)
        x = ref_rms(x, params["norm_f"]["scale"])
        logp = jax.nn.log_softmax(
            jnp.dot(x, params["head"]["embedding"].T, precision=HI), -1)
        total = total - jnp.sum(
            jnp.take_along_axis(logp, row[1:, None], -1))
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))


@pytest.fixture(scope="module")
def both_sides():
    """{kind: (program's loss, gradients; reference's)} for a one-layer
    model of each kind, and for the whole period of four."""
    from metaopt_tpu.models.lm import lm_loss_fn, make_lm

    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, S + 1), 2, V)
    out = {}
    for kind, layers in [(k, [v]) for k, v in KINDS.items()] + [
            ("period", [(0, 0), (1, 1), (1, 1), (1, 1)])]:
        model = make_lm(description(layers))
        params = seeded(model, tokens)
        prog = jax.value_and_grad(
            lambda p: lm_loss_fn(model, p, tokens, jax.random.PRNGKey(0)))(
                params)
        ref = jax.value_and_grad(ref_loss)(params, tokens, layers, (0, E))
        out[kind] = (prog, ref)
    return out


@pytest.mark.parametrize("kind", list(KINDS) + ["period"])
def test_loss_matches_the_reference(both_sides, kind):
    (prog, _), (ref, _) = both_sides[kind]
    assert abs(float(prog) - float(ref)) <= 2e-3 * abs(float(ref))


@pytest.mark.parametrize("path", LEAVES)
@pytest.mark.parametrize("kind", ["global-nope", "window-rope"])
def test_every_gradient_leaf_matches_the_reference(both_sides, kind, path):
    """bfloat16 products against float32: the difference's norm stays under
    a twentieth of the leaf's."""
    (_, prog), (_, ref) = both_sides[kind]
    p, r = leaf(prog, path), leaf(ref, path)
    assert np.linalg.norm(p - r) <= 0.05 * np.linalg.norm(r), path


@pytest.mark.parametrize("layer", ["h0", "h1", "h2", "h3"])
def test_a_whole_period_s_gradients_match_layer_by_layer(both_sides, layer):
    """Looser than a single layer: from layer 1 on, bfloat16 activations
    move a few of the 48 tokens' third and fourth router logits past each
    other, and at this size one such token shows in a leaf."""
    (_, prog), (_, ref) = both_sides["period"]
    for path in LEAVES[3:]:
        path = path.replace("h0", layer)
        p, r = leaf(prog, path), leaf(ref, path)
        assert np.linalg.norm(p - r) <= 0.12 * np.linalg.norm(r), path


@pytest.fixture(scope="module")
def handed_over():
    """{kind: {form: (loss, gradients)}} on the Pallas route, interpreted:
    q and k from their products by the one Pallas call a direction
    (ops/grouped_hand_over.py; the rotary layers engage it, a layer with
    neither norm nor positions has nothing for it) and by XLA's passes,
    every other line of the program the same."""
    from lm_pattern_cases import _on_the_kernels
    from metaopt_tpu.models import lm_description
    from metaopt_tpu.models.lm import lm_loss_fn, make_lm
    from metaopt_tpu.ops import grouped_hand_over

    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, S + 1), 2, V)
    out, patch = {}, pytest.MonkeyPatch()
    try:
        _on_the_kernels(patch)
        for kind, layers in [("window-rope", [KINDS["window-rope"]]),
                             ("period", [(0, 0), (1, 1), (1, 1), (1, 1)])]:
            model = make_lm(description(layers))
            params = seeded(model, tokens)
            run = lambda: jax.value_and_grad(lambda p: lm_loss_fn(  # noqa: E731
                model, p, tokens, jax.random.PRNGKey(0)))(params)
            said = lambda: {k: v["hand_over"] for k, v in (  # noqa: E731
                lm_description.describe_pattern(
                    description(layers), "pallas", tokens=2 * S,
                    seq_len=S)["attention_layers"].items())}
            out[kind] = {"one pass": run(), "said": said()}
            with pytest.MonkeyPatch.context() as inner:
                inner.setattr(grouped_hand_over, "hand_over",
                              lambda *a: "passes")
                out[kind]["passes"] = run()
                assert set(said().values()) == {"passes"}
    finally:
        patch.undo()
    return out


def test_the_span_says_which_layers_hand_over_in_one_pass(handed_over):
    assert handed_over["window-rope"]["said"] == {"window-rope": "one pass"}
    assert handed_over["period"]["said"] == {
        "global-nope": "passes", "window-rope": "one pass"}


@pytest.mark.parametrize("kind", ["window-rope", "period"])
def test_the_hand_over_in_one_pass_changes_no_loss(handed_over, kind):
    (one, _), (passes, _) = (handed_over[kind][form]
                             for form in ("one pass", "passes"))
    assert abs(float(one) - float(passes)) <= 2e-3 * abs(float(passes))


@pytest.mark.parametrize("path", LEAVES)
@pytest.mark.parametrize("kind", ["window-rope", "period"])
def test_the_hand_over_in_one_pass_changes_no_gradient(handed_over, kind,
                                                       path):
    """To the limit the reference is held to above; the period's first
    rotary layer is layer 1."""
    (_, one), (_, passes) = (handed_over[kind][form]
                             for form in ("one pass", "passes"))
    if kind == "period":
        path = path.replace("h0", "h1")
    p, r = leaf(one, path), leaf(passes, path)
    assert np.linalg.norm(p - r) <= 0.05 * np.linalg.norm(r), path


def test_window_and_global_layers_differ_and_a_late_token_is_unseen():
    """Poking a token changes later positions' logits inside the window and
    leaves those beyond it alone (one window layer sees no further)."""
    from metaopt_tpu.models.lm import make_lm

    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, S), 2, V)
    poked = tokens.at[0, 2].set((tokens[0, 2] + 1) % (V - 2) + 2)
    for sliding, reaches in ((1, False), (0, True)):
        model = make_lm(description([(sliding, 1)],
                                    moe_num_primary_experts=0))
        params = model.init(jax.random.PRNGKey(0), tokens, train=False)
        a = model.apply(params, tokens, train=False)
        b = model.apply(params, poked, train=False)
        changed = np.abs(np.asarray(a - b)).max(-1)[0]
        assert changed[:2].max() == 0 and changed[2] > 0
        assert changed[2 + WINDOW - 1] > 0          # the window's last
        assert (changed[2 + WINDOW:].max() > 0) == reaches


# -- the description -----------------------------------------------------------


def test_published_names_and_the_share_make_the_pattern():
    from metaopt_tpu.models.lm import make_lm

    model = make_lm(description([(0, 0), (1, 1), (1, 1), (1, 1)],
                                held=(4, 4), vocab_held=(16, 32)))
    p = model.pattern
    assert [(layer.mixer.window, layer.mixer.theta) for layer in p.layers] \
        == [(None, None)] + [(WINDOW, 1.5e6)] * 3
    attention, experts = p.layers[1].mixer, p.layers[1].ffn
    assert (attention.kv_heads, attention.head_dim, experts.top_k) == (
        KV, HD, TOPK)
    assert attention.qk_norm is None and not experts.router_after_mixer
    assert experts.held == (4, 4) and p.vocab_held == (16, 32)
    assert p.kinds() == ["global-nope", "window-rope"]
    assert (p.norm, p.tied) == ("rms", False)
    assert model.dropout == 0.0
    tokens = jnp.full((1, 8), 20, jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens, train=False)["params"]
    assert params["head"]["embedding"].value.shape == (32, D)
    assert nn.meta.unbox(params)["h1"]["experts"]["gate"].shape == (4, D, F)
    assert "pos_embed" not in params


def test_a_description_without_a_pattern_is_the_2017_stack():
    from metaopt_tpu.models.lm import make_lm

    model = make_lm({"d_model": 32, "n_heads": 2, "n_layers": 1})
    assert model.pattern is None and model.dropout == 0.1


def test_layouts_shorter_than_the_depth_are_refused():
    from metaopt_tpu.models.lm import make_lm

    with pytest.raises(ValueError, match="layouts"):
        make_lm(description([(0, 0)], num_hidden_layers=2))


def test_train_lm_reports_the_routing_s_counts_once(tmp_path):
    from metaopt_tpu.models.lm import train_lm
    from metaopt_tpu.utils import trace

    hp = description([(0, 0), (1, 1)], held=(4, 8), lr=1e-3, remat=True)
    loss = train_lm(hp, mesh=one_device(), n_train=8, batch_size=2,
                    seq_len=S, steps=3)
    assert np.isfinite(loss)
    train = trace.spans("trial.train")[-1]
    moe = train["attrs"]["moe"]
    assert moe["dropped"] == [0, 0]
    # 2 x S x top-k = 144 rows a layer are one chunk: a trip a step
    assert moe["chunks"] == [3, 3]
    assert np.asarray(moe["items"]).shape == (2, 8)
    # 3 steps x 2 rows x S tokens x top-k choices, the held experts' part
    assert 0 < np.sum(moe["items"]) < 3 * 2 * S * TOPK * 2
    setup = trace.spans("trial.setup")[-1]["attrs"]
    assert setup["attention"]["dropout"] == 0.0
    assert setup["attention_layers"] == {
        "global-nope": {"route": "reference", "mask": "dense: causal",
                        "hand_over": "passes"},
        "window-rope": {"route": "reference",
                        "mask": f"dense: causal, window {WINDOW}",
                        "hand_over": "passes"}}
    assert setup["moe"] == {"routed_over": E, "top_k": TOPK, "held": [4, 8],
                            "products": "ragged_dot",
                            "experts": {
                                "gate_up": f"one product of {2 * F} columns",
                                "gating": "chunks", "products": "ragged_dot",
                                "tiles": None},
                            "buffer_rows": 2 * S * TOPK,
                            "chunk_rows": 2 * S * TOPK}
    # this backend states no limit: the projections are sized and not kept
    assert setup["remat"] == {"blocks": 2, "keeps": [
        "attention.out", "attention.lse", "attention.selected"],
        "room": None, "bytes": {
            "attention.q_proj": 2 * 2 * 2 * S * H * HD,
            "attention.k_proj": 2 * 2 * 2 * S * KV * HD,
            "attention.v_proj": 2 * 2 * 2 * S * KV * HD,
            "attention.out_proj": 2 * 2 * 2 * S * D}}


def test_the_trial_hands_out_its_loop_step_by_step():
    """LMTrial.step(i) is the loop train_lm drives: the same steps by hand
    give the same loss."""
    from metaopt_tpu.models.lm import LMTrial, train_lm

    hp = description([(0, 0), (1, 1)], lr=1e-3)
    kw = dict(mesh=one_device(), n_train=8, batch_size=2, seq_len=S, steps=3,
              seed=4)
    whole = train_lm(hp, **kw)
    trial = LMTrial(hp, **kw)
    with trial:
        for i in range(3):
            loss = trial.step(i)
    assert float(loss) == whole
    assert trial.read_counts()["dropped"] == [0, 0]


def test_the_reader_prints_the_pattern_s_routes_and_counts(capsys):
    from metaopt_tpu.utils import trace

    setup = {"name": "trial.setup", "trial": "T-1", "attrs": {
        "attention": {"dropout": 0.0, "train": "pallas", "eval": "pallas"},
        "attention_layers": {
            "global-nope": {"route": "pallas", "mask": "structure: causal"}},
        "moe": {"routed_over": 64, "top_k": 6, "held": [0, 16],
                "products": "ragged_dot", "experts": {
                    "gate_up": "one product of 1536 columns",
                    "gating": "chunks", "products": "ragged_dot",
                    "tiles": None},
                "buffer_rows": 49152, "chunk_rows": 2048}}}
    train = {"name": "trial.train", "trial": "T-1", "attrs": {
        "steps": 2, "moe": {"items": [[18000, 6000]], "dropped": [0],
                            "chunks": [13]}}}
    trace.print_routes([setup, train])
    assert capsys.readouterr().out.splitlines() == [
        "trial T-1: attention pallas in training (dropout 0.0), pallas in "
        "evaluation",
        "trial T-1: global-nope layers: pallas, mask by structure: causal",
        "trial T-1: experts 0-15 of 64 held, top 6, products by ragged_dot, "
        "gate and up as one product of 1536 columns, gating by chunks",
        "trial T-1: layer 0: 24000 items to held experts, fullest 1.50x the "
        "mean, 0 dropped",
        "trial T-1: routing moved 27.1 % of the buffers' rows, the experts' "
        "passes 24.4 %"]


def test_the_example_takes_a_model_description(tmp_path, monkeypatch):
    """examples/lm_causal.py --model: a description file names the model,
    the command line the optimizer's hyperparameters."""
    import json
    import runpy
    import sys

    from metaopt_tpu import client
    from metaopt_tpu.utils import trace

    path = tmp_path / "model.json"
    path.write_text(json.dumps(description([(0, 0), (1, 1)], held=(0, 4))))
    reported = []
    monkeypatch.setattr(client, "report_results", reported.append)
    monkeypatch.setattr(sys, "argv", [
        "lm_causal.py", f"--model={path}", "--lr=1e-3", f"--seq-len={S}",
        "--batch-size=8", "--n-train=8", "--steps=2"])
    runpy.run_path(os.path.join(ROOT, "examples", "lm_causal.py"),
                   run_name="__main__")
    assert np.isfinite(reported[0][0]["value"])
    assert trace.spans("trial.setup")[-1]["attrs"]["moe"]["held"] == [0, 4]


def test_the_benchmark_prints_a_description_the_program_takes():
    """The program reads make_lm's description alone; the benchmark turns
    a configuration of its own into one."""
    import json
    import subprocess
    import sys

    from metaopt_tpu.models.lm import make_lm

    out = subprocess.run(
        [sys.executable, "-m", "chipbench.lm_config", os.path.join(
            "chipbench", "configs", "smallthinker-21b-a3b-ep4.json")],
        cwd=ROOT, check=True, capture_output=True, text=True).stdout
    desc = json.loads(out)
    assert desc["moe_num_primary_experts"] == 64
    model = make_lm(desc)
    assert model.n_layers == 4 and model.remat is True
    assert {layer.ffn.held for layer in model.pattern.layers} == {(0, 16)}
    assert model.pattern.vocab_held == (0, 37984)


@pytest.mark.parametrize("tree", ["examples", "metaopt_tpu"])
def test_the_program_knows_nothing_of_the_benchmark(tree):
    import pathlib

    assert [str(p) for p in pathlib.Path(ROOT, tree).rglob("*.py")
            if "import chipbench" in p.read_text()
            or "from chipbench" in p.read_text()] == []


@pytest.mark.parametrize("backend, rows, product", [
    ("cpu", 49152, "ragged_dot"), ("tpu", 49152, "megablox"),
    ("tpu", 600, "ragged_dot")])
def test_one_place_decides_the_grouped_product(monkeypatch, backend, rows,
                                               product):
    """The layer and trial.setup's span ask the same function, with the
    shapes: a row count no tile divides takes XLA's product and says so."""
    import jax

    from metaopt_tpu.models import lm_description, moe

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert moe.grouped_matmul_impl(rows, 2560, 768) == product
    said = lm_description.describe_pattern(
        {"rope_layout": [0], "sliding_window_layout": [0], "n_layers": 1,
         "d_model": 2560, "moe_num_primary_experts": 64,
         "moe_num_active_primary_experts": 6, "moe_ffn_hidden_size": 768},
        "reference", tokens=rows // 6)
    assert said["moe"]["products"] == product
    # the held experts' part says the same answer, the gating that goes
    # with it and, where the tiles are the program's to choose, the tiles
    # (rows in 256, k whole, n as wide as the kernel's VMEM count allows)
    how = said["moe"]["experts"]
    assert how["products"] == product
    assert how["gate_up"] == "one product of 1536 columns"
    if product == "megablox":
        assert how["gating"] == "pallas"
        assert how["tiles"] == {
            "gu": (256, 2560, 768), "out": (256, 768, 2560),
            "d_h": (256, 2560, 768), "d_rows": (256, 1536, 1280),
            "d_w_gu": (256, 2560, 512), "d_w_down": (256, 768, 1280),
            "gating": 512}
    else:
        assert (how["gating"], how["tiles"]) == ("chunks", None)
    # the embedding's gradient asks its own one place (ops/embed.py)
    assert said["embed"] == {
        "gradient": "sorted" if backend == "tpu" else "take", "rows": 1000,
        "width": 2560, "tokens": rows // 6, "tied": False}


def test_the_step_compiles_once_and_starts_near_the_uniform_loss():
    """The counts go into the jitted step as they come out of it, so step
    1 finds step 0's program; and the head's initial size makes the first
    loss about log(rows held)."""
    from metaopt_tpu.models.lm import LMTrial

    trial = LMTrial(description([(0, 0), (1, 1)], held=(0, 8)),
                    mesh=one_device(), n_train=8, batch_size=2, seq_len=S)
    with trial:
        losses = [float(trial.step(i)) for i in range(3)]
    assert trial._step_fn._cache_size() == 1
    assert abs(losses[0] - math.log(V)) < 1.0
