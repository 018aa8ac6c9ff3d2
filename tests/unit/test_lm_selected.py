"""Attention over the keys an indexer selects: the selection rule alone
(ops/sparse_index.py), the index scores' kernel (interpreted) against a
float64 sum and against its plain twin, the rule that names which of the
two runs, the kernels that take the selection as packed bits
(ops/selected_attention.py, interpreted) against plain attention under the
same mask, and the decoder built from a description with ``sa_config``
(models/lm.py) against the benchmark's plain float32 reference
(chipbench/reference/sparse_lm.py): loss and every gradient leaf, an
indexer that a step leaves to the bit, remat to the bit, and the
description that stood before still building the tree it built."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

D, H, KV, HD, F, E, TOPK, V, S = 32, 4, 2, 16, 24, 16, 3, 64, 40
IH, IK, KEYS = 4, 8, 12


def description(layers=1, held=(0, E), **over):
    h = dict(hidden_size=D, num_attention_heads=H, num_key_value_heads=KV,
             head_dim=HD, num_hidden_layers=layers, vocab_size=V,
             rope_theta=1e7, rms_norm_eps=1e-6, hidden_act="silu",
             num_experts=E, num_experts_per_tok=TOPK,
             moe_intermediate_size=F, experts_held=held,
             sa_config={"indexer_head_dim": IK, "indexer_num_heads": IH,
                        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                        "q_chunk_size": 512, "topk": KEYS})
    h.update(over)
    return h


# -- the selection rule alone --------------------------------------------------

def by_top_k(scores, k):
    """The rule in jax.lax.top_k's own words: (S, S) bool."""
    s = scores.shape[0]
    causal = np.tril(np.ones((s, s), bool))
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(k, s))
    out = np.zeros((s, s), bool)
    for t in range(s):
        out[t, np.asarray(idx[t, :min(k, t + 1)])] = True
    return out


def scores_of(kind, s, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (s, s))
    return {"distinct": x, "all-equal": jnp.zeros((s, s)),
            "many-ties": jnp.round(2 * x) / 2,
            "clipped": jnp.where(x > 0.8, x, 0.0) * jnp.sign(
                jax.random.normal(jax.random.PRNGKey(seed + 1), (s, 1)))
            }[kind]


SCORES = ["distinct", "all-equal", "many-ties", "clipped"]


@pytest.fixture(scope="module")
def chosen():
    from metaopt_tpu.ops.sparse_index import select_top_k

    return {(kind, s, k): (np.asarray(select_top_k(scores_of(kind, s), 0, k)),
                           by_top_k(scores_of(kind, s), k))
            for kind in SCORES for s, k in ((48, 10), (20, 32), (33, 1))}


@pytest.mark.parametrize("size", [(48, 10), (20, 32), (33, 1)],
                         ids=lambda x: "x".join(map(str, x)))
@pytest.mark.parametrize("kind", SCORES)
class TestTheRule:
    def test_a_row_takes_exactly_min_k_t_plus_1(self, chosen, kind, size):
        got, _ = chosen[(kind, *size)]
        s, k = size
        assert got.sum(1).tolist() == [min(k, t + 1) for t in range(s)]

    def test_never_a_key_after_the_query(self, chosen, kind, size):
        got, _ = chosen[(kind, *size)]
        assert not np.triu(got, 1).any()

    def test_a_row_shorter_than_k_takes_every_causal_key(self, chosen, kind,
                                                         size):
        got, _ = chosen[(kind, *size)]
        s, k = size
        short = min(k, s)
        assert (got[:short] == np.tril(np.ones((s, s), bool))[:short]).all()

    def test_it_is_what_top_k_gives_ties_to_the_lower_index(self, chosen,
                                                            kind, size):
        got, want = chosen[(kind, *size)]
        np.testing.assert_array_equal(got, want)


def test_equal_scores_select_the_lowest_indices():
    from metaopt_tpu.ops.sparse_index import select_top_k

    got = np.asarray(select_top_k(jnp.ones((6, 6)), 0, 2))
    assert got[5].tolist() == [True, True, False, False, False, False]
    # later rows of a block: the query's own position comes from first_row
    late = np.asarray(select_top_k(jnp.ones((2, 6)), 4, 2))
    np.testing.assert_array_equal(late, got[4:])


def index_operands(s, seed=3, batch=2, width=IK):
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (batch, s, IH, width))
    k = jax.random.normal(jax.random.fold_in(key, 1), (batch, s, width))
    w = jax.random.normal(jax.random.fold_in(key, 2), (batch, s, IH))
    return q, k, w


ROUTES = ["xla", "pallas"]


def on_route(monkeypatch, route, rows=256):
    """The index scores by ``route`` here, off the chip: for the kernel the
    backend reads as the TPU, blocks of ``rows`` rows in tiles of 128, and
    its call runs the interpreter. Returns the head width that takes the
    route (``index_scores_route``: six parts of 64 fill three passes)."""
    import functools

    from metaopt_tpu.ops import sparse_index

    monkeypatch.setattr(sparse_index, "ROWS", rows)
    if route == "pallas":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(sparse_index, "TILE", 128)
        monkeypatch.setattr(sparse_index, "_scores_pallas", functools.partial(
            sparse_index._scores_pallas, interpret=True))
    width = 64 if route == "pallas" else IK
    assert sparse_index.index_scores_route(rows, 2 * rows, width)[
        "route"] == ("xla" if rows % 128 else route)
    return width


def plain_scores(q, k, w):
    return jnp.einsum("bth,bths->bts", w, jax.nn.relu(jnp.einsum(
        "bthd,bsd->bths", q, k, precision="highest")), precision="highest")


@pytest.mark.parametrize("route", ROUTES)
@pytest.mark.parametrize("s, k, rows", [
    (40, 12, 1024), (300, 40, 1024), (600, 100, 256), (1280, 300, 256),
    (512, 600, 256)], ids=lambda x: str(x))
def test_blocks_and_groups_of_rows_select_what_whole_rows_would(
        monkeypatch, s, k, rows, route):
    """A length that pads, one of several blocks, one of several groups of
    blocks (each scored against the keys up to its own end), and a top-k
    longer than the sequence: the packed selection says what top_k of the
    whole rows says, and counts its pairs; whichever form scores the
    blocks (the kernel skips the tiles after a block's rows)."""
    from metaopt_tpu.ops import sparse_index

    q, k_, w = index_operands(s, width=on_route(monkeypatch, route, rows))
    mask, pairs = sparse_index.select(q, k_, w, k)
    dense = np.asarray(mask.dense(s, s))
    scores = plain_scores(q, k_, w)
    for b in range(q.shape[0]):
        want = by_top_k(scores[b], k)
        # float32 sums in another order: a near-tie may fall the other way
        assert (dense[b] != want).sum() <= 2
        assert dense[b].sum(1).tolist() == want.sum(1).tolist()
    assert int(pairs) == dense.sum()
    # padded queries and keys have no bit set
    whole = np.asarray(mask.dense(*mask.bits.shape[2:] * 2))
    assert whole.sum() == dense.sum()


@pytest.mark.parametrize("route", ROUTES)
def test_the_packed_bits_are_a_thirty_second_of_a_byte_mask(monkeypatch,
                                                            route):
    from metaopt_tpu.ops import sparse_index

    width = on_route(monkeypatch, route)
    mask, _ = sparse_index.select(
        *index_operands(300, batch=1, width=width), 40)
    assert mask.block == 256 and mask.bits.shape == (1, 512 // 32, 512)
    assert mask.bits.dtype == jnp.int32



# -- the index scores' kernel, interpreted --------------------------------------

# (rows, keys, first row) at the cell's 16 heads of 64, in tiles of 128: a
# block whose first row is not 0, key tiles after a block's last row
# (skipped), a block that sees every tile, one tile
EXTENTS = [(256, 384, 128), (128, 512, 0), (256, 256, 0), (128, 512, 384),
           (128, 128, 0)]


def cell_operands(rows, keys, seed=0):
    key = jax.random.PRNGKey(seed)
    return (jax.random.normal(key, (rows, 16, 64)),
            jax.random.normal(jax.random.fold_in(key, 1), (keys, 64)),
            jax.random.normal(jax.random.fold_in(key, 2), (rows, 16)))


def tile_seen(rows, keys, first, tile=128):
    """(rows, keys) bool: the pairs in tiles some row of the tile sees."""
    last = first + (np.arange(rows)[:, None] // tile + 1) * tile - 1
    return np.arange(keys)[None, :] // tile * tile <= last


@pytest.fixture
def kernel(monkeypatch):
    """``_scores_pallas`` interpreted in tiles of 128, traced anew (a test
    may plant another ``_stacked`` under it)."""
    import functools

    from metaopt_tpu.ops import sparse_index

    monkeypatch.setattr(sparse_index, "TILE", 128)
    sparse_index._scores_pallas.clear_cache()
    yield functools.partial(sparse_index._scores_pallas, interpret=True)
    sparse_index._scores_pallas.clear_cache()


def exact_and_size(q, k, w):
    """float64: the scores, and ``sum_j |w| (|q_j| . |k|)``, the size of
    the terms a score is summed from."""
    q, k, w = (np.asarray(x, np.float64) for x in (q, k, w))
    exact = np.einsum("rh,rhe->re", w, np.maximum(
        np.einsum("rhd,ed->rhe", q, k), 0))
    return exact, np.einsum("rh,rhe->re", np.abs(w), np.einsum(
        "rhd,ed->rhe", np.abs(q), np.abs(k)))


def assert_it_is_the_float64_sum(kernel, rows, keys, first):
    """Within what a float32 product at precision highest has: 2^-21 of
    the terms' size (six bfloat16 products leave out three of 2^-24 of it
    and sum in float32; one of the six left out is 2^-16 of a product or
    more)."""
    q, k, w = cell_operands(rows, keys)
    got = np.asarray(kernel(q, k, w, first), np.float64)
    exact, size = exact_and_size(q, k, w)
    seen = tile_seen(rows, keys, first)
    assert seen.any() and np.abs(exact[seen]).max() > 1
    assert (np.abs(got - exact)[seen] <= 2.0 ** -21 * size[seen]).all()


@pytest.mark.parametrize("extents", EXTENTS, ids=lambda x: "x".join(
    map(str, x)))
def test_the_kernel_s_scores_are_the_float64_sum(kernel, extents):
    assert_it_is_the_float64_sum(kernel, *extents)


@pytest.mark.parametrize("left_out", range(6), ids=[
    "hi.hi", "mid.mid", "hi.mid", "mid.hi", "hi.lo", "lo.hi"])
def test_five_of_the_six_products_are_not_the_float64_sum(
        monkeypatch, kernel, left_out):
    """The same comparison fails when a product is left out: the variant
    is planted here, under the kernel, by emptying one of the stacked
    queries' six parts."""
    from metaopt_tpu.ops import sparse_index

    stacked = sparse_index._stacked

    def five(x, parts):
        out = stacked(x, parts)
        if parts == sparse_index._Q_PARTS:
            d = x.shape[-1]
            out = out.at[..., left_out * d:(left_out + 1) * d].set(0)
        return out

    monkeypatch.setattr(sparse_index, "_stacked", five)
    with pytest.raises(AssertionError):
        assert_it_is_the_float64_sum(kernel, 256, 384, 128)


@pytest.mark.parametrize("extents", EXTENTS, ids=lambda x: "x".join(
    map(str, x)))
def test_the_kernel_and_its_plain_twin_agree_to_float32_rounding(kernel,
                                                                 extents):
    from metaopt_tpu.ops import sparse_index

    rows, keys, first = extents
    q, k, w = cell_operands(rows, keys, seed=1)
    got = np.asarray(kernel(q, k, w, first))
    want = np.asarray(sparse_index._scores_xla(q, k, w))
    seen = tile_seen(rows, keys, first)
    size = exact_and_size(q, k, w)[1]
    assert (np.abs(got - want)[seen] <= 2.0 ** -20 * size[seen]).all()
    assert (got[~seen] == 0).all()          # skipped, not computed
    assert not np.signbit(got[got == 0]).any()


def test_all_heads_clipped_is_one_zero_on_both_routes(kernel):
    """Every product negative and some weights too: w * relu(.) is 0.0 or
    -0.0 a head, and the score is +0.0 in one bit pattern."""
    from metaopt_tpu.ops import sparse_index

    q, k, w = cell_operands(128, 256)
    q, k = -jnp.abs(q), jnp.abs(k)
    for got in (kernel(q, k, w, 128), sparse_index._scores_xla(q, k, w)):
        bits = np.asarray(jax.lax.bitcast_convert_type(got, jnp.uint32))
        assert (bits == 0).all()


@pytest.mark.parametrize("backend, rows, keys, width, said", [
    ("tpu", 1024, 16384, 64,
     {"route": "pallas", "tiles": [512, 512], "depth": 128}),
    ("tpu", 1024, 4096, 128,
     {"route": "pallas", "tiles": [512, 512], "depth": 128}),
    ("cpu", 1024, 16384, 64, {"route": "xla"}),
    ("tpu", 256, 4096, 64, {"route": "xla"}),     # no tile divides the rows
    ("tpu", 1024, 4352, 64, {"route": "xla"}),    # nor the keys
    ("tpu", 1024, 4096, 8, {"route": "xla"}),     # 48 deep: no whole pass
], ids=lambda x: str(x) if not isinstance(x, dict) else x["route"])
def test_one_place_decides_how_a_block_is_scored(monkeypatch, backend, rows,
                                                 keys, width, said):
    """From the backend, the extents and the head width alone; and
    ``index_scores`` takes what it names."""
    from metaopt_tpu.ops import sparse_index

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert sparse_index.index_scores_route(rows, keys, width) == said
    taken = []
    monkeypatch.setattr(sparse_index, "_scores_pallas",
                        lambda *a: taken.append("pallas"))
    monkeypatch.setattr(sparse_index, "_scores_xla",
                        lambda *a: taken.append("xla"))
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32)  # noqa: E731
    sparse_index.index_scores(shape(rows, 16, width), shape(keys, width),
                              shape(rows, 16))
    assert taken == [said["route"]]


@pytest.mark.parametrize("backend, seq_len, said", [
    ("tpu", 16384, {"route": "pallas", "tiles": [512, 512], "depth": 128}),
    ("tpu", 1100, {"route": "xla"}), ("cpu", 16384, {"route": "xla"})])
def test_trial_setup_s_span_says_what_the_rule_said(monkeypatch, backend,
                                                    seq_len, said):
    """``describe_pattern`` asks the rule with the blocks a row of that
    length is scored in (1024 rows at 16 384, 256 at one that pads to
    1280)."""
    from metaopt_tpu.models import lm

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    hp = description(sa_config={
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "topk": 2048})
    layers = lm.describe_pattern(hp, "pallas", tokens=seq_len,
                                 seq_len=seq_len)["attention_layers"]
    assert layers["selected-rope"]["index_scores"] == said


# -- the kernels, interpreted ---------------------------------------------------

@pytest.fixture(scope="module")
def kernels_and_plain():
    """{case: {part: (kernels', plain attention's)}} of out, dq, dk, dv
    under one selected mask: lengths that pad to one tile and to several,
    grouped K/V heads."""
    from metaopt_tpu.ops import sparse_index
    from metaopt_tpu.ops.attention import (_reference_attention,
                                           flash_attention)

    out = {}
    for s, heads, kv, width, keys in ((300, 4, 2, 32, 40), (700, 2, 1, 16, 64),
                                      (40, 2, 2, 16, 8)):
        mask, _ = sparse_index.select(*index_operands(s, batch=2), keys)
        key = jax.random.PRNGKey(s)
        q = jax.random.normal(key, (2, s, heads, width)) / width ** 0.5
        k = jax.random.normal(jax.random.fold_in(key, 1), (2, s, kv, width))
        v = jax.random.normal(jax.random.fold_in(key, 2), (2, s, kv, width))
        tilt = jax.random.normal(jax.random.fold_in(key, 3), q.shape)

        def both(fn):
            o, vjp = jax.vjp(fn, q, k, v)
            return (o, *vjp(tilt))

        got = both(lambda q, k, v: flash_attention(q, k, v, mask,
                                                   interpret=True))
        want = both(lambda q, k, v: _reference_attention(q, k, v, mask))
        out[s] = dict(zip(("out", "dq", "dk", "dv"), zip(got, want)))
    return out


@pytest.mark.parametrize("part", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("case", [300, 700, 40])
def test_the_kernels_give_plain_attention_under_the_same_selection(
        kernels_and_plain, case, part):
    got, want = kernels_and_plain[case][part]
    assert got.shape == want.shape
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_a_selection_packed_for_another_length_is_refused():
    from metaopt_tpu.ops import sparse_index
    from metaopt_tpu.ops.attention import flash_attention

    mask, _ = sparse_index.select(*index_operands(300, batch=1), 40)
    x = jnp.zeros((1, 200, 2, 16))
    with pytest.raises(ValueError, match="does not fit"):
        flash_attention(x, x, x, mask, interpret=True)


@pytest.mark.parametrize("axes, message", [
    ({"dp": 1, "sp": 2}, "no sequence-parallel route"),
    ({"dp": 2, "tp": 1}, "no route over a mesh")])
def test_a_selected_mask_has_one_chip_s_routes_only(monkeypatch, axes,
                                                    message):
    from jax.sharding import Mesh

    from metaopt_tpu.ops import attention, sparse_index
    from metaopt_tpu.parallel.mesh import use_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mask, _ = sparse_index.select(*index_operands(64, batch=2), 8)
    x = jnp.zeros((2, 64, 2, 16))
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(*axes.values()),
                tuple(axes))
    with use_mesh(mesh), pytest.raises(ValueError, match=message):
        attention.attend(x, x, x, mask)


# -- the decoder against the plain reference -----------------------------------

def reference_cfg(layers):
    return {"d_model": D, "n_heads": H, "n_kv_heads": KV, "head_dim": HD,
            "n_layers": layers, "rope_theta": 1e7, "rms_eps": 1e-6,
            "index_heads": IH, "index_dim": IK, "top_keys": KEYS,
            "n_experts": E, "top_k": TOPK, "expert_d_ff": F,
            "activation": "silu", "experts_held": [0, E],
            "vocab_held": [0, V]}


@pytest.fixture(scope="module")
def both_sides():
    """{layers: (program's (loss, gradients), reference's)} on seeded
    weights, the gradients in the reference's form (an expert a leaf)."""
    from chipbench import weights_lm
    from chipbench.reference import sparse_lm as reference
    from metaopt_tpu.models import lm

    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, S + 1), 2, V)
    out = {}
    for layers in (1, 2):
        cfg = reference_cfg(layers)
        whole = weights_lm.make_weights(7, reference.param_shapes(cfg))
        model = lm.make_lm(description(layers))
        trained, frozen = lm.split_frozen(weights_lm.stacked(whole))
        loss, grads = jax.value_and_grad(lambda p: lm.lm_loss_fn(
            model, lm.merge_frozen(p, frozen), tokens,
            jax.random.PRNGKey(0)))(trained)
        ref = jax.value_and_grad(lambda p: reference.loss(
            reference.with_indexers(p, whole), tokens, cfg))(
                reference.trained(whole))
        out[layers] = ((loss, weights_lm.split(grads)), ref)
    return out


LEAVES = ["embed/embedding", "head/embedding", "norm_f/scale",
          "h0/norm_in/scale", "h0/norm_post/scale", "h0/router/kernel",
          "h0/attn/q/kernel", "h0/attn/k/kernel", "h0/attn/v/kernel",
          "h0/attn/out/kernel", "h0/attn/q_norm/scale",
          "h0/attn/k_norm/scale", "h0/experts/gate/e00",
          "h0/experts/up/e05", "h0/experts/down/e15"]


def leaf(tree, path):
    for part in path.split("/"):
        tree = tree[part]
    return np.asarray(tree, np.float32)


@pytest.mark.parametrize("layers", [1, 2])
def test_loss_matches_the_plain_reference(both_sides, layers):
    (prog, _), (ref, _) = both_sides[layers]
    assert abs(float(prog) - float(ref)) <= 2e-3 * abs(float(ref))


@pytest.mark.parametrize("path", LEAVES)
def test_every_gradient_leaf_matches_the_plain_reference(both_sides, path):
    """bfloat16 products against float32: the difference's norm stays under
    a twentieth of the leaf's."""
    (_, prog), (_, ref) = both_sides[1]
    p, r = leaf(prog, path), leaf(ref, path)
    assert np.linalg.norm(r) > 0
    assert np.linalg.norm(p - r) <= 0.05 * np.linalg.norm(r), path


def stack(tree, path):
    """A leaf, or an expert layer's matrices of one kind, all experts."""
    for part in path.split("/"):
        tree = tree[part]
    if isinstance(tree, dict):
        return np.stack([np.asarray(tree[e], np.float32)
                         for e in sorted(tree)])
    return np.asarray(tree, np.float32)


@pytest.mark.parametrize("path", [
    p.replace("h0", "h1") for p in LEAVES[3:12]] + [
    "h1/experts/gate", "h1/experts/up", "h1/experts/down"])
def test_a_second_layer_s_gradients_match_too(both_sides, path):
    """Looser: bfloat16 activations may move a key or an expert of the
    second layer's choice past its neighbour (an expert sees ~15 of the 80
    tokens: its matrices are compared all experts together)."""
    (_, prog), (_, ref) = both_sides[2]
    p, r = stack(prog, path), stack(ref, path)
    assert np.linalg.norm(p - r) <= 0.12 * np.linalg.norm(r), path


def test_the_gradient_tree_names_no_indexer(both_sides):
    (_, prog), (_, ref) = both_sides[2]
    names = lambda tree: sorted(  # noqa: E731
        "/".join(str(p.key) for p in path)
        for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0])
    assert names(prog) == names(ref)
    assert not any("indexer" in n for n in names(prog))


def test_the_selection_changes_the_output():
    """With top-k at the sequence's length every causal key is seen: the
    model is then another function than with KEYS of them."""
    from metaopt_tpu.models import lm

    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, S), 2, V)
    outs = []
    for keys in (KEYS, S):
        sa = dict(description()["sa_config"], topk=keys)
        model = lm.make_lm(description(sa_config=sa))
        params = model.init(jax.random.PRNGKey(0), tokens, train=False)
        outs.append(model.apply(params, tokens, train=False))
    changed = np.abs(np.asarray(outs[0] - outs[1])).max(-1)[0]
    assert changed[:KEYS].max() == 0 and changed[KEYS:].max() > 0


# -- the frozen indexer ----------------------------------------------------------

INDEXER = ["q/kernel", "k/kernel", "w/kernel", "k_norm/scale", "k_norm/bias"]


@pytest.fixture(scope="module")
def stepped():
    """(parameters before, after one AdamW step, the optimizer's state) of
    a two-layer trial."""
    from test_lm_pattern import one_device

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

    from metaopt_tpu.models import lm

    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, S + 1), 2, V)
    out = {}
    for remat in (False, True):
        model = lm.make_lm(description(2, remat=remat))
        if remat:  # every name the rule can say: the projections' too
            model = model.clone(keeps=tuple(lm.remat_keeps(
                model.pattern)["keeps"]) + lm.ATTENTION_REMAT_KEEPS)
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
        # the same to the bit compiled or not: test_lm_pattern.py holds it.
        np.testing.assert_array_max_ulp(got, want, maxulp=1)
        return
    np.testing.assert_array_equal(got, want)


def test_a_rematerialised_block_keeps_the_selection(monkeypatch):
    """On the kernels' route the gradient of a rematerialised two-layer
    stack holds one ``sparse_fwd`` a layer and makes the selection once
    (two bit casts a block of rows: the scores' order, the packed words):
    the policy keeps ``out``, ``lse`` and the packed bits."""
    from test_lm_pattern import _equations, _on_the_kernels

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
    assert p.layers == ((False, True),) * 3
    assert p.kinds() == ["selected-rope"]
    assert (p.n_experts, p.top_k, p.expert_d_ff) == (E, TOPK, F)
    assert p.qk_norm and p.router_after_attention
    assert p.activation == "silu" and p.selection == (IH, IK, KEYS)
    assert p.experts_held == (4, 8) and p.vocab_held == (16, 32)


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
    from test_lm_pattern import description as smallthinker

    from metaopt_tpu.models import lm

    model = lm.make_lm(smallthinker([(0, 0), (1, 1)]))
    p = model.pattern
    assert not p.qk_norm and not p.router_after_attention
    assert p.activation == "relu" and p.selection is None
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
    from test_lm_pattern import description as smallthinker, one_device

    from metaopt_tpu.models.lm import LMTrial, train_lm

    hp = smallthinker([(0, 0), (1, 1)], lr=1e-3)
    kw = dict(mesh=one_device(), n_train=8, batch_size=2, seq_len=24, seed=4)
    train_lm(hp, steps=2, save_dir=str(tmp_path), **kw)
    trial = LMTrial(hp, steps=2, restore_dir=str(tmp_path), **kw)
    with trial:
        assert np.isfinite(float(trial.step(2)))


# -- what the trace says ----------------------------------------------------------

def test_train_lm_says_which_layers_select_and_counts_their_pairs():
    from test_lm_pattern import one_device

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
        "index_scores": {"route": "xla"}}}
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
    assert (model.d_model, model.n_heads, p.n_kv_heads, p.head_dim) == (
        2048, 32, 4, 128)
    assert (p.n_experts, p.top_k, p.expert_d_ff) == (128, 8, 768)
    assert p.experts_held == (0, 16) and p.vocab_held == (0, 18992)
    assert p.selection == (16, 64, 2048) and p.rope_theta == 1e7
    assert p.activation == "silu" and p.kinds() == ["selected-rope"]
