"""Attention over the keys an indexer selects, the parts below the model:
the selection rule alone (ops/sparse_index.py), the index scores' kernel
(interpreted) against a float64 sum and against its plain twin, the rule
that names which of the two runs, and the kernels that take the selection
as packed bits (ops/selected_attention.py, interpreted) against plain
attention under the same mask. (One file with test_lm_selected.py until PR
44: split by topic, a file under 300 s of one worker.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from lm_selected_cases import IH, IK, S, V, description


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
    from metaopt_tpu.models import lm_description

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    hp = description(sa_config={
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "topk": 2048})
    layers = lm_description.describe_pattern(hp, "pallas", tokens=seq_len,
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
