"""The embedding's lookup and its gradient rule (metaopt_tpu/ops/embed.py).

The sorted rule's kernel runs in the interpreter here; what Mosaic makes of
it is tests/unit/test_attention_tpu_compile.py's. The forward is held to
``nn.Embed(dtype=bfloat16)``'s lookup bit for bit, the gradient to a plain
float32 scatter-add of the rows autodiff hands the rule (the cotangent of a
bfloat16 output: bfloat16 rows, summed in float32).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from metaopt_tpu.ops import embed
from metaopt_tpu.ops.embed import embed_gradient_route, embed_rows

#: the five decoder cells' widths (2048, 2560, 3840) at a sixteenth
WIDTHS = [128, 160, 240]
ROWS = 200  # a table of 25 groups of eight rows
TOKENS = 300  # more than a block of the kernel holds


def _ids(kind, rows=ROWS, tokens=TOKENS):
    """(1, tokens) int32: the ways a step's ids can lie."""
    from metaopt_tpu.models.data import synthetic_lm

    rng = np.random.default_rng(11)
    if kind == "distinct":
        ids = rng.permutation(max(rows, tokens))[:tokens] % rows \
            if rows < tokens else rng.permutation(rows)[:tokens]
    elif kind == "equal":
        ids = np.full(tokens, 7)
    elif kind == "sorted":
        ids = np.sort(rng.integers(0, rows, tokens))
    elif kind == "reversed":
        ids = np.sort(rng.integers(0, rows, tokens))[::-1]
    elif kind == "walk":  # synthetic_lm's permutation, on a short cycle
        row = np.asarray(synthetic_lm(jax.random.PRNGKey(3), 1, tokens,
                                      vocab=29))[0]
        assert len(set(row.tolist())) <= 27
        ids = row
    else:
        raise ValueError(kind)
    return jnp.asarray(np.asarray(ids).reshape(1, -1), jnp.int32)


def _table_and_rows(width, rows=ROWS, tokens=TOKENS, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((rows, width)), jnp.float32),
            jnp.asarray(rng.standard_normal((1, tokens, width)), jnp.float32))


def _gradient(table, ids, g, **how):
    return jax.jit(jax.grad(lambda t: jnp.sum(
        embed_rows(t, ids, **how).astype(jnp.float32) * g)))(table)


def _plain(table, ids, g):
    """The float32 scatter-add of the rows the rule is handed."""
    handed = g.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.zeros(table.shape, jnp.float32).at[ids.reshape(-1)].add(
        handed.reshape(-1, table.shape[1]))


@pytest.mark.parametrize("how", [{}, {"interpret": True}],
                         ids=["take", "sorted"])
@pytest.mark.parametrize("width", WIDTHS)
def test_the_forward_is_nn_embed_s_lookup_to_the_bit(width, how):
    table, _ = _table_and_rows(width)
    ids = _ids("distinct")
    want = nn.Embed(ROWS, width, dtype=jnp.bfloat16).apply(
        {"params": {"embedding": table}}, ids)
    got = jax.jit(functools.partial(embed_rows, **how))(table, ids)
    assert got.dtype == jnp.bfloat16 and got.shape == (1, TOKENS, width)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("kind", ["distinct", "equal", "sorted", "reversed",
                                  "walk"])
@pytest.mark.parametrize("width", WIDTHS)
def test_the_gradient_is_the_float32_sum_of_the_rows_an_id_names(width, kind):
    table, g = _table_and_rows(width)
    ids = _ids(kind)
    got = _gradient(table, ids, g, interpret=True)
    assert got.dtype == jnp.float32 and got.shape == table.shape
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_plain(table, ids, g)),
                               rtol=1e-6, atol=1e-6)


def test_equal_ids_are_summed_wider_than_bfloat16_adds_them():
    """300 rows on one id: the float32 sum, where a bfloat16 accumulator
    (autodiff's scatter-add into a bfloat16 table) loses the small ones."""
    table, g = _table_and_rows(128)
    ids = _ids("equal")
    exact = np.asarray(_plain(table, ids, g))[7]
    sorted_rule = np.asarray(_gradient(table, ids, g, interpret=True))[7]
    take = np.asarray(_gradient(table, ids, g))[7]
    assert np.abs(sorted_rule - exact).max() <= 1e-5
    assert np.abs(take - exact).max() > 10 * np.abs(sorted_rule - exact).max()


@pytest.mark.parametrize("rows, tokens", [(77, 37), (1000, 5), (50, 700),
                                          (8, 256)])
def test_any_rows_and_tokens(rows, tokens):
    """Rows that are no whole groups of eight, tokens that are no whole
    blocks: the padding writes nothing."""
    table, g = _table_and_rows(128, rows, tokens, seed=2)
    ids = jnp.asarray(np.random.default_rng(5).integers(
        0, rows, (1, tokens)), jnp.int32)
    np.testing.assert_allclose(
        np.asarray(_gradient(table, ids, g, interpret=True)),
        np.asarray(_plain(table, ids, g)), rtol=1e-6, atol=1e-6)


def test_an_id_outside_the_table_names_no_row():
    """What the scatter-add's ``mode="drop"`` did: no row is written for
    it, and no other row moves."""
    table, g = _table_and_rows(128, 40, 64)
    inside = np.random.default_rng(1).integers(0, 40, (1, 64))
    ids = inside.copy()
    ids[0, ::5] = [-3, 40, 41, 1 << 20, -1, 47, 48, 40, -8, 99, 64, 72, 400]
    masked = jnp.where(jnp.asarray(ids == inside)[..., None], g, 0.0)
    np.testing.assert_allclose(
        np.asarray(_gradient(table, jnp.asarray(ids, jnp.int32), g,
                             interpret=True)),
        np.asarray(_plain(table, jnp.asarray(inside, jnp.int32), masked)),
        rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("how", [{}, {"interpret": True}],
                         ids=["take", "sorted"])
def test_a_tied_table_s_gradient_is_the_lookup_s_plus_the_head_s(how):
    """The table embeds and reads out: autodiff adds the head's matmul
    gradient to the rule's table."""
    table, _ = _table_and_rows(128, 64, 48)
    ids = _ids("walk", 64, 48)
    targets = jnp.roll(ids, -1, axis=1)

    def loss(embedded, read):
        x = embed_rows(embedded, ids, **how).astype(jnp.float32)
        logits = jnp.einsum("btd,vd->btv", x, read)
        return -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), targets[..., None], -1))

    got = jax.jit(jax.grad(lambda t: loss(t, t)))(table)
    lookup, head = jax.jit(jax.grad(loss, argnums=(0, 1)))(table, table)
    np.testing.assert_allclose(np.asarray(got), np.asarray(lookup + head),
                               rtol=1e-6, atol=1e-7)
    assert float(jnp.abs(head).max()) > 0 and float(jnp.abs(lookup).max()) > 0
    named = np.zeros(64, bool)
    named[np.asarray(ids).reshape(-1)] = True
    assert not np.asarray(lookup)[~named].any()


@pytest.mark.parametrize("shape", [(1, 1), (2, 1), (1, 2), (2, 2), (4, 2)],
                         ids=lambda s: f"dp{s[0]}xtp{s[1]}")
def test_under_a_mesh_the_gradient_is_the_same(shape):
    """On the dp x tp meshes ``init_sharded_lm`` places a model on: the
    table whole on every device, the ids a dp share each."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from metaopt_tpu.parallel.mesh import use_mesh

    mesh = Mesh(np.array(jax.devices()[:shape[0] * shape[1]]).reshape(shape),
                ("dp", "tp"))
    rng = np.random.default_rng(4)
    table = jnp.asarray(rng.standard_normal((ROWS, 128)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, ROWS, (4, 32)), jnp.int32)
    g = jnp.asarray(rng.standard_normal((4, 32, 128)), jnp.float32)
    with use_mesh(mesh):
        assert embed_gradient_route(mesh) == "take"  # the CPU's, any mesh
        got = jax.jit(
            jax.grad(lambda t, i: jnp.sum(
                embed_rows(t, i).astype(jnp.float32) * g)),
            in_shardings=(NamedSharding(mesh, P()),
                          NamedSharding(mesh, P("dp"))))(table, ids)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_plain(table, ids, g)),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("backend, devices, route", [
    ("tpu", None, "sorted"), ("tpu", 1, "sorted"), ("tpu", 2, "take"),
    ("tpu", 8, "take"), ("cpu", None, "take"), ("cpu", 1, "take"),
    ("gpu", 1, "take")])
def test_one_rule_names_the_route(monkeypatch, backend, devices, route):
    """The sorted rule on one TPU device, the plain lookup elsewhere; the
    op asks the same function about the mesh that is active."""
    from jax.sharding import Mesh

    from metaopt_tpu.parallel.mesh import use_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    mesh = devices and Mesh(np.array(jax.devices()[:devices]), ("dp",))
    assert embed_gradient_route(mesh) == route
    taken = []
    monkeypatch.setattr(embed, "_sorted", lambda table, ids, interpret: (
        taken.append("sorted"), embed._take(table, ids))[1])
    table, ids = jnp.zeros((16, 128)), jnp.zeros((1, 8), jnp.int32)
    if mesh is None:
        embed_rows(table, ids)
    else:
        with use_mesh(mesh):
            embed_rows(table, ids)
    assert taken == (["sorted"] if route == "sorted" else [])


def test_the_kernel_runs_once_in_a_gradient_and_not_in_the_forward():
    from lm_pattern_cases import _equations

    table, g = _table_and_rows(128)
    ids = _ids("sorted")
    forward = jax.make_jaxpr(lambda t: embed_rows(t, ids, interpret=True))(
        table)
    assert [e for e in _equations(forward.jaxpr)
            if e.primitive.name == "pallas_call"] == []
    backward = jax.make_jaxpr(jax.grad(lambda t: jnp.sum(
        embed_rows(t, ids, interpret=True).astype(jnp.float32) * g)))(table)
    calls = [e.params["name"] for e in _equations(backward.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert calls == ["embed_rows_bwd"]
    assert "scatter-add" not in {e.primitive.name
                                 for e in _equations(backward.jaxpr)}


@pytest.mark.parametrize("tied, said", [
    (False, "37984 rows x 2560, 8192 tokens a step, gradient by sorted"),
    (True, "25008 rows x 2560, the head's table too, 8192 tokens a step, "
           "gradient by sorted")])
def test_the_reader_prints_the_table_and_its_gradient_s_route(capsys, tied,
                                                              said):
    """``python -m metaopt_tpu.utils.trace``'s line for what
    ``describe_pattern`` puts into ``trial.setup``'s span."""
    from metaopt_tpu.utils import trace

    trace.print_routes([{"name": "trial.setup", "trial": "T-1", "attrs": {
        "attention": {"dropout": 0.0, "train": "pallas", "eval": "pallas"},
        "embed": {"gradient": "sorted", "rows": 25008 if tied else 37984,
                  "width": 2560, "tokens": 8192, "tied": tied}}}])
    assert capsys.readouterr().out.splitlines()[1:] == [
        "trial T-1: embedding: " + said]
