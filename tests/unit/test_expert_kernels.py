"""The held experts' Pallas kernels (ops/experts.py), interpreted here:
the gating over filled tiles against ``act(g) * u`` and its ``jax.vjp``,
megablox's weight gradient handed its operands row-major against a product
a group, and the whole of ``dropless_experts`` on the route the chip takes
(megablox's products, the kernels) against the float32 reference."""

import functools
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metaopt_tpu.ops import experts as kernels

ACTS = {"relu": jax.nn.relu, "silu": jax.nn.silu}
N, F, TILE = 384, 128, 128
#: rows filled: nothing, one row, a tile's edge, a row past it, all rows
FILLED = [0, 1, TILE, TILE + 1, N]


@pytest.fixture(scope="module")
def operands():
    key = jax.random.PRNGKey(0)
    gu = jax.random.normal(key, (N, 2 * F)).astype(jnp.bfloat16)
    d_h = jax.random.normal(jax.random.fold_in(key, 1), (N, F))
    return gu, d_h.astype(jnp.bfloat16)


@pytest.mark.parametrize("filled", FILLED)
@pytest.mark.parametrize("activation", list(ACTS))
def test_the_gating_is_act_of_the_gate_times_up_in_the_filled_rows(
        operands, activation, filled):
    gu, _ = operands
    act = ACTS[activation]
    wide = gu.astype(jnp.float32)
    want = act(wide[:, :F]) * wide[:, F:]
    h = kernels.gating(gu, jnp.int32(filled), act, TILE, interpret=True)
    assert h.shape == (N, F) and h.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(h[:filled], np.float32),
        np.asarray(want[:filled].astype(jnp.bfloat16), np.float32))


@pytest.mark.parametrize("filled", FILLED)
@pytest.mark.parametrize("activation", list(ACTS))
def test_the_gating_s_gradient_is_its_vjp_in_the_filled_rows(
        operands, activation, filled):
    gu, d_h = operands
    act = ACTS[activation]
    _, back = jax.vjp(lambda x: act(x[:, :F]) * x[:, F:],
                      gu.astype(jnp.float32))
    want, = back(d_h.astype(jnp.float32))
    d_gu = kernels.gating_bwd(d_h, gu, jnp.int32(filled), act, TILE,
                              interpret=True)
    assert d_gu.shape == (N, 2 * F) and d_gu.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(d_gu[:filled], np.float32), np.asarray(want[:filled]),
        rtol=1e-2, atol=1e-6)


def test_a_tile_past_the_filled_rows_is_not_visited(operands):
    """The grid is as long as the filled rows need: behind the tile that
    holds the last of them the output holds what the memory held (the
    interpreter's NaN), not a product."""
    gu, d_h = operands
    filled = jnp.int32(TILE + 1)
    h = kernels.gating(gu, filled, jax.nn.silu, TILE, interpret=True)
    d_gu = kernels.gating_bwd(d_h, gu, filled, jax.nn.silu, TILE,
                              interpret=True)
    for out in (h, d_gu):
        out = np.asarray(out, np.float32)
        assert np.isfinite(out[:2 * TILE]).all()
        assert np.isnan(out[2 * TILE:]).all()


@pytest.mark.parametrize("sizes", [
    [100, 0, 130, 26], [0, 0, 0, 0], [512, 0, 0, 0], [128, 128, 128, 128],
    [1, 2, 3, 300]], ids=lambda s: "-".join(map(str, s)))
@pytest.mark.parametrize("tile", [128, 256])
def test_the_weight_gradient_reads_both_operands_as_they_lie(sizes, tile):
    """Per group ``lhs[rows].T @ rhs[rows]`` from (rows, width) operands,
    groups that start and end inside a tile, empty groups as zeros, and
    NaN in every row past the groups' sum."""
    m, k, n = 512, 256, 128
    key = jax.random.PRNGKey(3)
    lhs = jax.random.normal(key, (m, k)).astype(jnp.bfloat16)
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (m, n))
    rhs = rhs.astype(jnp.bfloat16)
    filled = sum(sizes)
    got = kernels.tgmm(
        lhs.at[filled:].set(jnp.nan), rhs.at[filled:].set(jnp.nan),
        jnp.array(sizes, jnp.int32), (tile, 128, 128), interpret=True)
    ends = np.cumsum(sizes)
    want = np.stack([
        np.asarray(lhs, np.float32)[b - a:b].T
        @ np.asarray(rhs, np.float32)[b - a:b] for a, b in zip(sizes, ends)])
    assert got.shape == (len(sizes), k, n) and got.dtype == jnp.bfloat16
    # float32 sums a tile at a time, rounded once to the operands' dtype:
    # a bfloat16 step at the most
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=2 ** -7, atol=1e-4)


# -- the whole part on the chip's route ----------------------------------------

T, D_MODEL, D_FF, E, TOPK = 512, 128, 128, 16, 3
HI = jax.lax.Precision.HIGHEST
#: share -> (held, whether the router sends every token to experts 0, 1, 2)
ROUTINGS = {"nothing": ((12, 4), True), "a quarter": ((4, 4), False),
            "everything": ((0, E), False),
            "the worst imbalance": ((0, 4), True),
            "32 of 256 at top 8, 512 wide": ((64, 32), False)}
#: the shares that are not E experts D_FF wide at top TOPK by the softmax
#: rule: (experts, top k, an expert's width, the routing rule's arguments);
#: the gated mixed-window cell's regime, 32 held experts that each see a
#: sixteenth of a tile's rows
REGIMES = {"32 of 256 at top 8, 512 wide": (
    256, 8, 512, ("sigmoid", False, True, 2.5))}
PARTS = ["y", "x", "weights", "gate", "up", "down"]


def reference(x, weights, experts, gate, up, down, first, act):
    y = jnp.zeros_like(x)
    for e in range(gate.shape[0]):
        we = jnp.sum(jnp.where(experts == first + e, weights, 0.0), -1)
        hid = act(jnp.dot(x, gate[e], precision=HI)) \
            * jnp.dot(x, up[e], precision=HI)
        y = y + we[:, None] * jnp.dot(hid, down[e], precision=HI)
    return y


@pytest.fixture(scope="module")
def on_the_kernels():
    """{(share, activation): (program's {part}, reference's {part})}: the
    output of ``dropless_experts`` and its gradient to every input, the
    backend read as the TPU and every Pallas call interpreted (1536 rows
    of buffers: three tiles of the gating, twelve of the products)."""
    from metaopt_tpu.models import moe

    patch = pytest.MonkeyPatch()
    patch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.grouped_matmul_impl(T * TOPK, D_MODEL, D_FF) == "megablox"
    patch.setattr(moe, "_kernels", lambda: types.SimpleNamespace(**{
        name: functools.partial(getattr(kernels, name), interpret=True)
        for name in ("gmm", "tgmm", "gating", "gating_bwd")}))
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    x = jax.random.normal(ks[0], (T, D_MODEL))
    cot = jax.random.normal(ks[1], (T, D_MODEL))
    out = {}
    try:
        for share, ((first, count), biased) in ROUTINGS.items():
            n_experts, top_k, d_ff, rule = REGIMES.get(
                share, (E, TOPK, D_FF, ()))
            assert moe.grouped_matmul_impl(T * top_k, D_MODEL, d_ff) \
                == "megablox"
            full = [jax.random.normal(k, shape) * shape[1] ** -0.5
                    for k, shape in zip(ks[2:5], [
                        (n_experts, D_MODEL, d_ff), (n_experts, D_MODEL, d_ff),
                        (n_experts, d_ff, D_MODEL)])]
            logits = 2.0 * jax.random.normal(ks[5], (T, n_experts))
            if biased:
                logits = logits + jnp.zeros((E,)).at[:TOPK].set(50.0)
            weights, experts = moe.route_top_k(logits, top_k,
                                               moe.RoutingRule(*rule))
            mats = [m[first:first + count] for m in full]
            for activation, act in ACTS.items():
                def both(fn):
                    def loss(x, weights, gate, up, down):
                        y = fn(x, weights, gate, up, down)
                        return jnp.sum(y * cot), y
                    grads, y = jax.jit(jax.grad(
                        loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(
                            x, weights, *mats)
                    return {k: np.asarray(v)
                            for k, v in zip(PARTS, (y,) + grads)}

                out[share, activation] = (
                    both(lambda x, w, g, u, d: moe.dropless_experts(
                        x, w, experts, g, u, d, first, act)[0]),
                    both(lambda x, w, g, u, d: reference(
                        x, w, experts, g, u, d, first, act)))
    finally:
        patch.undo()
    return out


@pytest.mark.parametrize("part", PARTS)
@pytest.mark.parametrize("activation", list(ACTS))
@pytest.mark.parametrize("share", list(ROUTINGS))
def test_on_the_chip_s_route_output_and_gradients_match(on_the_kernels, share,
                                                        activation, part):
    """bfloat16 products against float32, as test_lm_pattern_experts.py holds the
    other route: a fiftieth of the reference's norm, a tenth for the two
    gradients that pass through ReLU's step."""
    mine, ref = on_the_kernels[share, activation]
    assert mine[part].shape == ref[part].shape
    loose = activation == "relu" and part in ("x", "gate")
    assert np.linalg.norm(mine[part] - ref[part]) <= (
        0.1 if loose else 0.02) * max(np.linalg.norm(ref[part]), 1e-6)
    if share == "nothing":
        assert not np.any(mine[part])


# -- experts of two matrices: no gate, an activation of its own ----------------

RELU2 = lambda x: jnp.square(jax.nn.relu(x))  # noqa: E731
#: a width of no whole lanes, as the one-sublayer cell's 1856 = 29 x 64 is
ODD = 192


@pytest.mark.parametrize("filled", FILLED)
def test_the_activation_s_pass_and_its_gradient_in_the_filled_rows(filled):
    """``h = act(u)`` over the ONE product of experts that are not gated,
    (n, f) at a width of a lane and a half, and its ``jax.vjp``; a tile
    past the filled rows is not visited."""
    key = jax.random.PRNGKey(2)
    u = jax.random.normal(key, (N, ODD)).astype(jnp.bfloat16)
    d_h = jax.random.normal(jax.random.fold_in(key, 1),
                            (N, ODD)).astype(jnp.bfloat16)
    want, back = jax.vjp(RELU2, u.astype(jnp.float32))
    h = kernels.activation(u, jnp.int32(filled), RELU2, TILE, interpret=True)
    d_u = kernels.activation_bwd(d_h, u, jnp.int32(filled), RELU2, TILE,
                                 interpret=True)
    assert h.shape == d_u.shape == (N, ODD) and h.dtype == d_u.dtype == u.dtype
    np.testing.assert_array_equal(
        np.asarray(h[:filled], np.float32),
        np.asarray(want[:filled].astype(jnp.bfloat16), np.float32))
    np.testing.assert_allclose(
        np.asarray(d_u[:filled], np.float32),
        np.asarray(back(d_h.astype(jnp.float32))[0][:filled]),
        rtol=1e-2, atol=1e-6)
    visited = -(-filled // TILE) * TILE
    for out in (h, d_u):
        assert np.isnan(np.asarray(out[visited:], np.float32)).all()


@pytest.fixture(scope="module")
def two_matrices_on_the_kernels():
    """(program's {part}, reference's {part}) of ``dropless_experts``
    without a gate, squared ReLU, experts ``ODD`` wide: the backend read as
    the TPU, every Pallas call interpreted, the width one block."""
    from metaopt_tpu.models import moe

    patch = pytest.MonkeyPatch()
    patch.setattr(jax, "default_backend", lambda: "tpu")
    patch.setattr(moe, "_kernels", lambda: types.SimpleNamespace(**{
        name: functools.partial(getattr(kernels, name), interpret=True)
        for name in ("gmm", "tgmm", "activation", "activation_bwd")}))
    ks = jax.random.split(jax.random.PRNGKey(12), 5)
    x = jax.random.normal(ks[0], (T, D_MODEL))
    cot = jax.random.normal(ks[1], (T, D_MODEL))
    first, count = 4, 8
    up = jax.random.normal(ks[2], (E, D_MODEL, ODD)) * D_MODEL ** -0.5
    down = jax.random.normal(ks[3], (E, ODD, D_MODEL)) * ODD ** -0.5
    weights, experts = moe.route_top_k(
        2.0 * jax.random.normal(ks[4], (T, E)), TOPK)
    mats = [m[first:first + count] for m in (up, down)]

    def plain(x, w, u, d):
        y = jnp.zeros_like(x)
        for e in range(count):
            we = jnp.sum(jnp.where(experts == first + e, w, 0.0), -1)
            y = y + we[:, None] * jnp.dot(
                RELU2(jnp.dot(x, u[e], precision=HI)), d[e], precision=HI)
        return y

    def both(fn):
        def loss(x, w, u, d):
            y = fn(x, w, u, d)
            return jnp.sum(y * cot), y
        grads, y = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3),
                                    has_aux=True))(x, weights, *mats)
        return dict(zip(TWO, map(np.asarray, (y,) + grads)))

    try:
        assert moe.grouped_matmul_impl(T * TOPK, D_MODEL, ODD) == "megablox"
        said = moe.describe_experts(T * TOPK, D_MODEL, ODD, gated=False)
        return (both(lambda x, w, u, d: moe.dropless_experts(
            x, w, experts, None, u, d, first, RELU2)[0]), both(plain), said)
    finally:
        patch.undo()


TWO = ["y", "x", "weights", "up", "down"]


@pytest.mark.parametrize("part", TWO)
def test_two_matrix_experts_on_the_chip_s_route_match(
        two_matrices_on_the_kernels, part):
    """Output and every gradient, bfloat16 products against float32: a
    fiftieth of the reference's norm, a tenth for what passes through the
    squared ReLU's kink."""
    mine, ref, _ = two_matrices_on_the_kernels
    assert mine[part].shape == ref[part].shape
    assert np.linalg.norm(mine[part] - ref[part]) <= (
        0.1 if part in ("x", "up") else 0.02) * np.linalg.norm(ref[part])


def test_the_span_says_how_an_odd_width_is_tiled(two_matrices_on_the_kernels):
    said = two_matrices_on_the_kernels[2]
    assert said["gate_up"] == f"no gate: one product of {ODD} columns"
    assert said["products"] == "megablox" and said["gating"] == "pallas"
    assert said["width"].startswith(f"{ODD} is no whole lanes: one block")
    tiles = said["tiles"]
    assert tiles["gu"][2] == ODD and tiles["out"][1] == ODD
    assert tiles["d_w_gu"][2] == ODD and tiles["d_w_down"][1] == ODD


def test_the_models_load_without_the_kernels_file():
    """models/lm.py imports models/moe.py (through the description's reader)
    at its top: neither pulls in ops/experts.py (Pallas, megablox) before an expert
    layer on the chip's route asks for it, so no cell's set-up grows."""
    code = ("import sys; import metaopt_tpu.models.lm; "
            "assert 'metaopt_tpu.ops.experts' not in sys.modules; "
            "assert not any('megablox' in m for m in sys.modules)")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})
