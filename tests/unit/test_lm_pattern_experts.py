"""The pattern decoder's dropless expert layer (models/moe.py) against
plain float32 references written here: the shares of a deployment adding
up to the uncut layer, no item dropped under the worst imbalance, the
routing's passes over several chunks, and the held experts' part touching
the filled rows only. (One file with test_lm_pattern.py until PR 44.)"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from lm_pattern_cases import (D, E, F, HI, TOPK, _equations, ref_moe)


# -- the expert layer ----------------------------------------------------------

def expert_layer(held, logits_bias=None, seed=3, t=40, activation="relu"):
    """(program's output and counts, reference's output, the uncut
    reference's) of one DroplessMoE over ``held``, all shares from one set
    of weights."""
    return _expert_layer(held, logits_bias, seed, t, activation)[:4]


#: the laguna family's layer at this file's sizes: sigmoid scores
#: normalised over the chosen and scaled, top 4, beside one shared expert
GATED = {"top_k": 4, "normalised": True, "scale": 2.5}


#: the nemotron_h family's layer at this file's sizes: experts of two
#: matrices under a squared ReLU, a correction bias in the choice, a shared
#: expert twice as wide
TWO_MATRICES = {"top_k": 3, "normalised": True, "scale": 2.5}


def _two_matrix_layer(held, seed=3, t=40):
    """:func:`_expert_layer`'s five for the layer by ``TWO_MATRICES``, its
    references chipbench/reference/ssd_lm.py's."""
    from chipbench.reference import ssd_lm as reference
    from metaopt_tpu.models.lm_layers import PlainFeedForward
    from metaopt_tpu.models.moe import DroplessMoE, RoutingRule

    layer = lambda held: DroplessMoE(  # noqa: E731
        D, F, E, TWO_MATRICES["top_k"], held, "relu2",
        RoutingRule("sigmoid", True, True, TWO_MATRICES["scale"]), 2 * F,
        gated=False)
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (1, t, D))
    logits = 2.0 * jax.random.normal(jax.random.fold_in(key, 1), (1, t, E))
    bias = 0.05 * jax.random.normal(jax.random.fold_in(key, 2), (E,))
    full = nn.meta.unbox(layer((0, E)).init(key, x, logits, bias)["params"])
    assert set(full) == {"up", "down", "shared"}          # no gate anywhere
    assert set(full["shared"]) == {"up", "down"}
    first, count = held
    mine = {k: v if k == "shared" else v[first:first + count]
            for k, v in full.items()}
    y, state = layer(held).apply({"params": mine}, x, logits, bias,
                                 mutable=["moe_stats"])

    def ref(p, held):
        cfg = {**TWO_MATRICES, "experts_held": list(held)}
        each = {k: {f"e{e:02d}": p[k][e] for e in range(held[1])}
                for k in ("up", "down")}
        return reference._experts(
            "float32", each, x[0],
            reference.routing_weights(logits[0], bias, cfg), held[0], ()) \
            + reference._plain("float32", p["shared"]["up"]["kernel"],
                               p["shared"]["down"]["kernel"], x[0], ())

    alike = PlainFeedForward(D, 2 * F, "relu2").apply(
        {"params": full["shared"]}, x)[0].astype(jnp.float32)
    return y[0], state["moe_stats"], ref(mine, held), ref(full, (0, E)), alike


#: the lfm2_moe family's layer at this file's sizes: gated SiLU experts, top
#: 4 of score + a correction bias, the chosen scores over their sum + 1e-6,
#: no shared expert
LFM2 = {"top_k": 4, "normalised": True, "scale": 1.0, "routing_eps": 1e-6,
        "use_bias": True}


def _lfm2_layer(held, seed=3, t=40):
    """:func:`_expert_layer`'s five for the layer by ``LFM2``, its
    references chipbench/reference/conv_lm.py's."""
    from chipbench.reference import conv_lm as reference
    from metaopt_tpu.models.moe import DroplessMoE, RoutingRule

    layer = lambda held: DroplessMoE(  # noqa: E731
        D, F, E, LFM2["top_k"], held, "silu",
        RoutingRule("sigmoid", True, True, 1.0, eps=1e-6), 0)
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (1, t, D))
    logits = 2.0 * jax.random.normal(jax.random.fold_in(key, 1), (1, t, E))
    bias = 0.05 * jax.random.normal(jax.random.fold_in(key, 2), (E,))
    full = nn.meta.unbox(layer((0, E)).init(key, x, logits, bias)["params"])
    assert set(full) == {"gate", "up", "down"}            # no shared expert
    first, count = held
    mine = {k: v[first:first + count] for k, v in full.items()}
    y, state = layer(held).apply({"params": mine}, x, logits, bias,
                                 mutable=["moe_stats"])

    def ref(p, held):
        cfg = {**LFM2, "experts_held": list(held)}
        each = {k: {f"e{e:02d}": p[k][e] for e in range(held[1])}
                for k in ("gate", "up", "down")}
        return reference._experts(
            "float32", each, x[0],
            reference.routing_weights(logits[0], bias, cfg), held[0],
            jax.nn.silu)

    return y[0], state["moe_stats"], ref(mine, held), ref(full, (0, E)), 0.0


def _expert_layer(held, logits_bias=None, seed=3, t=40, activation="relu",
                  gated=False):
    """:func:`expert_layer`'s four and what every share computes alike (a
    shared expert's output; 0 without one). ``gated``: the layer by
    ``GATED``, its references chipbench/reference/gated_lm.py's."""
    from metaopt_tpu.models.lm_layers import GatedFeedForward
    from metaopt_tpu.models.moe import DroplessMoE, RoutingRule

    if activation == "relu2":
        return _two_matrix_layer(held, seed, t)
    if activation == "lfm2":
        return _lfm2_layer(held, seed, t)
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[activation]
    top_k = GATED["top_k"] if gated else TOPK
    layer = lambda held: DroplessMoE(  # noqa: E731
        D, F, E, top_k, held, activation,
        RoutingRule("sigmoid", False, True, GATED["scale"]) if gated
        else RoutingRule(), F if gated else 0)

    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (1, t, D))
    logits = 2.0 * jax.random.normal(jax.random.fold_in(key, 1), (1, t, E))
    if logits_bias is not None:
        logits = logits + logits_bias
    full = nn.meta.unbox(layer((0, E)).init(key, x, logits)["params"])
    first, count = held
    mine = {k: v if k == "shared" else v[first:first + count]
            for k, v in full.items()}
    y, state = layer(held).apply({"params": mine}, x, logits,
                                 mutable=["moe_stats"])
    if not gated:
        ref = ref_moe(x[0], logits[0], full_as_ref(mine), held, act)
        return y[0], state["moe_stats"], ref, ref_moe(
            x[0], logits[0], full_as_ref(full), (0, E), act), 0.0
    from chipbench.reference import gated_lm as reference

    def ref_gated(p, held):
        cfg = {**GATED, "experts_held": list(held)}
        each = {k: {f"e{e:02d}": p[k][e] for e in range(held[1])}
                for k in ("gate", "up", "down")}
        return reference._experts(
            "float32", each, x[0], reference.routing_weights(logits[0], cfg),
            held[0], act) + reference._gated("float32", p["shared"], x[0],
                                             act)

    alike = GatedFeedForward(D, F, activation).apply(
        {"params": full["shared"]}, x)[0].astype(jnp.float32)
    return (y[0], state["moe_stats"], ref_gated(mine, held),
            ref_gated(full, (0, E)), alike)


def full_as_ref(full):
    return {k: v.astype(jnp.float32) for k, v in full.items()}


SHARES = [(0, 4), (4, 4), (8, 4), (12, 4)]


@pytest.mark.parametrize("held", SHARES + [(0, 16), (3, 7)])
def test_a_share_gives_its_own_experts_part(held):
    y, _, ref, _ = expert_layer(held)
    assert np.linalg.norm(y - ref) <= 0.02 * max(np.linalg.norm(ref), 1e-6)


@pytest.mark.parametrize("shares, activation, gated", [
    (SHARES, "relu", False),
    ([(first, 2) for first in range(0, E, 2)], "silu", False),
    ([(first, 2) for first in range(0, E, 2)], "silu", True),
    ([(first, 2) for first in range(0, E, 2)], "relu2", False),
    ([(first, 8) for first in (0, 8)], "relu2", False),
    ([(first, 2) for first in range(0, E, 2)], "lfm2", False)],
    ids=["four-shares-relu", "eight-shares-silu",
         "eight-shares-sigmoid-top4-shared",
         "eight-shares-two-matrices-bias-shared",
         "two-shares-two-matrices-bias-shared",
         "eight-shares-silu-bias-top4-eps-no-shared"])
def test_the_shares_add_up_to_the_uncut_layer(shares, activation, gated):
    """16 experts, top 3, four shares of 4 (gated ReLU) or eight of 2
    (gated SiLU): the partial outputs sum to what the uncut reference
    gives for the whole layer. And the laguna family's layer (sigmoid
    scores normalised over all the chosen, times 2.5, top 4, beside a
    shared expert): the eight shares' routed parts plus the shared expert,
    which every share computes alike, counted once. And the nemotron_h
    family's (experts of two matrices under a squared ReLU, no gate, top 3
    of score + a correction bias, a shared expert twice as wide): eight
    shares of two experts, and two of eight. And the lfm2_moe family's
    (gated SiLU experts, top 4 of score + a correction bias, the chosen
    scores over their sum + 1e-6, no shared expert): eight shares of two."""
    parts = [_expert_layer(held, activation=activation, gated=gated)
             for held in shares]
    total = sum(p[0] for p in parts) - (len(parts) - 1) * parts[0][4]
    uncut = parts[0][3]
    assert np.linalg.norm(total - uncut) <= 0.02 * np.linalg.norm(uncut)
    # and no share is idle: each adds something of its own
    assert all(np.linalg.norm(p[0] - p[4]) > 0.05 * np.linalg.norm(uncut)
               for p in parts)
    # a share's own part is the reference's for that share
    assert all(np.linalg.norm(p[0] - p[2]) <= 0.02 * np.linalg.norm(uncut)
               for p in parts)


def test_the_lfm2_family_s_rule_top_4_of_64_by_score_and_bias():
    """The 4 largest of s + b choose; the weights are s / (sum of the four
    s + 1e-6), the bias in neither; written out here in numpy."""
    from metaopt_tpu.models.moe import RoutingRule, route_top_k

    key = jax.random.PRNGKey(7)
    logits = 2.0 * jax.random.normal(key, (50, 64))
    bias = 0.3 * jax.random.normal(jax.random.fold_in(key, 1), (64,))
    rule = RoutingRule("sigmoid", bias=True, normalised=True, scale=1.0,
                       eps=1e-6)
    weights, experts = route_top_k(logits, 4, rule, bias)
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    order = np.argsort(-(s + np.asarray(bias, np.float64)), axis=1,
                       kind="stable")[:, :4]
    assert (np.sort(order, axis=1) == np.sort(np.asarray(experts),
                                              axis=1)).all()
    chosen = np.take_along_axis(s, np.asarray(experts), axis=1)
    np.testing.assert_allclose(
        weights, chosen / (chosen.sum(axis=1, keepdims=True) + 1e-6),
        rtol=2e-6)
    # the bias moved some choices and is in no weight
    plain = np.argsort(-s, axis=1, kind="stable")[:, :4]
    assert (np.sort(plain, axis=1) != np.sort(order, axis=1)).any()


@pytest.mark.parametrize("eps", [None, 1e-6, 0.5])
def test_the_rule_s_epsilon_is_a_field_whose_default_is_the_old_literal(eps):
    """``RoutingRule.eps`` defaults to 1e-20, which every standing family
    keeps; a family's own is read where the literal stood (0.5 shows)."""
    from metaopt_tpu.models.moe import RoutingRule, route_top_k

    assert RoutingRule().eps == 1e-20
    assert RoutingRule("sigmoid", True, True, 2.5) \
        == RoutingRule("sigmoid", True, True, 2.5, 1e-20)
    rule = RoutingRule("sigmoid") if eps is None \
        else RoutingRule("sigmoid", eps=eps)
    logits = jax.random.normal(jax.random.PRNGKey(1), (9, E))
    weights, experts = route_top_k(logits, 3, rule)
    chosen = np.take_along_axis(np.asarray(jax.nn.sigmoid(logits)),
                                np.asarray(experts), axis=1)
    np.testing.assert_allclose(
        weights, chosen / (chosen.sum(axis=1, keepdims=True)
                           + (1e-20 if eps is None else eps)), rtol=2e-6)


@pytest.mark.parametrize("held", [(0, 4), (4, 4), (0, 16)])
def test_nothing_is_dropped_under_the_worst_imbalance(held):
    """A router biased so that every token picks experts 0, 1, 2: a share
    that holds them gets all t x k items, another none; both equal the
    reference and drop nothing."""
    bias = jnp.zeros((E,)).at[:TOPK].set(50.0)
    y, stats, ref, _ = expert_layer(held, logits_bias=bias)
    items = np.asarray(stats["items"][0])
    assert int(stats["dropped"][0]) == 0
    assert items.sum() == (40 * TOPK if held[0] == 0 else 0)
    assert np.linalg.norm(y - ref) <= 0.02 * max(np.linalg.norm(ref), 1e-6)


def test_the_counts_are_the_routing_s():
    _, stats, _, _ = expert_layer((4, 4))
    key = jax.random.PRNGKey(3)
    logits = 2.0 * jax.random.normal(jax.random.fold_in(key, 1), (40, E))
    _, idx = jax.lax.top_k(logits, TOPK)
    want = [int(jnp.sum(idx == e)) for e in range(4, 8)]
    assert np.asarray(stats["items"][0]).tolist() == want


def test_held_experts_outside_the_routed_ones_are_refused():
    from metaopt_tpu.models.moe import DroplessMoE

    x = jnp.zeros((1, 4, D))
    with pytest.raises(ValueError, match="held"):
        DroplessMoE(D, F, E, TOPK, (14, 4)).init(
            jax.random.PRNGKey(0), x, jnp.zeros((1, 4, E)))


@pytest.mark.parametrize("ep", [2, 4])
def test_on_an_ep_axis_each_chip_holds_its_part_and_the_sum_is_the_layer(ep):
    from jax.sharding import Mesh

    from metaopt_tpu.models.moe import DroplessMoE
    from metaopt_tpu.parallel.mesh import use_mesh

    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (2, 12, D))
    logits = 2.0 * jax.random.normal(jax.random.fold_in(key, 1), (2, 12, E))
    layer = DroplessMoE(D, F, E, TOPK, (4, 8))
    params = nn.meta.unbox(layer.init(key, x, logits)["params"])
    alone, one = layer.apply({"params": params}, x, logits,
                             mutable=["moe_stats"])
    mesh = Mesh(np.array(jax.devices()[:ep]).reshape(1, 1, ep),
                ("dp", "tp", "ep"))
    with use_mesh(mesh):
        shared, many = jax.jit(lambda p: layer.apply(
            {"params": p}, x, logits, mutable=["moe_stats"]))(params)
    np.testing.assert_allclose(np.asarray(shared), np.asarray(alone),
                               rtol=2e-2, atol=2e-3)
    assert np.asarray(many["moe_stats"]["items"][0]).tolist() \
        == np.asarray(one["moe_stats"]["items"][0]).tolist()
    assert int(many["moe_stats"]["dropped"][0]) == 0
    # 24 tokens x top-k rows are one chunk: the fullest chip's one trip
    assert int(many["moe_stats"]["chunks"][0]) == 1 == int(
        one["moe_stats"]["chunks"][0])


# -- the routing's passes over several chunks ----------------------------------

T_LONG = 4000   # t x k = 12 000 rows: five chunks of 2048 and a part of one


def ref_experts(x, weights, experts, gate, up, down, first,
                act=jax.nn.relu):
    """The held experts' part from the routing itself, float32: what
    ``ref_moe`` does after its own top-k."""
    y = jnp.zeros_like(x)
    for e in range(gate.shape[0]):
        we = jnp.sum(jnp.where(experts == first + e, weights, 0.0), -1)
        hid = act(jnp.dot(x, gate[e], precision=HI)) \
            * jnp.dot(x, up[e], precision=HI)
        y = y + we[:, None] * jnp.dot(hid, down[e], precision=HI)
    return y


#: share -> (held, whether the router sends every token to experts 0, 1, 2)
ROUTINGS = {"nothing": ((12, 4), True), "a quarter": ((4, 4), False),
            "everything": ((0, E), False), "the worst imbalance": ((0, 4),
                                                                   True)}
PARTS = ["y", "x", "weights", "gate", "up", "down"]


@pytest.fixture(scope="module")
def routed():
    """{share: (program's {part: array}, counts; reference's {part})}: the
    output and the gradient to each input of ``dropless_experts`` over
    T_LONG tokens, where the filled rows span several of the loops' chunks
    and end inside one."""
    from metaopt_tpu.models.moe import dropless_experts, route_top_k

    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (T_LONG, D))
    cot = jax.random.normal(ks[1], (T_LONG, D))
    full = [jax.random.normal(k, shape) * shape[1] ** -0.5 for k, shape in
            zip(ks[2:5], [(E, D, F), (E, D, F), (E, F, D)])]
    out = {}
    for share, ((first, count), biased) in ROUTINGS.items():
        logits = 2.0 * jax.random.normal(ks[5], (T_LONG, E))
        if biased:
            logits = logits + jnp.zeros((E,)).at[:TOPK].set(50.0)
        weights, experts = route_top_k(logits, TOPK)
        mats = [m[first:first + count] for m in full]

        def both(fn):
            """{part: array} and what ``fn`` counted, ``fn`` -> (y, counts)."""
            def loss(x, weights, gate, up, down):
                y, counts = fn(x, weights, gate, up, down)
                return jnp.sum(y * cot), (y, counts)
            grads, (y, counts) = jax.grad(
                loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(
                    x, weights, *mats)
            return dict(zip(PARTS, (y,) + grads)), counts

        mine, counts = both(lambda x, w, g, u, d: dropless_experts(
            x, w, experts, g, u, d, first))
        ref, _ = both(lambda x, w, g, u, d: (ref_experts(
            x, w, experts, g, u, d, first), {}))
        out[share] = (mine, {k: np.asarray(v) for k, v in counts.items()},
                      ref)
    return out


@pytest.mark.parametrize("part", PARTS)
@pytest.mark.parametrize("share", list(ROUTINGS))
def test_over_several_chunks_output_and_gradients_match(routed, share, part):
    """bfloat16 products against float32: within a fiftieth of the
    reference's norm (0.005 here), but for the two gradients that pass
    through ReLU's step, which bfloat16 moves for the pre-activations near
    0 (0.06 here at most; a chunk left out or added twice reads 0.15 or
    more)."""
    mine, _, ref = routed[share]
    assert mine[part].shape == ref[part].shape
    assert np.linalg.norm(mine[part] - ref[part]) <= (
        0.1 if part in ("x", "gate") else 0.02) * max(
            np.linalg.norm(ref[part]), 1e-6)
    if share == "nothing":
        assert not np.any(np.asarray(mine[part]))


@pytest.mark.parametrize("share", list(ROUTINGS))
def test_the_loops_run_as_many_trips_as_the_filled_rows_need(routed, share):
    from metaopt_tpu.models.moe import routing_chunk_rows

    _, counts, _ = routed[share]
    n = T_LONG * TOPK
    filled = int(counts["items"].sum())
    low, high = {"nothing": (0, 0), "a quarter": (0.2 * n, 0.3 * n)}.get(
        share, (n, n))
    assert low <= filled <= high
    chunk = routing_chunk_rows(n)
    # several chunks, the filled rows ending inside one
    assert n > 5 * chunk and (filled % chunk or not filled)
    assert int(counts["chunks"]) == -(-filled // chunk)
    assert int(counts["dropped"]) == 0


@pytest.mark.parametrize("share", list(ROUTINGS))
def test_rows_of_the_buffers_past_the_filled_ones_are_zeros(share):
    """Dispatch's buffer and the gradient combine hands the products: the
    filled rows are the items', every row behind them is 0, whatever was
    there (a masked tile of the products may read them)."""
    from metaopt_tpu.models import moe

    (first, count), biased = ROUTINGS[share]
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (T_LONG, D)).astype(jnp.bfloat16)
    logits = jax.random.normal(jax.random.fold_in(key, 1), (T_LONG, E))
    if biased:
        logits = logits + jnp.zeros((E,)).at[:TOPK].set(50.0)
    weights, experts = moe.route_top_k(logits, TOPK)
    local = experts - first
    plan = moe.routing_plan(jnp.where((local >= 0) & (local < count), local,
                                      count).astype(jnp.int32), count)
    filled = int(plan["filled"])
    token = np.asarray(plan["token"])
    rows = np.asarray(moe._dispatch(x, plan), np.float32)
    assert np.array_equal(rows[:filled], np.asarray(x, np.float32)[
        token[:filled]])
    assert not rows[filled:].any()
    out = jnp.full((T_LONG * TOPK, D), jnp.nan, jnp.bfloat16).at[
        :filled].set(1.0)                 # past the filled rows: anything
    g = jax.random.normal(key, (T_LONG, D))
    y, back = jax.vjp(lambda o, w: moe._combine(o, w, plan), out, weights)
    d_out, d_weights = back(g)
    assert np.isfinite(np.asarray(y)).all()
    assert np.isfinite(np.asarray(d_weights)).all()
    d_out = np.asarray(d_out, np.float32)
    assert np.isfinite(d_out).all() and not d_out[filled:].any()
    by_row = np.asarray(weights).reshape(-1)[np.asarray(plan["order"])]
    np.testing.assert_allclose(
        d_out[:filled], (by_row[:filled, None] * np.asarray(g)[
            token[:filled]]).astype(jnp.bfloat16).astype(np.float32),
        rtol=1e-2, atol=1e-6)


def test_no_pass_of_the_routing_has_the_worst_case_s_size():
    """The work the gradient of ``dropless_experts`` asks for at the
    benchmark cell's t and k (d cut; traced, nothing run): all six passes
    of the routing (dispatch, combine and the backward of each) are
    covered. No scatter-add, no gather of t x k rows of width d, and no
    float32 array of (t, k, d): each would be a pass over the buffers'
    worst case come back."""
    from metaopt_tpu.models.moe import dropless_experts

    t, k, d, f, held = 8192, 6, 128, 64, 16
    shapes = [jax.ShapeDtypeStruct(s, dt) for s, dt in [
        ((t, d), jnp.float32), ((t, k), jnp.float32), ((t, k), jnp.int32),
        ((held, d, f), jnp.float32), ((held, d, f), jnp.float32),
        ((held, f, d), jnp.float32)]]

    def loss(x, weights, experts, gate, up, down):
        return jnp.sum(dropless_experts(x, weights, experts, gate, up, down,
                                        0)[0])

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 3, 4, 5)))(*shapes)
    eqns = list(_equations(jaxpr.jaxpr))
    names = {e.primitive.name for e in eqns}
    assert "while" in names and "gather" in names
    assert not {n for n in names if n.startswith("scatter")}
    shapes_out = [(e.primitive.name, v.aval.shape, v.aval.dtype)
                  for e in eqns for v in e.outvars if hasattr(v.aval, "shape")]
    assert [s for s in shapes_out
            if s[0] == "gather" and s[1] == (t * k, d)] == []
    assert [s for s in shapes_out if d in s[1] and s[2] == jnp.float32
            and math.prod(s[1]) >= t * k * d] == []


# -- the held experts' part: the filled rows only ------------------------------

def _routed_inputs(t, d, f, share, seed=11):
    """x, a cotangent, the routing and the held share's three matrices for
    ``share`` of ROUTINGS over ``t`` tokens."""
    from metaopt_tpu.models.moe import route_top_k

    (first, count), biased = ROUTINGS[share]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (t, d))
    cot = jax.random.normal(ks[1], (t, d))
    mats = [jax.random.normal(k, shape)[first:first + count]
            * shape[1] ** -0.5 for k, shape in
            zip(ks[2:5], [(E, d, f), (E, d, f), (E, f, d)])]
    logits = 2.0 * jax.random.normal(ks[5], (t, E))
    if biased:
        logits = logits + jnp.zeros((E,)).at[:TOPK].set(50.0)
    weights, experts = route_top_k(logits, TOPK)
    return x, cot, weights, experts, mats, first


def _output_and_gradients(fn, x, cot, weights, mats):
    """{part: array}: ``fn``'s output and its gradient to each input."""
    def loss(x, weights, gate, up, down):
        y = fn(x, weights, gate, up, down)
        return jnp.sum(y * cot), y
    grads, y = jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(
        x, weights, *mats)
    return {k: np.asarray(v) for k, v in zip(PARTS, (y,) + grads)}


@pytest.mark.parametrize("activation", ["relu", "silu"])
@pytest.mark.parametrize("share", list(ROUTINGS))
def test_what_lies_past_the_filled_rows_reaches_nothing(monkeypatch, share,
                                                        activation):
    """``gu``, ``h``, ``d_h`` and ``d_gu`` hold whatever the passes left
    behind row ``filled``: NaN planted there on the way in and on the way
    out of the gating, forward and backward, reaches no output and no
    gradient, to the bit."""
    from metaopt_tpu.models import moe

    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[activation]
    x, cot, weights, experts, mats, first = _routed_inputs(T_LONG, D, F, share)

    def layer(x, weights, gate, up, down):
        return moe.dropless_experts(x, weights, experts, gate, up, down,
                                    first, act)[0]

    clean = _output_and_gradients(layer, x, cot, weights, mats)
    planted = []

    def past(a, filled):
        planted.append(a.shape)
        return jnp.where(jnp.arange(a.shape[0])[:, None] >= filled, jnp.nan,
                         a)

    gate, gate_bwd = moe._gate, moe._gate_bwd
    monkeypatch.setattr(moe, "_gate", lambda gu, filled, *how: past(
        gate(past(gu, filled), filled, *how), filled))
    monkeypatch.setattr(moe, "_gate_bwd", lambda d_h, gu, filled, *how: past(
        gate_bwd(past(d_h, filled), past(gu, filled), filled, *how), filled))
    dirty = _output_and_gradients(layer, x, cot, weights, mats)
    n = T_LONG * TOPK
    assert sorted(set(planted)) == [(n, F), (n, 2 * F)] and len(planted) >= 5
    for part in PARTS:
        assert np.isfinite(dirty[part]).all(), part
        np.testing.assert_array_equal(dirty[part], clean[part], err_msg=part)


@pytest.mark.parametrize("activation", ["relu", "silu"])
def test_the_held_experts_part_is_the_same_compiled_or_not(activation):
    """``_held_experts`` and its gradient rule give the same bits run
    operation by operation and compiled as one program, as a rematerialised
    block compiles them: what remat moves in a last bit on this CPU
    (test_lm_selected.py) is the routing's float32 sum back to tokens, not
    the experts' part."""
    from metaopt_tpu.models import moe

    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[activation]
    x, cot, _, experts, mats, first = _routed_inputs(T_LONG, D, F,
                                                     "a quarter")
    held = mats[0].shape[0]
    local = experts - first
    plan = moe.routing_plan(jnp.where((local >= 0) & (local < held), local,
                                      held).astype(jnp.int32), held)
    bf = jnp.bfloat16
    rows = moe._dispatch(x.astype(bf), plan)
    d_out = moe._dispatch(cot.astype(bf), plan)
    w_gu = jnp.concatenate(mats[:2], axis=2).astype(bf)

    def part(rows, w_gu, w_down, d_out):
        out, back = jax.vjp(lambda *a: moe._held_experts(
            *a, plan["items"], plan["filled"], act, "ragged_dot"),
            rows, w_gu, w_down)
        return (out,) + back(d_out)

    args = (rows, w_gu, mats[2].astype(bf), d_out)
    for one, other in zip(part(*args), jax.jit(part)(*args)):
        assert np.abs(np.asarray(one, np.float32)).max() > 0
        np.testing.assert_array_equal(np.asarray(one, np.float32),
                                      np.asarray(other, np.float32))


def test_the_experts_passes_touch_the_filled_rows_only(monkeypatch):
    """The work the gradient of ``dropless_experts`` asks for between
    dispatch and combine at the 16k cell's t and k (d, f cut) on the
    megablox route (the backend read as the TPU; traced, nothing run).
    Outside loops and kernels no gating and no sum of two input gradients
    has the buffers' t x k rows; a layer is six grouped calls (gate and up
    as one product: 2 forward, 2 + 2 backward) and the two gating kernels.
    A weight gradient hands megablox's ``tgmm`` its left operand turned
    and ``tgmm`` turns it back before its kernel: a pair the compiler
    cancels (test_attention_tpu_compile.py holds that no copy is made)."""
    from metaopt_tpu.models import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t, k, d, f, held = 16384, 8, 256, 128, 16
    assert moe.grouped_matmul_impl(t * k, d, f) == "megablox"
    shapes = [jax.ShapeDtypeStruct(s, dt) for s, dt in [
        ((t, d), jnp.float32), ((t, k), jnp.float32), ((t, k), jnp.int32),
        ((held, d, f), jnp.float32), ((held, d, f), jnp.float32),
        ((held, f, d), jnp.float32)]]

    def loss(x, weights, experts, gate, up, down):
        return jnp.sum(moe.dropless_experts(x, weights, experts, gate, up,
                                            down, 0, jax.nn.silu)[0])

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 3, 4, 5)))(*shapes)

    def outside(jaxpr):
        """The equations outside every loop and kernel."""
        for eqn in jaxpr.eqns:
            yield eqn
            if eqn.primitive.name in ("while", "scan", "pallas_call"):
                continue
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) \
                        else [value]:
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from outside(inner)

    eqns = list(outside(jaxpr.jaxpr))
    whole = [(e.primitive.name, v.aval.shape) for e in eqns
             for v in e.outvars
             if len(getattr(v.aval, "shape", ())) > 1 and t * k in v.aval.shape]
    assert [w for w in whole if w[0] in (
        "mul", "logistic", "max", "select_n", "add_any", "add")] == []
    # there and back, for each of the two weight gradients
    assert sorted(w[1] for w in whole if w[0] == "transpose") == sorted(
        [(d, t * k), (t * k, d), (f, t * k), (t * k, f)])
    # megablox gives its calls no name: a weight gradient's result has an
    # axis of experts
    calls = [e.params["name"] or ("tgmm" if len(e.outvars[0].aval.shape) == 3
                                  else "gmm")
             for e in eqns if e.primitive.name == "pallas_call"]
    assert sorted(calls) == ["expert_gating", "expert_gating_bwd"] \
        + 4 * ["gmm"] + 2 * ["tgmm"]
