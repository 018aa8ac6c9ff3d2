"""The pattern decoder (models/lm.py) and its dropless expert layer
(models/moe.py) against plain float32 references written here, in the test
tree's own words: block by block, loss and every gradient leaf, the shares
of a deployment adding up to the uncut layer, and no item dropped under
the worst imbalance."""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

HI = jax.lax.Precision.HIGHEST
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
D, H, KV, HD, F, E, TOPK, V, S, WINDOW = 32, 4, 2, 16, 24, 16, 3, 64, 24, 8


def description(layers, held=(0, E), **over):
    sliding, rotary = zip(*layers)
    h = dict(hidden_size=D, num_attention_heads=H, num_key_value_heads=KV,
             head_dim=HD, num_hidden_layers=len(layers), vocab_size=V,
             sliding_window_layout=list(sliding), rope_layout=list(rotary),
             sliding_window_size=WINDOW, rope_theta=1.5e6,
             moe_num_primary_experts=E, moe_num_active_primary_experts=TOPK,
             moe_ffn_hidden_size=F, experts_held=held, rms_norm_eps=1e-6)
    h.update(over)
    return h


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


def ref_moe(m, logits, p, held, act=jax.nn.relu):
    """Held experts (``p``: theirs alone) applied to every token, weighed
    by the routing."""
    top, idx = jax.lax.top_k(logits, TOPK)
    w = jax.nn.softmax(top, -1)
    y = jnp.zeros_like(m)
    first, count = held
    for e in range(count):
        we = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        hid = act(jnp.dot(m, p["gate"][e], precision=HI)) \
            * jnp.dot(m, p["up"][e], precision=HI)
        y = y + we[:, None] * jnp.dot(hid, p["down"][e], precision=HI)
    return y


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


def seeded(model, tokens, seed=0):
    """The model's parameters, unboxed, with a router spread enough that
    rounding rarely changes a token's chosen experts."""
    params = nn.meta.unbox(
        model.init(jax.random.PRNGKey(seed), tokens[:, :-1],
                   train=False)["params"])
    for name, sub in params.items():
        if "router" in sub:
            sub["router"]["kernel"] = 4.0 * sub["router"]["kernel"]
    params["embed"]["embedding"] = params["embed"]["embedding"].astype(
        jnp.float32)
    return params


KINDS = {"global-nope": (0, 0), "window-rope": (1, 1),
         "global-rope": (0, 1), "window-nope": (1, 0)}


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


LEAVES = ["embed/embedding", "head/embedding", "norm_f/scale",
          "h0/norm_in/scale", "h0/norm_post/scale", "h0/router/kernel",
          "h0/attn/q/kernel", "h0/attn/k/kernel", "h0/attn/v/kernel",
          "h0/attn/out/kernel", "h0/experts/gate", "h0/experts/up",
          "h0/experts/down"]


def leaf(tree, path):
    for part in path.split("/"):
        tree = tree[part]
    return np.asarray(tree, np.float32)


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


# -- the expert layer ----------------------------------------------------------

def expert_layer(held, logits_bias=None, seed=3, t=40, activation="relu"):
    """(program's output and counts, reference's output) of one
    DroplessMoE over ``held``, all shares from one set of weights."""
    from metaopt_tpu.models.moe import DroplessMoE

    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[activation]

    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (1, t, D))
    logits = 2.0 * jax.random.normal(jax.random.fold_in(key, 1), (1, t, E))
    if logits_bias is not None:
        logits = logits + logits_bias
    whole = DroplessMoE(D, F, E, TOPK, (0, E))
    full = nn.meta.unbox(whole.init(key, x, logits)["params"])
    first, count = held
    mine = {k: v[first:first + count] for k, v in full.items()}
    y, state = DroplessMoE(D, F, E, TOPK, held, activation).apply(
        {"params": mine}, x, logits, mutable=["moe_stats"])
    ref = ref_moe(x[0], logits[0], full_as_ref(mine), held, act)
    return y[0], state["moe_stats"], ref, ref_moe(
        x[0], logits[0], full_as_ref(full), (0, E), act)


def full_as_ref(full):
    return {k: v.astype(jnp.float32) for k, v in full.items()}


SHARES = [(0, 4), (4, 4), (8, 4), (12, 4)]


@pytest.mark.parametrize("held", SHARES + [(0, 16), (3, 7)])
def test_a_share_gives_its_own_experts_part(held):
    y, _, ref, _ = expert_layer(held)
    assert np.linalg.norm(y - ref) <= 0.02 * max(np.linalg.norm(ref), 1e-6)


@pytest.mark.parametrize("shares, activation", [
    (SHARES, "relu"), ([(first, 2) for first in range(0, E, 2)], "silu")],
    ids=["four-shares-relu", "eight-shares-silu"])
def test_the_shares_add_up_to_the_uncut_layer(shares, activation):
    """16 experts, top 3, four shares of 4 (gated ReLU) or eight of 2
    (gated SiLU): the partial outputs sum to what the uncut reference
    gives for the whole layer."""
    parts = [expert_layer(held, activation=activation) for held in shares]
    total = sum(p[0] for p in parts)
    uncut = parts[0][3]
    assert np.linalg.norm(total - uncut) <= 0.02 * np.linalg.norm(uncut)
    # and no share is idle: each adds something of its own
    assert all(np.linalg.norm(p[0]) > 0.05 * np.linalg.norm(uncut)
               for p in parts)


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


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


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


# -- the description -----------------------------------------------------------

def one_device():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))


def test_published_names_and_the_share_make_the_pattern():
    from metaopt_tpu.models.lm import make_lm

    model = make_lm(description([(0, 0), (1, 1), (1, 1), (1, 1)],
                                held=(4, 4), vocab_held=(16, 32)))
    p = model.pattern
    assert p.layers == ((False, False), (True, True), (True, True),
                        (True, True))
    assert (p.n_kv_heads, p.head_dim, p.window, p.top_k) == (KV, HD, WINDOW,
                                                             TOPK)
    assert p.experts_held == (4, 4) and p.vocab_held == (16, 32)
    assert p.kinds() == ["global-nope", "window-rope"]
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
        "global-nope": {"route": "reference", "mask": "dense: causal"},
        "window-rope": {"route": "reference",
                        "mask": f"dense: causal, window {WINDOW}"}}
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
    assert model.pattern.experts_held == (0, 16)
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

    from metaopt_tpu.models import lm, moe

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert moe.grouped_matmul_impl(rows, 2560, 768) == product
    said = lm.describe_pattern(
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


# -- what a rematerialised block keeps ----------------------------------------

def _bare_remat(cls, keeps=None, **kw):
    """``nn.remat`` without the rule's policy, in ``rematerialised``'s
    place."""
    return nn.remat(cls, **kw)


def _on_the_kernels(patch):
    """The Pallas route as the chip takes it, interpreted here: the backend
    reads as the TPU, and the kernels' entry runs the interpreter on float32
    operands (inside the structural kernels' loops this CPU's dot takes no
    pair of bfloat16); the embedding's sorted gradient rule likewise."""
    import functools

    from metaopt_tpu.models import lm
    from metaopt_tpu.ops import attention, embed

    real = attention.flash_attention

    def interpreted(q, k, v, mask=None, **kw):
        wide = [x.astype(jnp.float32) for x in (q, k, v)]
        return real(*wide, mask, interpret=True, **kw).astype(q.dtype)

    patch.setattr(jax, "default_backend", lambda: "tpu")
    patch.setattr(attention, "flash_attention", interpreted)
    patch.setattr(lm, "embed_rows", functools.partial(
        embed.embed_rows, interpret=True))


def _kernel_calls(jaxpr):
    """{"flash_fwd": n, "flash_bwd": m}: the Pallas calls a jaxpr holds."""
    names = [e.params["name"]
             for e in _equations(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    return {k: sum(k in n for n in names) for k in ("flash_fwd", "flash_bwd")}


def _primitives(jaxpr):
    return [e.primitive.name for e in _equations(jaxpr.jaxpr)]


REMAT_KINDS = ["global-nope", "window-rope"]


@pytest.fixture(scope="module")
def kept_or_not():
    """{kind: {how: (kernel calls of the gradient's jaxpr, loss, gradients)}}
    for a two-layer stack of each kind, rematerialised with the rule's
    policy (``kept``) and by a bare ``nn.remat`` (``bare``); and, not
    rematerialised, the gradient's primitives as they are (``plain``) and
    with the names taken out of the forward rules (``unnamed``)."""
    from metaopt_tpu.models import lm
    from metaopt_tpu.ops import attention

    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, S + 1), 2, V)
    out = {}
    for kind in REMAT_KINDS:
        layers = [KINDS[kind]] * 2

        def gradient(remat):
            model = lm.make_lm(description(layers, remat=remat))
            if remat:   # the projections' names too (a bare remat has none)
                model = model.clone(keeps=tuple(lm.remat_keeps(
                    model.pattern)["keeps"]) + lm.ATTENTION_REMAT_KEEPS)
            params = seeded(model, tokens)
            return jax.value_and_grad(lambda p: lm.lm_loss_fn(
                model, p, tokens, jax.random.PRNGKey(0))), params

        side = {}
        for how in ("kept", "bare"):
            with pytest.MonkeyPatch.context() as patch:
                _on_the_kernels(patch)
                if how == "bare":
                    patch.setattr(lm, "rematerialised", _bare_remat)
                fn, params = gradient(True)
                side[how] = (_kernel_calls(jax.make_jaxpr(fn)(params)),
                             *fn(params))
        for how in ("plain", "unnamed"):
            with pytest.MonkeyPatch.context() as patch:
                _on_the_kernels(patch)
                if how == "unnamed":
                    for module in (attention, lm):
                        patch.setattr(module, "checkpoint_name",
                                      lambda x, name: x)
                fn, params = gradient(False)
                side[how] = _primitives(jax.make_jaxpr(fn)(params))
        out[kind] = side
    return out


@pytest.mark.parametrize("kind", REMAT_KINDS)
def test_a_rematerialised_block_runs_its_forward_kernel_once(kept_or_not,
                                                             kind):
    """One ``flash_fwd`` a layer in the gradient where a bare remat has
    two: the policy keeps ``out`` AND ``lse`` (a name on one of them, or on
    the wrong value, leaves the count at two)."""
    assert kept_or_not[kind]["kept"][0] == {"flash_fwd": 2, "flash_bwd": 2}
    assert kept_or_not[kind]["bare"][0] == {"flash_fwd": 4, "flash_bwd": 2}


@pytest.mark.parametrize("path", ["loss"] + LEAVES + [
    "h1/attn/q/kernel", "h1/attn/out/kernel", "h1/experts/down",
    "h1/attn/k/kernel", "h1/attn/v/kernel"])
@pytest.mark.parametrize("kind", REMAT_KINDS)
def test_what_is_kept_is_what_would_be_made_again(kept_or_not, kind, path):
    """Loss and every gradient leaf equal to the last bit."""
    (_, loss, grads), (_, bare_loss, bare_grads) = (
        kept_or_not[kind]["kept"], kept_or_not[kind]["bare"])
    if path == "loss":
        assert np.isfinite(float(loss)) and float(loss) == float(bare_loss)
        return
    got, want = leaf(grads, path), leaf(bare_grads, path)
    assert np.abs(want).max() > 0
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kind", REMAT_KINDS)
def test_without_remat_the_names_are_identities(kept_or_not, kind):
    """Not rematerialised, the gradient is the one without the names, but
    for a ``name`` equation on ``out`` and on ``lse`` a layer and on each
    of its four projections' products."""
    plain, unnamed = kept_or_not[kind]["plain"], kept_or_not[kind]["unnamed"]
    assert "name" not in unnamed and "checkpoint" not in plain
    assert plain.count("name") == (2 + 4) * 2
    assert [p for p in plain if p != "name"] == unnamed


@pytest.mark.parametrize("shape", [None, (2, 2)],
                         ids=["one-device", "dp2-tp2-under-shard_map"])
def test_a_rematerialised_2017_layer_keeps_its_kernel_s_output_too(shape):
    """The dense-mask rule: the plain decoder's ``EncoderLayer`` under a
    causal mask, two layers; alone and, on a trial mesh, with the kernels'
    call inside ``shard_map``."""
    import contextlib

    from jax.sharding import Mesh

    from metaopt_tpu.models import lm
    from metaopt_tpu.parallel.mesh import use_mesh

    on_mesh = contextlib.nullcontext if shape is None else (
        lambda: use_mesh(Mesh(np.array(jax.devices()[:4]).reshape(shape),
                              ("dp", "tp"))))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, S + 1), 2, V)
    calls = {}
    for how in ("kept", "bare"):
        with pytest.MonkeyPatch.context() as patch, on_mesh():
            _on_the_kernels(patch)
            if how == "bare":
                patch.setattr(lm, "rematerialised", _bare_remat)
            model = lm.make_lm({"d_model": D, "n_heads": H, "n_layers": 2,
                                "d_ff": F, "vocab": V, "dropout": 0.0,
                                "remat": True})
            params = nn.meta.unbox(model.init(
                jax.random.PRNGKey(0), tokens[:, :-1], train=False)["params"])
            calls[how] = _kernel_calls(jax.make_jaxpr(jax.grad(
                lambda p: lm.lm_loss_fn(model, p, tokens,
                                        jax.random.PRNGKey(0))))(params))
    assert calls == {"kept": {"flash_fwd": 2, "flash_bwd": 2},
                     "bare": {"flash_fwd": 4, "flash_bwd": 2}}
