"""A gated-delta mixer's hand-over to the scan and back
(ops/delta_hand_over.py): the one Pallas call a side and direction,
interpreted here, against the passes it takes the place of
(``lm_layers._delta_mixed``'s other branch, written out below), value and
every gradient; the halo at the row's start, at tile edges and, backward,
at the row's end; the scan's heads-first door against
``gated_delta_rule``; the one rule; what the calls are named and what a
rematerialised layer makes again.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from metaopt_tpu.models import lm_layers
from metaopt_tpu.ops import delta_hand_over as dh, linear_attention
from metaopt_tpu.ops import ssd_hand_over as sh

from lm_pattern_cases import _equations, one_device

EPS, TAPS = 1e-6, 4
NAMES = ("q's product", "k's product", "v's product", "q's taps", "k's taps",
         "v's taps", "g", "beta", "the g product", "the norm's scale")


RULES = {False: linear_attention.gated_delta_rule,
         True: dh.gated_delta_rule_heads_first}


def scan(q, k, v, g, beta, door):
    """The scan interpreted, on float32 operands (inside an interpreted
    kernel's loop this CPU's dot takes no pair of bfloat16), the output
    bfloat16 as the kernels write it: by the heads-first door or by
    ``gated_delta_rule``."""
    return RULES[door](*(x.astype(jnp.float32) for x in (q, k, v)), g, beta,
                       interpret=True).astype(jnp.bfloat16)


def passes(q, k, v, tq, tk, tv, g, beta, gate, scale):
    """What the layer composes without the calls (``_delta_mixed``'s second
    branch)."""
    q, k, v = (jax.nn.silu(lm_layers.short_conv(p.astype(jnp.float32), t))
               for p, t in ((q, tq), (k, tk), (v, tv)))
    unit = lambda y: y * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(jnp.square(y), axis=-1, keepdims=True) + 1e-6)
    o = scan((unit(q) * q.shape[-1] ** -0.5).astype(jnp.bfloat16),
             unit(k).astype(jnp.bfloat16), v.astype(jnp.bfloat16), g, beta,
             False).astype(jnp.float32)
    normed = o * jax.lax.rsqrt(
        jnp.mean(jnp.square(o), axis=-1, keepdims=True) + EPS) * scale
    return (normed * jax.nn.silu(gate.astype(jnp.float32))).astype(
        jnp.bfloat16)


def one_pass(q, k, v, tq, tk, tv, g, beta, gate, scale, tile):
    o = scan(*dh.delta_operands(q, k, v, tq, tk, tv, tile, True), g, beta,
             True)
    return dh.delta_gated_norm(o, gate, scale, EPS, tile, True)


def operands(t, heads, dk, dv, seed=0, rows=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 11)
    product = lambda key, d: jax.random.normal(  # noqa: E731
        key, (rows, t, heads, d)).astype(jnp.bfloat16)
    taps = lambda key, d: 0.5 * jax.random.normal(  # noqa: E731
        key, (TAPS, heads, d))
    return (
        product(ks[0], dk), product(ks[1], dk), product(ks[2], dv),
        taps(ks[3], dk), taps(ks[4], dk), taps(ks[5], dv),
        -0.1 * jax.random.uniform(ks[6], (rows, t, heads)),
        1.5 * jax.random.uniform(ks[7], (rows, t, heads)),
        product(ks[8], dv), 1.0 + 0.3 * jax.random.normal(ks[9], (dv,)),
    ), jax.random.normal(ks[10], (rows, t, heads, dv)).astype(jnp.bfloat16)


def value_and_gradients(fn, args, weight):
    """(fn(*args), its gradients against ``weight``), one compiled program
    (a case is mostly compile time: op by op it takes twice as long)."""
    def run(*a):
        value, pull = jax.vjp(fn, *a)
        return value, pull(weight)

    return jax.jit(run)(*args)


def within(a, b, share):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.linalg.norm(a - b) <= share * np.linalg.norm(b)


def roundings_apart(a, b):
    """|a - b| in units of b's last bfloat16 place, elementwise."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.abs(a - b) / (2.0 ** (np.floor(np.log2(np.maximum(
        np.abs(b), 1e-3))) - 7))


def compare(heads, dk, dv, t, tile, seed=0, rows=1):
    """The calls against the passes: the output to one bfloat16 rounding
    of most numbers (the norms' sums run in another order, and a q or k
    that rounds the other way moves the scan's output by a rounding of
    its own), every gradient to a few thousandths of its norm."""
    args, weight = operands(t, heads, dk, dv, seed, rows)
    v1, grads1 = value_and_gradients(
        lambda *a: one_pass(*a, tile), args, weight)
    v2, grads2 = value_and_gradients(passes, args, weight)
    assert v1.dtype == v2.dtype == jnp.bfloat16 and v1.shape == v2.shape
    apart = roundings_apart(v1, v2)
    assert apart.max() <= 4.0 and np.mean(apart > 1.0) <= 0.01
    assert within(v1, v2, 4e-3)
    for name, g1, g2, like in zip(NAMES, grads1, grads2, args):
        assert g1.dtype == like.dtype and g1.shape == like.shape, name
        assert within(g1, g2, 8e-3), name


#: (tokens, rows a program): one tile of one chunk; several tiles; a length
#: that is no whole tile or chunk (the rows past the end are masked, the
#: operands' zero); two chunks in tiles that are no chunk
LENGTHS = {"one tile": (128, None), "tiles": (128, 32), "ragged": (80, 32),
           "chunks": (200, 64)}
#: (heads, key width, value width): the cell's ratio at a sixth of its
#: widths, one head, widths that are no multiple of each other
WIDTHS = [(3, 16, 32), (1, 32, 32), (2, 48, 16)]
CASES = [(w, n) for w in WIDTHS for n in LENGTHS]


@pytest.mark.parametrize("widths, length", CASES, ids=[
    "x".join(map(str, w)) + "-" + n.replace(" ", "-") for w, n in CASES])
def test_one_pass_is_the_passes_to_a_rounding(widths, length):
    """The halo carries the convolution over a tile's edge forward (rows
    before) and backward (rows after); the first tile's is zero."""
    t, tile = LENGTHS[length]
    compare(*widths, t, tile, seed=sum(widths))


def test_a_row_shorter_than_a_halo_is_one_tile():
    """Twelve tokens in one chunk of 128: no halo block is read, before or
    after, and the 116 rows past the end are zero operands."""
    compare(2, 16, 32, 12, None)


def test_two_rows_keep_their_own_halos():
    """A row's first tile reads nothing of the row before it."""
    compare(2, 16, 16, 96, 32, rows=2)


def test_the_operands_past_the_end_are_zero():
    """q, k and v come whole chunks long, the rows past T zero, as
    ``ops/linear_attention._heads_first`` pads them."""
    args, _ = operands(80, 2, 16, 32)
    for out in dh.delta_operands(*args[:6], 32, True):
        assert out.shape[:3] == (1, 2, 128) and out.dtype == jnp.bfloat16
        assert not np.asarray(out[:, :, 80:], np.float32).any()
        assert np.asarray(out[:, :, :80], np.float32).any()


def test_the_convolution_is_short_conv_s_number_bit_for_bit():
    """A head's convolution in the front call is ssd_hand_over's ``_fill``
    and ``_conv`` over the head's block with a zero bias: ``short_conv``'s
    float32 number at a row's start, inside a tile and across tiles' edges.
    (Taps that are powers of two: a tap times a bfloat16 number is then
    exact, so this CPU's compiled kernel, which fuses a multiply and an add
    into one rounding where the eager passes round twice, has nothing to
    fuse away and what is compared is the order of the sums.)"""
    t, tile, heads, wide = 96, 32, 3, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    product = jax.random.normal(ks[0], (1, t, heads, wide)).astype(
        jnp.bfloat16)
    taps = jnp.exp2(jax.random.randint(
        ks[1], (TAPS, heads, wide), -3, 3).astype(jnp.float32)) * jnp.where(
            jnp.arange(wide) % 3 == 0, -1.0, 1.0)
    plan = sh._plan(t, tile)
    rows, before, _, taps_spec, _ = dh._specs(plan, heads, TAPS, wide)

    def kernel(before_ref, tile_ref, tb_ref, o_ref, win):
        sh._fill(win, before_ref, tile_ref, pl.program_id(1) == 0)
        o_ref[...] = sh._conv(win, 0, tile, tb_ref)[0]

    cut = dh._rows_first(product, t)
    got = dh._call(
        kernel, "conv_again", (heads, plan.tiles, 1),
        [before, rows, taps_spec], [rows],
        [jax.ShapeDtypeStruct(cut.shape, jnp.float32)],
        [(sh._HALO + tile, wide)], [cut, cut, dh._taps_a_head(taps)],
        True)[0]
    want = lm_layers.short_conv(product.astype(jnp.float32), taps)
    assert np.array_equal(np.asarray(dh._tokens_first(got, product)),
                          np.asarray(want))


# -- the scan's second door ---------------------------------------------------

@pytest.mark.parametrize("t", [128, 200], ids=["whole", "ragged"])
def test_the_heads_first_door_is_gated_delta_rule(t):
    """The same kernels on the same operands: the output and the five
    gradients of ``gated_delta_rule`` bit for bit, heads first and whole
    chunks long."""
    heads, dk, dv = 2, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(t), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (1, t, heads, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (1, t, heads, dk)))
    v = jax.random.normal(ks[2], (1, t, heads, dv))
    g = -0.1 * jax.random.uniform(ks[3], (1, t, heads))
    beta = 1.5 * jax.random.uniform(ks[4], (1, t, heads))
    weight = jax.random.normal(ks[5], (1, t, heads, dv))
    first = lambda x: linear_attention._heads_first(x)  # noqa: E731
    back = lambda x, like: linear_attention._tokens_first(x, like)  # noqa: E731

    def by_door(q, k, v, g, beta):
        return back(dh.gated_delta_rule_heads_first(
            first(q), first(k), first(v), g, beta, interpret=True), v)

    want = value_and_gradients(functools.partial(
        linear_attention.gated_delta_rule, interpret=True),
        (q, k, v, g, beta), weight)
    got = value_and_gradients(by_door, (q, k, v, g, beta), weight)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and np.array_equal(np.asarray(a),
                                                     np.asarray(b))


def test_the_door_names_what_a_rematerialised_block_keeps():
    """The output and the chunks' entering states carry ``REMAT_KEEPS``'
    names inside the door's rule, as ``gated_delta_rule``'s do."""
    q = jnp.zeros((1, 2, 128, 16))
    v = jnp.zeros((1, 2, 128, 32))
    g = jnp.zeros((1, 128, 2))
    jaxpr = jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
        dh.gated_delta_rule_heads_first(q, q, v, g, g))))(q).jaxpr
    names = {e.params["name"] for e in _equations(jaxpr)
             if e.primitive.name == "name"}
    assert names == set(linear_attention.REMAT_KEEPS)


# -- the rule -----------------------------------------------------------------

MESHES = {"no mesh": lambda: None, "one device": one_device,
          "two devices": lambda: jax.sharding.Mesh(
              np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))}
CELL, REHEARSAL = dh.Sizes(15, 96, 192, 4), dh.Sizes(2, 8, 16, 4)
RULE = [("pallas", "no mesh", CELL, "one pass"),
        ("pallas", "one device", CELL, "one pass"),
        ("pallas", "two devices", CELL, "passes"),
        ("xla", "no mesh", CELL, "passes"),
        ("xla", "one device", CELL, "passes"),
        ("pallas", "one device", REHEARSAL, "passes"),
        ("pallas", "one device", dh.Sizes(4, 128, 128, 4), "one pass"),
        ("pallas", "one device", dh.Sizes(4, 96, 100, 4), "passes"),
        ("pallas", "one device", dh.Sizes(4, 96, 192, 10), "passes")]


@pytest.mark.parametrize("route, mesh, sizes, said", RULE, ids=[
    f"{r}-{m}-{'x'.join(map(str, s))}".replace(" ", "-")
    for r, m, s, _ in RULE])
def test_the_rule_for_the_hand_over(route, mesh, sizes, said):
    """One pass on the Pallas route of one device at widths of whole
    sublane tiles; the passes on the plain route, on a mesh of several
    devices, at a rehearsal's widths and past a halo's taps."""
    assert dh.hand_over(route, MESHES[mesh](), sizes) == said


SPEC = lm_layers.LinearSpec(3, 6, 16, 32, TAPS, True)
CALLS = ("delta_operands", "delta_gated_norm", "linear_scan_fwd",
         "delta_operands_bwd", "delta_gated_norm_bwd", "linear_scan_bwd")


def _layer(s=96, d_model=32, spec=SPEC):
    layer = lm_layers.LinearAttention(d_model, spec, EPS)
    x = jnp.zeros((1, s, d_model))
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0),
                                               x)["params"])
    return layer, params, x


def _outside_the_calls(jaxpr):
    return _equations(jaxpr, kernels=False)


def _counts(jaxpr):
    names = [str(e.params.get("name", "")) for e in _outside_the_calls(jaxpr)
             if e.primitive.name == "pallas_call"]
    return tuple(names.count(call) for call in CALLS)


def test_a_layer_asks_the_rule(monkeypatch):
    """On the Pallas route a mixer traces ``delta_operands``, the scan and
    ``delta_gated_norm``, once each, and its parameters keep their paths
    and shapes; off the TPU no call at all, on a mesh of two devices and at
    a rehearsal's widths the scan alone."""
    from jax.sharding import Mesh

    from metaopt_tpu.parallel.mesh import use_mesh

    layer, params, x = _layer()
    trace = lambda layer=layer, params=params: _counts(  # noqa: E731
        jax.make_jaxpr(lambda p, x: layer.apply({"params": p}, x))(
            params, x).jaxpr)
    assert trace() == (0,) * 6
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert trace() == (1, 1, 1, 0, 0, 0)
    again = _layer()[1]
    assert jax.tree.structure(again) == jax.tree.structure(params)
    assert jax.tree.leaves(again) == jax.tree.leaves(params)
    assert sorted(params) == ["A_log", "a", "b", "conv_k", "conv_q",
                              "conv_v", "dt_bias", "g", "k", "norm", "out",
                              "q", "v"]
    assert params["norm"]["scale"].shape == (32,)
    two = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    with use_mesh(two):
        assert trace() == (0, 0, 1, 0, 0, 0)
    narrow, its, _ = _layer(spec=lm_layers.LinearSpec(2, 4, 8, 16, TAPS,
                                                      True))
    assert trace(narrow, its) == (0, 0, 1, 0, 0, 0)


def test_the_spec_says_the_form(monkeypatch):
    """``trial.setup``'s ``attrs["attention_layers"]["linear"]`` says
    ``"hand_over"``: the rule's answer for the step's mesh."""
    import types

    step = types.SimpleNamespace(mesh=None)
    assert SPEC.describe(step, [], {})["hand_over"] == "passes"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    said = SPEC.describe(step, [], {})
    assert said["hand_over"] == "one pass" and said["route"] == "pallas"


def _interpreted(monkeypatch):
    """The backend read as the TPU and every kernel interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(linear_attention, "gated_delta_rule",
                        lambda q, k, v, g, beta: scan(
                            q, k, v, g, beta, False).astype(v.dtype))
    monkeypatch.setattr(dh, "gated_delta_rule_heads_first",
                        lambda q, k, v, g, beta: scan(q, k, v, g, beta, True))
    for name in ("delta_operands", "delta_gated_norm"):
        monkeypatch.setattr(dh, name, functools.partial(
            getattr(dh, name), interpret=True))


def test_a_layer_s_gradient_by_the_calls_is_the_passes(monkeypatch):
    """The mixer whole: value and each parameter's gradient by the calls
    against the same layer on XLA's passes (the rule answering
    ``"passes"``)."""
    _interpreted(monkeypatch)
    layer, _, _ = _layer()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 96, 32))
    params = jax.tree.map(
        lambda p: p + 0.05 * jax.random.normal(jax.random.PRNGKey(p.size),
                                               p.shape),
        layer.init(jax.random.PRNGKey(0), x)["params"])
    loss = lambda p, x: jnp.sum(jnp.square(  # noqa: E731
        layer.apply({"params": p}, x).astype(jnp.float32)))
    got = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    monkeypatch.setattr(dh, "hand_over", lambda *a: "passes")
    want = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    assert abs(got[0] - want[0]) <= 2e-3 * abs(want[0])
    for (path, g1), g2 in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                              jax.tree.leaves(want[1])):
        assert within(g1, g2, 2e-2), jax.tree_util.keystr(path)


def test_a_rematerialised_layer_makes_the_two_forward_calls_again(
        monkeypatch):
    """With the block's policy (the products kept, and what the scan
    made): the two forward calls again, each backward call once, the scan
    forward once and no second time, and outside the calls no float32
    array as large as q."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layer, params, x = _layer(s=128)
    policy = jax.checkpoint_policies.save_only_these_names(
        *SPEC.KEPT.values(), *SPEC.kernel_keeps())
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(jax.checkpoint(
        lambda p, x: layer.apply({"params": p}, x), policy=policy)(
            p, x).astype(jnp.float32)), argnums=(0, 1)))(params, x).jaxpr
    assert _counts(jaxpr) == (2, 2, 1, 1, 1, 1)
    q_sized = {e.primitive.name for e in _outside_the_calls(jaxpr)
               for v in e.outvars
               if getattr(v.aval, "dtype", None) == jnp.float32
               and v.aval.size >= x.shape[1] * SPEC.heads * SPEC.key_dim
               and 128 in v.aval.shape and len(v.aval.shape) >= 3
               and v.aval.shape[-1] in (SPEC.key_dim, SPEC.value_dim)}
    assert q_sized <= {"pallas_call", "jit", "pjit", "custom_vjp_call"}, \
        q_sized


def test_the_backward_calls_operations_are_the_mixer_s():
    """A backward rule has no forward name stack: the rules name the layer
    themselves, so a traced step's time stays the mixers' and outside
    ``linear_attention.core``."""
    import re

    from metaopt_tpu.utils import trace

    args, weight = operands(32, 2, 16, 32)

    def weighed(q, k, v):
        qh, kh, vh = dh.delta_operands(q, k, v, *args[3:6], None, True)
        out = dh.delta_gated_norm(vh + qh.sum(-1, keepdims=True)
                                  + kh.sum(-1, keepdims=True), args[8],
                                  args[9], EPS, None, True)
        return jnp.sum(out.astype(jnp.float32) * weight)

    text = jax.jit(jax.grad(weighed, argnums=(0, 1, 2))).lower(
        *args[:3]).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    for call in ("_operands_backward", "_gated_norm_backward"):
        bwd = [n for n in names if f"jit({call})" in n]
        assert bwd and {trace.layer_of(n) for n in bwd} == {
            "linear_attention"}, call
        assert not [n for n in bwd if "linear_attention.core" in n]


# -- what must not move -------------------------------------------------------

def test_the_other_mixers_are_where_the_compile_cache_has_them():
    """Mosaic keeps file and line of the frames above a ``pallas_call`` in
    a kernel's body and the persistent cache keys on the body (ROADMAP
    S10, S11): the eighth cell's scan lives in ops/linear_attention.py
    below the delta rule's, the sixth's and eighth's mixers in
    models/lm_layers.py below ``LinearAttention``; so this PR's edits of
    both keep their lines' count, and what it adds stands at the files'
    ends or in a file of its own. Whoever moves one pays a cold compile of
    that cell once, knowingly, and writes the new lines here."""
    import inspect

    first = lambda f: inspect.getsourcelines(f)[1]  # noqa: E731
    assert {name: first(getattr(linear_attention, name)) for name in (
        "_fwd_pallas", "_bwd_pallas", "gated_delta_rule",
        "_decay_fwd_pallas", "_decay_bwd_pallas", "scalar_decay_rule")} == {
            "_fwd_pallas": 260, "_bwd_pallas": 275, "gated_delta_rule": 437,
            "_decay_fwd_pallas": 579, "_decay_bwd_pallas": 594,
            "scalar_decay_rule": 712}
    assert {name: first(getattr(lm_layers, name)) for name in (
        "StateSpaceMixer", "ScalarDecayMixer")} == {
            "StateSpaceMixer": 802, "ScalarDecayMixer": 1196}
    assert first(lm_layers._delta_mixed) > first(lm_layers.ScalarDecayMixer)


def test_the_module_loads_only_where_a_linear_layer_asks():
    """No other cell's import grows: models/lm.py and models/lm_layers.py
    load without ops/delta_hand_over.py (and without ops/ssd_hand_over.py,
    whose helpers it imports); ``LinearSpec.describe`` and the layer load
    it."""
    import os
    import subprocess
    import sys

    code = ("import sys, types; import metaopt_tpu.models.lm; "
            "from metaopt_tpu.models import lm_layers; "
            "mods = ('metaopt_tpu.ops.delta_hand_over', "
            "'metaopt_tpu.ops.ssd_hand_over'); "
            "assert not [m for m in mods if m in sys.modules]; "
            "lm_layers.LinearSpec(2, 4, 16, 32, 4, True).describe("
            "types.SimpleNamespace(mesh=None), [], {}); "
            "assert all(m in sys.modules for m in mods)")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
