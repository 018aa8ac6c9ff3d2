"""A Mamba-2 mixer's hand-over to the scan and back (ops/ssd_hand_over.py):
the one Pallas call a side and direction, interpreted here, against the
passes it takes the place of (``ScalarDecayMixer._passes``, written out
below), value and every gradient; the halo at the row's start, at tile
edges and, backward, at the row's end; the one rule; what the calls are
named and what a rematerialised layer makes again.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from metaopt_tpu.models import lm_layers
from metaopt_tpu.ops import linear_attention, ssd_hand_over as sh

from lm_pattern_cases import _equations, one_device

EPS, TAPS, P, N = 1e-5, 4, 8, 16
NAMES = ("the product", "dt", "the taps", "the convolution's bias",
         "dt_bias", "A_log", "D", "the norm's weight")


def passes(zxbc, dt, taps, bias, dt_bias, a_log, d, weight, sz):
    """What the layer composes without the calls (``_passes`` there, with
    the softplus in front of it)."""
    inner, bc = sz.inner, sz.bc
    dt = jax.nn.softplus(dt + dt_bias)
    xbc = jax.nn.silu(lm_layers.short_conv(
        zxbc[..., inner:].astype(jnp.float32), taps) + bias)
    heads = lambda y, n: y.reshape(*y.shape[:2], n, -1)  # noqa: E731
    x = heads(xbc[..., :inner], sz.heads)
    b, c = (heads(xbc[..., inner + i * bc:inner + (i + 1) * bc],
                  sz.groups).astype(jnp.bfloat16) for i in (0, 1))
    y = linear_attention.scalar_decay_rule(
        c, b, (dt[..., None] * x).astype(jnp.bfloat16),
        dt * -jnp.exp(a_log)).astype(jnp.float32) + d[:, None] * x
    gated = y.reshape(zxbc.shape[:2] + (inner,)) * jax.nn.silu(
        zxbc[..., :inner].astype(jnp.float32))
    grouped = heads(gated, sz.groups)
    normed = (grouped * jax.lax.rsqrt(jnp.mean(
        jnp.square(grouped), axis=-1, keepdims=True) + EPS)).reshape(
            gated.shape) * weight
    return normed.astype(jnp.bfloat16)


def one_pass(zxbc, dt, taps, bias, dt_bias, a_log, d, weight, sz, tile):
    c, b, v, g, z, x = sh.ssd_operands(zxbc, dt, taps, bias, dt_bias, a_log,
                                       sz, tile, True)
    return sh.ssd_gated_norm(
        linear_attention.scalar_decay_rule(c, b, v, g), z, x,
        jax.lax.stop_gradient(zxbc), taps, bias, d, weight, sz, EPS, tile,
        True)


def operands(t, sz, seed=0, rows=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    wide = sz.inner + 2 * sz.bc
    return (
        jax.random.normal(ks[0], (rows, t, sz.inner + wide)).astype(
            jnp.bfloat16),
        jax.random.normal(ks[1], (rows, t, sz.heads)) - 2.0,
        0.5 * jax.random.normal(ks[2], (TAPS, wide)),
        0.3 * jax.random.normal(ks[3], (wide,)),
        0.3 * jax.random.normal(ks[4], (sz.heads,)),
        jnp.log(jax.random.uniform(ks[5], (sz.heads,), minval=1.0,
                                   maxval=4.0)),
        1.0 + 0.3 * jax.random.normal(ks[6], (sz.heads,)),
        1.0 + 0.3 * jax.random.normal(ks[7], (sz.inner,)),
    ), jax.random.normal(ks[8], (rows, t, sz.inner)).astype(jnp.bfloat16)


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


#: (tokens, rows a program): one tile; several tiles; a length that is no
#: multiple of the tile (the last program's rows past the end are masked)
LENGTHS = {"one tile": (48, None), "tiles": (96, 32), "ragged": (80, 32)}


def test_a_row_shorter_than_a_halo_is_one_tile():
    """Twelve tokens: no halo block is read, before or after."""
    sz = sh.Sizes(2, P, 1, N)
    args, weight = operands(12, sz)
    v1, grads1 = value_and_gradients(
        lambda *a: one_pass(*a, sz, None), args, weight)
    v2, grads2 = value_and_gradients(lambda *a: passes(*a, sz), args, weight)
    assert roundings_apart(v1, v2).max() <= 1.0
    for name, g1, g2 in zip(NAMES, grads1, grads2):
        assert within(g1, g2, 4e-3), name

CASES = [(groups, per, length) for groups in (1, 2, 8) for per in (1, 8)
         for length in LENGTHS]


@pytest.mark.parametrize("groups, per, length", CASES, ids=[
    f"{g}x{p}-{n}".replace(" ", "-") for g, p, n in CASES])
def test_one_pass_is_the_passes_to_a_rounding(groups, per, length):
    """The calls' output is the composed passes' to one bfloat16 rounding
    (the norm's sum runs in another order) and so is every gradient: the
    product's to bfloat16's rounding, the others float32 sums over the
    sequence (a thousandth: where a row's mean square is small the norm's
    backward magnifies the last place of its sum, and the scan's backward
    carries that on to the steps). The halo carries the convolution over a
    tile's edge forward (rows before) and backward (rows after); the first
    tile's is zero."""
    sz = sh.Sizes(groups * per, P, groups, N)
    t, tile = LENGTHS[length]
    args, weight = operands(t, sz, seed=groups + per)
    v1, grads1 = value_and_gradients(
        lambda *a: one_pass(*a, sz, tile), args, weight)
    v2, grads2 = value_and_gradients(lambda *a: passes(*a, sz), args, weight)
    assert v1.dtype == v2.dtype == jnp.bfloat16 and v1.shape == v2.shape
    assert roundings_apart(v1, v2).max() <= 1.0
    for name, g1, g2, like in zip(NAMES, grads1, grads2, args):
        assert g1.dtype == like.dtype and g1.shape == like.shape, name
        assert within(g1, g2, 4e-3 if name == "the product" else 1e-3), name


def test_x_made_again_is_short_conv_s_number_bit_for_bit():
    """Behind the scan x is not read from HBM in float32: the call makes
    it from the product with ``_fill`` and ``_conv``, the front call's own,
    in ``short_conv``'s order of the taps: the same float32 number at a
    row's start, inside a tile and across tiles' edges. (Taps that are
    powers of two: a tap times a bfloat16 number is then exact, so this
    CPU's compiled kernel, which fuses a multiply and an add into one
    rounding where the eager passes round twice, has nothing to fuse away
    and what is compared is the order of the sums.)"""
    t, tile, wide = 96, 32, 24
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    product = jax.random.normal(ks[0], (1, t, wide)).astype(jnp.bfloat16)
    taps = jnp.exp2(jax.random.randint(ks[1], (TAPS, wide), -3, 3).astype(
        jnp.float32)) * jnp.where(jnp.arange(wide) % 3 == 0, -1.0, 1.0)
    bias = 0.3 * jax.random.normal(ks[2], (wide,))
    plan = sh._plan(t, tile)

    def kernel(before_ref, tile_ref, tb_ref, o_ref, win):
        sh._fill(win, before_ref, tile_ref, pl.program_id(1) == 0)
        o_ref[...] = sh._conv(win, 0, tile, tb_ref)[0]

    own = lambda j: j  # noqa: E731
    got = sh._call(
        kernel, "x_again", (1, plan.tiles, 1), "parallel",
        [plan.before(wide, own), plan.rows(wide, own),
         pl.BlockSpec((TAPS + 1, wide), lambda b, i, j: (0, 0))],
        [plan.rows(wide, own)],
        [jax.ShapeDtypeStruct(product.shape, jnp.float32)],
        [(sh._HALO + tile, wide)],
        [product, product, sh._taps_and_bias(taps, bias)], True)[0]
    want = lm_layers.short_conv(product.astype(jnp.float32), taps) + bias
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_a_head_s_number_reaches_its_channels_bit_for_bit():
    """The step rides to a head's channels as three products with 0 and 1
    on the MXU: the sum of the three is the float32 number itself."""
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 4)) * jnp.array(
        [1e-3, 1.0, 1e3, 1e-6])
    spread = sh._spread(4, 32, 0, P, jnp.bfloat16)
    wide = sh._a_head(x, spread)
    assert np.array_equal(np.asarray(wide),
                          np.repeat(np.asarray(x), P, axis=1))
    back = sh._by_head(wide, spread)
    assert within(back, P * x, 1e-6)


# -- the rule -----------------------------------------------------------------

def test_the_rule_for_the_hand_over():
    from jax.sharding import Mesh

    one = one_device()
    two = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    cell, short = sh.Sizes(64, 64, 8, 128), sh.Sizes(16, 64, 2, 128)
    for mesh in (None, one):
        assert sh.hand_over("pallas", mesh, cell) == "one pass"
        assert sh.hand_over("pallas", mesh, short) == "one pass"
        assert sh.hand_over("xla", mesh, cell) == "passes"
    assert sh.hand_over("pallas", two, cell) == "passes"
    # a block of columns that is no whole lanes: a group of the norm 64
    # wide; B and C 64 wide beside x's 512 (a rehearsal's widths)
    assert sh.hand_over("pallas", one, sh.Sizes(8, 8, 1, 128)) == "passes"
    assert sh.hand_over("pallas", one, sh.Sizes(8, 64, 1, 64)) == "passes"
    assert sh.hand_over("pallas", one, sh.Sizes(8, 8, 2, 16)) == "passes"


SPEC = lm_layers.ScalarDecaySpec(8, 32, 2, 128, TAPS)  # whole lanes
CALLS = ("ssd_operands", "ssd_gated_norm", "ssd_scan_fwd",
         "ssd_operands_bwd", "ssd_gated_norm_bwd", "ssd_scan_bwd")


def _layer(s=96, d_model=32):
    layer = lm_layers.ScalarDecayMixer(d_model, SPEC, EPS)
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
    """On the Pallas route a mixer traces ``ssd_operands``, the scan and
    ``ssd_gated_norm``, once each, and its parameters keep their paths;
    off the TPU no call at all, and on a mesh of two devices the scan
    alone."""
    from jax.sharding import Mesh

    from metaopt_tpu.parallel.mesh import use_mesh

    layer, params, x = _layer()
    trace = lambda: _counts(jax.make_jaxpr(  # noqa: E731
        lambda p, x: layer.apply({"params": p}, x))(params, x).jaxpr)
    assert trace() == (0,) * 6
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert trace() == (1, 1, 1, 0, 0, 0)
    assert jax.tree.structure(_layer()[1]) == jax.tree.structure(params)
    assert sorted(params) == ["A_log", "D", "conv", "conv_bias", "dt_bias",
                              "in_proj", "norm", "out_proj"]
    two = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    with use_mesh(two):
        assert trace() == (0, 0, 1, 0, 0, 0)


def test_a_layer_s_gradient_by_the_calls_is_the_passes(monkeypatch):
    """The mixer whole, the backend read as the TPU and every kernel
    interpreted: value and each parameter's gradient by the calls against
    the same layer on XLA's passes (the rule answering ``"passes"``)."""
    import functools

    real = linear_attention.scalar_decay_rule
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # inside an interpreted kernel's loop this CPU's dot takes no pair of
    # bfloat16: the scan on float32 operands, on both sides
    monkeypatch.setattr(
        linear_attention, "scalar_decay_rule", lambda q, k, v, g: real(
            *(x.astype(jnp.float32) for x in (q, k, v)), g,
            interpret=True).astype(v.dtype))
    for name in ("ssd_operands", "ssd_gated_norm"):
        monkeypatch.setattr(sh, name, functools.partial(
            getattr(sh, name), interpret=True))
    layer, _, _ = _layer()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 96, 32))
    params = jax.tree.map(
        lambda p: p + 0.05 * jax.random.normal(jax.random.PRNGKey(p.size),
                                               p.shape),
        layer.init(jax.random.PRNGKey(0), x)["params"])
    loss = lambda p, x: jnp.sum(jnp.square(  # noqa: E731
        layer.apply({"params": p}, x).astype(jnp.float32)))
    got = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    monkeypatch.setattr(sh, "hand_over", lambda *a: "passes")
    want = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    assert abs(got[0] - want[0]) <= 1e-3 * abs(want[0])
    for (path, g1), g2 in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                              jax.tree.leaves(want[1])):
        assert within(g1, g2, 1e-2), jax.tree_util.keystr(path)


def test_a_rematerialised_layer_makes_the_two_forward_calls_again(
        monkeypatch):
    """With the block's policy (the products kept, and what the scan
    made): the two forward calls again, each backward call once, the scan
    forward once and no second time, and no float32 array as large as x
    but the skip's share, which one call writes and one reads, and the
    handle it rides on."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layer, params, x = _layer()
    policy = jax.checkpoint_policies.save_only_these_names(
        *SPEC.KEPT.values(), *SPEC.kernel_keeps())
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(jax.checkpoint(
        lambda p, x: layer.apply({"params": p}, x), policy=policy)(
            p, x).astype(jnp.float32)), argnums=(0, 1)))(params, x).jaxpr
    assert _counts(jaxpr) == (2, 2, 1, 1, 1, 1)
    x_sized = {e.primitive.name for e in _outside_the_calls(jaxpr)
               for v in e.outvars
               if getattr(v.aval, "dtype", None) == jnp.float32
               and v.aval.shape[:2] == x.shape[:2]
               and v.aval.size == x.shape[1] * SPEC.d_inner}
    assert x_sized <= {"pallas_call", "jit", "pjit", "custom_vjp_call",
                       "broadcast_in_dim"}, x_sized


def test_the_backward_calls_operations_are_the_mixer_s():
    """A backward rule has no forward name stack: the rules name the layer
    themselves, so a traced step's time stays the mixers' and outside
    ``ssd.core``."""
    import re

    from metaopt_tpu.utils import trace

    sz = sh.Sizes(2, P, 1, N)
    args, weight = operands(32, sz)
    text = jax.jit(jax.grad(lambda zxbc: jnp.sum(one_pass(
        zxbc, *args[1:], sz, None).astype(jnp.float32) * weight))).lower(
            args[0]).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    for call in ("_operands_backward", "_gated_norm_backward"):
        bwd = [n for n in names if f"jit({call})" in n]
        assert bwd and {trace.layer_of(n) for n in bwd} == {"ssd"}, call
        assert not [n for n in bwd if "ssd.core" in n]
