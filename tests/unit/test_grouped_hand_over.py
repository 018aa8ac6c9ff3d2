"""A grouped layer's hand-over of q and k to the attention kernels
(ops/grouped_hand_over.py): the one Pallas call a direction, interpreted
here, against the passes it takes the place of (``RMSNorm``, ``rope``, the
score scale and the cast of models/lm_layers.py, composed), value and
gradients; the one rule; what the calls are named and what they leave in
memory; and the lines of models/lm_layers.py that must not move.
"""

import inspect
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metaopt_tpu.models import lm_layers
from metaopt_tpu.ops import grouped_hand_over as gh

from lm_pattern_cases import _equations, one_device

D, EPS = 128, 1e-6
RULES = {
    "none": None,
    "plain": lm_layers.Rotary(1e4),
    "partial": lm_layers.Rotary(1e4, turned=64),
    "yarn": lm_layers.Rotary(5e5, 64, (64.0, 4096, 64.0, 1.0),
                             1.4158883083359672),
}


def passes(x, scale, rule, multiplier):
    """What the layer composes without the call: the float32 norm, the
    turn, the scale as a division, one cast."""
    if scale is not None:
        x = lm_layers.RMSNorm(EPS).apply({"params": {"scale": scale}}, x)
    if rule is not None:
        x = lm_layers.rope(x, rule)
    x = x.astype(jnp.float32)
    if multiplier != 1.0:
        x = x / (1.0 / multiplier)
    return x.astype(jnp.bfloat16)


def one_pass(x, scale, rule, multiplier):
    cos = sin = None
    if rule is not None:
        cos, sin = gh.tables(rule.frequencies(rule.turned or D), rule.factor,
                             x.shape[1])
    return gh.operand(x, scale, cos, sin, EPS, multiplier, True)


def both(x, scale, rule, multiplier, weight):
    """(value, the product's gradient, the scale's or None) each way."""
    wrt = (0, 1) if scale is not None else (0,)
    got = []
    for fn in (one_pass, passes):
        value, pull = jax.vjp(
            lambda *a: fn(*a, *(() if scale is not None else (None,)),
                          rule, multiplier), *(x, scale)[:len(wrt)])
        got.append((value, *pull(weight.astype(value.dtype))))
    return got


def operands(s, heads, normed, seed=0):
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = (3.0 * jax.random.normal(kx, (1, s, heads, D))).astype(jnp.bfloat16)
    scale = 1.0 + 0.3 * jax.random.normal(kw, (D,)) if normed else None
    return x, scale, jax.random.normal(kg, (1, s, heads, D))


def roundings_apart(a, b):
    """|a - b| in units of b's last bfloat16 place, elementwise; a number
    under a thousandth of the operands' size counts as one that large
    (where ``a cos - b sin`` cancels, this CPU's compiled passes fuse the
    multiply and the subtraction into one rounding and the interpreter's
    do not: the float32 results differ in their own last place, which a
    tiny result magnifies)."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.abs(a - b) / (2.0 ** (np.floor(np.log2(np.maximum(
        np.abs(b), 1e-3))) - 7))


def within(a, b, share):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.linalg.norm(a - b) <= share * np.linalg.norm(b)


CASES = [(normed, rule, which, heads)
         for normed in (True, False) for rule in RULES
         for which in ("q", "k") for heads in (48, 64)
         if normed or rule != "none"]


@pytest.mark.parametrize("normed, rule, which, heads", CASES, ids=[
    f"{'norm' if n else 'bare'}-{r}-{w}-{h}" for n, r, w, h in CASES])
def test_one_pass_is_the_passes_to_a_rounding(normed, rule, which, heads):
    """The call's operand is the composed passes' to one bfloat16 rounding
    (the sums run in another order; the scale is a product where the layer
    divides) on a length no tile divides, and so are the cotangents it
    hands back: the product's to bfloat16's rounding, the norm scale's,
    a float32 sum over positions and heads, far inside it."""
    multiplier = 1.0 / math.sqrt(D) if which == "q" else 1.0
    x, scale, weight = operands(200, heads, normed)
    (v1, dx1, *dw1), (v2, dx2, *dw2) = both(x, scale, RULES[rule],
                                            multiplier, weight)
    assert v1.dtype == v2.dtype == jnp.bfloat16 and v1.shape == x.shape
    apart = roundings_apart(v1, v2)
    assert apart.max() <= 1.0
    assert np.mean(apart > 0) < 1e-3
    assert dx1.dtype == x.dtype and within(dx1, dx2, 1e-3)
    if normed:
        assert dw1[0].dtype == scale.dtype and dw1[0].shape == (D,)
        assert within(dw1[0], dw2[0], 1e-5)


@pytest.mark.parametrize("s", [128, 512, 640, 2048 + 256])
def test_tiles_and_lanes_cover_every_length(s):
    """One tile of 128; a tile of 512 walked 256 positions at a time; five
    tiles of 128, whose shares of the scale's gradient are summed outside;
    nine tiles of 256."""
    x, scale, weight = operands(s, 4, True, seed=s)
    (v1, dx1, dw1), (v2, dx2, dw2) = both(
        x, scale, RULES["yarn"], 1.0 / math.sqrt(D), weight)
    assert roundings_apart(v1, v2).max() <= 1.0
    assert within(dx1, dx2, 1e-3) and within(dw1, dw2, 1e-5)


def test_the_unturned_channels_pass_as_the_norm_left_them():
    """Past ``turned`` a head's channels are the normed product times the
    scale, bit for bit what the passes give, and without a norm and a scale
    the product itself."""
    x, scale, _ = operands(200, 6, True)
    rule = RULES["partial"]
    assert np.array_equal(
        np.asarray(one_pass(x, scale, rule, 1.0)[..., 64:], np.float32),
        np.asarray(passes(x, scale, None, 1.0)[..., 64:], np.float32))
    assert np.array_equal(
        np.asarray(one_pass(x, None, rule, 1.0)[..., 64:], np.float32),
        np.asarray(x[..., 64:], np.float32))


def test_the_tables_are_rope_s_numbers_with_the_sequence_last():
    rule = RULES["yarn"]
    cos, sin = gh.tables(rule.frequencies(64), rule.factor, 300)
    assert cos.shape == sin.shape == (32, 300) and cos.dtype == jnp.float32
    angle = jnp.arange(300, dtype=jnp.float32)[:, None] \
        * rule.frequencies(64)[None]
    assert np.array_equal(cos.T, jnp.cos(angle) * rule.factor)
    assert np.array_equal(sin.T, jnp.sin(angle) * rule.factor)


# -- the rule -----------------------------------------------------------------

def test_the_rule_for_the_hand_over():
    from jax.sharding import Mesh

    one = one_device()
    two = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    for mesh in (None, one):
        assert gh.hand_over("pallas", mesh, "head", True) == "one pass"
        assert gh.hand_over("pallas", mesh, "head", False) == "one pass"
        assert gh.hand_over("pallas", mesh, None, True) == "one pass"
    # nothing a head does alone in float32; a norm over all the heads; a
    # mesh of several devices; a route that does not run the kernels
    assert gh.hand_over("pallas", one, None, False) == "passes"
    assert gh.hand_over("pallas", one, "whole", True) == "passes"
    assert gh.hand_over("pallas", two, "head", True) == "passes"
    for route in ("reference", "chunked", "ring", "ulysses"):
        assert gh.hand_over(route, one, "head", True) == "passes"


def _layer(spec, s=256, d_model=64):
    layer = lm_layers.GroupedAttention(d_model, spec, EPS)
    x = jnp.zeros((1, s, d_model))
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0),
                                               x)["params"])
    return layer, params, x


def _names(jaxpr):
    return [str(e.params.get("name", "")) for e in _equations(jaxpr)
            if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("qk_norm, theta, calls", [
    ("head", 1e4, 2), (None, 1e4, 2), ("head", None, 2), (None, None, 0),
    ("whole", 1e4, 0)])
def test_a_layer_asks_the_rule(monkeypatch, qk_norm, theta, calls):
    """On the Pallas route a layer traces ``grouped_qk`` for q and for k
    where the rule says one pass, and the norms' scales keep their paths;
    off the TPU none."""
    spec = lm_layers.GroupedSpec(4, 2, 32, None, theta, qk_norm, None)
    layer, params, x = _layer(spec)
    paths = {k: sorted(v) for k, v in params.items() if "norm" in k}
    trace = lambda: _names(jax.make_jaxpr(  # noqa: E731
        lambda p, x: layer.apply({"params": p}, x))(params, x).jaxpr)
    assert trace().count("grouped_qk") == 0
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert trace().count("grouped_qk") == calls
    assert {k: sorted(v) for k, v in _layer(spec)[1].items()
            if "norm" in k} == paths
    assert paths == ({"q_norm": ["scale"], "k_norm": ["scale"]}
                     if qk_norm else {})


def test_a_rematerialised_layer_s_gradient_makes_the_forward_call_again(
        monkeypatch):
    """With the block's policy (the products kept, and what the kernel
    made): ``grouped_qk`` for q and k forward and again, ``grouped_qk_bwd``
    once each, and no float32 array as large as q outside the calls."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spec = lm_layers.GroupedSpec(4, 2, 32, None, 1e4, "head", None,
                                 lm_layers.Rotary(1e4, turned=16))
    layer, params, x = _layer(spec)
    policy = jax.checkpoint_policies.save_only_these_names(
        "attention.out", "attention.lse", *spec.KEPT.values())
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(jax.checkpoint(
        lambda p, x: layer.apply({"params": p}, x), policy=policy)(
            p, x).astype(jnp.float32)), argnums=(0, 1)))(params, x).jaxpr
    names = _names(jaxpr)
    assert (names.count("grouped_qk"), names.count("grouped_qk_bwd")) \
        == (4, 2)
    assert (names.count("flash_fwd"), names.count("flash_bwd")) == (1, 1)
    q_sized = [e for e in jaxpr.eqns for v in e.outvars
               if v.aval.dtype == jnp.float32
               and v.aval.size == x.shape[1] * spec.heads * spec.head_dim]
    assert not q_sized, q_sized


def test_the_backward_call_s_operations_are_attention_s(monkeypatch):
    """A backward rule has no forward name stack: the rule names the layer
    itself, so a traced step's time stays attention's projections'."""
    from metaopt_tpu.utils import trace

    x, scale, weight = operands(128, 2, True)
    text = jax.jit(jax.grad(lambda x: jnp.sum(one_pass(
        x, scale, RULES["plain"], 1.0).astype(jnp.float32) * weight))).lower(
            x).as_text(debug_info=True)
    import re

    bwd = [n for n in set(re.findall(r'loc\("([^"]+)"', text))
           if "jit(_backward)" in n]
    assert bwd and {trace.layer_of(n) for n in bwd} == {"attention"}


# -- what must not move -------------------------------------------------------

def test_the_latent_layer_is_where_the_compile_cache_has_it():
    """Mosaic keeps file and line of the frames above a ``pallas_call`` in
    a kernel's body and the persistent cache keys on the body (ROADMAP
    S11): the latent cell's kernels are reached through
    ``LatentAttention.__call__``, the sixth cell's through
    ``DifferentialAttention.__call__``, so what this PR adds to
    models/lm_layers.py stands below both. Whoever moves one pays a cold
    compile of that cell once, knowingly, and writes the new lines here."""
    first = lambda f: inspect.getsourcelines(f)[1]  # noqa: E731
    assert {name: first(getattr(lm_layers, name)) for name in (
        "LatentSpec", "LatentAttention", "DifferentialAttention")} == {
            "LatentSpec": 391, "LatentAttention": 453,
            "DifferentialAttention": 959}
    assert first(lm_layers._handed_over) > first(
        lm_layers.DifferentialAttention)
