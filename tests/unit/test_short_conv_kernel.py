"""The gated short convolution's core (metaopt_tpu/ops/short_conv.py): the
two Pallas calls, interpreted, against their plain twin and against the
equations written out here, and the one rule for which of the two a mixer
takes. (The calls' TPU lowering: tests/unit/test_attention_tpu_compile.py.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metaopt_tpu.models import lm_layers
from metaopt_tpu.ops import short_conv as sc


def operands(b, t, d, taps=3, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k[0], (b, t, 3 * d)).astype(jnp.bfloat16),
            jax.random.uniform(k[1], (taps, d), jnp.float32, -0.5, 0.5),
            jax.random.normal(k[2], (b, t, d)).astype(jnp.bfloat16))


def f32(x):
    return np.asarray(x.astype(jnp.float32))


def both(bcx, taps, dy, tile):
    """((y, d product, d taps) by the calls, the same by the twin)."""
    sides = []
    for fn in (lambda p, k: sc.gated_short_conv(p, k, tile=tile,
                                                interpret=True),
               sc.gated_short_conv_plain):
        y, vjp = jax.vjp(fn, bcx, taps)
        sides.append((y,) + vjp(dy))
    return sides


#: (rows, tokens, channels, taps, (forward's tile, backward's)): a row of one
#: tile; whole tiles; rows that are no multiple of the tile, where the last
#: tile's rows past the row's end are what Pallas pads them with; several
#: blocks of columns; two and four taps
SHAPES = [(1, 96, 128, 3, None), (2, 128, 128, 3, (32, 16)),
          (2, 200, 256, 3, (64, 32)), (1, 80, 128, 3, (32, 16)),
          (1, 256, 1024, 3, (128, 64)), (2, 48, 128, 2, (16, 16)),
          (1, 112, 128, 4, (32, 32))]


@pytest.mark.parametrize("b, t, d, taps, tile", SHAPES,
                         ids=lambda v: str(v).replace(" ", ""))
def test_the_calls_are_their_twin(b, t, d, taps, tile):
    """Output, the product's cotangent and the taps' gradient: one
    bfloat16 rounding apart at most (the calls and the twin may contract a
    multiply-add differently), the taps' float32 sums to a rounding of the
    sum's."""
    (y, dp, dk), (y2, dp2, dk2) = both(*operands(b, t, d, taps), tile)
    assert y.dtype == dp.dtype == jnp.bfloat16 and dk.dtype == jnp.float32
    assert y.shape == (b, t, d) and dp.shape == (b, t, 3 * d)
    for ours, twin in ((y, y2), (dp, dp2)):
        ours, twin = f32(ours), f32(twin)
        assert np.all(np.abs(ours - twin) <= 2 ** -7 * np.abs(twin) + 1e-30)
    np.testing.assert_allclose(dk, dk2, rtol=1e-5,
                               atol=1e-5 * float(jnp.max(jnp.abs(dk2))))


def test_the_twin_is_short_conv_between_two_products():
    bcx, taps, _ = operands(2, 40, 64)
    b, c, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    np.testing.assert_array_equal(
        f32(sc.gated_short_conv_plain(bcx, taps)),
        f32((c * lm_layers.short_conv(b * x, taps)).astype(jnp.bfloat16)))


@pytest.mark.parametrize("tile", [None, (32, 16)], ids=["one-tile", "tiles"])
def test_a_row_s_first_two_tokens_read_nothing_before_it(tile):
    """y_0 = C_0 k_2 u_0 and y_1 = C_1 (k_1 u_0 + k_2 u_1), u = B X: what
    lies before the row's start is zero, in a tile's halo too."""
    bcx, taps, _ = operands(2, 64, 128, seed=3)
    y = f32(sc.gated_short_conv(bcx, taps, tile=tile, interpret=True))
    b, c, x = (np.asarray(v) for v in jnp.split(
        bcx.astype(jnp.float32), 3, axis=-1))
    u, k = b * x, np.asarray(taps)
    want = np.stack([c[:, 0] * (k[2] * u[:, 0]),
                     c[:, 1] * (k[1] * u[:, 0] + k[2] * u[:, 1]),
                     c[:, 2] * (k[0] * u[:, 0] + k[1] * u[:, 1]
                                + k[2] * u[:, 2])], axis=1)
    np.testing.assert_allclose(y[:, :3], want, rtol=2 ** -7, atol=1e-30)


def test_no_tap_crosses_from_one_row_into_the_next():
    """Batch 2: row 1's output and cotangents are the same whatever row 0
    holds, and row 0's whatever row 1's cotangent is."""
    bcx, taps, dy = operands(2, 64, 128, seed=4)
    other, _, dy2 = operands(2, 64, 128, seed=5)
    run = lambda p, d: both(p, taps, d, (32, 16))[0]  # noqa: E731
    y, dp, _ = run(bcx, dy)
    y2, dp2, _ = run(bcx.at[0].set(other[0]), dy.at[0].set(dy2[0]))
    np.testing.assert_array_equal(f32(y[1]), f32(y2[1]))
    np.testing.assert_array_equal(f32(dp[1]), f32(dp2[1]))
    assert np.abs(f32(y[0]) - f32(y2[0])).max() > 0


def test_the_taps_gradient_is_the_sum_over_tokens_and_rows():
    """dk_i = sum over rows and tokens of (dy C)_t u_{t-2+i}, float32,
    written out here in float64."""
    bcx, taps, dy = operands(2, 72, 128, seed=6)
    dk = both(bcx, taps, dy, (32, 16))[0][2]
    b, c, x = (np.asarray(v, np.float64) for v in jnp.split(
        bcx.astype(jnp.float32), 3, axis=-1))
    u, dc = b * x, f32(dy).astype(np.float64) * c
    padded = np.pad(u, ((0, 0), (2, 0), (0, 0)))
    want = np.stack([(dc * padded[:, i:i + 72]).sum((0, 1))
                     for i in range(3)])
    np.testing.assert_allclose(dk, want, rtol=1e-5, atol=1e-4)


def test_a_product_that_is_no_three_thirds_is_refused():
    bcx, taps, _ = operands(1, 32, 128)
    with pytest.raises(ValueError, match="three thirds"):
        sc.gated_short_conv(bcx[..., :256], taps)
    with pytest.raises(ValueError, match="at most 9 taps"):
        sc.gated_short_conv(bcx, jnp.zeros((10, 128)))


class _Mesh:
    def __init__(self, size):
        self.size = size


@pytest.mark.parametrize("backend, mesh, channels, tokens, route", [
    ("tpu", None, 2048, 8192, "pallas"),
    ("tpu", _Mesh(1), 2048, 8192, "pallas"),
    ("tpu", _Mesh(4), 2048, 8192, "plain"),      # a mesh of several devices
    ("tpu", None, 64, 96, "plain"),              # a rehearsal's widths
    ("tpu", None, 2048, 8200, "plain"),          # no whole sublane tiles
    ("cpu", None, 2048, 8192, "plain")])
def test_the_one_rule_names_the_route(monkeypatch, backend, mesh, channels,
                                      tokens, route):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert sc.short_conv_route(mesh, channels, tokens) == route


def test_a_mixer_on_the_kernels_is_the_mixer_on_the_twin(monkeypatch):
    """``ShortConvMixer`` with the backend faked and the calls interpreted
    against itself on the plain route: output and every gradient."""
    import functools

    spec = lm_layers.ShortConvSpec(channels=128, taps=3)
    mixer = lm_layers.ShortConvMixer(128, spec)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 128))
    params = mixer.init(jax.random.PRNGKey(1), u)

    def run():
        return jax.value_and_grad(lambda p, v: jnp.sum(jnp.square(
            mixer.apply(p, v).astype(jnp.float32))), argnums=(0, 1))(
                params, u)

    plain = run()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sc, "gated_short_conv", functools.partial(
        sc.gated_short_conv, interpret=True))
    kernels = run()
    for a, b in zip(jax.tree.leaves(kernels), jax.tree.leaves(plain)):
        a, b = f32(a), f32(b)
        assert np.linalg.norm(a - b) <= 5e-3 * np.linalg.norm(b)
