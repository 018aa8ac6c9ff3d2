"""The scalar decay rule (ops/linear_attention.py::scalar_decay_rule:
Mamba-2's recurrence in its matmul form, q and k shared by groups of heads)
against the recurrence written here token by token, at small sizes on the
CPU: forward and the gradients of q, k, v and g, in float64 and float32, at
lengths that are and are not whole chunks, the Pallas kernels interpreted
against the plain chunked twin."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metaopt_tpu.ops import linear_attention
from metaopt_tpu.ops.linear_attention import scalar_decay_rule

B, H, G, DK, DV = 2, 4, 2, 16, 8
OPERANDS = "qkvg"


def recurrence(q, k, v, g):
    """S_t = exp(g_t) S_{t-1} + k_t v_t^T, o_t = S_t^T q_t, a token at a
    time; head h reads group h // (H / G)."""
    q, k, v = (x.astype(g.dtype) for x in (q, k, v))
    b, _, h, dv = v.shape
    share = h // q.shape[2]
    qh, kh = jnp.repeat(q, share, axis=2), jnp.repeat(k, share, axis=2)

    def step(s, x):
        qt, kt, vt, gt = x
        s = jnp.exp(gt)[..., None, None] * s \
            + kt[..., :, None] * vt[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt)

    _, o = jax.lax.scan(step, jnp.zeros((b, h, q.shape[-1], dv), v.dtype),
                        tuple(jnp.moveaxis(x, 1, 0) for x in (qh, kh, v, g)))
    return jnp.moveaxis(o, 0, 1)


def operands(t, dtype, groups=G, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, t, groups, DK), dtype)
    k = 0.3 * jax.random.normal(ks[1], (B, t, groups, DK), dtype)
    v = jax.random.normal(ks[2], (B, t, H, DV), dtype)
    # decays from ~1 to exp(-1.6) a token, as a trained model's steps give
    g = -1.6 * jax.random.uniform(ks[3], (B, t, H), dtype) ** 4
    w = jax.random.normal(ks[4], (B, t, H, DV), dtype)
    return (q, k, v, g), w


def _both(args, w, interpret):
    mine = lambda *a: jnp.sum(  # noqa: E731
        scalar_decay_rule(*a, interpret=interpret).astype(w.dtype) * w)
    ref = lambda *a: jnp.sum(recurrence(*a) * w)  # noqa: E731
    return (scalar_decay_rule(*args, interpret=interpret), recurrence(*args),
            jax.grad(mine, argnums=(0, 1, 2, 3))(*args),
            jax.grad(ref, argnums=(0, 1, 2, 3))(*args))


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / np.abs(b).max()


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["plain-twin", "pallas-interpreted"])
@pytest.mark.parametrize("dtype, tol, t", [
    ("float64", 1e-12, 256), ("float64", 1e-12, 200), ("float32", 2e-5, 200),
    ("float32", 2e-5, 72)], ids=lambda x: str(x))
def test_output_and_gradients_are_the_recurrence_s(dtype, tol, t, interpret):
    """Whole chunks (256), a length that pads (200) and one under a chunk
    (72); two groups of two heads. Not an approximation: float64 agrees to
    rounding."""
    with jax.enable_x64(dtype == "float64"):
        args, w = operands(t, jnp.dtype(dtype))
        o, o_ref, grads, grads_ref = _both(args, w, interpret)
        assert o.shape == o_ref.shape == (B, t, H, DV) and o.dtype == w.dtype
        assert rel(o, o_ref) <= tol
        for name, mine, ref in zip(OPERANDS, grads, grads_ref):
            assert mine.shape == ref.shape, name
            assert rel(mine, ref) <= tol, name


@pytest.mark.parametrize("groups", [1, 4], ids=["one-group", "a-head-a-group"])
def test_any_grouping_of_the_heads(groups):
    """All heads on one q and k, and a q and k a head."""
    args, w = operands(200, jnp.float32, groups)
    o, o_ref, grads, grads_ref = _both(args, w, True)
    assert rel(o, o_ref) <= 2e-5
    for mine, ref in zip(grads, grads_ref):
        assert mine.shape == ref.shape and rel(mine, ref) <= 2e-5


def test_the_kernels_and_the_twin_agree_in_bfloat16():
    """bfloat16 operands, float32 decays, as the mixer calls the rule: the
    two routes run one algebra and round alike; against the float32
    recurrence both read bfloat16's rounding."""
    (q, k, v, g), w = operands(256, jnp.float32)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
    twin, _, twin_grads, _ = _both((q, k, v, g), w, None)
    kern, ref, kern_grads, ref_grads = _both((q, k, v, g), w, True)
    assert kern.dtype == twin.dtype == jnp.bfloat16
    np.testing.assert_array_equal(f32(kern), f32(twin))
    for a, b in zip(kern_grads, twin_grads):
        assert rel(f32(a), f32(b)) <= 1e-6
    assert rel(f32(kern), f32(ref)) <= 0.03
    for a, b in zip(kern_grads, ref_grads):
        assert rel(f32(a), f32(b)) <= 0.05


def test_a_padded_token_leaves_the_state_alone():
    """The first 200 outputs of a row of 256 whose tail is anything are the
    row of 200's: a padded token has g 0 and v 0."""
    (q, k, v, g), _ = operands(256, jnp.float32)
    short = scalar_decay_rule(q[:, :200], k[:, :200], v[:, :200], g[:, :200])
    np.testing.assert_allclose(np.asarray(scalar_decay_rule(q, k, v,
                                                            g)[:, :200]),
                               np.asarray(short), rtol=2e-5, atol=2e-5)


def test_the_route_is_the_delta_rule_s_and_the_names_are_the_rule_s():
    assert linear_attention.linear_attention_route() == {
        "route": "xla", "chunk": 128}
    assert linear_attention.SCALAR_DECAY_KEEPS == ("ssd.out", "ssd.states")
    assert not set(linear_attention.SCALAR_DECAY_KEEPS) \
        & set(linear_attention.REMAT_KEEPS)
