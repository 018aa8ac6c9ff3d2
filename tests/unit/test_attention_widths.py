"""q.k and v of different widths under a ``CausalMask``: the Pallas kernels
in interpret mode and the chunked twin against plain attention written
here (jnp at matmul precision highest). Forward, ``lse``, and every
gradient; and a latent layer's one shared rotary key, a copy of which is
joined to every head's keys: its gradient is the heads' sum.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metaopt_tpu.ops import attention
from metaopt_tpu.ops.attention import CausalMask, flash_attention

#: (a head's own key width, the shared key's, v's, query heads, K/V heads,
#: length, window): the tests' 24 / 16 with and without a shared part, the
#: latent layer's 192 / 128 likewise, grouped K/V heads with unequal
#: widths, a window, a length that pads, and equal widths
CASES = [(16, 8, 16, 4, 4, 96, None), (24, 0, 16, 4, 2, 130, None),
         (128, 64, 128, 2, 2, 256, None), (192, 0, 128, 2, 2, 256, None),
         (16, 8, 32, 4, 4, 200, 64), (128, 0, 128, 2, 1, 256, None)]
IDS = ["x".join(map(str, c)) for c in CASES]


def operands(case, batch=2):
    dk, ds, dv, h, hkv, s, _ = case
    ks = jax.random.split(jax.random.PRNGKey(sum(case[:6])), 5)
    q = jax.random.normal(ks[0], (batch, s, h, dk + ds)) * (dk + ds) ** -0.5
    k = jax.random.normal(ks[1], (batch, s, hkv, dk))
    v = jax.random.normal(ks[2], (batch, s, hkv, dv))
    shared = jax.random.normal(ks[3], (batch, s, ds)) if ds else None
    w = jax.random.normal(ks[4], (batch, s, h, dv))
    return q, k, v, shared, w


def joined(k, shared):
    """A copy of the shared key (B, S, Ds) joined to every head's own."""
    if shared is None:
        return k
    return jnp.concatenate([k, jnp.broadcast_to(
        shared[:, :, None], (*k.shape[:3], shared.shape[-1]))], axis=-1)


def plain(q, k, v, shared, window):
    """(out, lse): dense scores under the mask, one softmax a row; the
    shared key's scores are summed in by hand, not through a joined K."""
    b, s, h, _ = q.shape
    extra = 0.0
    if shared is not None:
        ds = shared.shape[-1]
        extra = jnp.einsum("bqhd,bkd->bhqk", q[..., -ds:], shared,
                           precision="highest")
        q = q[..., :-ds]
    group = h // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") + extra
    diff = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = diff >= 0
    if window is not None:
        seen &= diff < window
    scores = jnp.where(seen, scores, -jnp.inf)
    lse = jax.nn.logsumexp(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(scores - lse[..., None]), v,
                     precision="highest")
    return out, lse


@pytest.fixture(scope="module")
def results():
    """(case, impl) -> the route's (out, gradients) and plain attention's."""
    made = {}

    def get(case, impl):
        if (case, impl) not in made:
            q, k, v, shared, w = operands(case)
            args = (q, k, v) + ((shared,) if shared is not None else ())
            nums = tuple(range(len(args)))

            def route(q, k, v, shared=None):
                return flash_attention(q, joined(k, shared), v,
                                       CausalMask(case[6]), impl=impl,
                                       interpret=True)

            def oracle(q, k, v, shared=None):
                return plain(q, k, v, shared, case[6])[0]

            made[case, impl] = tuple(
                (f(*args), jax.grad(lambda *a: jnp.sum(f(*a) * w), nums)(
                    *args)) for f in (route, oracle))
        return made[case, impl]

    return get


@pytest.mark.parametrize("impl", ["pallas", "chunked"])
@pytest.mark.parametrize("case", CASES, ids=IDS)
def test_the_output_is_v_wide_and_plain_attention_s(results, case, impl):
    (out, _), (want, _) = results(case, impl)
    dk, ds, dv, h, hkv, s, _ = case
    assert out.shape == (2, s, h, dv)
    np.testing.assert_allclose(out, want, atol=3e-6)


@pytest.mark.parametrize("impl", ["pallas", "chunked"])
@pytest.mark.parametrize("case, which", [
    (case, which) for case in CASES for which in ("dq", "dk", "dv", "dshared")
    if which != "dshared" or case[1]],
    ids=lambda x: x if isinstance(x, str) else "x".join(map(str, x)))
def test_a_gradient_is_plain_attention_s(results, case, impl, which):
    """dq at the q.k width, dk at a head's own, dv at v's; the shared key's
    gradient is one (B, S, Ds) array: the sum over the heads of the last
    Ds columns of the joined K's gradient."""
    index = ["dq", "dk", "dv", "dshared"].index(which)
    (_, got), (_, want) = results(case, impl)
    assert got[index].shape == want[index].shape
    scale = float(jnp.max(jnp.abs(want[index])))
    np.testing.assert_allclose(got[index], want[index], atol=3e-6 * scale
                               + 1e-6)


@pytest.mark.parametrize("case", CASES[:4], ids=IDS[:4])
def test_the_kernel_s_lse_is_the_rows_logsumexp(case):
    q, k, v, shared, _ = operands(case, batch=1)
    s = q.shape[1]
    block, s_p = attention._derived_block(s)
    pad = lambda x: jnp.pad(  # noqa: E731
        x, ((0, 0), (0, s_p - s)) + ((0, 0),) * (x.ndim - 2))
    _, lse = attention._causal_forward(
        pad(q), pad(joined(k, shared)), pad(v), None, block, block, True)
    want = plain(q, k, v, shared, None)[1]
    group = q.shape[2] // k.shape[2]
    assert lse.shape == (1, q.shape[2], 1, s_p) and group >= 1
    np.testing.assert_allclose(lse[:, :, 0, :s], want, atol=3e-6)


@pytest.mark.parametrize("case", CASES[:2], ids=IDS[:2])
def test_the_chunked_twin_s_lse_is_the_rows_logsumexp(case):
    q, k, v, shared, _ = operands(case, batch=1)
    s = q.shape[1]
    qp, kp, vp, mask = attention._plain_operands(q, joined(k, shared), v,
                                                 CausalMask())
    block, s_p = attention._block_and_pad(s, 128)
    pad = lambda x: jnp.pad(  # noqa: E731
        x, ((0, 0), (0, s_p - s), (0, 0), (0, 0)))
    mask = jnp.pad(mask, ((0, 0), (0, s_p - s), (0, s_p - s)))
    _, lse = attention._chunked_forward(pad(qp), pad(kp), pad(vp), mask,
                                        block, 0.0, None)
    np.testing.assert_allclose(lse[:, :, :s], plain(q, k, v, shared, None)[1],
                               atol=3e-6)


@pytest.mark.parametrize("impl, mask, message", [
    ("pallas", None, "under a CausalMask alone"),
    ("pallas", "dense", "under a CausalMask alone")])
def test_unequal_widths_have_kernels_under_a_causal_mask_alone(impl, mask,
                                                              message):
    q, k, v, _, _ = operands(CASES[1])
    dense = None if mask is None else jnp.ones(
        (2, q.shape[1], q.shape[1]), bool)
    with pytest.raises(ValueError, match=message):
        flash_attention(q, k, v, dense, impl=impl, interpret=True)


def test_q_and_k_have_one_width():
    q, k, v, shared, _ = operands(CASES[0])
    with pytest.raises(ValueError, match="q is 24 wide, its keys 16"):
        flash_attention(q, k, v, CausalMask(), interpret=True)


def test_the_chunked_twin_takes_unequal_widths_under_a_dense_mask():
    q, k, v, shared, _ = operands(CASES[0])
    s = q.shape[1]
    dense = jnp.broadcast_to(CausalMask().dense(s, s), (2, s, s))
    out = flash_attention(q, joined(k, shared), v, dense, impl="chunked")
    np.testing.assert_allclose(out, plain(q, k, v, shared, None)[0],
                               atol=3e-6)
