"""The selective scan (ops/selective_scan.py): Mamba-1's recurrence.

The kernels (interpreted here), the plain chunked twin and the sequential
recurrence, token by token, agree forward and backward to float32's
rounding, for every operand; a chunk that does not divide the row is
refused by name; the one rule names the route; the names a rematerialised
block keeps are on the rule's own values.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metaopt_tpu.ops import selective_scan as ss

R, T, D, N = 2, 96, 160, 4
OPERANDS = ("x", "dt", "a", "b", "c")


def sequential(x, dt, a, b, c):
    """The recurrence as the module's docstring writes it, a token at a
    time."""
    def token(h, xs):
        xt, dtt, bt, ct = xs
        h = jnp.exp(dtt[..., None] * a) * h \
            + (dtt * xt)[..., None] * bt[:, None, :]
        return h, jnp.einsum("rdn,rn->rd", h, ct)

    _, y = jax.lax.scan(
        token, jnp.zeros((x.shape[0], x.shape[2], a.shape[1])),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


@pytest.fixture(scope="module")
def every_way():
    """{how: (y, {operand: gradient})} of a weighted sum of the output."""
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    args = (jax.random.normal(k[0], (R, T, D)),
            jax.nn.softplus(jax.random.normal(k[1], (R, T, D)) - 2.0),
            -jnp.exp(jax.random.normal(k[2], (D, N))),
            jax.random.normal(k[3], (R, T, N)),
            jax.random.normal(k[4], (R, T, N)))
    w = jax.random.normal(k[5], (R, T, D))
    ways = {"sequential": sequential,
            "twin": ss.selective_scan,
            "twin, chunks of 16": lambda *v: ss.selective_scan(*v, chunk=16),
            "kernels": lambda *v: ss.selective_scan(*v, interpret=True)}
    out = {}
    for how, fn in ways.items():
        y, grads = jax.value_and_grad(
            lambda *v: jnp.sum(fn(*v) * w), argnums=tuple(range(5)))(*args)
        out[how] = (fn(*args), dict(zip(OPERANDS, grads)))
    return out


@pytest.mark.parametrize("what", ("y",) + OPERANDS)
@pytest.mark.parametrize("how", ["twin", "twin, chunks of 16", "kernels"])
def test_both_routes_are_the_sequential_recurrence(every_way, how, what):
    pick = lambda way: np.asarray(  # noqa: E731
        every_way[way][0] if what == "y" else every_way[way][1][what])
    got, want = pick(how), pick("sequential")
    assert got.shape == want.shape and np.abs(want).max() > 0
    assert np.abs(got - want).max() <= 5e-6 * np.abs(want).max()


def test_a_row_that_is_no_whole_number_of_chunks_is_refused():
    x = jnp.zeros((1, 96, 8))
    bc = jnp.zeros((1, 96, N))
    with pytest.raises(ValueError, match="96 tokens is no whole number of "
                                         "chunks of 64"):
        ss.selective_scan(x, x, -jnp.ones((8, N)), bc, bc, chunk=64)


@pytest.mark.parametrize("backend, devices, route", [
    ("cpu", 1, "xla"), ("tpu", 1, "pallas"), ("tpu", 4, "xla")])
def test_the_one_rule_names_the_route(monkeypatch, backend, devices, route):
    from jax.sharding import Mesh

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    mesh = Mesh(np.array(jax.devices()[:devices]), ("tp",))
    said = ss.selective_scan_route(96, mesh)
    assert said == {"route": route, "chunk": 32, "state": "float32"}
    assert ss.selective_scan_route(8192)["chunk"] == ss.CHUNK


def test_the_kept_names_are_on_the_rules_own_values():
    """Under ``save_only_these_names`` the gradient of a rematerialised
    call walks the row forward once: the output AND the chunks' states are
    kept, so no second forward kernel is in the backward pass."""
    from lm_pattern_cases import _equations

    x = jnp.ones((1, 64, 8))
    bc = jnp.ones((1, 64, N))
    a = -jnp.ones((8, N))

    def loss(keep):
        policy = jax.checkpoint_policies.save_only_these_names(*keep)
        return jax.make_jaxpr(jax.grad(jax.checkpoint(
            lambda v: jnp.sum(ss.selective_scan(v, x, a, bc, bc,
                                                interpret=True)),
            policy=policy)))(x)

    def calls(jaxpr):
        names = [e.params["name"] for e in _equations(jaxpr.jaxpr)
                 if e.primitive.name == "pallas_call"]
        return tuple(sum(k in n for n in names) for k in (
            "selective_scan_fwd", "selective_scan_bwd"))

    assert calls(loss(ss.REMAT_KEEPS)) == (1, 1)
    assert calls(loss(ss.REMAT_KEEPS[:1])) == (2, 1)
