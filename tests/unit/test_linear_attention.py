"""ops/linear_attention.py: the gated delta rule in chunks.

The chunked form, on both routes (the plain twin, and the Pallas kernels
run by the interpreter here), against the recurrence as it is written,
token by token in float64: the output and every gradient, at lengths that
are no whole number of chunks (padded), with decays near 0 and near 1 and
steps near 2; the inverse by block elimination; the one rule that names the
route; the names a rematerialised block keeps.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metaopt_tpu.ops import linear_attention as la

B, H, DK, DV = 1, 2, 16, 24
OPERANDS = ("q", "k", "v", "g", "beta")


def recurrence(q, k, v, g, beta):
    """The module docstring's recurrence, a token at a time."""
    def head(q, k, v, g, beta):
        def token(state, x):
            qt, kt, vt, gt, bt = x
            a = jnp.exp(gt)
            state = a * state + bt * jnp.outer(kt, vt - a * (state.T @ kt))
            return state, state.T @ qt

        return jax.lax.scan(token, jnp.zeros((k.shape[-1], v.shape[-1]),
                                             q.dtype), (q, k, v, g, beta))[1]

    over_heads = jax.vmap(head, in_axes=1, out_axes=1)
    return jax.vmap(over_heads)(q, k, v, g, beta)


#: name -> (tokens, log decays' range, the step's pre-sigmoid mean)
CASES = {
    "padded": (200, (1e-3, 1.0), 0.0),
    "one-short-chunk": (40, (1e-2, 0.3), 0.0),
    "decay-near-0": (200, (3.0, 12.0), 0.0),
    "decay-near-1": (200, (1e-7, 1e-5), 0.0),
    "step-near-2": (200, (1e-3, 0.1), 6.0),
}


def operands(case):
    t, (lo, hi), step = CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (B, t, H, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (B, t, H, DK)))
    v = jax.random.normal(ks[2], (B, t, H, DV))
    g = -jnp.exp(jax.random.uniform(ks[3], (B, t, H), minval=np.log(lo),
                                    maxval=np.log(hi)))
    beta = 2 * jax.nn.sigmoid(step + jax.random.normal(ks[4], (B, t, H)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (B, t, H, DV))


@functools.partial(jax.jit, static_argnames=("how",))
def results(fn_args, w, how="recurrence"):
    """[o, dq, dk, dv, dg, dbeta], the gradients those of sum(o * w):
    of the recurrence, or of the chunked form (``how``: None the plain
    twin, True the kernels interpreted). Jitted: cases of one shape share
    a compile."""
    fn = recurrence if how == "recurrence" else functools.partial(
        la.gated_delta_rule, interpret=how)
    run = lambda *a: fn(*a).astype(w.dtype)  # noqa: E731
    return [run(*fn_args), *jax.grad(lambda *a: jnp.sum(run(*a) * w),
                                     argnums=range(5))(*fn_args)]


@pytest.fixture(scope="module")
def sides():
    """{(route, case): (got, want)} as float64 arrays: the chunked form's
    results and the recurrence's."""
    done = {}

    def of(route, case):
        if (route, case) not in done:
            with jax.enable_x64(True):
                args, w = operands(case)
                wide = [x.astype(jnp.float64) for x in args]
                # the plain twin in float64; the kernels, interpreted, in
                # float32: the operands' own type is the products'
                fed, how = (wide, None) if route == "xla" else (
                    [x.astype(jnp.float32) for x in args], True)
                done[route, case] = tuple(
                    [np.asarray(x, np.float64) for x in r] for r in (
                        results(fed, w.astype(fed[0].dtype), how),
                        results(wide, w.astype(jnp.float64))))
        return done[route, case]

    return of


@pytest.mark.parametrize("what", ("o",) + OPERANDS)
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("route", ["xla", "pallas"])
def test_the_chunked_form_is_the_recurrence(sides, route, case, what):
    """Not an approximation of it: float64 operands agree to float64's
    rounding, float32 ones (the interpreted kernels) to float32's."""
    got, want = sides(route, case)
    i = (("o",) + OPERANDS).index(what)
    assert got[i].shape == want[i].shape
    scale = np.abs(want[i]).max()
    assert scale > 0
    tol = 1e-11 if route == "xla" else 3e-5
    assert np.abs(got[i] - want[i]).max() <= tol * max(scale, 1.0), what


@pytest.mark.parametrize("what", ("o",) + OPERANDS)
def test_bfloat16_operands_keep_the_state_in_float32(what):
    """The models' call: bfloat16 q, k, v, float32 gates. The products
    round, the state and the decays do not: every result stays within a
    hundredth of its size of the recurrence on the same rounded operands."""
    (q, k, v, g, beta), w = operands("padded")
    args = [x.astype(jnp.bfloat16) for x in (q, k, v)] + [g, beta]
    with jax.enable_x64(True):
        want = results([x.astype(jnp.float64) for x in args],
                       w.astype(jnp.float64))
    got = results(args, w, None)
    i = (("o",) + OPERANDS).index(what)
    a, b = np.asarray(got[i], np.float64), np.asarray(want[i], np.float64)
    assert got[i].dtype == (w.dtype if i == 0 else args[i - 1].dtype)
    assert np.linalg.norm(a - b) <= 0.01 * np.linalg.norm(b)


@pytest.mark.parametrize("size", [2, 8, 64, 128])
def test_block_elimination_inverts_a_unit_triangular_matrix(size):
    with jax.enable_x64(True):
        a = jnp.tril(jax.random.normal(jax.random.PRNGKey(size),
                                       (size, size), jnp.float64), -1)
        t = la._unit_lower_inverse(a, *la._grid(size))
        eye = jnp.eye(size, dtype=jnp.float64)
        assert float(jnp.abs(t @ (eye + a) - eye).max()) <= 1e-9 * max(
            1.0, float(jnp.abs(t).max()))


def test_identical_keys_at_a_step_of_two_reflect_and_do_not_grow():
    """beta = 2 on one repeated key, no decay: the state's update is a
    reflection, the hardest case for the inverse (every entry of A is 2)."""
    t = 2 * la.CHUNK
    k = jnp.broadcast_to(jnp.eye(DK)[0], (1, t, 1, DK))
    v = jax.random.normal(jax.random.PRNGKey(0), (1, t, 1, DV))
    args = (k * DK ** -0.5, k, v, jnp.zeros((1, t, 1)),
            jnp.full((1, t, 1), 2.0))
    with jax.enable_x64(True):
        wide = [x.astype(jnp.float64) for x in args]
        want = np.asarray(recurrence(*wide))
        got = np.asarray(la.gated_delta_rule(*wide))
    assert np.abs(got - want).max() <= 1e-9 * np.abs(want).max()


@pytest.mark.parametrize("backend, route", [("tpu", "pallas"),
                                            ("cpu", "xla"), ("gpu", "xla")])
def test_one_place_names_the_route(monkeypatch, backend, route):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert la.linear_attention_route() == {
        "route": route, "chunk": la.CHUNK}


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_a_rematerialised_caller_keeps_the_output_and_the_states():
    """Under ``save_only_these_names(*REMAT_KEEPS)`` the gradient holds one
    forward walk of the chunks, without a policy two."""
    (q, k, v, g, beta), w = operands("one-short-chunk")

    def walks(policy):
        def loss(q):
            return jnp.sum(la.gated_delta_rule(q, k, v, g, beta,
                                               interpret=True) * w)

        names = [e.params["name"] for e in _equations(jax.make_jaxpr(
            jax.grad(jax.checkpoint(loss, policy=policy)))(q).jaxpr)
                 if e.primitive.name == "pallas_call"]
        return (names.count("linear_scan_fwd"),
                names.count("linear_scan_bwd"))

    keep = jax.checkpoint_policies.save_only_these_names(*la.REMAT_KEEPS)
    assert walks(keep) == (1, 1)
    assert walks(None) == (2, 1)
