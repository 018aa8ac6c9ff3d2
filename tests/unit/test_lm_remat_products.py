"""What a rematerialised block keeps of its mixer's projections
(models/lm_layers.py: ``GroupedSpec.KEPT``, ``LinearSpec.KEPT``), for each
of the three kinds of pattern block: the MoE decoder's, the one that
selects its keys, the hybrid's linear and full layers.

The trial's own step, lowered as ``LMTrial`` builds it: where the device
states room (pinned in ``device_bytes_limit``'s place) no product of a kept
name is under ``rematted_computation``; where it states none, every one is.
"""

import collections

import jax
import jax.numpy as jnp
import pytest
from flax import linen as nn

S = 24


def _description(kind, **over):
    import lm_pattern_cases
    import lm_selected_cases
    import test_lm_hybrid

    return {"pattern": lambda: lm_pattern_cases.description(
                [(0, 0), (1, 1)], **over),
            "selected": lambda: lm_selected_cases.description(2, **over),
            "hybrid": lambda: test_lm_hybrid.description(
                **test_lm_hybrid.PAIR, **over)}[kind]()


#: kind -> the (scope, projection) pairs of its mixers, and how many layers
#: of the two-layer stack make each
ATTENTION = [("attention", n) for n in ("q", "k", "v", "out")]
LINEAR = [("linear_attention", n) for n in ("q", "k", "v", "g", "a", "b",
                                            "out")]
PROJECTIONS = {"pattern": (ATTENTION, 2), "selected": (ATTENTION, 2),
               "hybrid": (ATTENTION + LINEAR, 1)}
CASES = [(kind, scope, name) for kind, (pairs, _) in PROJECTIONS.items()
         for scope, name in pairs]


def products_by_direction(operations):
    """{(scope, projection): Counter(direction)} of the ``dot_general``s
    directly under a mixer's scope: the core's, the indexer's and the
    selection's lie a scope deeper."""
    from metaopt_tpu.utils import trace

    out = collections.defaultdict(collections.Counter)
    for kind, name in operations:
        parts = name.split("/")
        if kind == "stablehlo.dot_general" and len(parts) > 3 \
                and parts[-3] in ("attention", "linear_attention"):
            out[parts[-3], parts[-2]][trace.direction(name)] += 1
    return out


@pytest.fixture(scope="module")
def lowered():
    """(kind, limit) -> the products of the trial's lowered step, each
    lowered once a module."""
    from lm_pattern_cases import one_device
    from test_trace_layers import operations

    from metaopt_tpu.models import lm, lm_remat

    made = {}

    def get(kind, limit):
        if (kind, limit) not in made:
            with pytest.MonkeyPatch.context() as patch:
                patch.setattr(lm_remat, "device_bytes_limit",
                              lambda mesh: limit)
                trial = lm.LMTrial(
                    {**_description(kind), "remat": True, "lr": 1e-3},
                    mesh=one_device(), n_train=4, batch_size=2, seq_len=S)
            with trial:
                module = trial._step_fn.lower(
                    trial.params, trial.opt_state, trial.counts,
                    trial.rows(0), jax.random.PRNGKey(0)).compiler_ir()
            made[kind, limit] = (set(trial.model.keeps),
                                 products_by_direction(operations(module)))
        return made[kind, limit]

    return get


@pytest.mark.parametrize("kind, scope, name", CASES)
def test_a_kept_product_runs_once_in_the_forward_direction(lowered, kind,
                                                           scope, name):
    """Without room a projection's product is made in the forward pass,
    again in the block's second run and twice in the backward pass (to the
    input, to the weights); with room its second run is gone and nothing
    else moves."""
    layers = PROJECTIONS[kind][1]
    keeps, today = lowered(kind, None)
    assert f"{scope}.{name}_proj" not in keeps
    assert today[scope, name] == {
        "forward": layers, "forward.again": layers, "backward": 2 * layers}
    keeps, kept = lowered(kind, 2 ** 34)
    assert f"{scope}.{name}_proj" in keeps
    assert kept[scope, name] == {"forward": layers, "backward": 2 * layers}


@pytest.mark.parametrize("kind", list(PROJECTIONS))
def test_no_other_product_of_a_mixer_is_counted(lowered, kind):
    """The cases above are all the products directly under the mixers'
    scopes."""
    for limit in (None, 2 ** 34):
        assert set(lowered(kind, limit)[1]) == set(PROJECTIONS[kind][0])


def test_a_declined_name_s_product_is_still_made_again():
    """The hybrid cell's answer (the linear layers' output projection
    declined): that product alone keeps its second run."""
    from test_trace_layers import operations

    from metaopt_tpu.models import lm, lm_layers, lm_remat

    model = lm.make_lm(_description("hybrid", remat=True))
    linear = lm_layers.LinearSpec.KEPT
    model = model.clone(keeps=(
        *lm_remat.remat_keeps(model.pattern)["keeps"],
        *lm_layers.GroupedSpec.KEPT.values(),
        *(name for name in linear.values() if name != linear["out"])))
    tokens = jnp.zeros((1, S + 1), jnp.int32)
    params = jax.eval_shape(lambda: nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), tokens[:, :-1], train=False)["params"]))
    found = products_by_direction(operations(jax.jit(jax.grad(
        lambda p: lm.lm_loss_fn(model, p, tokens, jax.random.PRNGKey(0)))
    ).lower(params).compiler_ir()))
    again = {key for key, by in found.items() if by["forward.again"]}
    assert again == {("linear_attention", "out")}


@pytest.mark.parametrize("kind, names", [("pattern", 4 * 2),
                                         ("selected", 4 * 2)])
def test_without_a_policy_the_names_are_identities(kind, names):
    """Not rematerialised, the gradient is the one without models/lm.py's
    names but for a ``name`` equation a product (the hybrid's blocks:
    test_lm_hybrid.py, with the feed-forward's)."""
    from lm_pattern_cases import _equations

    from metaopt_tpu.models import lm, lm_layers

    tokens = jnp.zeros((1, S + 1), jnp.int32)
    primitives = {}
    for how in ("named", "unnamed"):
        with pytest.MonkeyPatch.context() as patch:
            if how == "unnamed":
                patch.setattr(lm_layers, "checkpoint_name",
                              lambda x, name: x)
            model = lm.make_lm(_description(kind))
            trained, frozen = lm.split_frozen(nn.meta.unbox(model.init(
                jax.random.PRNGKey(0), tokens[:, :-1],
                train=False)["params"]))
            jaxpr = jax.make_jaxpr(jax.grad(lambda p: lm.lm_loss_fn(
                model, lm.merge_frozen(p, frozen), tokens,
                jax.random.PRNGKey(0))))(trained)
        primitives[how] = [e.primitive.name
                           for e in _equations(jaxpr.jaxpr)]
    named, unnamed = primitives["named"], primitives["unnamed"]
    assert "checkpoint" not in named
    assert named.count("name") - unnamed.count("name") == names
    assert [p for p in named if p != "name"] \
        == [p for p in unnamed if p != "name"]


@pytest.mark.parametrize("kind, feed_forwards", [
    ("pattern", 0), ("selected", 0), ("hybrid", 2)])
def test_the_head_reads_a_cast_that_stands(kind, feed_forwards):
    """The forward pass holds one barrier a gated feed-forward and one on
    the head's input: the stream as bfloat16, the one value the head's
    product reads beside its table."""
    from lm_pattern_cases import _equations

    from metaopt_tpu.models import lm

    tokens = jnp.zeros((2, S), jnp.int32)
    model = lm.make_lm(_description(kind))
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(0), tokens,
                                      train=False)["params"])
    eqs = list(_equations(jax.make_jaxpr(lambda p: model.apply(
        {"params": p}, tokens, train=True,
        mutable=["moe_stats", "attn_stats"])[0])(params).jaxpr))
    barriers = [e for e in eqs if e.primitive.name == "optimization_barrier"]
    assert len(barriers) == feed_forwards + 1
    (stream,) = barriers[-1].outvars
    assert (stream.aval.dtype, stream.aval.shape) == (
        jnp.bfloat16, (2, S, model.d_model))
    head = [e for e in eqs if e.primitive.name == "dot_general"][-1]
    assert stream in head.invars
