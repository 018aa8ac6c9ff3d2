"""What a rematerialised block of the pattern decoder keeps (models/lm.py,
models/lm_remat.py): its forward kernel run once, what is kept equal to
what would be made again, the names identities without remat, and the 2017
layer's kernel output kept too. (One file with test_lm_pattern.py until PR
44.)"""

import jax
import numpy as np
import pytest
from flax import linen as nn

from lm_pattern_cases import (D, F, H, KINDS, LEAVES, S, V, _equations,
                              _on_the_kernels, description, leaf, seeded)


# -- what a rematerialised block keeps ----------------------------------------

def _bare_remat(cls, keeps=None, **kw):
    """``nn.remat`` without the rule's policy, in ``rematerialised``'s
    place."""
    return nn.remat(cls, **kw)


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
    from metaopt_tpu.models import lm, lm_layers, lm_remat
    from metaopt_tpu.ops import attention

    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, S + 1), 2, V)
    out = {}
    for kind in REMAT_KINDS:
        layers = [KINDS[kind]] * 2

        def gradient(remat):
            model = lm.make_lm(description(layers, remat=remat))
            if remat:   # the projections' names too (a bare remat has none)
                model = model.clone(keeps=(
                    *lm_remat.remat_keeps(model.pattern)["keeps"],
                    *lm_layers.GroupedSpec.KEPT.values()))
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
                    for module in (attention, lm_layers):
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
