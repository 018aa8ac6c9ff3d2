"""The decoder built from a description with ``sa_config`` (models/lm.py)
against the benchmark's plain float32 reference
(chipbench/reference/sparse_lm.py): loss and every gradient leaf, a second
layer's too, and a gradient tree that names no indexer. (One file with
test_lm_selected.py until PR 44.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lm_selected_cases import (D, E, F, H, HD, IH, IK, KEYS, KV, LEAVES, S,
                               TOPK, V, description, leaf)


# -- the decoder against the plain reference -----------------------------------

def reference_cfg(layers):
    return {"d_model": D, "n_heads": H, "n_kv_heads": KV, "head_dim": HD,
            "n_layers": layers, "rope_theta": 1e7, "rms_eps": 1e-6,
            "index_heads": IH, "index_dim": IK, "top_keys": KEYS,
            "n_experts": E, "top_k": TOPK, "expert_d_ff": F,
            "activation": "silu", "experts_held": [0, E],
            "vocab_held": [0, V]}


@pytest.fixture(scope="module")
def both_sides():
    """{layers: (program's (loss, gradients), reference's)} on seeded
    weights, the gradients in the reference's form (an expert a leaf)."""
    from chipbench import weights_lm
    from chipbench.reference import sparse_lm as reference
    from metaopt_tpu.models import lm

    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, S + 1), 2, V)
    out = {}
    for layers in (1, 2):
        cfg = reference_cfg(layers)
        whole = weights_lm.make_weights(7, reference.param_shapes(cfg))
        model = lm.make_lm(description(layers))
        trained, frozen = lm.split_frozen(weights_lm.stacked(whole))
        loss, grads = jax.value_and_grad(lambda p: lm.lm_loss_fn(
            model, lm.merge_frozen(p, frozen), tokens,
            jax.random.PRNGKey(0)))(trained)
        ref = jax.value_and_grad(lambda p: reference.loss(
            reference.with_indexers(p, whole), tokens, cfg))(
                reference.trained(whole))
        out[layers] = ((loss, weights_lm.split(grads)), ref)
    return out


@pytest.mark.parametrize("layers", [1, 2])
def test_loss_matches_the_plain_reference(both_sides, layers):
    (prog, _), (ref, _) = both_sides[layers]
    assert abs(float(prog) - float(ref)) <= 2e-3 * abs(float(ref))


@pytest.mark.parametrize("path", LEAVES)
def test_every_gradient_leaf_matches_the_plain_reference(both_sides, path):
    """bfloat16 products against float32: the difference's norm stays under
    a twentieth of the leaf's."""
    (_, prog), (_, ref) = both_sides[1]
    p, r = leaf(prog, path), leaf(ref, path)
    assert np.linalg.norm(r) > 0
    assert np.linalg.norm(p - r) <= 0.05 * np.linalg.norm(r), path


def stack(tree, path):
    """A leaf, or an expert layer's matrices of one kind, all experts."""
    for part in path.split("/"):
        tree = tree[part]
    if isinstance(tree, dict):
        return np.stack([np.asarray(tree[e], np.float32)
                         for e in sorted(tree)])
    return np.asarray(tree, np.float32)


@pytest.mark.parametrize("path", [
    p.replace("h0", "h1") for p in LEAVES[3:12]] + [
    "h1/experts/gate", "h1/experts/up", "h1/experts/down"])
def test_a_second_layer_s_gradients_match_too(both_sides, path):
    """Looser: bfloat16 activations may move a key or an expert of the
    second layer's choice past its neighbour (an expert sees ~15 of the 80
    tokens: its matrices are compared all experts together)."""
    (_, prog), (_, ref) = both_sides[2]
    p, r = stack(prog, path), stack(ref, path)
    assert np.linalg.norm(p - r) <= 0.12 * np.linalg.norm(r), path


def test_the_gradient_tree_names_no_indexer(both_sides):
    (_, prog), (_, ref) = both_sides[2]
    names = lambda tree: sorted(  # noqa: E731
        "/".join(str(p.key) for p in path)
        for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0])
    assert names(prog) == names(ref)
    assert not any("indexer" in n for n in names(prog))


def test_the_selection_changes_the_output():
    """With top-k at the sequence's length every causal key is seen: the
    model is then another function than with KEYS of them."""
    from metaopt_tpu.models import lm

    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, S), 2, V)
    outs = []
    for keys in (KEYS, S):
        sa = dict(description()["sa_config"], topk=keys)
        model = lm.make_lm(description(sa_config=sa))
        params = model.init(jax.random.PRNGKey(0), tokens, train=False)
        outs.append(model.apply(params, tokens, train=False))
    changed = np.abs(np.asarray(outs[0] - outs[1])).max(-1)[0]
    assert changed[:KEYS].max() == 0 and changed[KEYS:].max() > 0
