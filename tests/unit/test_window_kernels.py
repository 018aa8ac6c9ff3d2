"""The causal kernels under a window no wider than a tile
(ops/window_attention.py): which calls take them, the window's edge to the
pair, and what ``trial.setup`` and the trace's reader say of a layer.

The kernels' numerics against the reference are cases of
``tests/unit/test_attention.py::TestStructuralMask`` (``SLAB``)."""

import importlib
import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metaopt_tpu.ops import attention, window_attention
from metaopt_tpu.ops.attention import (CausalMask, _reference_attention,
                                       flash_attention)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# -- the rule -----------------------------------------------------------------

@pytest.mark.parametrize("window, block, length, sub", [
    (512, 512, 8192, window_attention.SUB),   # the two cells' window layers
    (128, 128, 384, 128), (256, 256, 768, window_attention.SUB),
    (128, 512, 1024, window_attention.SUB),   # narrower than the tile
    (4096, 512, 8192, None),                  # the 8k cell: 8 tiles wide
    (None, 512, 8192, None), (640, 512, 8192, None),
    (500, 512, 8192, None), (100, 128, 384, None),  # no whole lane tiles
    (16, 32, 96, None), (64, 64, 192, None)])       # the tests' small tiles
def test_one_rule_says_which_calls_take_the_slab_kernels(window, block,
                                                         length, sub):
    assert window_attention.slab_sub(window, block, length) == sub


def _taken(monkeypatch):
    """The names of the kernels' entries a call reaches, in order."""
    taken = []
    for module, name in ((window_attention, "flash_window"),
                         (attention, "_flash_causal")):
        entry = getattr(module, name)

        def spy(*args, _entry=entry, _name=name, **kwargs):
            taken.append(_name)
            return _entry(*args, **kwargs)
        monkeypatch.setattr(module, name, spy)
    return taken


@pytest.mark.parametrize("window, tile, entry", [
    (512, 512, "flash_window"), (4096, 512, "_flash_causal"),
    (None, 512, "_flash_causal"), (128, None, "flash_window"),
    (100, None, "_flash_causal")])
def test_a_call_takes_the_kernels_the_rule_names(monkeypatch, window, tile,
                                                 entry):
    """From ``mask.window``, the tile and the length alone: the cells'
    window of 512 at tiles of 512 the slab kernels, the 8k cell's 4096 and
    no window the walk. Traced, not run: no kernel is interpreted."""
    taken = _taken(monkeypatch)
    s = 1024 if tile else 384
    q = jax.ShapeDtypeStruct((1, s, 2, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, s, 1, 128), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: flash_attention(
        q, k, v, CausalMask(window), impl="pallas", interpret=True,
        block_q=tile, block_k=tile), q, k, k)
    assert taken == [entry]


def test_the_walk_s_kernels_are_where_the_compile_cache_has_them():
    """Mosaic keeps file and line of the frames above a ``pallas_call`` in
    a kernel's body and the persistent cache keys on the body: a line moved
    above ``attend`` is a cold compile of every kernel in every cell
    (ROADMAP S1c, S10). Whoever moves one pays that once, knowingly, and
    writes the new lines here."""
    first = lambda f: inspect.getsourcelines(f)[1]  # noqa: E731
    assert {name: first(getattr(attention, name)) for name in (
        "_call", "_causal_fwd_kernel", "_causal_bwd_kernel",
        "_causal_forward", "_causal_backward", "flash_attention",
        "attend")} == {
            "_call": 316, "_causal_fwd_kernel": 523,
            "_causal_bwd_kernel": 561, "_causal_forward": 616,
            "_causal_backward": 640, "flash_attention": 979, "attend": 1177}
    # the choice lives below them all
    assert first(attention._causal_kernels) > first(attention.attend)


# -- the window's edge, to the pair -------------------------------------------

@pytest.fixture(scope="module")
def edge():
    """One row of 1100 tokens (three tiles of 512, the last padded) under
    the cells' window of 512 on the slab kernels, output and gradients."""
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(7), 4)
    s, d = 1100, 64
    q = jax.random.normal(kq, (1, s, 2, d)) / np.sqrt(d)
    k = jax.random.normal(kk, (1, s, 1, d))
    v = jax.random.normal(kv, (1, s, 1, 128))
    g = jax.random.normal(kg, (1, s, 2, 128))

    def both(fn):
        out, pull = jax.vjp(fn, q, k, v)
        return (out, *pull(g))

    assert window_attention.slab_sub(512, 512, 1536)
    got = both(lambda q, k, v: flash_attention(
        q, k, v, CausalMask(512), impl="pallas", interpret=True))
    dense = lambda w: both(lambda q, k, v: _reference_attention(  # noqa: E731
        q, k, v, CausalMask(w).dense(s, s)))
    return got, dense


def test_the_window_is_512_pairs_wide(edge):
    got, dense = edge
    for a, b in zip(got, dense(512)):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("other", [511, 513])
def test_a_window_one_pair_off_is_another_answer(edge, other):
    """The cell's check does not see a window of 513 (ROADMAP S11: one key
    more of 512 is inside bfloat16's rounding at the timed size): here, in
    float32, output and every gradient differ by far more than the
    tolerance the right window passes."""
    got, dense = edge
    for a, b in zip(got, dense(other)):
        assert float(jnp.max(jnp.abs(a - b))) > 1e-2
    # and only where the edge is: a query with fewer keys before it than
    # the narrower window sees the same keys under all three
    np.testing.assert_allclose(got[0][:, :511], dense(other)[0][:, :511],
                               atol=1e-4, rtol=1e-4)


# -- what a trial says of its layers ------------------------------------------

CELLS = {"laguna-xs2-33b-a3b-ep8": ("gated_lm_config", "window-rope"),
         "phi-4-mini-flash-vp8": ("ssm_lm_config", "window-nope"),
         "smallthinker-21b-a3b-ep4": ("lm_config", "window-rope")}


def _said(cell, route="pallas"):
    from metaopt_tpu.models import lm_description

    with open(os.path.join(ROOT, "chipbench", "configs", cell + ".json")) as f:
        config = json.load(f)
    a = config["script_args"]
    description = importlib.import_module(
        "chipbench." + CELLS[cell][0]).description(config)
    return lm_description.describe_pattern(
        description, route, tokens=a["batch_size"] * a["seq_len"],
        seq_len=a["seq_len"])["attention_layers"]


@pytest.mark.parametrize("cell", ["laguna-xs2-33b-a3b-ep8",
                                  "phi-4-mini-flash-vp8"])
def test_the_window_512_layers_say_slab(cell):
    """The seventh and the sixth configuration at their own rows of 8192:
    tiles of 512, the slab kernels, their sub-tile and the keys a program
    reads a key seen, from the function that sizes the slab."""
    sub = window_attention.SUB
    said = _said(cell)
    assert said[CELLS[cell][1]]["kernels"] == {
        "kernels": "slab", "tile": 512, "sub": sub,
        "walked_over_seen": (512 + sub) / 512}
    # a layer without a window says nothing of it, nor does another route
    assert all("kernels" not in how for kind, how in said.items()
               if not kind.startswith("window"))
    assert "kernels" not in _said(cell, "reference")[CELLS[cell][1]]


def test_the_window_4096_layers_say_walk():
    assert _said("smallthinker-21b-a3b-ep4")["window-rope"]["kernels"] == {
        "kernels": "walk", "tile": 512, "walked_over_seen": 1.125}


def test_the_walk_read_twice_the_keys_seen_under_512(monkeypatch):
    """What the parent's kernels did to the window-512 layers, from the
    walk's own ranges: two tiles of 512 for 512 keys."""
    monkeypatch.setattr(window_attention, "slab_sub", lambda *a: None)
    assert window_attention.window_kernels(512, 8192) == {
        "kernels": "walk", "tile": 512, "walked_over_seen": 2.0}


def test_the_reader_prints_which_kernels_a_window_layer_takes(capsys):
    from metaopt_tpu.utils import trace

    sub = window_attention.SUB
    setup = {"name": "trial.setup", "trial": "T-1", "attrs": {
        "attention": {"dropout": 0.0, "train": "pallas", "eval": "pallas"},
        "attention_layers": {
            "global-rope": {"route": "pallas", "mask": "structure: causal"},
            "window-rope": {
                "route": "pallas", "mask": "structure: causal, window 512",
                "kernels": window_attention.window_kernels(512, 8192)},
            "window-nope": {
                "route": "pallas", "mask": "structure: causal, window 4096",
                "kernels": window_attention.window_kernels(4096, 8192)}}}}
    trace.print_routes([setup])
    assert capsys.readouterr().out.splitlines()[1:] == [
        "trial T-1: global-rope layers: pallas, mask by structure: causal",
        "trial T-1: window-rope layers: pallas, mask by structure: causal, "
        f"window 512, kernels one slab of keys a sub-tile of {sub} in tiles "
        f"of 512, {(512 + sub) / 512:g} keys read a key seen",
        "trial T-1: window-nope layers: pallas, mask by structure: causal, "
        "window 4096, kernels a walk over the tiles seen in tiles of 512, "
        "1.125 keys read a key seen"]
