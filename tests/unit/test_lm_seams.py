"""The seams between the modules that took models/lm.py's place: a spec's
candidates for the remat rule carry exactly the names its module marks
(models/lm_layers.py, models/moe.py), the rule gives the five decoder cells
the answers it gave before the specs (models/lm_remat.py; the table was
written by the parent of PR 44), and the modules import one way, a family's
name in the description's reader alone (models/lm_description.py)."""

import ast
import importlib
import json
import os
import re
from typing import Any

import jax
import jax.numpy as jnp
import pytest
from flax import linen as nn

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MODELS = os.path.join(ROOT, "metaopt_tpu", "models")
D, S = 32, 16


class Host(nn.Module):
    """A block's place around one spec: what ``PatternBlock`` hands it."""

    spec: Any
    d_model: int = D
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x, *read):
        if hasattr(self.spec, "mix"):
            return self.spec.mix(self, x, *read)[0]
        return self.spec.feed(self, x, self.spec.before_mixer(self, x))


def _specs():
    from metaopt_tpu.models import lm_layers as ll
    from metaopt_tpu.models import moe

    grouped = dict(heads=4, kv_heads=2, head_dim=8, window=None, theta=1e4,
                   qk_norm=None, selection=None)
    routed = dict(n_experts=4, top_k=2, d_ff=16, held=(0, 4),
                  activation="silu", shared_d_ff=0, rule=moe.RoutingRule(),
                  router_after_mixer=True)
    kv = (jnp.zeros((1, S, 2, 8), jnp.bfloat16),) * 2
    return {
        "grouped": (ll.GroupedSpec(**grouped), ()),
        "grouped-window-norms": (ll.GroupedSpec(**{
            **grouped, "window": 4, "theta": None, "qk_norm": "whole"}), ()),
        "grouped-selected": (ll.GroupedSpec(**{
            **grouped, "selection": (2, 8, 4)}), ()),
        "grouped-gated-yarn": (ll.GroupedSpec(**{
            **grouped, "theta": 5e5, "qk_norm": "head", "gate": "sigmoid",
            "rotary": ll.Rotary(5e5, 4, (64.0, 4096, 64.0, 1.0), 1.4159)}),
            ()),
        "latent": (ll.LatentSpec(4, 16, 8, 4, 8, True, 1e4), ()),
        "linear": (ll.LinearSpec(2, 2, 8, 8, 4, True), ()),
        "ssm": (ll.StateSpaceSpec(64, 4, 4, 2), ()),
        "gmu": (ll.MemoryUnitSpec(64), (jnp.zeros((1, S, 64)),)),
        "differential": (ll.DifferentialSpec(4, 2, 8, 4, 0.5, False), ()),
        "differential-cross": (ll.DifferentialSpec(4, 2, 8, None, 0.5, True),
                               (kv,)),
        "gated": (ll.GatedSpec(48, "relu"), ()),
        "routed": (moe.RoutedSpec(**routed), ()),
        "routed-early-router": (moe.RoutedSpec(**{
            **routed, "router_after_mixer": False}), ()),
        "routed-shared-bias": (moe.RoutedSpec(**{
            **routed, "shared_d_ff": 24,
            "rule": moe.RoutingRule("sigmoid", bias=True)}), ()),
    }


def _marked(jaxpr, found):
    """The names ``checkpoint_name`` gave in ``jaxpr`` and every jaxpr
    under it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            found.add(eqn.params["name"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _marked(sub, found)
    return found


@pytest.mark.parametrize("case", [
    "grouped", "grouped-window-norms", "grouped-selected",
    "grouped-gated-yarn", "latent", "linear",
    "ssm", "gmu", "differential", "differential-cross", "gated", "routed",
    "routed-early-router", "routed-shared-bias"])
def test_a_spec_offers_the_names_its_module_marks(case):
    """A product renamed in the module and not in the spec (or the other way
    round) would silently never be kept: the cell would lose the remat
    rule's gain with every other test green."""
    spec, read = _specs()[case]
    host, x = Host(spec), jnp.zeros((1, S, D))
    params = jax.eval_shape(
        lambda: host.init(jax.random.PRNGKey(0), x, *read))["params"]
    marked = _marked(jax.make_jaxpr(lambda p: host.apply(
        {"params": p}, x, *read, mutable=["moe_stats", "attn_stats"])[0])(
            params).jaxpr, set())
    kernels = set(spec.kernel_keeps()) if hasattr(spec, "mix") else set()
    offered = [name for _, sizes in spec.products(D) for name in sizes]
    assert len(offered) == len(set(offered))
    assert marked - kernels == set(offered)
    assert all(width > 0 and min(sizes.values()) > 0
               for width, sizes in spec.products(D))
    # half the heads, or half the width, on a tp axis of two: no name lost
    halved = [name for _, sizes in spec.under_tp(2).products(D)
              for name in sizes]
    assert halved == offered


with open(os.path.join(HERE, "lm_remat_table.json")) as _f:
    #: cell -> {limit: what the parent's ``remat_on`` said of it on one
    #: device that states ``limit`` bytes, "parameters": its count}
    TABLE = json.load(_f)
CELLS = {"smallthinker-21b-a3b-ep4": "lm_config",
         "keye-vl2-30b-a3b-ep8": "sparse_lm_config",
         "olmo-hybrid-7b-tp2": "hybrid_lm_config",
         "kanana-2-30b-a3b-ep8": "mla_lm_config",
         "phi-4-mini-flash-vp8": "ssm_lm_config"}


@pytest.mark.parametrize("limit", [16 * 2 ** 30, 12 * 2 ** 30],
                         ids=["16GiB", "12GiB"])
@pytest.mark.parametrize("cell", list(CELLS))
def test_the_rule_gives_a_cell_the_answer_the_parent_gave(cell, limit):
    """``keeps`` (in the order they were tried), ``room`` and ``bytes`` to
    the byte, at the cell's own tokens and the parent's parameter count;
    under the tighter limit some candidates are declined and the ones
    behind them still tried."""
    from metaopt_tpu.models import lm, lm_remat

    with open(os.path.join(ROOT, "chipbench", "configs", cell + ".json")) as f:
        config = json.load(f)
    a = config["script_args"]
    assert [a["batch_size"], a["seq_len"]] == TABLE[cell]["batch_shape"]
    model = lm.make_lm(importlib.import_module(
        "chipbench." + CELLS[cell]).description(config))
    said = lm_remat.remat_keeps(
        model.pattern.under_tp(a["tp"]),
        tokens=a["batch_size"] * a["seq_len"], d_model=model.d_model,
        parameters=TABLE[cell]["parameters"], bytes_limit=limit)
    want = TABLE[cell][str(limit)]
    assert said == want
    assert list(said["bytes"]) == list(want["bytes"])


def _modules():
    return {name[:-3]: ast.parse(open(os.path.join(MODELS, name)).read())
            for name in sorted(os.listdir(MODELS)) if name.endswith(".py")}


def _siblings(nodes):
    """The modules of metaopt_tpu/models that ``nodes`` import."""
    found = set()
    for node in nodes:
        if isinstance(node, ast.ImportFrom) and node.module:
            if node.module == "metaopt_tpu.models":
                found |= {alias.name for alias in node.names}
            elif node.module.startswith("metaopt_tpu.models."):
                found.add(node.module.split(".")[2])
        elif isinstance(node, ast.Import):
            found |= {alias.name.split(".")[2] for alias in node.names
                      if alias.name.startswith("metaopt_tpu.models.")}
    return found


def test_the_models_import_one_way():
    """No cycle among the top-level imports of metaopt_tpu/models/*.py."""
    graph = {name: _siblings(tree.body) & set(_modules())
             for name, tree in _modules().items()}
    assert graph["lm"] >= {"lm_layers", "lm_description", "lm_remat"}
    assert "lm_layers" in graph["moe"] and "moe" in graph["lm_description"]
    done = set()
    while len(done) < len(graph):     # peel off what imports only the peeled
        free = {n for n, deps in graph.items()
                if n not in done and deps <= done}
        assert free, f"a cycle among {sorted(set(graph) - done)}"
        done |= free


#: where a function may still import a sibling when it runs, and why
LATE = {"transformer": {"moe",         # moe -> lm_layers -> transformer
                        "checkpoint"},  # orbax, only where a trial restores
        "lm": {"checkpoint"}}


def test_no_function_of_the_decoder_s_modules_imports_a_sibling():
    for name, tree in _modules().items():
        inside = [node for fn in ast.walk(tree)
                  if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
                  for node in ast.walk(fn)]
        assert _siblings(inside) <= LATE.get(name, set()), name


def test_a_family_s_name_is_the_reader_s_alone():
    """Outside models/lm_description.py no code of metaopt_tpu/models names
    a family (``_FAMILIES``' keys) or asks which mechanism a pattern has: a
    docstring may say who publishes a layer, a line of code may not."""
    from metaopt_tpu.models import lm_description

    families = "|".join(map(re.escape, lm_description._FAMILIES))
    asks = re.compile(
        r"family ==|\.(hybrid|latent|linear|selection) is not None"
        r"|[A-Z]_REMAT_KEEPS")  # a kept name is read by its key, from KEPT
    for name, tree in _modules().items():
        if name == "lm_description":
            continue
        docstrings = {id(node.body[0].value) for node in ast.walk(tree)
                      if isinstance(node, (ast.Module, ast.ClassDef,
                                           ast.FunctionDef))
                      and node.body and isinstance(node.body[0], ast.Expr)}
        for node in ast.walk(tree):
            said = (node.value if isinstance(node, ast.Constant)
                    and isinstance(node.value, str)
                    and id(node) not in docstrings else
                    node.id if isinstance(node, ast.Name) else
                    node.attr if isinstance(node, ast.Attribute) else "")
            assert not re.search(rf"\b({families})\b", said), (name, said)
        with open(os.path.join(MODELS, name + ".py")) as f:
            code = "\n".join(line.split("#")[0] for line in f)
        assert not asks.search(code), (name, asks.search(code))
