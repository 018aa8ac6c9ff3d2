"""Each reader on a canned run, and each entry of ``per_layer`` on a recorded
step of every cell that lists it.

``data/records_<kind>.json`` are the ``records`` of one run of a runner kind
(as ``.runs/<cell>/result.json`` keeps them). ``data/step_<cell>.json`` is
what a traced run of the cell on the chip handed its readers, cut to one
step (``record_cell_step.py``), with what PR 47's 128 entries read from it
under PR 47's names: PR 48 folded every set of entries that read the same
thing with the same code into one entry with a ``workloads`` list, and
``FOLDED`` is the table old name -> folded name that follows a metric across
that PR in the ledger.
"""

import ast
import functools
import itertools
import json
import os

import pytest

import record_cell_step
from chipbench.run import _applies, _reader, per_layer_metrics

HERE = os.path.dirname(__file__)
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
ENTRIES = {m["name"]: m for m in BENCH["per_layer"]}
CELLS = [w["name"] for w in BENCH["workloads"]]

#: the prefix under which a cell copied a shared metric before PR 48; the
#: copies listed that cell alone (an accepted list took no new cell)
COPIES = {
    "attention_core_device_ms": ("lm_", "hybrid_lm_", "ssm_lm_", "gated_lm_",
                                 "sparse_", "mla_"),
    **dict.fromkeys(
        ("readout_xent_device_ms", "optimizer_device_ms",
         "scoped_device_share", "program_load_s"),
        ("lm_", "sparse_lm_", "hybrid_lm_", "mla_lm_", "ssm_lm_",
         "gated_lm_")),
    "compile_cache_hit_share": ("lm_", "sparse_lm_", "hybrid_lm_", "mla_lm_",
                                "ssm_lm_"),
    **dict.fromkeys(("moe_device_ms", "moe_experts_device_ms",
                     "moe_dropped_share"),
                    ("sparse_lm_", "mla_lm_", "gated_lm_")),
    "flash_fwd_roofline": ("hybrid_lm_", "ssm_lm_", "mla_"),
    "flash_bwd_roofline": ("hybrid_lm_", "ssm_lm_", "mla_"),
    **dict.fromkeys(("embed_device_ms", "attention_proj_device_ms",
                     "trunk_device_ms", "unnamed_device_ms"),
                    ("mla_lm_", "ssm_lm_", "gated_lm_")),
    # ``hybrid_lm_ffn_device_ms`` read the SCOPE ``ffn``, the others the
    # LAYER: one number where no ``ffn`` lies under another layer's scope
    "ffn_device_ms": ("hybrid_lm_", "mla_lm_", "ssm_lm_", "gated_lm_"),
    "forward_again_device_ms": ("mla_lm_", "ssm_lm_"),
    "backward_device_ms": ("mla_lm_", "ssm_lm_"),
    "trial_data_s": ("lm_",), "trial_init_s": ("lm_",),
    # ``mla_lm_moe_route_device_ms`` took ``moe.shared`` out of the
    # difference and the accepted reader did not: the one reader takes it
    # out, and reads 0 ms under ``moe.shared`` in a cell that has none
    "moe_route_device_ms": ("sparse_lm_", "mla_lm_"),
    "moe_shared_device_ms": ("mla_lm_",),
    # the latent and the gated cell scaled the passes by the ROUTED layers
    # (``kernel_work["routed_layers"]``), the two others by all of them
    "moe_experts_roofline": ("sparse_lm_", "mla_lm_", "gated_lm_"),
    "moe_held_load_max_over_mean": ("mla_lm_",),
}
FOLDED = {prefix + name: name for name, prefixes in COPIES.items()
          for prefix in prefixes}
#: what ``laguna-xs2.steady-8k`` reports since PR 48 and had no name for
JOINED = {"laguna-xs2.steady-8k": {
    "moe_route_device_ms", "moe_shared_device_ms",
    "moe_held_load_max_over_mean", "forward_again_device_ms",
    "backward_device_ms", "compile_cache_hit_share"}}

READERS = sorted(f[:-3] for f in os.listdir(os.path.join(
    ROOT, "chipbench", "readers")) if f.endswith(".py"))


def records(kind):
    return json.load(open(os.path.join(HERE, "data", f"records_{kind}.json")))


@functools.lru_cache(maxsize=None)
def step(cell):
    with open(os.path.join(HERE, "data", f"step_{cell}.json")) as f:
        return json.load(f)


#: every (cell, metric) of PR 47's ``per_layer``, as the recorded files hold it
PARENT_S = [(cell, old) for cell in CELLS for old in step(cell)["expected"]]


def test_the_recorded_files_hold_pr_47_s_entries():
    olds = {old for _, old in PARENT_S}
    assert len(olds) == 128 and len(PARENT_S) == 169
    assert set(FOLDED) <= olds
    renamed = set(FOLDED.values()) - olds          # ``moe_shared_device_ms``
    assert len(olds) - len(FOLDED) + len(renamed) == len(ENTRIES)
    assert {FOLDED.get(old, old) for old in olds} == set(ENTRIES)


@pytest.mark.parametrize("cell, old", PARENT_S)
def test_a_folded_entry_reads_what_pr_47_s_read_in_that_cell(
        monkeypatch, cell, old):
    doc = step(cell)
    entry = ENTRIES[FOLDED.get(old, old)]
    assert _applies(entry, cell)
    record_cell_step.hand_out(doc, monkeypatch.setattr)
    value = _reader(entry["name"]).read(doc["records"])
    assert value is not None and value == doc["expected"][old]


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_s_line_holds_every_metric_that_lists_it(monkeypatch, cell):
    doc = step(cell)
    record_cell_step.hand_out(doc, monkeypatch.setattr)
    line = per_layer_metrics(BENCH, cell, doc["records"])
    was = {FOLDED.get(old, old) for old in doc["expected"]}
    assert set(line) == was | JOINED.get(cell, set())
    assert set(line) == {m["name"] for m in BENCH["per_layer"]
                         if _applies(m, cell)}       # none reads None
    got = {name: m["value"] for name, m in line.items()}
    if "moe_route_device_ms" in got:
        assert got["moe_route_device_ms"] == pytest.approx(
            got["moe_device_ms"] - got["moe_experts_device_ms"]
            - got.get("moe_shared_device_ms", 0.0))
    if "moe_shared_device_ms" in got:
        assert 0 < got["moe_shared_device_ms"] < got["moe_device_ms"]


def _body(name):
    with open(os.path.join(ROOT, "chipbench", "readers", name + ".py")) as f:
        tree = ast.parse(f.read())
    body = tree.body
    if body and isinstance(body[0], ast.Expr) \
            and isinstance(body[0].value, ast.Constant):
        body = body[1:]                              # the docstring
    return ast.dump(ast.Module(body=body, type_ignores=[]))


def test_every_metric_has_a_reader_and_every_reader_a_metric():
    assert [m["name"] for m in BENCH["per_layer"]] == list(ENTRIES)  # once
    assert set(ENTRIES) == set(READERS)
    assert len(ENTRIES) <= 60


def test_no_two_entries_share_a_reader_s_body_and_a_cell():
    bodies = {name: _body(name) for name in READERS}
    for a, b in itertools.combinations(READERS, 2):
        if bodies[a] == bodies[b]:
            shared = [c for c in CELLS
                      if _applies(ENTRIES[a], c) and _applies(ENTRIES[b], c)]
            assert not shared, (a, b, shared)
    for name, body in bodies.items():    # and none calls another's reader
        assert "_reader" not in body, name


WANT = {
    "steady_steps": {
        "step_ms_p50": 128.0, "mfu": 100.0 * 1e9 * 49.25e3 / 197e12,
        "device_idle_share.train": 100.0 * (1 - 2.94 / 3.0),
        "peak_hbm_gb": 6.8,
    },
}


@pytest.mark.parametrize("kind", sorted(WANT))
def test_every_reader_on_a_canned_run(kind):
    rec = records(kind)
    for name in READERS:
        value = _reader(name).read(rec)
        if name in WANT[kind]:
            assert value == pytest.approx(WANT[kind][name]), name
        else:  # nothing of that metric in this kind of run: left out
            assert value is None, name


def test_every_reader_reads_nothing_from_an_empty_run():
    for name in READERS:
        assert _reader(name).read({}) is None, name


def test_a_cell_s_line_holds_its_own_metrics_only():
    got = per_layer_metrics(BENCH, "transformer-base.steady",
                            records("steady_steps"))
    assert set(got) == set(WANT["steady_steps"])
    assert got["mfu"]["unit"] == "%"
