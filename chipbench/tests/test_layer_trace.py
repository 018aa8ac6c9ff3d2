"""The readers of a step's partition by the program's two rules
(chipbench/layer_trace.py): on a dozen operations whose milliseconds are
worked out by hand below, on the operations recorded on the chip
(``data/lm_ops.json``, PR 26's, whose trunk still had no names, and
``data/layer_ops.json``, PR 34's: ``record_layer_ops.py``), and ``None``
where the program has no rules."""

import json
import os
import types

import pytest

from chipbench import layer_trace, program_trace
from chipbench.run import _reader

HERE = os.path.dirname(__file__)
NEW = ("embed_device_ms", "attention_proj_device_ms", "ffn_device_ms",
       "trunk_device_ms", "unnamed_device_ms", "forward_again_device_ms",
       "backward_device_ms")
#: the accepted metrics of the other top-level layers in a pattern
#: decoder's cell: with the first five of NEW they are a step's busy time
ACCEPTED_LAYERS = ("attention_core_device_ms", "moe_device_ms",
                   "readout_xent_device_ms", "optimizer_device_ms")
TRACED = {"trace": {"busy_s": 1.0, "window_s": 1.0}}
PLANE = "/device:TPU:0"

_F = "jit(train_step)/jvp(DecoderOnlyLM)/DecoderOnlyLM._patterned/"
_B = ("jit(train_step)/transpose(jvp(DecoderOnlyLM))/DecoderOnlyLM."
      "_patterned/jvp(DecoderOnlyLM)/DecoderOnlyLM._patterned/checkpoint/")
MS = 1e-3
#: one step, back to back from 0: (op_name, milliseconds)
BY_HAND = [
    (_F + "embed/embed/jit(_take)/gather", 1.0),
    (_F + "h0/norm_in/norm/mul", 0.5),
    (_F + "h0/attn/attention/q/dot_general", 2.0),
    (_F + "h0/attn/attention/q_norm/norm/mul", 0.25),      # attention's
    (_F + "h0/attn/attention/attention.core/jit(_causal_forward)/"
     "flash_fwd/pallas_call", 3.0),
    (_F + "h0/residual/add", 0.25),
    ("", 1.0),                                             # a copy
    (_F + "readout_xent/btd,vd->btv/dot_general", 4.0),
    ("jit(train_step)/jvp(loss)/reduce_sum", 0.25),
    ("jit(train_step)/transpose(jvp(loss))/div", 0.25),
    ("jit(train_step)/transpose(jvp(readout_xent))/dot_general", 8.0),
    (_B + "rematted_computation/h0/norm_in/norm/mul", 0.5),
    (_B + "rematted_computation/h0/attn/attention/q/dot_general", 2.0),
    (_B + "h0/attn/attention/attention.core/jit(_causal_backward)/"
     "flash_bwd/pallas_call", 6.0),
    (_B + "h0/attn/attention/q/dot_general", 4.0),
    (_B + "h0/norm_in/norm/mul", 1.0),
    ("jit(train_step)/optimizer/mul", 1.5),
]
#: and a loop the compiler gave no name, around two operations of ``moe``
LOOP_MS, LOOP_BODY = 4.0, [
    (_F + "h0/experts/moe/moe.dispatch/while/body/gather", 1.5),
    (_F + "h0/experts/moe/moe.dispatch/while/body/gather", 1.5)]


def by_hand_ops():
    ops, at = [], 0.0
    for path, ms in BY_HAND:
        ops.append((path, at, ms * MS))
        at += ms * MS
    ops.append(("", at, LOOP_MS * MS))
    inner = at + 0.5 * MS
    for path, ms in LOOP_BODY:
        ops.append((path, inner, ms * MS))
        inner += ms * MS
    return ops, (at + LOOP_MS * MS) * 1e3


def hand_out(monkeypatch, ops, programs):
    loaded = {"ops": {PLANE: ops}, "programs": {PLANE: programs}}
    monkeypatch.setattr(program_trace, "load", lambda directory: loaded)
    monkeypatch.setattr(program_trace, "run_dir", lambda: "unused")


def test_the_readers_on_operations_worked_out_by_hand(monkeypatch):
    ops, busy = by_hand_ops()
    hand_out(monkeypatch, ops, ["jit_train_step(1)"])
    got = {name: _reader(name).read(TRACED) for name in NEW}
    assert got == pytest.approx({
        "embed_device_ms": 1.0,
        # q forward, again and backward, and the q norm inside attention
        "attention_proj_device_ms": 2.0 + 0.25 + 2.0 + 4.0,
        "ffn_device_ms": 0.0,
        # norm_in three times, the residual sum, the loss both ways
        "trunk_device_ms": 0.5 + 0.5 + 1.0 + 0.25 + 0.25 + 0.25,
        # the copy, and the loop less its body: the body is moe's
        "unnamed_device_ms": 1.0 + LOOP_MS - 3.0,
        "forward_again_device_ms": 0.5 + 2.0,
        "backward_device_ms": 0.25 + 8.0 + 6.0 + 4.0 + 1.0,
    })
    assert layer_trace.ms_a_step(TRACED, None) == pytest.approx(busy)
    # the partitions add up: by layer ...
    layers = got["embed_device_ms"] + got["attention_proj_device_ms"] \
        + got["trunk_device_ms"] + got["unnamed_device_ms"] + sum(
            layer_trace.layers_ms(TRACED, [layer]) for layer in
            ("moe", "readout_xent", "optimizer")) \
        + program_trace.scope_ms_a_step(TRACED, "attention.core",
                                        "train_step")
    assert layers == pytest.approx(busy)
    # ... and by direction
    directions = got["forward_again_device_ms"] \
        + got["backward_device_ms"] + got["unnamed_device_ms"] \
        + layer_trace.direction_ms(TRACED, "forward") \
        + layer_trace.direction_ms(TRACED, "update")
    assert directions == pytest.approx(busy)
    assert layer_trace.direction_ms(TRACED, "update") == pytest.approx(1.5)


def test_milliseconds_are_a_step_s(monkeypatch):
    ops, _ = by_hand_ops()
    hand_out(monkeypatch, ops, ["jit_train_step(1)", "jit_train_step(1)",
                                "jit_init_fn(2)"])
    assert _reader("embed_device_ms").read(TRACED) == pytest.approx(0.5)


def recorded(name):
    with open(os.path.join(HERE, "data", name)) as f:
        doc = json.load(f)
    paths = doc.get("paths")
    ops = [(paths[p] if paths else p, s, d) for p, s, d in doc["ops"]]
    return doc, ops


def test_the_partition_of_pr_26_s_recorded_steps_adds_up(monkeypatch):
    """Two steps of ``smallthinker-21b.steady-8k`` as PR 26's program named
    them: no scope on the trunk yet, so the trunk reads 0 and its time is
    among the unnamed; the kernels ran twice a step then."""
    doc, ops = recorded("lm_ops.json")
    hand_out(monkeypatch, ops, doc["programs"])
    got = {name: _reader(name).read(TRACED)
           for name in NEW + ACCEPTED_LAYERS}
    busy = layer_trace.ms_a_step(TRACED, None)
    assert sum(got[n] for n in NEW[:5] + ACCEPTED_LAYERS) \
        == pytest.approx(busy, rel=1e-9)
    assert got["trunk_device_ms"] == 0.0 and got["ffn_device_ms"] == 0.0
    assert got["embed_device_ms"] == pytest.approx(16.7138, rel=1e-4)
    assert 0 < got["forward_again_device_ms"] < got["backward_device_ms"]
    assert got["forward_again_device_ms"] + got["backward_device_ms"] \
        + got["unnamed_device_ms"] \
        + layer_trace.direction_ms(TRACED, "forward") \
        + layer_trace.direction_ms(TRACED, "update") \
        == pytest.approx(busy, rel=1e-9)


def test_the_readers_on_pr_34_s_recorded_step(monkeypatch):
    """``data/layer_ops.json``: a step of the same cell with the trunk
    named, recorded with what the readers made of it on the chip's host."""
    doc, ops = recorded("layer_ops.json")
    hand_out(monkeypatch, ops, doc["programs"])
    got = {name: _reader(name).read(TRACED)
           for name in list(doc["expected"]) + ["ffn_device_ms"]}
    assert got["ffn_device_ms"] == 0.0       # an MoE decoder runs no dense one
    for name, value in doc["expected"].items():
        assert got[name] == pytest.approx(value, rel=1e-6), name
    assert got["trunk_device_ms"] > 0
    busy = layer_trace.ms_a_step(TRACED, None)
    assert sum(got[n] for n in NEW[:5] + ACCEPTED_LAYERS) \
        == pytest.approx(busy, rel=0.01)
    # a kept-output kernel never runs again (PR 29)
    calls = {}
    from metaopt_tpu.utils import trace
    for path, _, _ in ops:
        if "flash_fwd" in path.split("/"):
            calls[trace.direction(path)] = calls.get(
                trace.direction(path), 0) + 1
    assert calls == {"forward": 4 * len(doc["programs"])}


@pytest.mark.parametrize("program", [
    None,                                        # no trace module at all
    types.SimpleNamespace(SCOPES=("embed",)),    # PR 33's: scopes, no rules
])
@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_rules_reads_none(monkeypatch, name, program):
    ops, _ = by_hand_ops()
    hand_out(monkeypatch, ops, ["jit_train_step(1)"])
    monkeypatch.setattr(program_trace, "program_trace", lambda: program)
    assert _reader(name).read(TRACED) is None


@pytest.mark.parametrize("name", NEW)
def test_an_untraced_run_or_a_trace_without_a_step_reads_none(monkeypatch,
                                                              name):
    ops, _ = by_hand_ops()
    hand_out(monkeypatch, ops, ["jit_train_step(1)"])
    assert _reader(name).read({}) is None
    hand_out(monkeypatch, ops, ["jit_init_fn(2)"])
    assert _reader(name).read(TRACED) is None
    monkeypatch.setattr(program_trace, "load", lambda directory: None)
    assert _reader(name).read(TRACED) is None


def test_the_partition_s_metrics_list_the_cells_that_run_what_they_read():
    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    #: the two MoE decoders whose every feed-forward is an expert layer
    no_dense = ("smallthinker-21b.steady-8k", "keye-vl2-30b.steady-16k")
    for name in NEW:
        assert os.path.exists(os.path.join(root, "chipbench", "readers",
                                           name + ".py"))
        m = entries[name]
        assert (m["source"], m["moves"], m["unit"], m["better"]) == (
            "device_trace", "train_items_per_s", "ms", "lower")
        want = {"ffn_device_ms": [c for c in cells if c not in no_dense],
                # the 2017 cell runs without remat: nothing is made twice
                "forward_again_device_ms": cells[1:]}.get(name, cells)
        assert m["workloads"] == want
