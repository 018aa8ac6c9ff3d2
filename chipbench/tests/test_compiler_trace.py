"""The readers of the compiler's operations (chipbench/compiler_trace.py):
on a step worked out by hand below, on the two steps recorded on the chip
(``data/compiler_ops.json``: ``record_compiler_ops.py``), and ``None`` where
the program has no ``trace_device`` or the file no program."""

import json
import os

import pytest

import record_compiler_ops
from chipbench import compiler_trace, layer_trace, program_trace
from chipbench.run import _reader
from metaopt_tpu.utils import trace, trace_device

HERE = os.path.dirname(__file__)
TRACED = record_compiler_ops.TRACED
PLANE = record_compiler_ops.PLANE
KINDS = [f"compiler_{k}_device_ms" for k in trace.COMPILER_KINDS]
OWNERS = [f"compiler_for_{g}_device_ms" for g in compiler_trace.GROUPS]
MS = 1e-3

_A = "jit(train_step)/jvp(M)/h0/attn/attention/q/dot_general"
_E = "jit(train_step)/jvp(M)/h1/experts/moe/moe.experts/dot_general"
_O = "jit(train_step)/optimizer/mul"


def _program(name, rows):
    """rows: (id, name, opcode, op_name, operands) in one computation."""
    return trace_device.link(
        name, 1, [trace_device.Computation(1, "main", [r[0] for r in rows],
                                           rows[-1][0])],
        [trace_device.Instruction(id, n, opcode, path, tuple(operands), 1, ())
         for id, n, opcode, path, operands in rows])


def by_hand():
    """One step of ``jit_train_step(7)`` from 0 to 20 ms, and a run of
    another program whose ``copy.1`` is not the step's."""
    step = _program("jit_train_step(7)", [
        (1, "p", "parameter", "", []),
        (2, "copy.1", "copy", "", [1]),               # for attention
        (3, "q", "fusion", _A, [2]),
        (4, "slice-start.1", "slice-start", "", [3]),
        (5, "slice-done.1", "slice-done", "", [4]),   # attention made it,
        (6, "e", "fusion", _E, [5]),                  # moe reads it: moe's
        (7, "while.1", "while", "", [6]),             # the update reads it
        (8, "copy.2", "copy", "", [3]),               # read by moe and the
        (9, "e2", "fusion", _E, [8]),                 # optimizer, made by
        (10, "u", "fusion", _O, [8, 7]),              # attention: its
        (11, "bitcast_fusion", "fusion", "", [1]),    # read by nobody
        (12, "rng", "rng-bit-generator", "", [])])    # of no kind, nobody's
    other = _program("jit_convert(9)", [
        (1, "x", "parameter", "", []), (2, "copy.1", "copy", "", [1])])
    ops = [
        ("copy.1", "", 0.0, 1 * MS),
        ("q", _A, 1 * MS, 4 * MS),
        ("slice-start.1", "", 4.5 * MS, 1 * MS),      # 0.5 under q, 0.5 own
        ("slice-done.1", "", 5.5 * MS, 1.5 * MS),
        ("e", _E, 7 * MS, 3 * MS),
        ("while.1", "", 10 * MS, 6 * MS),             # around two copies
        ("copy.2", "", 11 * MS, 1 * MS),
        ("copy.2", "", 13 * MS, 1 * MS),
        ("e2", _E, 14.5 * MS, 1 * MS),                # inside the loop
        ("u", _O, 16 * MS, 2 * MS),
        ("bitcast_fusion", "", 18 * MS, 1.5 * MS),
        ("rng", "", 19.5 * MS, 0.5 * MS),
        ("copy.1", "", 21 * MS, 2 * MS),              # the other program's
    ]
    runs = [("jit_train_step(7)", 0.0, 20 * MS), ("jit_convert(9)", 21 * MS,
                                                  2 * MS)]
    return trace_device.Loaded("by hand", {PLANE: ops}, {PLANE: runs},
                               {p.name: p for p in (step, other)})


#: a step: the whole slice holds one run of ``train_step``
BY_HAND = {
    "compiler_copy_device_ms": 1 + 2 + 2,   # copy.1, copy.2 twice, the other
    "compiler_slice_device_ms": 0.5 + 1.5,
    "compiler_loop_device_ms": 6 - 2 - 1,   # less its copies and ``e2``
    "compiler_fusion_device_ms": 1.5,
    "compiler_other_device_ms": 0.5,
    "compiler_for_attention_device_ms": 1 + 2,  # copy.1; copy.2 by producer
    "compiler_for_moe_device_ms": 0.5 + 1.5,    # the asynchronous slice
    "compiler_for_optimizer_device_ms": 3.0,    # the loop, less its inside
    # the slice holds no operation of these layers
    "compiler_for_ffn_device_ms": None, "compiler_for_mixer_device_ms": None,
    "compiler_for_ends_device_ms": None, "compiler_for_trunk_device_ms": None,
    # nobody's: the fusion, the rng and the other program's copy
    "compiler_owned_share": 100 * 8 / (8 + 1.5 + 0.5 + 2),
}


def hand_out(monkeypatch, loaded):
    older = {"ops": {p: [(path, s, d) for _, path, s, d in ops]
                     for p, ops in loaded.ops.items()},
             "programs": {p: [r[0] for r in runs]
                          for p, runs in loaded.runs.items()}}
    monkeypatch.setattr(trace_device, "load", lambda directory: loaded)
    monkeypatch.setattr(program_trace, "load", lambda directory: older)
    monkeypatch.setattr(program_trace, "run_dir", lambda: "unused")
    compiler_trace._split.clear()


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_a_reader_on_a_step_worked_out_by_hand(monkeypatch, metric):
    hand_out(monkeypatch, by_hand())
    got = _reader(metric).read(TRACED)
    assert got == (None if BY_HAND[metric] is None
                   else pytest.approx(BY_HAND[metric]))


def test_the_parts_add_up_on_the_step_worked_out_by_hand(monkeypatch):
    hand_out(monkeypatch, by_hand())
    read = lambda name: _reader(name).read(TRACED)  # noqa: E731
    unnamed = layer_trace.unnamed_ms(TRACED)
    assert unnamed == pytest.approx(12.0)
    assert sum(read(k) for k in KINDS) == pytest.approx(unnamed, abs=1e-6)
    owned = sum(read(o) or 0.0 for o in OWNERS)
    assert owned == pytest.approx(
        read("compiler_owned_share") / 100 * unnamed, abs=1e-6)


def test_an_instruction_is_looked_up_in_its_own_run_s_program(monkeypatch):
    """``copy.1`` of the second program is not the step's ``copy.1``: it has
    no reader there, so it is nobody's."""
    loaded = by_hand()
    hand_out(monkeypatch, loaded)
    found = compiler_trace.split(TRACED)
    assert found["owners"][None] == pytest.approx(1.5 + 0.5 + 2)
    del loaded.programs["jit_convert(9)"]   # a run without its program
    compiler_trace._split.clear()
    assert compiler_trace.split(TRACED)["kinds"]["copy"] == pytest.approx(5)


# -- nothing to read --------------------------------------------------------


@pytest.mark.parametrize("metric", KINDS + OWNERS + ["compiler_owned_share"])
def test_none_on_a_program_without_the_device_side(monkeypatch, metric):
    hand_out(monkeypatch, by_hand())
    monkeypatch.setattr(compiler_trace, "device_side", lambda: None)
    assert _reader(metric).read(TRACED) is None


def test_none_without_a_trace_or_a_step(monkeypatch):
    hand_out(monkeypatch, by_hand())
    assert _reader("compiler_copy_device_ms").read({}) is None
    monkeypatch.setattr(trace_device, "load", lambda directory: None)
    assert _reader("compiler_copy_device_ms").read(TRACED) is None
    empty = trace_device.Loaded("none", {}, {}, {})
    monkeypatch.setattr(trace_device, "load", lambda directory: empty)
    assert _reader("compiler_owned_share").read(TRACED) is None


def test_a_file_without_its_programs_has_kinds_and_no_owners(monkeypatch):
    loaded = by_hand()
    loaded.programs.clear()
    hand_out(monkeypatch, loaded)
    assert _reader("compiler_copy_device_ms").read(TRACED) \
        == pytest.approx(5.0)  # from the events' names
    assert _reader("compiler_loop_device_ms").read(TRACED) \
        == pytest.approx(3.0)
    assert _reader("compiler_for_moe_device_ms").read(TRACED) is None
    assert _reader("compiler_owned_share").read(TRACED) is None


def test_a_reader_that_fails_leaves_its_metric_out(monkeypatch, capsys):
    hand_out(monkeypatch, by_hand())

    def broken(directory):
        raise ValueError("a torn file")

    monkeypatch.setattr(trace_device, "load", broken)
    assert _reader("compiler_copy_device_ms").read(TRACED) is None
    assert "a torn file" in capsys.readouterr().err


# -- the two steps recorded on the chip ---------------------------------------

with open(os.path.join(HERE, "data", "compiler_ops.json")) as _f:
    RECORDED = json.load(_f)


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_the_readers_on_a_recorded_step(monkeypatch, cell):
    doc = RECORDED[cell]
    record_compiler_ops.hand_out(doc, monkeypatch.setattr)
    for metric in record_compiler_ops.READERS:
        want = doc["expected"][metric]
        got = _reader(metric).read(TRACED)
        assert got == (None if want is None
                       else pytest.approx(want, abs=1e-9)), metric


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_the_partition_on_a_recorded_step(monkeypatch, cell):
    doc = RECORDED[cell]
    record_compiler_ops.hand_out(doc, monkeypatch.setattr)
    read = lambda name: _reader(name).read(TRACED)  # noqa: E731
    unnamed = layer_trace.unnamed_ms(TRACED)
    assert unnamed == pytest.approx(doc["expected"]["unnamed_device_ms"],
                                    abs=1e-9)
    kinds = {k: read(k) for k in KINDS}
    assert sum(kinds.values()) == pytest.approx(unnamed, abs=1e-6)
    assert all(v >= 0 for v in kinds.values())
    share = read("compiler_owned_share")
    assert 0 <= share <= 100
    assert sum(read(o) or 0.0 for o in OWNERS) == pytest.approx(
        share / 100 * unnamed, abs=1e-6)
    # the closed list's guard: what no kind takes stays small
    assert kinds["compiler_other_device_ms"] < 0.05 * unnamed
