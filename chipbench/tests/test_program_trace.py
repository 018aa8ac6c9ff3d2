"""The readers of what the program's trace layer leaves. The device ones
read a trace file written here field by field, two steps of a dozen
operations whose milliseconds are worked out by hand below. The host ones
read ``data/ring_spans.jsonl``: the ring a first run of
``transformer-base.steady`` on the chip dumped
(``METAOPT_TPU_PROFILE_DIR=D python3 -m chipbench ...``), cut to its
``trial.setup``, ``trial.data``, ``trial.init`` and the ``compile`` spans of
``init_fn``, ``train_step`` and the reference's ``step``. And each reads
``None`` where there is nothing to read."""

import json
import os

import pytest

from chipbench import program_trace
from chipbench.run import _reader

HERE = os.path.dirname(__file__)
DEVICE = ("attention_core_device_ms", "readout_xent_device_ms",
          "optimizer_device_ms", "scoped_device_share")
HOST = ("trial_data_s", "trial_init_s", "program_load_s",
        "compile_cache_hit_share")
TRACED = {"trace": {"busy_s": 1.0, "window_s": 1.0}}


def ring():
    with open(os.path.join(HERE, "data", "ring_spans.jsonl")) as f:
        return [r for r in map(json.loads, f) if "name" in r]


@pytest.fixture
def recorded_ring(monkeypatch):
    from metaopt_tpu.utils import trace

    monkeypatch.setattr(trace, "_ring", ring())


@pytest.mark.parametrize("path, scope, inside", [
    ("jit(train_step)/jvp(Transformer)/dec0/self_attn/attention/"
     "attention.core/while", "attention.core", True),
    ("jit(train_step)/jvp(Transformer)/dec0/self_attn/attention/"
     "attention.core/while", "attention", True),
    ("jit(train_step)/jvp(Transformer)/dec0/self_attn/attention/q/"
     "dot_general", "attention.core", False),
    ("jit(train_step)/transpose(jvp(readout_xent))/mul", "readout_xent", True),
    ("attention.core/while/body/add", "attention.core", True),  # shard_map
    ("jit(train_step)/optimizer/add", "optimizer", True),
    ("jit(train_step)/jvp(Transformer)/enc0/mlp/ffn/wi/dot_general",
     "optimizer", False),
    ("", "embed", False),
])
def test_a_scope_is_a_component_of_the_op_name(path, scope, inside):
    assert program_trace.in_scope(path, scope) is inside


def _varint(n):
    out = b""
    while n >= 0x80:
        out += bytes([n & 0x7F | 0x80])
        n >>= 7
    return out + bytes([n])


def _message(*fields):
    """A protobuf message of (number, bytes | int) fields, by hand."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += bytes([number << 3]) + _varint(value)
        else:
            out += bytes([number << 3 | 2]) + _varint(len(value)) + value
    return out


def test_op_names_are_read_from_the_operations_metadata():
    """An xplane of one device plane with two operations, written field by
    field as xplane.proto numbers them: ``tf_op`` as text and as a
    reference to an interned string."""
    stat_names = [_message((1, i), (2, _message((1, i), (2, name))))
                  for i, name in ((3, b"flops"), (4, b"tf_op"),
                                  (9, b"a/attention.core/while:"))]
    fusion = _message((1, 7), (2, b"%fusion.369 = f32[2] fusion(%p)"), (5, _message(
        (1, 3), (3, 99))), (5, _message(
            (1, 4), (5, b"jit(train_step)/optimizer/add:"))))
    scan = _message((1, 8), (2, b"%while.58 = () while(%t)"),
                    (5, _message((1, 4), (7, 9))))
    bare = _message((1, 6), (2, b"%copy-done.1 = f32[2] copy-done(%c)"))
    events = [_message((1, i), (2, m))
              for i, m in ((7, fusion), (8, scan), (6, bare))]
    device = _message((2, b"/device:TPU:0"), *[(5, m) for m in stat_names],
                      *[(4, m) for m in events])
    host = _message((2, b"/host:CPU"), *[(5, m) for m in stat_names],
                    *[(4, m) for m in events])
    assert program_trace.op_names(_message((1, host), (1, device))) == {
        "/device:TPU:0": {
            "%fusion.369 = f32[2] fusion(%p)": "jit(train_step)/optimizer/add",
            "%while.58 = () while(%t)": "a/attention.core/while"}}


#: one step's operations, hand-written after the cell's trace: (event,
#: ``op_name``, start and duration in microseconds from the step's start)
J = "jit(train_step)/"
STEP_OPS = [
    # three backward scans of 754 us and three forward ones of 310 us; a
    # scan's body runs inside it and must not count twice
    *[(f"%while.{i} = () while(%t{i})",
       J + f"transpose(jvp(Transformer))/dec{i}/attention/attention.core/"
       "while", 1000 * i, 754) for i in range(3)],
    ("%fusion.7 = f32[8] fusion(%a)",
     J + "transpose(jvp(Transformer))/dec0/attention/attention.core/while/"
     "body/dot_general", 100, 200),
    *[(f"%while.{5 + i} = () while(%u{i})",
       J + f"jvp(Transformer)/dec{i}/attention/attention.core/while",
       3000 + 400 * i, 310) for i in range(3)],
    # a projection of MHA: under ``attention``, not under ``attention.core``
    ("%fusion.20 = bf16[8] fusion(%q)",
     J + "jvp(Transformer)/dec0/attention/q/dot_general", 4200, 600),
    # the readout's gradient with AdamW's update folded in, named by the
    # matmul; and a pass over the logits that overlaps it by 150 us
    ("%fusion.369 = f32[8] fusion(%g)",
     J + "transpose(jvp(readout_xent))/dot_general", 5000, 4850),
    ("%fusion.81 = f32[8] fusion(%l)", J + "jvp(readout_xent)/reduce_sum",
     9700, 1000),
    ("%fusion.90 = f32[8] fusion(%b)", J + "optimizer/add", 10700, 40),
    # what the compiler added carries no ``op_name`` at all
    ("%copy-done.1 = f32[8] copy-done(%c)", None, 10800, 500),
]
STEP_US = 12_000
BY_HAND = {"attention_core_device_ms": 3 * 0.754 + 3 * 0.310,  # 3.192
           "readout_xent_device_ms": (10700 - 5000) / 1e3,      # 5.7
           "optimizer_device_ms": 0.040}
BUSY_MS = 3.192 + 0.6 + 5.7 + 0.04 + 0.5                        # 10.032


def write_trace(directory, steps=2, program=b"jit_train_step(123)"):
    """An xplane of one device plane holding ``steps`` runs of STEP_OPS
    under as many runs of ``program``, as xplane.proto numbers its
    fields."""
    tf_op = _message((1, 4), (2, _message((1, 4), (2, b"tf_op"))))
    metadata = [_message((1, 1), (2, _message((1, 1), (2, program))))]
    ops, runs = [], []
    for k in range(steps):
        runs.append(_message((1, 1), (2, k * STEP_US * 10 ** 6),
                             (3, (STEP_US - 500) * 10 ** 6)))
        for i, (_, _, start, dur) in enumerate(STEP_OPS):
            ops.append(_message((1, 2 + i),
                                (2, (k * STEP_US + start) * 10 ** 6),
                                (3, dur * 10 ** 6)))
    for i, (event, path, _, _) in enumerate(STEP_OPS):
        stat = [(5, _message((1, 4), (5, path.encode() + b":")))] if path \
            else []
        metadata.append(_message((1, 2 + i), (2, _message(
            (1, 2 + i), (2, event.encode()), *stat))))
    plane = _message(
        (2, b"/device:TPU:0"), (5, tf_op), *[(4, m) for m in metadata],
        (3, _message((1, 1), (2, b"XLA Modules"), (3, 1000),
                     *[(4, e) for e in runs])),
        (3, _message((1, 2), (2, b"XLA Ops"), (3, 1000),
                     *[(4, e) for e in ops])))
    os.makedirs(os.path.join(directory, "trace"))
    with open(os.path.join(directory, "trace", "t.xplane.pb"), "wb") as f:
        f.write(_message((1, _message((2, b"/host:CPU"))), (1, plane)))


@pytest.fixture
def this_run_s_trace(tmp_path, monkeypatch):
    """The trace of a ``--trace 1`` run of cell ``c``, in its own directory;
    another cell's newer one lies beside it and must not be read."""
    monkeypatch.setattr(program_trace, "RUNS", str(tmp_path))
    monkeypatch.setattr("sys.argv", ["chipbench", "--workload", "c",
                                     "--seed", "1", "--trace", "1"])
    write_trace(str(tmp_path / "c-trace1"))
    write_trace(str(tmp_path / "d-trace1"), steps=1)
    assert program_trace.run_dir() == str(tmp_path / "c-trace1")


def test_device_readers_on_a_trace_worked_out_by_hand(this_run_s_trace,
                                                      monkeypatch):
    got = {name: _reader(name).read(TRACED) for name in DEVICE}
    for name, ms in BY_HAND.items():
        assert got[name] == pytest.approx(ms, rel=1e-6), name
    assert got["scoped_device_share"] == pytest.approx(
        100 * (BUSY_MS - 0.5) / BUSY_MS, rel=1e-6)
    # the traced slice holds no run of the function a reader names: nothing
    assert program_trace.scope_ms_a_step(TRACED, "optimizer", "epoch") is None
    # a program without the layer (the parent commit): left out, not 0.0
    monkeypatch.setattr(program_trace, "program_trace", lambda: None)
    assert [_reader(name).read(TRACED) for name in DEVICE] == [None] * 4


def test_host_readers_on_the_recorded_ring(recorded_ring):
    """By hand from the file's stamps: ``trial.data`` 19.824 s; ``init_fn``
    30.407 s inside a ``trial.init`` of 30.870 s; ``train_step`` 52.880 s;
    the reference's ``step`` (52.3 s) is no function of the program's."""
    got = {name: _reader(name).read({}) for name in HOST}
    assert got["trial_data_s"] == pytest.approx(19.82368052)
    assert got["program_load_s"] == pytest.approx(30.406947404 + 52.880393883)
    assert got["trial_init_s"] == pytest.approx(30.869649876 - 30.406947404)
    assert got["compile_cache_hit_share"] == 0.0


@pytest.mark.parametrize("name", DEVICE)
def test_device_readers_read_nothing_without_a_trace(name, tmp_path,
                                                     monkeypatch):
    assert _reader(name).read({}) is None  # not a traced run
    monkeypatch.setattr(program_trace, "RUNS", str(tmp_path))  # no file
    monkeypatch.setattr("sys.argv", ["chipbench", "--workload", "c"])
    assert program_trace.load(program_trace.run_dir()) is None
    assert _reader(name).read(TRACED) is None
    monkeypatch.setattr("sys.argv", ["pytest"])  # no cell on the command line
    assert program_trace.run_dir() is None
    assert _reader(name).read(TRACED) is None


@pytest.mark.parametrize("name", HOST)
def test_host_readers_read_nothing_without_one_set_up(name, monkeypatch):
    from metaopt_tpu.utils import trace

    monkeypatch.setattr(trace, "_ring", [])  # no span at all
    assert _reader(name).read(TRACED) is None
    monkeypatch.setattr(trace, "_ring", ring() + ring())  # two trials'
    assert _reader(name).read(TRACED) is None
    # a program without the layer (the parent commit)
    monkeypatch.setattr(program_trace, "program_trace", lambda: None)
    assert _reader(name).read(TRACED) is None
