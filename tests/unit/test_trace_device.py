"""The device side of the program's trace layer (metaopt_tpu/utils/
trace_device.py and ``trace.compiler_kind``): a trace file read as the
program that ran, the kind and the owner of an operation the compiler made,
and the step's table ``python -m metaopt_tpu.utils.trace DIR`` prints."""

import json
import os
import subprocess
import sys

import pytest

from metaopt_tpu.utils import trace, trace_device

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# -- the wire format, written (the module only reads it) ----------------------


def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def instruction(id, name, opcode, op_name="", operands=(), calls=(),
                index=0, number=0, shape=b""):
    return (field(1, name) + field(2, opcode) + field(3, shape)
            + field(7, field(2, op_name)) + field(9, number)
            + field(13, index) + field(35, id)
            + b"".join(field(36, o) for o in operands)
            + field(38, b"".join(map(varint, calls))))  # packed, as protoc


def computation(id, name, instructions):
    """The last instruction is the root."""
    root = next(v for n, v in trace_device._fields(instructions[-1])
                if n == 35)
    return (field(1, name) + b"".join(field(2, i) for i in instructions)
            + field(5, id) + field(6, root))


def program(*computations):
    """An HloProto of ``computations``, the last one the entry."""
    entry = next(v for n, v in trace_device._fields(computations[-1])
                 if n == 5)
    module = b"".join(field(3, c) for c in computations) + field(6, entry)
    return trace_device.parse_program(field(1, module), "jit_step(1)")


def owner(prog, name):
    return trace_device.owner_of(prog.get(name), prog)


A = "jit(step)/jvp(M)/h0/attn/attention/q/dot_general"
F = "jit(step)/jvp(M)/h0/ffn/ffn/up/dot_general"
E = "jit(step)/transpose(jvp(M))/h1/experts/moe/moe.experts/dot_general"


# -- compiler_kind ------------------------------------------------------------

KINDS = {
    "copy": "copy", "copy-start": "copy", "copy-done": "copy",
    "transpose": "copy", "reshape": "copy", "convert": "copy",
    "slice": "slice", "slice-start": "slice", "slice-done": "slice",
    "dynamic-slice": "slice", "dynamic-update-slice": "slice",
    "concatenate": "slice", "pad": "slice",
    "while": "loop", "conditional": "loop", "call": "loop",
    "tuple": "loop", "get-tuple-element": "loop",
    "fusion": "fusion",
    "broadcast": "other", "custom-call": "other", "bitcast": "other",
    "add": "other", "iota": "other", "reduce": "other",
    # an asynchronous pair is what it wraps: ``trace_device.opcode_of``
    "async-start": "other", "async-done": "other",
    # an instruction's name, where a file holds no program
    "copy-done.14": "copy", "slice-start.3": "slice", "while.1532": "loop",
    "fusion.3058": "fusion", "bitcast_fusion.3": "fusion",
    "%copy.1": "copy", "convert.7": "copy", "slice-done.235": "slice",
    "broadcast.571": "other",
}


@pytest.mark.parametrize("opcode", sorted(KINDS))
def test_compiler_kind_on_literal_opcodes(opcode):
    assert trace.compiler_kind(opcode) == KINDS[opcode]
    assert KINDS[opcode] in trace.COMPILER_KINDS


def test_the_kinds_are_a_closed_list():
    assert trace.COMPILER_KINDS == ("copy", "slice", "loop", "fusion",
                                    "other")
    assert set(trace._KIND_OF.values()) <= set(trace.COMPILER_KINDS)


# -- owner_of on programs made by hand ----------------------------------------


def test_an_instruction_with_a_layer_owns_itself():
    prog = program(computation(1, "main", [
        instruction(1, "p", "parameter"),
        instruction(2, "dot.1", "dot", A, [1])]))
    assert owner(prog, "dot.1") == "attention"
    assert owner(prog, "p") == "attention"  # its one reader's


def test_a_nameless_copy_takes_the_layer_its_users_agree_on():
    prog = program(computation(1, "main", [
        instruction(1, "p", "parameter"),
        instruction(2, "copy.1", "copy", "", [1]),
        instruction(3, "q", "dot", A, [2]),
        instruction(4, "k", "dot", A.replace("/q/", "/k/"), [2]),
        instruction(5, "out", "tuple", "", [3, 4])]))
    assert owner(prog, "copy.1") == "attention"
    assert trace_device.readers_layers(prog.get("copy.1"), prog) == {
        "attention"}


def test_users_that_disagree_leave_it_to_the_producers():
    prog = program(computation(1, "main", [
        instruction(1, "p", "parameter"),
        instruction(2, "up", "fusion", F, [1]),
        instruction(3, "bitcast.1", "bitcast", "", [2]),
        instruction(4, "copy.1", "copy", "", [3]),
        instruction(5, "q", "dot", A, [4]),
        instruction(6, "e", "dot", E, [4])]))
    assert trace_device.readers_layers(prog.get("copy.1"), prog) == {
        "attention", "moe"}
    assert owner(prog, "copy.1") == "ffn"  # through the nameless bitcast


def test_the_owner_is_seen_through_an_asynchronous_pair():
    prog = program(computation(1, "main", [
        instruction(1, "w", "parameter"),
        instruction(2, "copy-start.1", "copy-start", "", [1]),
        instruction(3, "copy-done.1", "copy-done", "", [2]),
        instruction(4, "e", "dot", E, [3])]))
    assert owner(prog, "copy-start.1") == owner(prog, "copy-done.1") == "moe"


def test_the_owner_is_seen_through_a_loop_s_parameter_by_its_index():
    """``copy.1`` rides into the loop as element 1 of its tuple; the body
    reads element 1 under ``ssd`` and element 0 (the counter) under
    nothing, the condition reads element 0 alone."""
    scan = "jit(step)/jvp(M)/h2/mixer/ssd/ssd.core/while/body/dot_general"
    body = computation(1, "body", [
        instruction(10, "arg", "parameter"),
        instruction(11, "i", "get-tuple-element", "", [10], index=0),
        instruction(12, "x", "get-tuple-element", "", [10], index=1),
        instruction(13, "next", "add", "", [11]),
        instruction(14, "y", "dot", scan, [12]),
        instruction(15, "turn", "tuple", "", [13, 14])])
    cond = computation(2, "cond", [
        instruction(20, "arg.1", "parameter"),
        instruction(21, "i.1", "get-tuple-element", "", [20], index=0),
        instruction(22, "lt", "compare", "", [21])])
    prog = program(body, cond, computation(3, "main", [
        instruction(30, "p", "parameter"),
        instruction(31, "zero", "constant"),
        instruction(32, "copy.1", "copy", "", [30]),
        instruction(33, "copy.2", "copy", "", [31]),
        instruction(34, "init", "tuple", "", [33, 32]),
        instruction(35, "while.1", "while", "", [34], calls=[1, 2]),
        instruction(36, "out", "get-tuple-element", "", [35], index=1)]))
    assert owner(prog, "copy.1") == "ssd"
    assert owner(prog, "copy.2") is None     # the counter's: nobody's
    assert owner(prog, "next") is None
    # the loop itself: the one layer that reads what it carries
    assert owner(prog, "while.1") == "ssd"
    # backwards through the same hand-overs: the loop's result is the
    # body's root's element, made under ``ssd``
    assert trace_device.producers_layers(prog.get("out"), prog) == {"ssd"}


def test_a_fusion_under_a_nameless_root_is_what_it_fused():
    fused = computation(1, "fused", [
        instruction(10, "param_0", "parameter"),
        instruction(11, "gather.1", "gather",
                    "jit(step)/jvp(M)/embed/embed/jit(_take)/gather", [10]),
        instruction(12, "copy.9", "copy", "", [11]),
        instruction(13, "bitcast.9", "bitcast", "", [12])])
    plain = computation(2, "fused.1", [
        instruction(20, "param_0.1", "parameter"),
        instruction(21, "copy.10", "copy", "", [20])])
    prog = program(fused, plain, computation(3, "main", [
        instruction(30, "ids", "parameter"),
        instruction(31, "copy_bitcast_fusion", "fusion", "", [30], calls=[1]),
        instruction(32, "copy_fusion.1", "fusion", "", [31], calls=[2]),
        instruction(33, "q", "dot", A, [32])]))
    assert owner(prog, "copy_bitcast_fusion") == "embed"
    assert trace_device.named_layer(prog.get("copy_fusion.1"), prog) is None
    assert owner(prog, "copy_fusion.1") == "attention"  # its user's
    assert trace.compiler_kind(prog.get("copy_fusion.1").opcode) == "fusion"


def test_an_asynchronous_pair_is_what_it_wraps():
    """The TPU's compiler writes an asynchronous slice as ``async-start`` /
    ``async-done`` around a computation whose root is the ``slice``; the
    ``async-done`` calls nothing, its operand does."""
    wrapped = computation(1, "async_computation", [
        instruction(10, "param_0", "parameter"),
        instruction(11, "slice.7", "slice", "", [10])])
    prog = program(wrapped, computation(2, "main", [
        instruction(20, "w", "parameter"),
        instruction(21, "slice-start.3", "async-start", "", [20], calls=[1]),
        instruction(22, "slice-update.3", "async-update", "", [21]),
        instruction(23, "slice-done.3", "async-done", "", [22]),
        instruction(24, "up", "dot", F, [23])]))
    for name in ("slice-start.3", "slice-update.3", "slice-done.3"):
        assert trace_device.opcode_of(prog.get(name), prog) == "slice"
        assert owner(prog, name) == "ffn"
    assert trace_device.opcode_of(prog.get("up"), prog) == "dot"
    assert trace.compiler_kind(
        trace_device.opcode_of(prog.get("slice-done.3"), prog)) == "slice"


def test_a_copy_three_layers_read_is_nobody_s():
    prog = program(computation(1, "main", [
        instruction(1, "stream", "parameter"),
        instruction(2, "copy.1", "copy", "", [1]),
        instruction(3, "q", "dot", A, [2]),
        instruction(4, "up", "dot", F, [2]),
        instruction(5, "e", "dot", E, [2])]))
    assert owner(prog, "copy.1") is None
    assert set(trace.LAYERS) >= {"attention", "ffn", "moe"}


def test_shape_layout_and_memory_space_are_read_when_asked():
    tile = lambda *dims: field(6, b"".join(field(1, d) for d in dims))  # noqa
    layout = field(1, 1) + field(1, 0) + tile(8, 128) + tile(2, 1) \
        + field(8, 1)
    shape = field(2, 16) + field(3, 8192) + field(3, 2048) + field(5, layout)
    prog = program(computation(1, "main", [
        instruction(1, "p", "parameter", shape=shape),
        instruction(2, "t", "tuple", "", [1], shape=field(2, 13)
                    + field(4, shape) + field(4, field(2, 11)))]))
    p = prog.get("p")
    assert (p.shape, p.layout, p.memory_space) == (
        "bf16[8192,2048]", "{1,0:T(8,128)(2,1)}", 1)
    assert prog.get("t").shape == "(bf16[8192,2048], f32[])"
    assert trace_device._bytes(p.shape) == 8192 * 2048 * 2


# -- the partition of the nameless time ---------------------------------------

MS = 1e-3


def test_each_nameless_instant_goes_to_the_innermost_operation():
    ops = [
        ("fusion.1", A, 0.0, 2 * MS),
        ("while.1", "", 2 * MS, 10 * MS),          # nameless, 2..12
        ("copy.1", "", 3 * MS, 1 * MS),            # in the loop, 3..4
        ("fusion.2", F, 5 * MS, 2 * MS),           # a layer's, 5..7
        ("copy-done.1", "", 6 * MS, 3 * MS),       # 6..9, a layer runs to 7
        ("copy.1", "", 10 * MS, 1 * MS),           # the loop's second turn
        ("slice.1", "", 13 * MS, 1 * MS),          # after a gap
        ("fusion.3", E, 13.5 * MS, 1 * MS),        # overlaps the slice's end
    ]
    got = trace_device.nameless_seconds(ops)
    assert got == pytest.approx({
        "copy.1": 2 * MS, "copy-done.1": 2 * MS, "slice.1": 0.5 * MS,
        "while.1": (10 - 2 - 2 - 2) * MS})
    named = trace_device.union((s, s + d) for _, p, s, d in ops if p)
    assert sum(got.values()) == pytest.approx(
        trace_device.busy_seconds(ops) - sum(e - s for s, e in named))


def test_operations_that_do_not_nest_still_partition():
    ops = [("copy-start.1", "", 0.0, 3 * MS), ("copy.2", "", 1 * MS, 4 * MS),
           ("copy.3", "", 2 * MS, 1 * MS)]
    got = trace_device.nameless_seconds(ops)
    assert got == pytest.approx({"copy-start.1": 1 * MS, "copy.2": 3 * MS,
                                 "copy.3": 1 * MS})


# -- a device plane, written by hand as the TPU's profiler writes it ----------


def xspace(ops, runs, programs):
    """``ops``: (event name, tf_op, start ps, duration ps)."""
    names = sorted({(n, p) for n, p, _, _ in ops}) + [
        (n, None) for n in sorted({r[0] for r in runs})]
    ids = {pair: i + 1 for i, pair in enumerate(names)}
    stat_meta = field(5, field(1, 1) + field(2, field(1, 1)
                                             + field(2, "tf_op")))
    # an interned string: a stat metadata entry whose name is the value
    stat_meta += field(5, field(1, 2) + field(2, field(1, 2)
                                              + field(2, A + ":")))
    event_meta = b""
    for (name, path), id in ids.items():
        stat = b"" if path is None else field(5, field(1, 1) + (
            field(7, 2) if path == A else field(5, path + ":")))
        event_meta += field(4, field(1, id) + field(
            2, field(1, id) + field(2, name) + stat))

    def line(name, events):
        return field(3, field(2, name) + field(3, 1000) + b"".join(
            field(4, field(1, ids[key]) + field(2, at) + field(3, dur))
            for key, at, dur in events))

    device = (field(2, "/device:TPU:0") + stat_meta + event_meta
              + line("XLA Ops", [((n, p), s, d) for n, p, s, d in ops])
              + line("XLA Modules", [((n, None), s, d) for n, s, d in runs])
              + line("Steps", []))
    meta = field(2, "/host:metadata") + field(5, field(1, 1) + field(
        2, field(1, 1) + field(2, "Hlo Proto")))
    for i, (name, proto) in enumerate(programs.items()):
        meta += field(4, field(1, i + 1) + field(2, field(1, i + 1) + field(
            2, name) + field(5, field(1, 1) + field(6, proto))))
    return field(1, device) + field(1, meta) + field(
        1, field(2, "/host:CPU"))


def test_a_device_plane_and_its_programs_are_read_by_field_number():
    main = computation(1, "main", [
        instruction(1, "p", "parameter"),
        instruction(2, "copy.1", "copy", "", [1]),
        instruction(3, "fusion.7", "fusion", A, [2])])
    proto = field(1, field(3, main) + field(6, 1))
    ps = 1_000_000  # a microsecond
    raw = xspace(
        ops=[("%copy.1 = bf16[8,128]{1,0} copy(%p)", "", 0, 2 * ps),
             ("%fusion.7 = bf16[8,128]{1,0} fusion(%copy.1)", A, 2 * ps,
              6 * ps),
             ("%copy.1 = bf16[8,128]{1,0} copy(%p)", "", 10 * ps + 999,
              2 * ps + 999),  # whole nanoseconds, cut as ProfileData cuts
             ("%fusion.7 = bf16[8,128]{1,0} fusion(%copy.1)", A, 12 * ps,
              6 * ps)],
        runs=[("jit_step(1)", 0, 9 * ps), ("jit_step(1)", 10 * ps, 9 * ps),
              ("jit_other(2)", 30 * ps, ps)],
        programs={"jit_step(1)": proto})
    loaded = trace_device.parse(raw, "hand")
    (plane, ops), = loaded.ops.items()
    assert plane == "/device:TPU:0"
    assert [(n, p) for n, p, _, _ in ops] == [("copy.1", ""),
                                              ("fusion.7", A)] * 2
    # the line counts from 1000 ns; ps -> s as ProfileData would
    assert ops[1][2:] == pytest.approx((1000e-9 + 2e-6, 6e-6))
    assert ops[2][2:] == (float(1000 + 10_000) * 1e-9, 2000.0 * 1e-9)
    assert [r[0] for r in loaded.runs[plane]] == ["jit_step(1)"] * 2 + [
        "jit_other(2)"]
    assert trace_device.the_step(loaded) == "jit_step(1)"
    prog = loaded.programs["jit_step(1)"]
    assert prog.get("copy.1").opcode == "copy"
    table = trace_device.step_table(loaded)
    assert table["unit"] == "ms" and table["runs"] == 2
    assert table["layers"] == {"attention": {"forward": pytest.approx(6e-3)}}
    assert table["kinds"] == {"copy": pytest.approx(2e-3)}
    assert table["owners"] == {"attention": pytest.approx(2e-3)}
    (row,) = table["largest"]
    assert (row["name"], row["opcode"], row["owner"], row["events"]) == (
        "copy.1", "copy", "attention", 1.0)
    scoped = trace_device.scope_table(loaded, "attention")
    assert scoped["rows"] == [["fusion q/dot_general", "forward",
                               pytest.approx(6e-3), 1.0]]


def test_a_file_without_its_programs_still_has_kinds():
    ps = 1_000_000
    raw = xspace(ops=[("%copy-done.4 = bf16[8]{0} copy-done(%x)", "", 0, ps),
                      ("%fusion.7 = bf16[8]{0} fusion(%y)", A, ps, ps)],
                 runs=[("jit_step(1)", 0, 2 * ps)], programs={})
    table = trace_device.step_table(trace_device.parse(raw))
    assert table["kinds"] == {"copy": pytest.approx(1e-3)}
    assert table["owners"] == {None: pytest.approx(1e-3)}
    assert table["instructions"] is None


def test_no_trace_no_table(tmp_path):
    assert trace_device.load(str(tmp_path)) is None
    assert trace_device.step_table(trace_device.parse(b"")) is None


# -- a real profile: a small jitted function under two scopes -----------------

_TRIAL = """
import jax, jax.numpy as jnp
from jax import lax
from metaopt_tpu import client
from metaopt_tpu.utils import trace

@jax.jit
def train_step(x, w, v):
    with trace.scope("attention"):
        y = jnp.tanh(x @ w)
    y = jnp.transpose(y).reshape(128, 512)  # outside every scope: a copy

    def body(i, acc):
        with trace.scope("ffn"):
            return jnp.tanh(acc @ v)

    return lax.fori_loop(0, 3, body, y)

x, w, v = jnp.ones((256, 128)), jnp.ones((128, 256)), jnp.ones((512, 512))
train_step(x, w, v).block_until_ready()
with client.profiled():
    for _ in range(2):
        train_step(x, w, v).block_until_ready()
"""


@pytest.fixture(scope="module")
def profile(tmp_path_factory):
    """(the sweep's directory, the trial's) of one profiled trial on the
    CPU."""
    base = tmp_path_factory.mktemp("profiled")
    env = {k: v for k, v in os.environ.items()
           if k != "METAOPT_TPU_TRIAL_INFO"}
    env.update(PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               METAOPT_TPU_TRIAL_INFO=json.dumps(
                   {"id": "trial-7", "experiment": "e"}),
               **{trace.PROFILE_DIR_ENV: str(base)})
    done = subprocess.run([sys.executable, "-c", _TRIAL], cwd=base, env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr
    return base, base / "trial-7"


def test_the_file_holds_the_program_under_the_runs_name(profile):
    loaded = trace_device.load(str(profile[1]))
    name = trace_device.the_step(loaded)
    assert name.startswith("jit_train_step(") and name.endswith(")")
    assert name[len("jit_train_step("):-1].isdigit()  # ``name(id)``
    prog = loaded.programs.get(name)
    assert prog is not None and prog.name == name
    assert all(i.opcode and i.name for i in prog.instructions.values())
    named = [i for i in prog.instructions.values() if i.op_name]
    assert {trace.layer_of(i.op_name) for i in named} >= {"attention", "ffn"}
    # one parse a file
    assert trace_device.load(str(profile[1])) is loaded


def test_every_instruction_is_as_jax_s_own_reader_has_it(profile):
    """The wire reader against the compiler's own text of the same module:
    every instruction name, with its opcode."""
    import re

    import jax

    loaded = trace_device.load(str(profile[1]))
    prog = loaded.programs[trace_device.the_step(loaded)]
    space = {}
    exec(_TRIAL.split("x, w, v =")[0], space)
    import jax.numpy as jnp
    text = space["train_step"].lower(
        jnp.ones((256, 128)), jnp.ones((128, 256)),
        jnp.ones((512, 512))).compile().as_text()
    said = dict(re.findall(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\(", text, re.M))
    assert len(said) > 20
    assert {i.name: i.opcode for i in prog.instructions.values()} == said
    assert jax.default_backend() == "cpu"


def test_the_layout_copy_between_the_scopes_has_a_kind_and_an_owner(profile):
    loaded = trace_device.load(str(profile[1]))
    prog = loaded.programs[trace_device.the_step(loaded)]
    moves = [i for i in prog.instructions.values()
             if i.opcode in ("copy", "transpose")
             and "transpose" in i.op_name]
    assert moves and all(trace.layer_of(i.op_name) is None for i in moves)
    assert {trace.compiler_kind(i.opcode) for i in moves} == {"copy"}
    # the operation it runs as: alone, or fused under its nameless root
    ran = [i for i in prog.operations() if "transpose" in i.op_name]
    assert ran and {trace_device.owner_of(i, prog) for i in ran} <= {
        "attention", "ffn"}
    loop = next(i for i in prog.operations() if i.opcode == "while")
    body, cond = (prog.computations[c] for c in loop.calls)
    assert prog.instructions[cond.root].shape == "pred[]"
    assert prog.instructions[body.root].opcode == "tuple"
    # ``v`` rides into the loop through its tuple: ``ffn``'s matmul reads it
    v = next(i for i in prog.operations()
             if i.opcode == "parameter" and i.parameter_number == 2
             and i.computation == prog.entry)
    assert trace_device.owner_of(v, prog) == "ffn"


def test_the_reader_prints_the_step_s_table(profile, capsys):
    assert trace.main([str(profile[0])]) == 0
    out = capsys.readouterr().out
    assert "trial trial-7: device trace " in out
    assert "program jit_train_step(" in out
    assert "instructions counted, not timed" in out  # a CPU's trace
    for word in ("attention", "ffn", "forward", "by kind:", "by owner:",
                 "largest by the bytes they make:"):
        assert word in out, word
    assert trace.main([str(profile[0]), "--scope", "ffn"]) == 0
    out = capsys.readouterr().out
    assert "under ffn, by operation" in out and "ffn/dot_general" in out
    assert trace.main([str(profile[0]), "--scope", "lunch"]) == 2


def test_a_trial_s_imports_leave_the_reader_out():
    code = ("import sys, metaopt_tpu.models.lm, metaopt_tpu.client\n"
            "assert 'metaopt_tpu.utils.trace' in sys.modules\n"
            "print('metaopt_tpu.utils.trace_device' in sys.modules)\n")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr
    assert done.stdout.strip() == "False"


def test_trace_still_imports_no_jax():
    code = ("import sys\nfrom metaopt_tpu.utils import trace, trace_device\n"
            "print('jax' in sys.modules)\n")
    done = subprocess.run([sys.executable, "-c", code],
                          env=dict(os.environ, PYTHONPATH=REPO),
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr
    assert done.stdout.strip() == "False"
