"""The device trace read as the program that ran: the third side of the
program's trace layer (utils/trace.py has the host spans and the names).

A ``jax.profiler`` trace is one ``*.xplane.pb``. It holds, besides each
device's operations (the line ``XLA Ops`` of a plane ``/device:TPU:<n>``:
an event is named by its HLO instruction and carries its ``op_name`` as the
statistic ``tf_op``) and the runs of each program (the line ``XLA
Modules``, an event ``jit_train_step(<id>)``), **the programs themselves**:
the plane ``/host:metadata`` has one entry a program that ran, under the
same ``name(id)``, whose statistic is the compiled module's ``HloProto``.
So what the compiler made of a step is in the file the step's trace is in:
no second compile, no dump flag, nothing on the untraced path.

:func:`load` reads the file once, by field number (xplane.proto, hlo.proto,
xla_data.proto: no generated classes, and no jax: a reader beside a live
chip must not reach for it). :func:`owner_of` is the one rule this module
adds to ``trace.layer_of``, ``trace.direction`` and ``trace.compiler_kind``:
which layer an operation without a name of the program's was made for.
:func:`step_table` is the arithmetic the reader ``python -m
metaopt_tpu.utils.trace DIR`` prints; the benchmark's
(chipbench/compiler_trace.py) is its own.

Nothing a trial imports imports this module.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

from metaopt_tpu.utils import trace

#: a device operation: the HLO instruction's name, its ``op_name`` ("" for
#: what the compiler made), start and duration in seconds on the file's clock
Op = Tuple[str, str, float, float]
#: a run of a program on a device: ``name(id)``, start, duration
Run = Tuple[str, float, float]

OPS_LINE, RUNS_LINE = "XLA Ops", "XLA Modules"
PROGRAMS_PLANE = "/host:metadata"

#: xla_data.proto's PrimitiveType, the ones a step's shapes use
_TYPES = {1: "pred", 2: "s8", 3: "s16", 4: "s32", 5: "s64", 6: "u8",
          7: "u16", 8: "u32", 9: "u64", 10: "f16", 11: "f32", 12: "f64",
          13: "tuple", 14: "opaque", 15: "c64", 16: "bf16", 17: "token",
          18: "c128", 19: "f8e5m2", 20: "f8e4m3fn", 21: "s4", 22: "u4"}


# -- the wire format ---------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    shift = out = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int, or a view of the bytes
    of a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:  # fixed 64 / fixed 32
            size = 8 if wire == 1 else 4
            value, i = bytes(buf[i:i + size]), i + size
        yield key >> 3, value


def _ints(value) -> List[int]:
    """A repeated integer field's values: one a key, or packed."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >> 63 else value


# -- a program as a small graph ----------------------------------------------

@dataclasses.dataclass(eq=False)
class Instruction:
    """One HLO instruction of a compiled program."""
    id: int
    name: str
    opcode: str
    op_name: str
    operands: Tuple[int, ...]
    computation: int            # the computation it stands in
    calls: Tuple[int, ...]      # a fusion's, a loop's, a call's computations
    parameter_number: int = 0   # of a ``parameter``
    tuple_index: int = 0        # of a ``get-tuple-element``
    _shape: object = None       # the ShapeProto's bytes, read when asked

    @property
    def shape(self) -> str:
        """``bf16[8192,2048]``; a tuple's parts in brackets."""
        return _shape_words(self._shape)[0] if self._shape is not None else ""

    @property
    def layout(self) -> str:
        """``{1,0:T(8,128)(2,1)}``: minor to major, and the tiles."""
        return _shape_words(self._shape)[1] if self._shape is not None else ""

    @property
    def memory_space(self) -> Optional[int]:
        """The layout's memory space (``S(n)`` in HLO text; 0 is the
        device's main memory), None without a layout."""
        return _shape_words(self._shape)[2] if self._shape is not None \
            else None


@dataclasses.dataclass(eq=False)
class Computation:
    id: int
    name: str
    instructions: List[int]
    root: int


@dataclasses.dataclass(eq=False)
class Program:
    """A compiled module: ``name`` is the ``XLA Modules`` line's
    ``jit_train_step(<id>)``."""
    name: str
    entry: int
    computations: Dict[int, Computation]
    instructions: Dict[int, Instruction]
    by_name: Dict[str, int]
    users: Dict[int, List[int]]       # instruction -> those that read it
    callers: Dict[int, List[int]]     # computation -> instructions calling it
    #: (computation, parameter number) -> that ``parameter`` instruction
    parameters: Dict[Tuple[int, int], int]
    _layers: Dict[int, Optional[str]] = dataclasses.field(
        default_factory=dict)
    _owners: Dict[int, Optional[str]] = dataclasses.field(
        default_factory=dict)

    def get(self, hlo_name: str) -> Optional[Instruction]:
        found = self.by_name.get(hlo_name)
        return None if found is None else self.instructions[found]

    def operations(self) -> Iterator[Instruction]:
        """The instructions that run as operations of their own: the entry
        computation's, and those of the loops' bodies and conditions, the
        branches and the calls under it (not a fusion's inside, nor the
        scalar function a reduction applies)."""
        todo, seen = [self.entry], set()
        while todo:
            comp = self.computations.get(todo.pop())
            if comp is None or comp.id in seen:
                continue
            seen.add(comp.id)
            for ins in map(self.instructions.__getitem__, comp.instructions):
                if ins.opcode in ("while", "conditional", "call"):
                    todo += ins.calls
                yield ins


def _shape_words(buf) -> Tuple[str, str, Optional[int]]:
    """(shape, layout, memory space) of a ShapeProto."""
    kind, dims, parts, layout = 0, [], [], None
    for number, value in _fields(buf):
        if number == 2:
            kind = value
        elif number == 3:
            dims += _ints(value)
        elif number == 4:
            parts.append(value)
        elif number == 5:
            layout = value
    if parts or kind == 13:
        shown = [_shape_words(p) for p in parts[:4]]
        more = ", ..." if len(parts) > 4 else ""
        spaces = {s[2] for s in shown if s[2] is not None}
        return ("(" + ", ".join(s[0] for s in shown) + more + ")",
                "(" + ", ".join(s[1] for s in shown) + more + ")",
                max(spaces) if spaces else None)
    name = _TYPES.get(kind, f"type{kind}")
    shape = f"{name}[{','.join(map(str, dims))}]"
    if layout is None:
        return shape, "", None
    order, tiles, space = [], [], 0
    for number, value in _fields(layout):
        if number == 1:
            order += _ints(value)
        elif number == 6:
            tile = [d for n, v in _fields(value) if n == 1 for d in _ints(v)]
            tiles.append("(" + ",".join(map(str, map(_signed, tile))) + ")")
        elif number == 8:
            space = value
    words = ",".join(map(str, order)) + (":T" + "".join(tiles)
                                         if tiles else "")
    return shape, "{" + words + "}", space


def _instruction(buf, computation: int) -> Instruction:
    name = opcode = op_name = ""
    id = number_ = index = 0
    operands: List[int] = []
    calls: List[int] = []
    shape = None
    for number, value in _fields(buf):
        if number == 1:
            name = _text(value)
        elif number == 2:
            opcode = _text(value)
        elif number == 3:
            shape = value
        elif number == 7:  # OpMetadata: op_name = 2
            for n, v in _fields(value):
                if n == 2:
                    op_name = _text(v)
        elif number == 9:
            number_ = value
        elif number == 13:
            index = value
        elif number == 35:
            id = value
        elif number == 36:
            operands += _ints(value)
        elif number == 38:
            calls += _ints(value)
    return Instruction(id, name, opcode, op_name, tuple(operands),
                       computation, tuple(calls), number_, index, shape)


def parse_program(hlo_proto, name: str = "") -> Program:
    """The graph of a serialized ``HloProto`` (hlo.proto: a module's
    computations, a computation's instructions, an instruction's name,
    opcode, shape, metadata, ids of operands and called computations)."""
    module = next((v for n, v in _fields(memoryview(hlo_proto)) if n == 1),
                  b"")
    computations: Dict[int, Computation] = {}
    instructions: Dict[int, Instruction] = {}
    entry = 0
    for number, value in _fields(module):
        if number == 6:
            entry = value
        if number != 3:
            continue
        comp = Computation(0, "", [], 0)
        raw = []
        for n, v in _fields(value):
            if n == 1:
                comp.name = _text(v)
            elif n == 2:
                raw.append(v)
            elif n == 5:
                comp.id = v
            elif n == 6:
                comp.root = v
        for buf in raw:
            ins = _instruction(buf, comp.id)
            instructions[ins.id] = ins
            comp.instructions.append(ins.id)
        computations[comp.id] = comp
    return link(name, entry, computations.values(), instructions.values())


def link(name: str, entry: int, computations: Iterable[Computation],
         instructions: Iterable[Instruction]) -> Program:
    """A :class:`Program` of its parts: who reads whom, who calls what."""
    instructions = {i.id: i for i in instructions}
    users: Dict[int, List[int]] = {}
    callers: Dict[int, List[int]] = {}
    for ins in instructions.values():
        for operand in dict.fromkeys(ins.operands):
            users.setdefault(operand, []).append(ins.id)
        for called in ins.calls:
            callers.setdefault(called, []).append(ins.id)
    return Program(name, entry, {c.id: c for c in computations},
                   instructions,
                   {i.name: i.id for i in instructions.values()},
                   users, callers,
                   {(i.computation, i.parameter_number): i.id
                    for i in instructions.values()
                    if i.opcode == "parameter"})


# -- the file ----------------------------------------------------------------

@dataclasses.dataclass(eq=False)
class Loaded:
    """What one trace file holds of the devices' side."""
    path: str
    ops: Dict[str, List[Op]]          # device plane -> its operations
    runs: Dict[str, List[Run]]        # device plane -> the programs' runs
    #: the ``name(id)`` of an ``XLA Modules`` event -> that program
    programs: Dict[str, Program]


def newest(trace_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load(trace_dir: str) -> Optional[Loaded]:
    """The newest ``*.xplane.pb`` under ``trace_dir``, or None without one.
    One parse a file: a second call for the same file is handed the first's
    answer."""
    path = newest(trace_dir)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _loaded:
        _loaded.clear()
        with open(path, "rb") as f:
            _loaded[key] = parse(f.read(), path)
    return _loaded[key]


_loaded: Dict[tuple, Loaded] = {}


def hlo_name(event_name: str) -> str:
    """``fusion.3058`` from ``%fusion.3058 = (f32[...]) fusion(...)``: the
    TPU's profiler names a device event by its whole HLO instruction."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _metadata(plane) -> Tuple[Dict[int, str], Dict[int, object]]:
    """({stat id: name}, {event metadata id: the XEventMetadata's bytes})."""
    stats, events = {}, {}
    for number, value in _fields(plane):
        if number not in (4, 5):
            continue
        for n, entry in _fields(value):  # a map's entry: key = 1, value = 2
            if n != 2:
                continue
            if number == 4:
                events[next(v for k, v in _fields(entry) if k == 1)] = entry
            else:
                pairs = dict((k, v) for k, v in _fields(entry) if k in (1, 2))
                stats[pairs.get(1, 0)] = _text(pairs.get(2, b""))
    return stats, events


def _stat_text(stat, interned: Dict[int, str]) -> str:
    """An XStat's string: its own, or the interned one it refers to."""
    for number, value in _fields(stat):
        if number == 5:
            return _text(value)
        if number == 7:
            return interned.get(value, "")
    return ""


def _device_plane(plane) -> Tuple[List[Op], List[Run]]:
    stats, events = _metadata(plane)
    tf_op = next((i for i, n in stats.items() if n == "tf_op"), None)
    named: Dict[int, Tuple[str, str]] = {}  # metadata id -> (hlo, op_name)
    for id, meta in events.items():
        name = path = ""
        for number, value in _fields(meta):
            if number == 2:
                name = _text(value)
            elif number == 5 and tf_op is not None:
                if next((v for k, v in _fields(value) if k == 1),
                        None) == tf_op:
                    path = _stat_text(value, stats).rstrip(":")
        named[id] = (name, path)
    ops: List[Op] = []
    runs: List[Run] = []
    for number, line in _fields(plane):
        if number != 3:
            continue
        name, at_ns, raw = "", 0, []
        for n, v in _fields(line):
            if n == 2:
                name = _text(v)
            elif n == 3:
                at_ns = v
            elif n == 4:
                raw.append(v)
        if name not in (OPS_LINE, RUNS_LINE):
            continue
        for event in raw:
            meta = offset_ps = duration_ps = 0
            for n, v in _fields(event):
                if n == 1:
                    meta = v
                elif n == 2:
                    offset_ps = v
                elif n == 3:
                    duration_ps = v
            # whole nanoseconds, cut and not rounded, as
            # jax.profiler.ProfileData hands them out (``start_ns``,
            # ``duration_ns``): chipbench/program_trace.py reads those, and
            # this module's seconds are its seconds to the bit, so that what
            # both add up agrees
            start = float(at_ns + offset_ps // 1000) * 1e-9
            duration = float(duration_ps // 1000) * 1e-9
            full, path = named.get(meta, ("", ""))
            if name == OPS_LINE:
                ops.append((hlo_name(full), path, start, duration))
            else:
                runs.append((full, start, duration))
    return ops, runs


def _programs(plane) -> Dict[str, Program]:
    """The ``/host:metadata`` plane's programs by ``name(id)``."""
    out = {}
    _, events = _metadata(plane)
    for meta in events.values():
        name, proto = "", None
        for number, value in _fields(meta):
            if number == 2:
                name = _text(value)
            elif number == 5:  # the one statistic: the HloProto's bytes
                proto = next((v for k, v in _fields(value) if k == 6), proto)
        if proto is not None:
            out[name] = parse_program(proto, name)
    return out


def parse(xspace: bytes, path: str = "") -> Loaded:
    """An XSpace's device planes and programs."""
    loaded = Loaded(path, {}, {}, {})
    for number, plane in _fields(memoryview(xspace)):
        if number != 1:
            continue
        name = next((_text(v) for n, v in _fields(plane) if n == 2), "")
        if name.startswith("/device:TPU:"):
            ops, runs = _device_plane(plane)
            if ops:
                loaded.ops[name], loaded.runs[name] = ops, runs
        elif name == PROGRAMS_PLANE:
            loaded.programs.update(_programs(plane))
    return loaded


# -- the one rule: whom a nameless operation was made for -------------------

def named_layer(ins: Instruction, program: Program) -> Optional[str]:
    """The layer an instruction carries itself: ``trace.layer_of`` of its
    ``op_name``; for a fusion whose root has no name of the program's, the
    one layer the instructions fused into it carry, if they agree."""
    if ins.id not in program._layers:
        layer = trace.layer_of(ins.op_name)
        if layer is None and ins.opcode == "fusion":
            inside = set()
            todo, seen = list(ins.calls), set()
            while todo:
                comp = program.computations.get(todo.pop())
                if comp is None or comp.id in seen:
                    continue
                seen.add(comp.id)
                for id in comp.instructions:
                    inner = program.instructions[id]
                    inside.add(trace.layer_of(inner.op_name))
                    todo += inner.calls
            inside.discard(None)
            layer = inside.pop() if len(inside) == 1 else None
        program._layers[ins.id] = layer
    return program._layers[ins.id]


def opcode_of(ins: Instruction, program: Program) -> str:
    """The opcode of the work an instruction does: its own, but for an
    asynchronous pair (``async-start``, ``async-update``, ``async-done``)
    the opcode of what the pair wraps: ``slice-start.3`` / ``slice-done.3``
    are an ``async-start`` and its ``async-done`` around a ``slice``."""
    at: Optional[Instruction] = ins
    while at is not None and at.operands and at.opcode in (
            "async-update", "async-done"):
        at = program.instructions.get(at.operands[0])
    if at is not None and at.opcode == "async-start" and at.calls:
        comp = program.computations.get(at.calls[0])
        root = comp and program.instructions.get(comp.root)
        if root is not None:
            return root.opcode
    return ins.opcode


Value = Tuple[int, Tuple[int, ...]]  # an instruction, and a path into a tuple


def _uses(program: Program, at: Instruction,
          path: Tuple[int, ...]) -> Iterator[Tuple[Instruction, Value]]:
    """Where a value goes: (the reader, the value it makes of it), through
    what only hands a value on (a tuple and its element, a loop's, a call's
    or a branch's parameter, a body's root into the next turn and into the
    loop's result)."""
    get = program.instructions.__getitem__
    comp = program.computations[at.computation]
    if comp.root == at.id:
        for caller in map(get, program.callers.get(comp.id, ())):
            if caller.opcode not in ("while", "call", "conditional") or (
                    caller.opcode == "while" and caller.calls[0] != comp.id):
                continue  # a fusion's root is the fusion; a condition's
                # answer is read by its loop alone
            if caller.opcode == "while":  # the body's root: the next turn
                for called in caller.calls:
                    param = program.parameters.get((called, 0))
                    if param is not None:
                        yield caller, (param, path)
            yield caller, (caller.id, path)
    for user in map(get, program.users.get(at.id, ())):
        places = [k for k, o in enumerate(user.operands) if o == at.id]
        if user.opcode == "get-tuple-element":
            if not path or path[0] == user.tuple_index:
                yield user, (user.id, path[1:])
        elif user.opcode == "tuple":
            for k in places:
                yield user, (user.id, (k,) + path)
        elif user.opcode == "while":
            for called in user.calls:
                param = program.parameters.get((called, 0))
                if param is not None:
                    yield user, (param, path)
        elif user.opcode == "call":
            for k in places:
                param = program.parameters.get((user.calls[0], k))
                if param is not None:
                    yield user, (param, path)
        elif user.opcode == "conditional":
            for k in places:
                if 1 <= k <= len(user.calls):
                    param = program.parameters.get((user.calls[k - 1], 0))
                    if param is not None:
                        yield user, (param, path)
        else:
            yield user, (user.id, ())


def _sources(program: Program, at: Instruction,
             path: Tuple[int, ...]) -> Iterator[Value]:
    """What a value is made from, through the same hand-overs backwards."""
    get = program.instructions.__getitem__
    roots = lambda calls: (  # noqa: E731
        (program.computations[c].root, path) for c in calls
        if c in program.computations)
    if at.opcode == "get-tuple-element":
        yield at.operands[0], (at.tuple_index,) + path
    elif at.opcode == "tuple" and path and path[0] < len(at.operands):
        yield at.operands[path[0]], path[1:]
    elif at.opcode == "parameter":
        for caller in map(get, program.callers.get(at.computation, ())):
            if caller.opcode == "while":
                yield caller.operands[0], path
                yield from roots(caller.calls[:1])
            elif caller.opcode in ("fusion", "call"):
                if at.parameter_number < len(caller.operands):
                    yield caller.operands[at.parameter_number], path
            elif caller.opcode == "conditional":
                branch = caller.calls.index(at.computation) + 1
                if branch < len(caller.operands):
                    yield caller.operands[branch], path
    elif at.opcode == "while":
        yield at.operands[0], path
        yield from roots(at.calls[:1])
    elif at.opcode in ("call", "conditional"):
        yield from roots(at.calls)
    else:
        for operand in at.operands:
            yield operand, ()


def readers_layers(ins: Instruction, program: Program) -> set:
    """The layers of the named instructions that read what ``ins`` makes,
    looking through those without a name."""
    found, seen, todo = set(), set(), [(ins.id, ())]
    while todo:
        value = todo.pop()
        if value in seen:
            continue
        seen.add(value)
        for reader, onward in _uses(program, program.instructions[value[0]],
                                    value[1]):
            layer = named_layer(reader, program)
            if layer is not None:
                found.add(layer)
            else:
                todo.append(onward)
    return found


def producers_layers(ins: Instruction, program: Program) -> set:
    """The layers of the named instructions ``ins`` is made from, looking
    through those without a name."""
    found, seen = set(), set()
    todo = list(_sources(program, ins, ()))
    while todo:
        value = todo.pop()
        if value in seen or value[0] not in program.instructions:
            continue
        seen.add(value)
        at = program.instructions[value[0]]
        layer = named_layer(at, program)
        if layer is not None:
            found.add(layer)
        else:
            todo += _sources(program, at, value[1])
    return found


def owner_of(ins: Instruction, program: Program) -> Optional[str]:
    """The layer (a name of ``trace.LAYERS``) an instruction of a compiled
    program was made for, or None.

    An instruction that has a layer by ``trace.layer_of`` owns itself (a
    fusion under a nameless root: the one layer the instructions fused into
    it agree on). One without takes **the layer its users agree on**,
    looking through users that have no name either (``copy-start`` ->
    ``copy-done`` -> the user, tuples and their elements, bitcasts, a
    loop's parameter into its body); where the users carry no layer or
    disagree, the layer its **operands' producers** agree on, looked
    through the same way; else None: a copy of the residual stream that
    three layers read is nobody's, and says so.

    The same limit as ``layer_of``'s: the answer is for the instruction as
    compiled. A copy XLA shares between two layers' matmuls has two users
    and no owner, though the source wrote it for one."""
    if ins.id not in program._owners:
        layer = named_layer(ins, program)
        for side in (readers_layers, producers_layers):
            if layer is None:
                found = side(ins, program)
                layer = found.pop() if len(found) == 1 else None
        program._owners[ins.id] = layer
    return program._owners[ins.id]


# -- arithmetic on a device's operations -------------------------------------

def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Sorted disjoint [start, end) covered by any of ``intervals``."""
    out: List[Tuple[float, float]] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def nameless_seconds(ops: Sequence[Op], key=lambda op: op[0]) -> dict:
    """{HLO instruction: seconds} of one device plane's operations (or by
    another ``key`` of an operation, where several programs ran): the time
    in which the device was busy and no operation with a layer ran
    (``trace.layer_of`` of its ``op_name``), **each instant given to the
    innermost nameless operation running then** (the one begun last: a
    nameless ``while`` around nameless copies has the copies' time taken
    out of it). A partition: the values sum to the busy time less the union
    of the operations that have a layer."""
    layered = {p: trace.layer_of(p) is not None for p in {o[1] for o in ops}}
    named = union((s, s + d) for _, p, s, d in ops if layered[p])
    starts = [s for s, _ in named]
    before = [0.0]  # seconds covered by ``named`` before each of its pieces
    for s, e in named:
        before.append(before[-1] + (e - s))

    def covered(t: float) -> float:
        i = bisect.bisect_right(starts, t) - 1
        return 0.0 if i < 0 else before[i] + min(t, named[i][1]) - starts[i]

    out: dict = {}

    def give(name, start: float, end: float) -> None:
        if end > start:
            out[name] = out.get(name, 0.0) + (end - start) - (
                covered(end) - covered(start))

    open_: list = []  # (end, key), the innermost last
    at = 0.0
    for name, start, dur in sorted(
            ((key(o), o[2], o[3]) for o in ops
             if not layered[o[1]] and o[3] > 0),
            key=lambda o: (o[1], -o[2])):
        while open_ and open_[-1][0] <= start:
            end, inner = open_.pop()
            give(inner, at, end)
            at = max(at, end)
        if open_:
            give(open_[-1][1], at, start)
        at = start
        open_.append((start + dur, name))
    while open_:
        end, inner = open_.pop()
        give(inner, at, end)
        at = max(at, end)
    return out


def busy_seconds(ops: Sequence[Op]) -> float:
    return sum(e - s for s, e in union((s, s + d) for _, _, s, d in ops))


def the_step(loaded: Loaded) -> Optional[str]:
    """The ``name(id)`` of the program a trace is about: the one whose runs
    take most of the devices' time; in a file without a device plane (a
    CPU's) the largest program."""
    total: Dict[str, float] = {}
    for runs in loaded.runs.values():
        for name, _, dur in runs:
            total[name] = total.get(name, 0.0) + dur
    if total:
        return max(total, key=total.get)
    sizes = {n: len(p.instructions) for n, p in loaded.programs.items()}
    return max(sizes, key=sizes.get) if sizes else None


def run_of(runs: Sequence[Run]):
    """op -> the ``name(id)`` of the run an operation of that device began
    in ("" outside every run): instruction names are a program's own, and
    a trace holds several programs."""
    spans = sorted((s, s + d, n) for n, s, d in runs)
    starts = [s for s, _, _ in spans]

    def of(op: Op) -> str:
        i = bisect.bisect_right(starts, op[2]) - 1
        return spans[i][2] if i >= 0 and op[2] < spans[i][1] else ""

    return of


def step_ops(loaded: Loaded, step: str) -> Tuple[Dict[str, List[Op]], int]:
    """(each device plane's operations inside the runs of the program
    ``step``, a ``name(id)``, and nothing of another program's; the runs of
    it a device made). No plane: the file times no operation of it."""
    out = {}
    for plane, ops in loaded.ops.items():
        of = run_of(loaded.runs.get(plane, ()))
        mine = [op for op in ops if of(op) == step]
        if mine:
            out[plane] = mine
    return out, max((sum(n == step for n, _, _ in loaded.runs[plane])
                     for plane in out), default=0)


# -- a step's table: what ``python -m metaopt_tpu.utils.trace DIR`` prints ----

_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1, "f8e5m2": 1,
          "f8e4m3fn": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4,
          "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
          "c128": 16}


def _bytes(shape: str) -> int:
    """Bytes of an array's ``shape`` as :attr:`Instruction.shape` words it
    (0 for a tuple or a token)."""
    kind, _, dims = shape.partition("[")
    if kind not in _BYTES or not dims.endswith("]"):
        return 0
    size = _BYTES[kind]
    for d in filter(None, dims[:-1].split(",")):
        size *= int(d)
    return size


def _describe(program: Optional[Program], hlo: str) -> dict:
    """What the program says of the instruction an event names."""
    ins = program.get(hlo) if program is not None else None
    if ins is None:  # no program in the file: the name begins with the opcode
        return {"name": hlo, "opcode": hlo.partition(".")[0], "shape": "",
                "layout": "", "memory_space": None, "owner": None,
                "kind": trace.compiler_kind(hlo)}
    return {"name": hlo, "opcode": ins.opcode, "shape": ins.shape,
            "layout": ins.layout, "memory_space": ins.memory_space,
            "owner": owner_of(ins, program),
            "kind": trace.compiler_kind(opcode_of(ins, program))}


def step_table(loaded: Loaded, step: Optional[str] = None,
               top: int = 10) -> Optional[dict]:
    """One program of a trace as a table: ``layers`` ``{layer: {direction:
    value}}`` by ``trace.layer_of`` and ``trace.direction``; the compiler's
    operations (those without a layer) by ``kinds`` (``trace.
    compiler_kind``), by ``owners`` (:func:`owner_of`; the key None for
    nobody's) and the ``top`` largest of them. ``unit`` says what a value
    is: ``ms`` a run of the program where the file has a device plane (a
    layer's: the union of its operations; the compiler's: the partition of
    :func:`nameless_seconds`), else ``instructions``, counted in the
    program (a CPU's trace times no operation). None: no program ran."""
    step = step or the_step(loaded)
    if step is None:
        return None
    program = loaded.programs.get(step)
    planes, runs = step_ops(loaded, step)
    layers: Dict[str, Dict[str, float]] = {}
    nameless: Dict[str, List[float]] = {}  # instruction -> [value, events]
    if planes:
        scale = 1e3 / (runs * len(planes))
        busy = sum(map(busy_seconds, planes.values())) * scale
        for ops in planes.values():
            by: Dict[Tuple[str, str], list] = {}
            for path in {o[1] for o in ops}:
                layer = trace.layer_of(path)
                if layer is not None:
                    by[path] = (layer, trace.direction(path))
            spans: Dict[Tuple[str, str], list] = {}
            for _, path, start, dur in ops:
                if path in by:
                    spans.setdefault(by[path], []).append((start, start + dur))
            for (layer, way), found in spans.items():
                row = layers.setdefault(layer, {})
                row[way] = row.get(way, 0.0) + scale * sum(
                    e - s for s, e in union(found))
            for name, secs in nameless_seconds(ops).items():
                nameless.setdefault(name, [0.0, 0])[0] += secs * scale
            for name, path, _, _ in ops:
                if path not in by:
                    nameless.setdefault(name, [0.0, 0])[1] += 1 / (
                        runs * len(planes))
    elif program is not None:
        busy = None
        for ins in program.operations():
            layer = trace.layer_of(ins.op_name)
            if layer is not None:
                row = layers.setdefault(layer, {})
                way = trace.direction(ins.op_name)
                row[way] = row.get(way, 0) + 1
            elif ins.opcode not in ("parameter", "constant"):
                nameless[ins.name] = [1, 1]
    else:
        return None
    kinds: Dict[str, float] = {}
    owners: Dict[Optional[str], float] = {}
    rows = []
    for name, (value, events) in nameless.items():
        row = _describe(program, name)
        row.update(value=value, events=events)
        kinds[row["kind"]] = kinds.get(row["kind"], 0) + value
        owners[row["owner"]] = owners.get(row["owner"], 0) + value
        rows.append(row)
    size = (lambda r: r["value"]) if planes else (
        lambda r: _bytes(r["shape"]))
    return {"program": step, "runs": runs, "busy": busy,
            "unit": "ms" if planes else "instructions",
            "instructions": len(program.instructions) if program else None,
            "layers": layers, "unnamed": sum(kinds.values()),
            "kinds": kinds, "owners": owners,
            "largest": sorted(rows, key=size, reverse=True)[:top]}


def scope_table(loaded: Loaded, scope: str,
                step: Optional[str] = None) -> Optional[dict]:
    """The operations of one program under one scope of ``trace.SCOPES``
    (a component of their ``op_name``, bare or in a transform's brackets),
    by operation: ``rows`` of ``[what, direction, value, events]``, ``what``
    the opcode and the tail of the path; ``unit`` ``ms`` a run (durations
    summed: a loop counts beside what it holds) or ``instructions``."""
    step = step or the_step(loaded)
    program = loaded.programs.get(step)
    planes, runs = step_ops(loaded, step) if step else ({}, 0)

    def under(path: str) -> bool:
        return scope in path and scope in re.split(r"[/()]", path)

    def what(opcode: str, path: str) -> str:
        tail = path.replace("(", "/").replace(")", "").rsplit("/", 2)[-2:]
        return f"{opcode} {'/'.join(tail)}"

    total: Dict[Tuple[str, str], List[float]] = {}
    if planes:
        runs *= len(planes)
        for ops in planes.values():
            for name, path, _, dur in ops:
                if under(path):
                    ins = program.get(name) if program else None
                    key = (what(ins.opcode if ins else name.partition(".")[0],
                                path), trace.direction(path))
                    cell = total.setdefault(key, [0.0, 0.0])
                    cell[0] += 1e3 * dur / runs
                    cell[1] += 1 / runs
    elif program is not None:
        for ins in program.operations():
            if under(ins.op_name):
                key = (what(ins.opcode, ins.op_name),
                       trace.direction(ins.op_name))
                cell = total.setdefault(key, [0, 0])
                cell[0] += 1
                cell[1] += 1
    else:
        return None
    return {"program": step, "scope": scope,
            "unit": "ms" if planes else "instructions",
            "rows": sorted(([w, d, v, n] for (w, d), (v, n) in total.items()),
                           key=lambda r: -r[2])}


def _number(value, unit: str) -> str:
    return f"{value:10.3f}" if unit == "ms" else f"{value:10d}"


def print_step(table: dict) -> None:
    """:func:`step_table` as text."""
    unit = table["unit"]
    said = (f"{table['runs']} runs, the device busy {table['busy']:.3f} ms "
            "a run; ms a run" if unit == "ms" else
            "no device plane in this file (a CPU's trace times no "
            "operation): instructions counted, not timed")
    print(f"program {table['program']}: {said}"
          + (f"; {table['instructions']} instructions compiled"
             if table["instructions"] else "; the file holds no program: "
             "kinds from the events' names, no owner"))
    ways = [w for w in trace.DIRECTIONS
            if any(w in row for row in table["layers"].values())]
    print(f"  {'layer':<18}" + "".join(f"{w:>14}" for w in ways))
    for layer in trace.LAYERS:
        row = table["layers"].get(layer)
        if row:
            print(f"  {layer:<18}" + "".join(
                f"    {_number(row.get(w, 0), unit)}" for w in ways))
    print(f"  the compiler's operations (no name of the program's): "
          f"{_number(table['unnamed'], unit).strip()} {unit}")
    print("  by kind:  " + ", ".join(
        f"{k} {_number(table['kinds'][k], unit).strip()}"
        for k in trace.COMPILER_KINDS if k in table["kinds"]))
    owners = table["owners"]
    print("  by owner: " + ", ".join(
        f"{k or 'nobody'} {_number(v, unit).strip()}" for k, v in sorted(
            owners.items(), key=lambda kv: -kv[1])))
    print(f"  the {len(table['largest'])} largest"
          + ("" if unit == "ms" else " by the bytes they make") + ":")
    for r in table["largest"]:
        space = "" if not r["memory_space"] else f" S({r['memory_space']})"
        size = (f"{r['value']:10.3f} ms x{r['events']:<6.4g}" if unit == "ms"
                else f"{_bytes(r['shape']):12d} B")
        print(f"    {size} {r['name']:<28} {r['opcode']:<20} {r['shape']}"
              f"{r['layout']}{space} for {r['owner'] or 'nobody'}")


def print_scope(table: dict, top: int = 40) -> None:
    """:func:`scope_table` as text."""
    unit = table["unit"]
    print(f"program {table['program']}: under {table['scope']}, by "
          f"operation ({unit}" + (" a run, durations summed: a loop counts "
                                   "beside what it holds)" if unit == "ms"
                                   else ")"))
    for what, way, value, events in table["rows"][:top]:
        calls = f" x{events:<8.4g}" if unit == "ms" else ""
        print(f"    {_number(value, unit)}{calls} {way:<14} {what}")
    rest = table["rows"][top:]
    if rest:
        print(f"    {_number(sum(r[2] for r in rest), unit)} in "
              f"{len(rest)} more")
