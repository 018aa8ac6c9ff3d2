"""How ``data/compiler_ops.json`` was made: of a ``--trace 1`` run's trace
file, the first step as ``trace_device.load`` returns it, cut to what the
readers of the compiler's operations (chipbench/compiler_trace.py) need and
a test can hold:

- ``names``, ``ops``: the first step's operations without a layer, by HLO
  name, ``[name, start, duration]`` (seconds from the step's start);
- ``named``: the operations WITH a layer merged into the intervals they
  cover, ``[start, end]``: only their union enters the arithmetic; and
  ``layers``, the layers they carry;
- ``program``: the step's compiled program cut to the instructions
  ``owner_of`` and ``opcode_of`` reach from those operations (rows ``[id,
  name, opcode, op_name (an index into ``paths``), operands, computation,
  calls, parameter number, tuple index]``, ids renumbered, 0 for what was
  not reached; ``computations`` rows ``[id, name, root]``);
- ``expected``: what each of the thirteen readers makes of exactly that,
  and ``unnamed_device_ms`` by the accepted reader's own arithmetic.

On the chip after the traced runs, or here on trace files brought back:

    PYTHONPATH=. python3 chipbench/tests/record_compiler_ops.py OUT.json \
        CELL=TRACE_DIR [CELL=TRACE_DIR ...]
"""

import json
import sys

from chipbench import compiler_trace, layer_trace, program_trace
from chipbench.run import _reader
from metaopt_tpu.utils import trace, trace_device

READERS = tuple(f"compiler_{k}_device_ms" for k in trace.COMPILER_KINDS) + \
    tuple(f"compiler_for_{g}_device_ms" for g in compiler_trace.GROUPS) + \
    ("compiler_owned_share",)
PLANE = "/device:TPU:0"
_load = trace_device.load  # ``hand_out`` puts a recorded cell in its place
TRACED = {"trace": {"busy_s": 1.0, "window_s": 1.0}}
#: the path a merged interval of named operations is recorded under, a layer
_PATH = "jit(train_step)/{}/recorded"


class _Touched(dict):
    """A program's instructions, remembering which were asked for."""

    def __init__(self, *args):
        super().__init__(*args)
        self.touched = set()

    def __getitem__(self, key):
        self.touched.add(key)
        return super().__getitem__(key)

    def get(self, key, default=None):
        return self[key] if key in self else default


def loaded_of(doc):
    """A recorded cell as ``trace_device.load`` would hand it out."""
    names, first = doc["names"], doc["layers"][0]
    ops = [(names[n], "", s, d) for n, s, d in doc["ops"]]
    ops += [("named", _PATH.format(first), s, e - s) for s, e in doc["named"]]
    ops += [("named", _PATH.format(layer), 0.0, 0.0)
            for layer in doc["layers"]]
    p = doc["program"]
    program = trace_device.link(
        p["name"], p["entry"],
        [trace_device.Computation(id, name, [], root)
         for id, name, root in p["computations"]],
        [trace_device.Instruction(id, name, opcode, p["paths"][path],
                                  tuple(operands), computation, tuple(calls),
                                  number, index)
         for id, name, opcode, path, operands, computation, calls, number,
         index in p["instructions"]])
    for ins in program.instructions.values():
        program.computations[ins.computation].instructions.append(ins.id)
    end = max(s + d for _, _, s, d in ops)
    return trace_device.Loaded(
        "recorded", {PLANE: ops}, {PLANE: [(p["name"], 0.0, end)]},
        {p["name"]: program})


def hand_out(doc, setattr_):
    """Put a recorded cell in the place of this run's trace, for the
    program's reader and for the benchmark's older one."""
    loaded = loaded_of(doc)
    older = {"ops": {PLANE: [(p, s, d) for _, p, s, d in loaded.ops[PLANE]]},
             "programs": {PLANE: [doc["program"]["name"]]}}
    setattr_(trace_device, "load", lambda directory: loaded)
    setattr_(program_trace, "load", lambda directory: older)
    setattr_(program_trace, "run_dir", lambda: "recorded")
    compiler_trace._split.clear()
    return loaded


def cut(trace_dir):
    loaded = _load(trace_dir)
    step = trace_device.the_step(loaded)
    (plane, runs), = loaded.runs.items()
    _, at, dur = min((r for r in runs if r[0] == step), key=lambda r: r[1])
    ops = [o for o in loaded.ops[plane] if at <= o[2] < at + dur]
    nameless = [o for o in ops if trace.layer_of(o[1]) is None]
    names = sorted({o[0] for o in nameless})
    index = {n: i for i, n in enumerate(names)}
    named = trace_device.union(
        (o[2] - at, o[2] - at + o[3]) for o in ops
        if trace.layer_of(o[1]) is not None)
    full = loaded.programs[step]
    seen = full.instructions = _Touched(full.instructions)
    for ins in filter(None, map(full.get, names)):
        trace_device.owner_of(ins, full)
        trace_device.opcode_of(ins, full)
    # a loop, a call or a branch that READS a value is found through its
    # body's parameter: keep those of the ones reached, named or not
    for id in sorted(seen.touched):
        ins = dict.__getitem__(seen, id)
        if ins.opcode in ("while", "call", "conditional"):
            seen.touched.update(
                param for (comp, _), param in full.parameters.items()
                if comp in ins.calls)
    small = {id: i + 1 for i, id in enumerate(sorted(seen.touched))}
    comps = sorted({seen[id].computation for id in small} | {full.entry})
    comp_of = {c: i + 1 for i, c in enumerate(comps)}
    rows = []
    paths = sorted({dict.__getitem__(seen, id).op_name for id in small})
    path_of = {p: i for i, p in enumerate(paths)}
    for id in sorted(seen.touched):
        i = dict.__getitem__(seen, id)
        rows.append([small[id], i.name, i.opcode, path_of[i.op_name],
                     [small.get(o, 0) for o in i.operands],  # 0: not reached
                     comp_of[i.computation],
                     [comp_of.get(c, 0) for c in i.calls],
                     i.parameter_number, i.tuple_index])
    return {
        "recorded": f"the first run of {step} in {trace_dir}: "
                    f"{len(nameless)} of its {len(ops)} operations have no "
                    f"layer; {len(rows)} of {len(seen)} instructions reached",
        "names": names,
        "ops": [[index[o[0]], round(o[2] - at, 9), round(o[3], 9)]
                for o in nameless],
        "named": [[round(s, 9), round(e, 9)] for s, e in named],
        "layers": sorted({trace.layer_of(o[1]) for o in ops} - {None}),
        "program": {
            "name": step, "entry": comp_of[full.entry], "paths": paths,
            "computations": [
                [comp_of[c], full.computations[c].name,
                 small.get(full.computations[c].root, 0)] for c in comps],
            "instructions": rows}}


def main(out, *cells):
    doc = {}
    for cell, trace_dir in (c.split("=", 1) for c in cells):
        one = cut(trace_dir)
        hand_out(one, setattr)
        one["expected"] = {name: _reader(name).read(TRACED)
                           for name in READERS}
        one["expected"]["unnamed_device_ms"] = layer_trace.unnamed_ms(TRACED)
        doc[cell] = one
        print(cell, one["recorded"], one["expected"])
    with open(out, "w") as f:
        json.dump(doc, f, separators=(",", ":"))


if __name__ == "__main__":
    main(*sys.argv[1:])
