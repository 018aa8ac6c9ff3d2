"""Device milliseconds a step of the operations the compiler made (those
whose ``op_name`` carries no name of the program's: ``layer_trace.
unnamed_ms``'s own definition, the busy time less the union of the
operations that have a layer), by the program's two rules for them:
``trace.compiler_kind`` (what the operation is) and ``trace_device.owner_of``
(the layer it was made for, read from the step's own HLO, which the trace
file holds: metaopt_tpu/utils/trace_device.py). Arithmetic on what
``trace_device.load`` returns; nothing here parses a trace file, an opcode
or a graph.

**Each instant of the nameless time belongs to the innermost nameless
operation running then** (``trace_device.nameless_seconds``: a nameless
``while`` around nameless copies has the copies' time under ``copy`` and the
rest under ``loop``), so the five kinds are a partition of
``unnamed_device_ms`` and the owners' parts a partition of
``compiler_owned_share`` of it, not unions that overlap. An instruction the
step's program does not hold (another program's in the window; a file
without its programs) has its kind from its name and no owner.

Every function returns ``None`` where there is nothing to read: no trace,
no step in it, a program without ``trace_device`` (the parent of PR 51), for
an owner a file without the programs, or a cell whose program has no layer
of the group.
"""

from __future__ import annotations

import traceback
from typing import Dict, Optional, Sequence

from chipbench import program_trace

#: the owners' groups: every top-level layer of ``trace.LAYERS`` but ``eval``
#: (which runs in no step) is in one
GROUPS = {
    "attention": ("attention",),
    "moe": ("moe",),
    "ffn": ("ffn",),
    "mixer": ("linear_attention", "ssm", "ssd", "gmu"),
    "ends": ("embed", "readout_xent", "loss"),
    "optimizer": ("optimizer",),
    "trunk": ("norm", "residual"),
}


def device_side():
    """The program's reader of a trace file, or None on a program that has
    none."""
    try:
        from metaopt_tpu.utils import trace_device
    except ImportError:
        return None
    return trace_device


def split(records: dict, step: str = "train_step",
          directory: Optional[str] = None) -> Optional[dict]:
    """{"kinds": {kind: ms}, "owners": {layer or None: ms} or None without
    the step's program, "layers": the layers the step's operations carry}
    a run of the jitted function ``step``, over the whole traced slice."""
    side = device_side()
    if side is None or not records.get("trace"):
        return None
    try:
        return _split_of(side, step, directory or program_trace.run_dir())
    except Exception:  # a reader leaves its metric out; it never ends a run
        traceback.print_exc()
        return None


def _split_of(side, step: str, directory: Optional[str]) -> Optional[dict]:
    loaded = side.load(directory or "")
    steps = loaded and max((sum(step in name for name, _, _ in runs)
                            for runs in loaded.runs.values()), default=0)
    if not steps:
        return None
    key = (loaded.path, step)
    if key not in _split:
        _split.clear()
        trace = side.trace
        scale = 1e3 / (steps * len(loaded.ops))
        kinds = dict.fromkeys(trace.COMPILER_KINDS, 0.0)
        owners: Dict[Optional[str], float] = {}
        for plane, ops in loaded.ops.items():
            of = side.run_of(loaded.runs.get(plane, ()))
            for (run, name), secs in side.nameless_seconds(
                    ops, key=lambda op: (of(op), op[0])).items():
                program = loaded.programs.get(run)
                ins = program.get(name) if program is not None else None
                kinds[trace.compiler_kind(
                    side.opcode_of(ins, program) if ins is not None
                    else name)] += secs * scale
                owner = side.owner_of(ins, program) if ins is not None \
                    else None
                owners[owner] = owners.get(owner, 0.0) + secs * scale
        paths = {op[1] for ops in loaded.ops.values() for op in ops}
        _split[key] = {"kinds": kinds,
                       "owners": owners if loaded.programs else None,
                       "layers": {trace.layer_of(p) for p in paths} - {None}}
    return _split[key]


_split: Dict[tuple, dict] = {}


def kind_ms(records: dict, kind: str,
            directory: Optional[str] = None) -> Optional[float]:
    """Of the nameless time, the part of the operations of ``kind``."""
    found = split(records, directory=directory)
    return None if found is None else found["kinds"][kind]


def owner_ms(records: dict, group: str,
             directory: Optional[str] = None) -> Optional[float]:
    """Of the nameless time, the part whose owner is a layer of ``group``;
    None in a cell whose step runs no layer of the group."""
    found = split(records, directory=directory)
    layers: Sequence[str] = GROUPS[group]
    if found is None or found["owners"] is None \
            or not found["layers"].intersection(layers):
        return None
    return sum(found["owners"].get(layer, 0.0) for layer in layers)


def owned_share(records: dict,
                directory: Optional[str] = None) -> Optional[float]:
    """% of the nameless time whose owner is not None."""
    found = split(records, directory=directory)
    if found is None or found["owners"] is None:
        return None
    whole = sum(found["owners"].values())
    if not whole:
        return None
    return 100.0 * (whole - found["owners"].get(None, 0.0)) / whole
