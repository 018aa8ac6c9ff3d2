"""What the program's own trace layer (metaopt_tpu/utils/trace.py) leaves
for the per-layer readers: scope names on device operations, and the ring
of host spans and compile records of this process.

Device side. The program wraps its train step's parts in
``jax.named_scope``; an operation's ``op_name`` is then a path such as
``jit(train_step)/transpose(jvp(Transformer))/dec0/self_attn/attention/
attention.core/while``. This runtime names a device event by its HLO
instruction *without* the metadata, and ``ProfileData`` hands out an event's
own statistics only; but the TPU's profiler keeps the ``op_name`` (as
``tf_op``, beside ``flops`` and ``bytes_accessed``) with each operation's
metadata in the file. So ``load`` opens the newest ``*.xplane.pb`` under
this run's own directory (``run_dir``) itself and reads that statistic with
a few lines of wire format (no generated classes: importing tensorflow's beside a live
chip is not worth it). Seconds under a scope are a union
of intervals (a ``while`` contains its body's operations), divided by the
steps the traced slice holds: the runs of the jitted function that the
reader names. **XLA fuses across scopes and a fusion
carries its root's name**: AdamW's update of the tied embedding with the
readout's gradient folded in (``fusion.369`` of PR 23's trace) is one
operation under one name, so a scope's milliseconds are those of the
operations *named* by it, not of the source lines inside it.

Host side. The cell's runner calls the program's ``trial_setup``,
``synthetic_seq2seq``, ``init_sharded`` and its jitted step in this
process, so their spans and compile records are in this process's ring.

Every function returns ``None`` where there is nothing to read: no trace,
no device plane, a program without the layer (the parent commit), or a ring
that does not hold exactly one trial's set-up.
"""

from __future__ import annotations

import argparse
import glob
import os
import re
import sys
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from chipbench import trace_reduce

RUNS = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".runs")

Op = Tuple[str, float, float]  # op_name path, start_s, duration_s


# -- the file's own protos, by field number (xplane.proto) -------------------

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
    """(field number, value) of one message: an int, or a view of the
    bytes of a length-delimited field."""
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


def _all(buf, number: int) -> list:
    return [v for n, v in _fields(buf) if n == number]


def _text(buf, number: int) -> str:
    found = _all(buf, number)
    return bytes(found[0]).decode("utf-8", "replace") if found else ""


def op_names(xplane: bytes) -> Dict[str, Dict[str, str]]:
    """{device plane: {event name: op_name}} from the ``tf_op`` statistic
    that the TPU's profiler keeps with each operation's metadata (the
    ``op_name`` and a ``:``; as text, or as a reference to an interned
    one)."""
    out: Dict[str, Dict[str, str]] = {}
    for plane in _all(memoryview(xplane), 1):                 # XSpace.planes
        if not _text(plane, 2).startswith("/device:TPU:"):    # XPlane.name
            continue
        interned = {}
        for entry in _all(plane, 5):                          # .stat_metadata
            for meta in _all(entry, 2):
                interned[_all(meta, 1)[0]] = _text(meta, 2)   # id, name
        tf_op = next((i for i, n in interned.items() if n == "tf_op"), None)
        names = out[_text(plane, 2)] = {}
        for entry in _all(plane, 4):                          # .event_metadata
            for meta in _all(entry, 2):                       # XEventMetadata
                for stat in _all(meta, 5):                    # .stats
                    if _all(stat, 1) == [tf_op]:              # .metadata_id
                        ref = _all(stat, 7)                   # .ref_value
                        names[_text(meta, 2)] = (
                            interned.get(ref[0], "") if ref
                            else _text(stat, 5)).rstrip(":")  # .str_value
    return out


def program_trace():
    """The program's trace module, or None on a commit that has none."""
    try:
        from metaopt_tpu.utils import trace
    except ImportError:
        return None
    return trace


def in_scope(path: str, scope: str) -> bool:
    """Is ``scope`` a component of the path, bare or inside a transform's
    brackets (``transpose(jvp(readout_xent))``)? ``attention`` does not
    match ``attention.core``."""
    return re.search(r"(?:^|[/(])" + re.escape(scope) + r"(?:$|[/)])",
                     path) is not None


def run_dir() -> Optional[str]:
    """This traced run's own directory, as chipbench/run.py names it from
    the command line (a reader is handed the records alone); None where
    the command line names no cell."""
    parser = argparse.ArgumentParser(add_help=False)
    parser.add_argument("--workload")
    workload = parser.parse_known_args(sys.argv[1:])[0].workload
    return workload and os.path.join(RUNS, workload + "-trace1")


def load(directory: Optional[str]) -> Optional[dict]:
    """{"ops": {plane: [Op]}, "programs": {plane: [name of each program
    run]}} of the newest trace under ``directory``; None without a trace
    or a TPU plane in it."""
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True) if directory else ()
    if not paths:
        return None
    newest = max(paths, key=os.path.getmtime)
    key = (newest, os.path.getmtime(newest))
    if key not in _loaded:  # four readers ask for the same file
        _loaded.clear()
        _loaded[key] = _load(newest)
    return _loaded[key]


_loaded: Dict[tuple, Optional[dict]] = {}


def _load(path: str) -> Optional[dict]:
    import jax

    with open(path, "rb") as f:
        raw = f.read()
    names = op_names(raw)
    data = jax.profiler.ProfileData.from_serialized_xspace(raw)
    ops: Dict[str, List[Op]] = {}
    programs: Dict[str, List[str]] = {}
    for plane in data.planes:
        for line in plane.lines if plane.name in names else ():
            if line.name == trace_reduce.OPS_LINE:
                ops[plane.name] = [
                    (names[plane.name].get(e.name, ""), e.start_ns * 1e-9,
                     e.duration_ns * 1e-9) for e in line.events]
            elif line.name == "XLA Modules":
                programs[plane.name] = [e.name for e in line.events]
    return {"ops": ops, "programs": programs} if ops else None


def scope_seconds(ops: Dict[str, Sequence[Op]],
                  scopes: Sequence[str]) -> float:
    """Seconds in which an operation under any of ``scopes`` ran, averaged
    over the device planes."""
    paths = {e[0] for evs in ops.values() for e in evs}  # far fewer than ops
    inside = {p for p in paths if any(in_scope(p, s) for s in scopes)}
    return trace_reduce.busy_seconds({
        plane: [e for e in evs if e[0] in inside]
        for plane, evs in ops.items()})


def scope_ms_a_step(records: dict, scope: str, step: str,
                    directory: Optional[str] = None) -> Optional[float]:
    """Device milliseconds under ``scope`` in this run's trace, a run of the
    jitted function ``step``."""
    if program_trace() is None or not records.get("trace"):
        return None  # a program without scopes has nothing under them: no 0.0
    loaded = load(directory or run_dir())
    steps = loaded and max((sum(step in name for name in runs)
                            for runs in loaded["programs"].values()),
                           default=0)
    if not steps:
        return None
    return 1e3 * scope_seconds(loaded["ops"], [scope]) / steps


def scoped_share(records: dict,
                 directory: Optional[str] = None) -> Optional[float]:
    """% of the device's busy time spent under any of the program's scopes."""
    trace = program_trace()
    if trace is None or not records.get("trace"):
        return None
    loaded = load(directory or run_dir())
    busy = loaded and trace_reduce.busy_seconds(loaded["ops"])
    if not busy:
        return None
    return 100.0 * scope_seconds(loaded["ops"], trace.SCOPES) / busy


def one_setup() -> Optional[Dict[str, List[dict]]]:
    """This process's spans by name, if the ring holds one trial's set-up
    (one ``trial.data`` and one ``trial.init``); None otherwise."""
    trace = program_trace()
    if trace is None:
        return None
    by_name: Dict[str, List[dict]] = {}
    for rec in trace.spans():
        by_name.setdefault(rec["name"], []).append(rec)
    if any(len(by_name.get(n, ())) != 1 for n in ("trial.data", "trial.init")):
        return None
    return by_name


def compiles_of(functions: Sequence[str]) -> Optional[List[dict]]:
    """The ``compile`` spans of the jitted ``functions`` (by name: the
    reference's float32 step compiles in this process too, after the
    window)."""
    by_name = one_setup()
    if by_name is None:
        return None
    return [c for c in by_name.get("compile", ())
            if c["attrs"].get("fn") in functions] or None
