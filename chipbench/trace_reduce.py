"""From a profiler trace to numbers: busy union, op table, idle gaps.

``load`` reads the newest ``*.xplane.pb`` under a directory with nothing
but jax and returns plain lists; everything after it is arithmetic on
those lists, which the tests drive with a small recorded trace.

A device's plane is named ``/device:TPU:<n>``. Its operations are the
events of the line ``XLA Ops``; a traced step's ``while`` contains its
body's operations, so seconds are a union of intervals, never a sum.
Host spans are ``jax.profiler.TraceAnnotation`` events of the plane
``/host:CPU`` whose names the caller lists.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_s, duration_s

OPS_LINE = "XLA Ops"


def load(trace_dir: str, span_names: Iterable[str] = ()) -> dict:
    """{"devices": {plane: [Event]}, "spans": [Event], "lines": {...}}"""
    import jax

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    want = set(span_names)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    lines: Dict[str, List[str]] = {}
    for plane in data.planes:
        lines[plane.name] = [ln.name for ln in plane.lines]
        if plane.name.startswith("/device:TPU:"):
            devices[plane.name] = [
                (op_name(e.name), e.start_ns * 1e-9, e.duration_ns * 1e-9)
                for ln in plane.lines if ln.name == OPS_LINE
                for e in ln.events]
        elif plane.name == "/host:CPU" and want:
            spans += [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                      for ln in plane.lines for e in ln.events
                      if e.name in want]
    return {"devices": devices, "spans": spans, "lines": lines}


def op_name(event_name: str) -> str:
    """``fusion.3058`` from ``%fusion.3058 = (f32[...]) fusion(...)``: this
    runtime names a device event by its whole HLO instruction."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:96]


def union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Sorted disjoint [start, end) intervals covered by any event."""
    out: List[Tuple[float, float]] = []
    for start, end in sorted((s, s + d) for _, s, d in events if d > 0):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def busy_seconds(devices: Dict[str, Sequence[Event]]) -> float:
    """Seconds in which an operation ran, averaged over the device planes."""
    if not devices:
        return 0.0
    return sum(sum(e - s for s, e in union(evs))
               for evs in devices.values()) / len(devices)


def op_table(devices: Dict[str, Sequence[Event]], top: int = 10) -> list:
    """[[name, seconds]] of the operations with most time, all planes
    together; an enclosing ``while`` counts beside what it encloses."""
    total: Dict[str, float] = {}
    for evs in devices.values():
        for name, _, dur in evs:
            total[name] = total.get(name, 0.0) + dur
    return [[n, s] for n, s in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:top]]


def idle_gaps(events: Sequence[Event], spans: Sequence[Event],
              top: int = 10) -> list:
    """[[what the host was doing, seconds]] for one device plane: every gap
    between its first and last operation goes to the host span that covers
    most of it, or to ``unattributed``."""
    busy = union(events)
    total: Dict[str, float] = {}
    for (_, gap_start), (gap_end, _) in zip(busy, busy[1:]):
        best, best_cover = "unattributed", 0.0
        for name, s, d in spans:
            cover = min(gap_end, s + d) - max(gap_start, s)
            if cover > best_cover:
                best, best_cover = name, cover
        total[best] = total.get(best, 0.0) + (gap_end - gap_start)
    return [[n, s] for n, s in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:top]]


def reduce(trace_dir: str, window_s: float,
           span_names: Iterable[str] = ()) -> dict:
    """What a traced run reports: busy_s, window_s, the two tables."""
    loaded = load(trace_dir, span_names)
    devices = loaded["devices"]
    first = next(iter(devices.values()), [])
    return {"busy_s": busy_seconds(devices), "window_s": window_s,
            "device_ops": op_table(devices),
            "idle_gaps": idle_gaps(first, loaded["spans"]),
            "lines": loaded["lines"]}
