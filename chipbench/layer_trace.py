"""Device milliseconds a step by the program's two rules for a device
operation, ``layer_of`` and ``direction`` (metaopt_tpu/utils/trace.py): which
top-level scope owns the ``op_name``, and whether the operation is the
forward, its second run under remat, the backward or the update. Arithmetic
on what ``program_trace.load`` reads, for the readers of a whole step's
partition; nothing here parses an ``op_name`` for a layer or a direction.

With these a traced line adds up: the top-level layers (the accepted
``*_device_ms`` of ``moe``, ``ffn``, ``linear_attention``, ``readout_xent``,
``optimizer``, attention's core, index and select; and ``embed``,
attention's projections, the trunk) and ``unnamed_device_ms`` are the
device's busy time a step, and so are the four directions and
``unnamed_device_ms``. Each is a union of intervals (a ``while`` contains
its body's operations), so the sums differ from the busy time by what
overlaps: operations of two layers that run at once (an asynchronous copy
under a kernel). **XLA fuses across scopes and directions and a fusion
carries its root's name**: the numbers are of the operations *named* so.

Every function returns ``None`` where there is nothing to read: no trace,
no step in it, or a program without the two rules (the parent of PR 34).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

from chipbench import program_trace, trace_reduce

#: the parts of ``attention`` that have a metric of their own
ATTENTION_PARTS = ("attention.core", "attention.index", "attention.select")
#: the trunk between the layers (PR 34's three names)
TRUNK = ("norm", "residual", "loss")


def rules():
    """The program's trace module if it has both rules, else None."""
    trace = program_trace.program_trace()
    if trace is None or not (hasattr(trace, "layer_of")
                             and hasattr(trace, "direction")):
        return None
    return trace


def ms_a_step(records: dict, keep: Optional[Callable[[object, str], bool]],
              step: str = "train_step",
              directory: Optional[str] = None) -> Optional[float]:
    """Device milliseconds, a run of the jitted function ``step``, in which
    an operation ran whose ``op_name`` ``keep(trace, op_name)`` holds
    (``trace``: the program's module, for its rules); ``keep`` None: every
    operation, the device's busy time a step."""
    trace = rules()
    if trace is None or not records.get("trace"):
        return None
    loaded = program_trace.load(directory or program_trace.run_dir())
    steps = loaded and max((sum(step in name for name in runs)
                            for runs in loaded["programs"].values()),
                           default=0)
    if not steps:
        return None
    ops = loaded["ops"]
    if keep is not None:
        paths = {e[0] for evs in ops.values() for e in evs}  # few, of many
        inside = {p for p in paths if keep(trace, p)}
        ops = {plane: [e for e in evs if e[0] in inside]
               for plane, evs in ops.items()}
    return 1e3 * trace_reduce.busy_seconds(ops) / steps


def layers_ms(records: dict, layers: Sequence[str],
              less: Sequence[str] = (),
              directory: Optional[str] = None) -> Optional[float]:
    """Under any of the top-level ``layers``, but for the operations under
    a scope of ``less`` (a layer's parts that are measured apart)."""
    return ms_a_step(
        records, lambda trace, path: trace.layer_of(path) in layers
        and not any(program_trace.in_scope(path, part) for part in less),
        directory=directory)


def direction_ms(records: dict, direction: str,
                 directory: Optional[str] = None) -> Optional[float]:
    """Of the operations with a layer that run in ``direction``."""
    return ms_a_step(
        records, lambda trace, path: trace.layer_of(path) is not None
        and trace.direction(path) == direction, directory=directory)


def unnamed_ms(records: dict,
               directory: Optional[str] = None) -> Optional[float]:
    """In which the device was busy and no operation with a layer ran: the
    compiler's own copies, slices and loops' bookkeeping. An unnamed
    ``while`` around a layer's operations is that layer's time, not this."""
    busy = ms_a_step(records, None, directory=directory)
    named = ms_a_step(
        records, lambda trace, path: trace.layer_of(path) is not None,
        directory=directory)
    return None if busy is None or named is None else busy - named
