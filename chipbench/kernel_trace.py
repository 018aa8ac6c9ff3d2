"""Device seconds of a named kernel or scope in this traced run, and the
roofline shares made from them: arithmetic on what ``program_trace.load``
reads, for the readers of the pattern decoder's cell. Everything returns
``None`` where there is nothing to read (no trace, no such kernel, a
program without the layer).
"""

from __future__ import annotations

from typing import Optional, Tuple

from chipbench import flops, flops_lm, program_trace, trace_reduce


def kernel_seconds(records: dict, kernel: str,
                   directory: Optional[str] = None
                   ) -> Optional[Tuple[float, int]]:
    """(device seconds, calls) of the operations whose ``op_name`` names
    the Pallas call ``kernel`` (``pallas_call(name=...)`` puts it into the
    path) in this run's traced slice."""
    if program_trace.program_trace() is None or not records.get("trace"):
        return None
    loaded = program_trace.load(directory or program_trace.run_dir())
    if not loaded:
        return None
    mine = {plane: [e for e in evs if kernel in e[0].split("/")]
            for plane, evs in loaded["ops"].items()}
    calls = sum(map(len, mine.values()))
    if not calls:
        return None
    return trace_reduce.busy_seconds(mine), calls // len(mine)


def _sum(works) -> dict:
    return {k: sum(w[k] for w in works) for k in ("flops", "bytes")}


def attention_kernel_roofline(records: dict, kernel: str,
                              directory: Optional[str] = None):
    """% of its roofline that ``kernel`` (``flash_fwd`` / ``flash_bwd``)
    reached: the work of its calls in the slice (each layer's kernel is
    called equally often) over their device seconds."""
    work = records.get("kernel_work")
    timed = work and kernel_seconds(records, kernel, directory)
    if not timed:
        return None
    seconds, calls = timed
    whole = _sum(work[kernel])                 # one call of every layer
    scale = calls / work["layers"]
    return flops_lm.roofline_share(
        {k: v * scale for k, v in whole.items()}, seconds,
        flops.peak(records["device_kind"]))


def experts_roofline(records: dict, directory: Optional[str] = None):
    """% of their roofline that the grouped products reached: a step's
    passes over every layer (the forward once, once more where blocks are
    rematerialised, the backward twice a forward) over the device seconds
    a step under the scope ``moe.experts``."""
    work = records.get("kernel_work")
    ms = work and program_trace.scope_ms_a_step(
        records, "moe.experts", "train_step", directory)
    if not ms:
        return None
    passes = work["layers"] * (3 + int(work["remat"]))
    return flops_lm.roofline_share(
        {k: v * passes for k, v in work["experts_pass"].items()}, ms * 1e-3,
        flops.peak(records["device_kind"]))
