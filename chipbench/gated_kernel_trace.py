"""Roofline shares of the attention kernels by KIND of layer, for the
readers of a cell whose window and full layers call ``flash_fwd`` /
``flash_bwd`` with different work a call (64 query heads x the pairs
inside the window, 48 x the causal pairs):
``kernel_trace.attention_kernel_roofline`` divides the work of one call of
every layer by all the kernel's seconds, one share for both kinds; here the
calls are split first.

How a call's kind is told in the trace: a device operation's ``op_name`` is
a path that holds the flax name of the block that made it
(``.../DecoderOnlyLM._patterned/h3/attn/attention/attention.core/
jit(_causal_forward)/flash_fwd/pallas_call``, and the same under
``checkpoint/rematted_computation/`` for a rematerialised block's second
run), and ``kernel_work["blocks"]`` names each kind's blocks (``{"window":
["h1", "h2", "h3"], "full": ["h0", "h4"]}``, from the configuration's
``layer_types``): a call belongs to the kind whose block is a component of
its path (``chipbench/tests/data/gated_lm_ops.json`` is a recorded sample).
``None`` where there is nothing to read (no trace, no such kernel, a
program without the layer)."""

from __future__ import annotations

from typing import Optional

from chipbench import flops, flops_lm, program_trace, trace_reduce


def kernel_roofline(records: dict, kernel: str, kind: str,
                    directory: Optional[str] = None):
    """% of its roofline that ``kernel`` reached on the layers of ``kind``:
    the work of its calls there in the slice (each layer's kernel is called
    equally often) over their device seconds."""
    work = records.get("kernel_work")
    blocks = set((work or {}).get("blocks", {}).get(kind, ()))
    if not blocks or program_trace.program_trace() is None \
            or not records.get("trace"):
        return None
    loaded = program_trace.load(directory or program_trace.run_dir())
    if not loaded:
        return None
    of_kind = lambda parts: kernel in parts and blocks & parts  # noqa: E731
    mine = {plane: [e for e in evs if of_kind(set(e[0].split("/")))]
            for plane, evs in loaded["ops"].items()}
    calls = sum(map(len, mine.values())) // len(mine)
    if not calls:
        return None
    # one call of every layer of the kind, times the calls a layer
    scale = calls / len(blocks)
    whole = {k: scale * sum(w[k] for w, block in zip(
        work[kernel], work["block_of_layer"]) if block in blocks)
        for k in ("flops", "bytes")}
    return flops_lm.roofline_share(whole, trace_reduce.busy_seconds(mine),
                                   flops.peak(records["device_kind"]))

