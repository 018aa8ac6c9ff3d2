"""Roofline shares of the linear-attention scan's kernels, for the readers
of a hybrid decoder's cell: ``kernel_trace.attention_kernel_roofline``'s
arithmetic with the calls scaled by the LINEAR layers' count (that function
scales by ``kernel_work["layers"]``, which in this cell counts the layers
that call the flash kernels). ``None`` where there is nothing to read (no
trace, no such kernel: a program without the scan)."""

from __future__ import annotations

from typing import Optional

from chipbench import kernel_trace


def linear_kernel_roofline(records: dict, kernel: str,
                           directory: Optional[str] = None):
    """% of its roofline that ``kernel`` (``linear_scan_fwd`` /
    ``linear_scan_bwd``) reached: the work of its calls in the slice (the
    counted form's, chipbench/flops_hybrid_lm.py) over their device
    seconds."""
    work = records.get("kernel_work")
    if not work or not work.get(kernel):
        return None
    return kernel_trace.attention_kernel_roofline(
        {**records, "kernel_work": {**work, "layers": work["linear_layers"]}},
        kernel, directory)
