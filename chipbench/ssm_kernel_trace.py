"""Roofline shares of the selective scan's kernels, for the readers of a
state-space decoder's cell: ``kernel_trace.attention_kernel_roofline``'s
arithmetic with the calls scaled by the MAMBA layers' count (that function
scales by ``kernel_work["layers"]``, which in this cell counts the calls of
the flash kernels: two a layer of differential attention). ``None`` where
there is nothing to read (no trace, no such kernel: a program without the
scan, or one that walks it outside a kernel of that name)."""

from __future__ import annotations

from typing import Optional

from chipbench import kernel_trace


def scan_kernel_roofline(records: dict, kernel: str,
                         directory: Optional[str] = None):
    """% of its roofline that ``kernel`` (``selective_scan_fwd`` /
    ``selective_scan_bwd``) reached: the work of its calls in the slice
    (the recurrence's own, counted from the model's shapes:
    chipbench/flops_ssm_lm.py) over their device seconds."""
    work = records.get("kernel_work")
    if not work or not work.get(kernel):
        return None
    return kernel_trace.attention_kernel_roofline(
        {**records, "kernel_work": {**work, "layers": work["ssm_layers"]}},
        kernel, directory)
