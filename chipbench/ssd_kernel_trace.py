"""Roofline shares of the kernels a decoder of one sublayer a block adds,
for the readers of its cell: ``kernel_trace.attention_kernel_roofline``'s
arithmetic with the calls scaled by the count of the blocks that call the
kernel (that function scales by ``kernel_work["layers"]``, which in this
cell counts the ONE block that calls the flash kernels), and the
activation's pass of experts that are not gated, whose two kernels are
bound by bytes. ``None`` where there is nothing to read (no trace, no such
kernel: a program without the scan or the pass)."""

from __future__ import annotations

from typing import Optional

from chipbench import flops, flops_lm, kernel_trace


def scan_kernel_roofline(records: dict, kernel: str,
                         directory: Optional[str] = None):
    """% of its roofline that ``kernel`` (``ssd_scan_fwd`` /
    ``ssd_scan_bwd``) reached: the work of its calls in the slice (the
    counted form's, chipbench/flops_ssd_lm.py) over their device seconds."""
    work = records.get("kernel_work")
    if not work or not work.get(kernel):
        return None
    return kernel_trace.attention_kernel_roofline(
        {**records, "kernel_work": {**work, "layers": work["ssd_layers"]}},
        kernel, directory)


def expert_act_roofline(records: dict, directory: Optional[str] = None):
    """% of their roofline that the activation's two kernels
    (``expert_activation``, ``expert_activation_bwd``) reached together:
    each call's bytes over the rows the window's steps filled on average
    (``kernel_work["expert_act"]``: one call forward, one backward) over
    both kernels' device seconds."""
    work = (records.get("kernel_work") or {}).get("expert_act")
    if not work:
        return None
    timed = [kernel_trace.kernel_seconds(records, kernel, directory)
             for kernel in ("expert_activation", "expert_activation_bwd")]
    if not all(timed):
        return None
    whole = {k: sum(calls * w[k] for (_, calls), w in zip(timed, work))
             for k in ("flops", "bytes")}
    return flops_lm.roofline_share(whole, sum(s for s, _ in timed),
                                   flops.peak(records["device_kind"]))
