"""% of its roofline the forward scan kernel ``linear_scan_fwd`` reached in
the traced slice: the counted form's operations (a chunk of 64, whatever
the kernel runs) and the bytes its calls need (chipbench/flops_hybrid_lm.py)
over their device time and the chip's peaks
(chipbench/hybrid_kernel_trace.py)."""

from chipbench import hybrid_kernel_trace


def read(records):
    return hybrid_kernel_trace.linear_kernel_roofline(records,
                                                      "linear_scan_fwd")
