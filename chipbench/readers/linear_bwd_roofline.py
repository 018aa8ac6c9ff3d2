"""% of its roofline the backward scan kernel ``linear_scan_bwd`` reached
in the traced slice, as ``linear_fwd_roofline`` for the forward one."""

from chipbench import hybrid_kernel_trace


def read(records):
    return hybrid_kernel_trace.linear_kernel_roofline(records,
                                                      "linear_scan_bwd")
