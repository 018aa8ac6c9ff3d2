"""% of its roofline the forward scan kernel ``selective_scan_fwd`` reached in
the traced slice: the recurrence's own operations (T d_inner N state
updates, whatever implements them) and the bytes its calls need, each
operand read once and each result written once
(chipbench/flops_ssm_lm.py), over their device time and the chip's peaks
(chipbench/ssm_kernel_trace.py). The bytes bind: the share is of the
memory's time."""

from chipbench import ssm_kernel_trace


def read(records):
    return ssm_kernel_trace.scan_kernel_roofline(records, "selective_scan_fwd")
