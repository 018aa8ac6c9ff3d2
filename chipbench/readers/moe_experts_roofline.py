"""% of their roofline the expert layers' grouped products reached: the
operations and bytes a step's passes need for the items the window routed
to held experts (chipbench/flops_lm.py) over the device time under the
scope ``moe.experts`` and the chip's peaks (chipbench/kernel_trace.py)."""

from chipbench import kernel_trace


def read(records):
    return kernel_trace.experts_roofline(records)
