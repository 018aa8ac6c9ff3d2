"""% of its roofline the attention kernel ``flash_fwd`` reached on the three
WINDOW layers: 64 query heads on 8 K/V heads, the pairs inside the window of
512, in the traced slice: the operations and bytes its calls there need
(chipbench/flops_gated_lm.py: q, k, v, out and lse once a call) over their
device time and the chip's peaks. The calls are told apart by the block's
name on an operation's path (chipbench/gated_kernel_trace.py); the work is
the model's, whatever implements the layer."""

from chipbench import gated_kernel_trace


def read(records):
    return gated_kernel_trace.kernel_roofline(records, "flash_fwd", "window")
