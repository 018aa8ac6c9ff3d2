"""% of its roofline the forward attention kernel ``flash_fwd`` reached in
the traced slice: the operations and bytes its calls need
(chipbench/flops_lm.py: the seen pairs only) over their device time and
the chip's peaks (chipbench/kernel_trace.py)."""

from chipbench import kernel_trace


def read(records):
    return kernel_trace.attention_kernel_roofline(records, "flash_fwd")
