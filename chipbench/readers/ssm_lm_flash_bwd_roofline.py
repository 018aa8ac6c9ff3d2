"""% of its roofline the backward attention kernel ``flash_bwd`` reached on
the differential layers in the traced slice: the operations and bytes
its calls need (chipbench/flops_ssm_lm.py: the seen pairs of each map at
64 + 128, causal or inside the window of 512; q_i, k_i, the joined v,
out and lse once a call) over their device time and the chip's peaks
(chipbench/kernel_trace.py)."""

from chipbench import kernel_trace


def read(records):
    return kernel_trace.attention_kernel_roofline(records, "flash_bwd")
