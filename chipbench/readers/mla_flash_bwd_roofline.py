"""% of its roofline the backward attention kernel ``flash_bwd`` reached on
the latent layers in the traced slice: its five products a seen pair at
their own widths and its bytes (chipbench/flops_mla_lm.py) over their
device time and the chip's peaks (chipbench/kernel_trace.py)."""

from chipbench import kernel_trace


def read(records):
    return kernel_trace.attention_kernel_roofline(records, "flash_bwd")
