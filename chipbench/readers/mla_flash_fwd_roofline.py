"""% of its roofline the forward attention kernel ``flash_fwd`` reached on
the latent layers in the traced slice: the operations and bytes its calls
need (chipbench/flops_mla_lm.py: the seen pairs at 192 + 128, q, k_nope,
v, out and lse once a head, the shared rotary key once a layer) over
their device time and the chip's peaks (chipbench/kernel_trace.py)."""

from chipbench import kernel_trace


def read(records):
    return kernel_trace.attention_kernel_roofline(records, "flash_fwd")
