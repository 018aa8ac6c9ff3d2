"""% of their roofline the routed layers' grouped products reached:
``moe_experts_roofline``'s arithmetic over the four ROUTED layers (the
leading dense layer has no experts; chipbench/mla_kernel_trace.py, as the
latent cell's), the items the window routed to the 32 held experts of width 512 against the
device time under ``moe.experts``."""

from chipbench import mla_kernel_trace


def read(records):
    return mla_kernel_trace.experts_roofline(records)
