"""% of its roofline the core's kernel ``short_conv_bwd`` reached in the
traced slice: the equations' work a token and channel (four arrays read and three written, twenty-one operations),
the bytes at the two a number the configuration's precision states whatever
the kernel passes (chipbench/flops_conv_lm.py), over its calls' device time
and the chip's peaks; the bytes bind (chipbench/conv_kernel_trace.py)."""

from chipbench import conv_kernel_trace


def read(records):
    return conv_kernel_trace.core_kernel_roofline(records, "short_conv_bwd")
