"""% of its roofline the backward attention kernel ``sparse_bwd`` reached
in the traced slice: the operations of the SELECTED pairs only and the
bytes its calls need (chipbench/flops_sparse_lm.py) over their device time
and the chip's peaks (chipbench/kernel_trace.py). The kernel walks and
masks every causal tile, so it cannot read above the selected share of what
it reaches on the MXU: which is the point of the number."""

from chipbench import kernel_trace


def read(records):
    return kernel_trace.attention_kernel_roofline(records, "sparse_bwd")
