"""% of their roofline the activation's pass of experts that are not gated
reached in the traced slice: the kernels ``expert_activation`` and
``expert_activation_bwd`` (ops/experts.py) over the tiles the held experts'
rows fill, bound by bytes: a filled row's 1856 numbers in and out
(chipbench/flops_ssd_lm.py::expert_act_call) over both kernels' device time
and the chip's peaks (chipbench/ssd_kernel_trace.py)."""

from chipbench import ssd_kernel_trace


def read(records):
    return ssd_kernel_trace.expert_act_roofline(records)
