"""% of their roofline the expert blocks' grouped products reached where an
expert is TWO matrices: the operations and bytes a step's passes need for
the items the window routed to held experts, two products f wide
(chipbench/flops_ssd_lm.py::experts_pass; ``moe_experts_roofline`` counts a
first product 2f wide, the gate's and the up matrix joined, and would
overstate this cell's work by half), over the device time under the scope
``moe.experts`` and the chip's peaks, the passes scaled by the blocks that
have experts (chipbench/mla_kernel_trace.py)."""

from chipbench import mla_kernel_trace


def read(records):
    return mla_kernel_trace.experts_roofline(records)
