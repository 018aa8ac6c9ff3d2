"""% of their roofline the expert layers' grouped products reached: the
operations and bytes a step's passes need for the items the window routed
to held experts (chipbench/flops_lm.py) over the device time under the
scope ``moe.experts`` and the chip's peaks (chipbench/kernel_trace.py).
The passes are scaled by the layers that HAVE experts: where the runner
says how many are routed (``kernel_work["routed_layers"]``: a leading
dense layer calls the attention kernels and has no experts) by that count
(chipbench/mla_kernel_trace.py), else by ``kernel_work["layers"]``, all
of them.

``moe_experts_roofline`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""

from chipbench import kernel_trace, mla_kernel_trace


def read(records):
    work = records.get("kernel_work") or {}
    if "routed_layers" in work:
        return mla_kernel_trace.experts_roofline(records)
    return kernel_trace.experts_roofline(records)
