"""% of its roofline the forward attention kernel ``flash_fwd`` reached in
the traced slice: the operations and bytes its calls need, as the cell's
runner counts them into ``kernel_work`` (the seen pairs only, each layer
or call at its own widths and mask: chipbench/flops_lm.py,
flops_hybrid_lm.py, flops_mla_lm.py, flops_ssm_lm.py), over their device
time and the chip's peaks (chipbench/kernel_trace.py). A cell whose kinds
of layer differ in work a call reads a share a kind instead
(chipbench/gated_kernel_trace.py).

``flash_fwd_roofline`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""

from chipbench import kernel_trace


def read(records):
    return kernel_trace.attention_kernel_roofline(records, "flash_fwd")
