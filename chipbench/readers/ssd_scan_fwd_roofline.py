"""% of its roofline the scan kernel ``ssd_scan_fwd`` reached in the traced
slice: the matmul form's operations at the published chunk of 128 (a
group's Q K^T once, its eight heads' masked products, the state products;
whatever the kernel runs) and the bytes its calls need
(chipbench/flops_ssd_lm.py) over their device time and the chip's peaks
(chipbench/ssd_kernel_trace.py)."""

from chipbench import ssd_kernel_trace


def read(records):
    return ssd_kernel_trace.scan_kernel_roofline(records, "ssd_scan_fwd")
