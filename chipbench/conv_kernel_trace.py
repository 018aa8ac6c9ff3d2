"""Roofline shares of the kernels a decoder with gated short convolutions
adds, for the readers of its cell: ``kernel_trace.attention_kernel_
roofline``'s arithmetic with the calls scaled by the count of the layers
that call the kernel (that function scales by ``kernel_work["layers"]``,
which in this cell counts the layers that call the flash kernels), and the
compiler's operations made for the mixer with ``short_conv`` among the
mixers' layers (``compiler_trace.GROUPS`` names the four older ones).
``None`` where there is nothing to read (no trace, no such kernel: a
program without the mixer's calls)."""

from __future__ import annotations

from typing import Optional

from chipbench import compiler_trace, kernel_trace

#: the layers whose compiler-made operations count as a mixer's here
MIXERS = compiler_trace.GROUPS["mixer"] + ("short_conv",)


def core_kernel_roofline(records: dict, kernel: str,
                         directory: Optional[str] = None):
    """% of its roofline that ``kernel`` (``short_conv_fwd`` /
    ``short_conv_bwd``) reached: the work of its calls in the slice (the
    equations' at two bytes a number, chipbench/flops_conv_lm.py) over
    their device seconds."""
    work = records.get("kernel_work")
    if not work or not work.get(kernel):
        return None
    return kernel_trace.attention_kernel_roofline(
        {**records, "kernel_work": {**work, "layers": work["conv_layers"]}},
        kernel, directory)


def mixer_owner_ms(records: dict, directory: Optional[str] = None):
    """Of the nameless time, the part whose owner is a mixer's layer, the
    gated short convolution among them; None in a cell whose step runs no
    such layer (``compiler_trace.owner_ms``'s rule)."""
    found = compiler_trace.split(records, directory=directory)
    if found is None or found["owners"] is None \
            or not found["layers"].intersection(MIXERS):
        return None
    return sum(found["owners"].get(layer, 0.0) for layer in MIXERS)
