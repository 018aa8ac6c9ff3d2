"""Roofline share of the grouped products in a latent-attention MoE
decoder's cell: ``kernel_trace.experts_roofline``'s arithmetic with the
passes scaled by the ROUTED layers' count (that function scales by
``kernel_work["layers"]``, which in this cell counts the layers that call
the flash kernels: the leading dense layer has attention and no experts).
``None`` where there is nothing to read (no trace, a program without the
layer)."""

from __future__ import annotations

from typing import Optional

from chipbench import kernel_trace


def experts_roofline(records: dict, directory: Optional[str] = None):
    work = records.get("kernel_work")
    if not work or not work.get("routed_layers"):
        return None
    return kernel_trace.experts_roofline(
        {**records, "kernel_work": {**work, "layers": work["routed_layers"]}},
        directory)
