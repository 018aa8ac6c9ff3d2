"""``attention_proj_device_ms`` in a gated mixed-window MoE decoder's cell,
read by that metric's own reader: the layer ``attention`` less
``attention.core``: the four projections at each layer's own head count, the
q and k norms, the rotations (YaRN on half a head, the plain rule on the
whole), the scaling and casts, and the gate (``gated_lm_gate_device_ms``)
inside it. An accepted metric's list of cells takes no new cell, so the cell
reports it under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("attention_proj_device_ms").read(records)
