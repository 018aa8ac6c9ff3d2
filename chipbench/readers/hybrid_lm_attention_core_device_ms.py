"""``attention_core_device_ms`` in a hybrid linear-attention decoder's cell,
read by that metric's own reader: the scope ``attention.core`` of the one
full-attention layer: ``flash_fwd`` under a causal mask without positions
(once a step), ``flash_bwd``, the backward's delta. An accepted metric's
list of cells takes no new cell, so the cell reports it under a name of its
own."""

from chipbench.run import _reader


def read(records):
    return _reader("attention_core_device_ms").read(records)
