"""``optimizer_device_ms`` in a gated mixed-window MoE decoder's cell, read by
that metric's own reader: the scope ``optimizer``: AdamW over what no
matmul's fusion updates. An accepted metric's list of cells takes no new
cell, so the cell reports it under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("optimizer_device_ms").read(records)
