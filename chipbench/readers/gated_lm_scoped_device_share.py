"""``scoped_device_share`` in a gated mixed-window MoE decoder's cell, read by
that metric's own reader: busy time under any scope over busy time: a guard,
low means the names were lost. An accepted metric's list of cells takes no
new cell, so the cell reports it under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("scoped_device_share").read(records)
