"""``optimizer_device_ms`` in a cell whose attention runs over selected keys,
read by that metric's own reader: the scope ``optimizer``: AdamW over the
trained leaves (an indexer has no moments). An accepted metric's list of
cells takes no new cell, so the cell reports it under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("optimizer_device_ms").read(records)
