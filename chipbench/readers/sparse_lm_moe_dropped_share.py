"""``moe_dropped_share`` in a cell whose attention runs over selected keys,
read by that metric's own reader: the share of items the expert layers
dropped, 0 for a dropless layer. An accepted metric's list of cells takes no
new cell, so the cell reports it under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("moe_dropped_share").read(records)
