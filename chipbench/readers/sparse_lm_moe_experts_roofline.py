"""``moe_experts_roofline`` in a cell whose attention runs over selected keys,
read by that metric's own reader: the grouped products' share of their
roofline over the items the window routed to held experts. An accepted
metric's list of cells takes no new cell, so the cell reports it under a
name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("moe_experts_roofline").read(records)
