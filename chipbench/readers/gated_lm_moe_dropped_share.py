"""``moe_dropped_share`` in a gated mixed-window MoE decoder's cell, read by
that metric's own reader: the items the routed layers dropped over the
window: 0 for a dropless layer. An accepted metric's list of cells takes no
new cell, so the cell reports it under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("moe_dropped_share").read(records)
