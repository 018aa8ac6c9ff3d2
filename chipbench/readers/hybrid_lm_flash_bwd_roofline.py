"""``flash_bwd_roofline`` in a hybrid linear-attention decoder's cell, read
by that metric's own reader: ``flash_bwd``'s calls against the causal pairs
of the full layers. An accepted metric's list of cells takes no new cell, so
the cell reports it under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("flash_bwd_roofline").read(records)
