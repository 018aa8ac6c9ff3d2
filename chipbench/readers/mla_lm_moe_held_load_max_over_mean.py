"""``moe_held_load_max_over_mean`` in a latent-attention MoE decoder's cell, read by
that metric's own reader: the fullest held expert's items over the held experts' mean, of the routed layer where that is largest.
An accepted metric's list of cells takes no new cell, so the cell reports it under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("moe_held_load_max_over_mean").read(records)
