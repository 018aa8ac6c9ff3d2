"""``moe_experts_device_ms`` in a gated mixed-window MoE decoder's cell, read
by that metric's own reader: the scope ``moe.experts``: the grouped products
and the gating of the 32 held experts, 512 wide, each met by 256 tokens a
step on average. An accepted metric's list of cells takes no new cell, so
the cell reports it under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("moe_experts_device_ms").read(records)
