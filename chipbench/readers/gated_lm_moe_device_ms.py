"""``moe_device_ms`` in a gated mixed-window MoE decoder's cell, read by that
metric's own reader: the scope ``moe``: router, choice, dispatch, grouped
products, combine and the shared expert of the four routed layers. An
accepted metric's list of cells takes no new cell, so the cell reports it
under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("moe_device_ms").read(records)
