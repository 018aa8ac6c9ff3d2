"""``readout_xent_device_ms`` in a gated mixed-window MoE decoder's cell, read
by that metric's own reader: the untied head's logits over the 12544 held
vocabulary rows and the cross-entropy over them, forward and backward. An
accepted metric's list of cells takes no new cell, so the cell reports it
under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("readout_xent_device_ms").read(records)
