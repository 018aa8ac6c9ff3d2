"""``forward_again_device_ms`` in a state-space decoder's cell, read by that
metric's own reader: what rematerialised blocks make a second time in the backward pass.
An accepted metric's list of cells takes no new cell, so the cell reports
it under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("forward_again_device_ms").read(records)
