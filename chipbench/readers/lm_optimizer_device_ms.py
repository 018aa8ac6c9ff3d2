"""``optimizer_device_ms`` in a pattern decoder's cell, read by that metric's own
reader: AdamW over the parameters whose gradient no matmul of XLA's makes
(the experts' through megablox, the embedding's through a scatter).
An accepted metric's list of cells takes no new cell, so the cell reports
it under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("optimizer_device_ms").read(records)
