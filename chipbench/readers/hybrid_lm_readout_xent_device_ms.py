"""``readout_xent_device_ms`` in a hybrid linear-attention decoder's cell,
read by that metric's own reader: the scope ``readout_xent``: the untied
head over the held rows, its loss and their backward. An accepted metric's
list of cells takes no new cell, so the cell reports it under a name of its
own."""

from chipbench.run import _reader


def read(records):
    return _reader("readout_xent_device_ms").read(records)
