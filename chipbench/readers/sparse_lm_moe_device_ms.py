"""``moe_device_ms`` in a cell whose attention runs over selected keys, read by
that metric's own reader: the scope ``moe``: the router's product (read
after attention here), top-k, dispatch, the grouped products and the
combine. An accepted metric's list of cells takes no new cell, so the cell
reports it under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("moe_device_ms").read(records)
