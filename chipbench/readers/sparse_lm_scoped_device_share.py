"""``scoped_device_share`` in a cell whose attention runs over selected keys,
read by that metric's own reader: the share of busy time under any of the
program's scopes, ``attention.index`` and ``attention.select`` included. An
accepted metric's list of cells takes no new cell, so the cell reports it
under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("scoped_device_share").read(records)
