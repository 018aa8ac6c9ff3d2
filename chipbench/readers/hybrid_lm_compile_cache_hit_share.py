"""``compile_cache_hit_share`` in a hybrid linear-attention decoder's cell,
read by that metric's own reader: the ``cache_hit`` of those ``compile``
spans. An accepted metric's list of cells takes no new cell, so the cell
reports it under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("compile_cache_hit_share").read(records)
