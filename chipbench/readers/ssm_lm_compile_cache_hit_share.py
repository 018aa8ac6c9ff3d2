"""``compile_cache_hit_share`` in a state-space decoder's cell, read by that
metric's own reader: of ``init_fn`` and of ``LMTrial``'s ``train_step``.
An accepted metric's list of cells takes no new cell, so the cell reports
it under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("compile_cache_hit_share").read(records)
