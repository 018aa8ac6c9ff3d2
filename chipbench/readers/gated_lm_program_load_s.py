"""``program_load_s`` in a gated mixed-window MoE decoder's cell, read by that
metric's own reader: the ``compile`` spans of ``init_fn`` and of
``LMTrial``'s ``train_step``. An accepted metric's list of cells takes no
new cell, so the cell reports it under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("program_load_s").read(records)
