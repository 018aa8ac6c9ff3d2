"""``trial_data_s`` in a pattern decoder's cell, read by that metric's own
reader: ``synthetic_lm``'s span ``trial.data``.
An accepted metric's list of cells takes no new cell, so the cell reports
it under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("trial_data_s").read(records)
