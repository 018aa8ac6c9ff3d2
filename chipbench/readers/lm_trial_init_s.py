"""``trial_init_s`` in a pattern decoder's cell, read by that metric's own
reader: ``init_sharded_lm``'s span ``trial.init`` less the compile of ``init_fn``.
An accepted metric's list of cells takes no new cell, so the cell reports
it under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("trial_init_s").read(records)
