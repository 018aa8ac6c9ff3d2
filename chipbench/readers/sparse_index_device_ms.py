"""Device milliseconds a step under the scope ``attention.index``: the
indexer's three projections, its layer norm and rotary, and the index
scores of every (query, key) pair a block of queries is scored against
(ops/sparse_index.py), forward only: no gradient reaches the indexer, and
a rematerialised block keeps the selection (chipbench/program_trace.py)."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "attention.index",
                                         "train_step")
