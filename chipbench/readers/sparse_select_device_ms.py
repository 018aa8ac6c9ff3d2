"""Device milliseconds a step under the scope ``attention.select``: the
exact top-k of each query's causal index scores (the threshold found by
counting, the ties' prefix sum where a row has one), the packing of the
selection into bits and the count of its pairs (ops/sparse_index.py)
(chipbench/program_trace.py)."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "attention.select",
                                         "train_step")
