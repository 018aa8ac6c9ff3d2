"""Device milliseconds a step under the scope ``moe``: the router's
product (read before attention), top-k, dispatch, the grouped products and
the combine of every expert layer, forward and backward
(chipbench/program_trace.py)."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "moe", "train_step")
