"""Device milliseconds a step under the scope ``moe.experts``: the
grouped matrix products of every expert layer with the gating between
them, forward, second run and backward (chipbench/program_trace.py)."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "moe.experts",
                                         "train_step")
