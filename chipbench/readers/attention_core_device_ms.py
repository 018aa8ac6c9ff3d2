"""Device milliseconds a step under the scope ``attention.core`` (the
ops/attention.py call: chunked scans or the Pallas kernels, forward and
backward): union of the traced slice's operations whose ``op_name`` has
that scope, over its steps (chipbench/program_trace.py)."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "attention.core",
                                         "train_step")
