"""Device milliseconds a step of the expert layers' routing: the scope
``moe`` less ``moe.experts`` (router, top-k, ordering, gather and
combine), each a union of intervals (chipbench/program_trace.py)."""

from chipbench import program_trace


def read(records):
    whole = program_trace.scope_ms_a_step(records, "moe", "train_step")
    experts = program_trace.scope_ms_a_step(records, "moe.experts",
                                            "train_step")
    if whole is None or experts is None:
        return None
    return whole - experts
