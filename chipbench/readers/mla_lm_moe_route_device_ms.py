"""Device milliseconds a step of the expert layers' routing: the scope
``moe`` less ``moe.experts`` less ``moe.shared`` (router, scores, top-k,
ordering, gather and combine), each a union of intervals
(chipbench/program_trace.py). Its own difference: the accepted
``moe_route_device_ms`` takes ``moe`` less ``moe.experts`` and would count
the shared branch as routing."""

from chipbench import program_trace


def read(records):
    parts = [program_trace.scope_ms_a_step(records, scope, "train_step")
             for scope in ("moe", "moe.experts", "moe.shared")]
    if any(part is None for part in parts):
        return None
    return parts[0] - parts[1] - parts[2]
