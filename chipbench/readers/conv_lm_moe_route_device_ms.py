"""Device milliseconds a step of the expert layers' routing: the scope
``moe`` less ``moe.experts`` less ``moe.shared`` (router, scores, top-k,
ordering, gather and combine), each a union of intervals
(chipbench/program_trace.py). The shared experts' branch is not routing;
in a cell whose expert layers have none, no operation is under
``moe.shared`` and the difference is ``moe`` less ``moe.experts``.

``moe_route_device_ms`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""

from chipbench import program_trace


def read(records):
    parts = [program_trace.scope_ms_a_step(records, scope, "train_step")
             for scope in ("moe", "moe.experts", "moe.shared")]
    if any(part is None for part in parts):
        return None
    return parts[0] - parts[1] - parts[2]
