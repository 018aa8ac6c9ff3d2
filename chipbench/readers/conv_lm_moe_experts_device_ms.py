"""Device milliseconds a step under the scope ``moe.experts``: the
grouped matrix products of every expert layer with the gating between
them, forward, second run and backward (chipbench/program_trace.py).

``moe_experts_device_ms`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "moe.experts",
                                         "train_step")
