"""Device milliseconds a step of the operations in direction
``forward.again`` (``trace.direction``: ``rematted_computation`` on the
``op_name``), every layer: what rematerialised blocks make a second time
in the backward pass. Work made twice; 0 would be a step that keeps
everything. A second-run product that XLA folds into a backward fusion
reads as backward (a fusion carries its root's name).

``forward_again_device_ms`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""

from chipbench import layer_trace


def read(records):
    return layer_trace.direction_ms(records, "forward.again")
