"""Device milliseconds a step under the scope ``optimizer`` (``tx.update``
and ``apply_updates`` of ``make_train_step``): union of the traced slice's
operations whose ``op_name`` has that scope, over its steps
(chipbench/program_trace.py).

``optimizer_device_ms`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "optimizer", "train_step")
