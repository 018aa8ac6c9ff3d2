"""Device milliseconds a step under the scope ``readout_xent`` (the tied
readout's logits and the cross-entropy over them, forward and backward):
union of the traced slice's operations whose ``op_name`` has that scope,
over its steps (chipbench/program_trace.py). A fusion carries its root's
name: one that folds the readout's gradient into the optimizer's update
counts where XLA named it.

``readout_xent_device_ms`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "readout_xent",
                                         "train_step")
