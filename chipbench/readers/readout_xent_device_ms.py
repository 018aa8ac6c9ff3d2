"""Device milliseconds a step under the scope ``readout_xent`` (the tied
readout's logits and the cross-entropy over them, forward and backward):
union of the traced slice's operations whose ``op_name`` has that scope,
over its steps (chipbench/program_trace.py). A fusion carries its root's
name: one that folds the readout's gradient into the optimizer's update
counts where XLA named it."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "readout_xent",
                                         "train_step")
