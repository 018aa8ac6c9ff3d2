"""Device milliseconds a step under the scope ``attention.core`` in a
cell whose attention runs over selected keys: the Pallas kernels
``sparse_fwd`` (once a step a layer: a rematerialised block keeps ``out``
and ``lse``) and ``sparse_bwd``, the backward's delta and the sum of a K/V
head's gradients over its query heads (chipbench/program_trace.py)."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "attention.core",
                                         "train_step")
