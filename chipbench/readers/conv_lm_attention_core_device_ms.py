"""Device milliseconds a step under the scope ``attention.core``, whatever
attention a cell's layers run there (the ops/attention.py call: the
dense-mask kernels of the 2017 cell; ``flash_fwd``, once a step a layer
where a rematerialised block keeps ``out`` and ``lse``, and ``flash_bwd``
under a structural mask, at equal or unequal q.k and v widths;
``sparse_fwd`` / ``sparse_bwd`` over selected keys; the backward's delta
and the sum of a K/V head's gradients over its query heads where they are
operations of their own): union of the traced slice's operations whose
``op_name`` has that scope, over its steps (chipbench/program_trace.py).

``attention_core_device_ms`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "attention.core",
                                         "train_step")
