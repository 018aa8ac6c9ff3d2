"""Share of the compiler's operations' time a step, in %, whose owner is not
None (``trace_device.owner_of``): the guard of the ``compiler_for_*``
metrics, as ``scoped_device_share`` is of the names. 100 less it is what is
nobody's: operations several layers read and several made.

``compiler_owned_share`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""

from chipbench import compiler_trace


def read(records):
    return compiler_trace.owned_share(records)
