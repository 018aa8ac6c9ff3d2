"""Share of the compiler's operations' time a step, in %, whose owner is not
None (``trace_device.owner_of``): the guard of the ``compiler_for_*``
metrics, as ``scoped_device_share`` is of the names. 100 less it is what is
nobody's: operations several layers read and several made."""

from chipbench import compiler_trace


def read(records):
    return compiler_trace.owned_share(records)
