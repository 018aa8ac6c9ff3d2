"""Device milliseconds a step of the compiler's operations made for the trunk
between the layers (``norm``, ``residual``): those whose owner by
``trace_device.owner_of`` (the layer their users in the step's own HLO agree
on, else their operands' producers) is of that group. Left out in a cell
whose step runs no such layer, and on a file that does not hold the step's
program."""

from chipbench import compiler_trace


def read(records):
    return compiler_trace.owner_ms(records, "trunk")
