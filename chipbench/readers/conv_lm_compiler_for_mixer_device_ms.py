"""Device milliseconds a step of the compiler's operations made for a mixer
that is not attention, the gated short convolution (``short_conv``) among
them: those whose owner by ``trace_device.owner_of`` (the layer their users
in the step's own HLO agree on, else their operands' producers) is of that
group: whether the new mixer's operands are copied around it.
``compiler_for_mixer_device_ms``'s rule with ``short_conv`` in the group
(chipbench/conv_kernel_trace.py; ``compiler_trace.GROUPS`` names the four
older mixers and is not this cell's PR's to edit). Left out in a cell whose
step runs no such layer, and on a file that does not hold the step's
program."""

from chipbench import conv_kernel_trace


def read(records):
    return conv_kernel_trace.mixer_owner_ms(records)
