"""Device milliseconds a step of the compiler's operations of kind ``slice``:
moves of a part of an array (``slice``, ``slice-start`` / ``slice-done``,
``dynamic-slice``, ``dynamic-update-slice``, ``concatenate``, ``pad``). One
of the five parts of ``unnamed_device_ms`` (chipbench/compiler_trace.py:
each nameless instant goes to the innermost nameless operation running
then)."""

from chipbench import compiler_trace


def read(records):
    return compiler_trace.kind_ms(records, "slice")
