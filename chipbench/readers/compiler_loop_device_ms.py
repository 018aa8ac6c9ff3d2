"""Device milliseconds a step of the compiler's operations of kind ``loop``:
control and its bookkeeping (``while``, ``conditional``, ``call``,
``tuple``, ``get-tuple-element``), less what runs inside. One of the five
parts of ``unnamed_device_ms`` (chipbench/compiler_trace.py: each nameless
instant goes to the innermost nameless operation running then)."""

from chipbench import compiler_trace


def read(records):
    return compiler_trace.kind_ms(records, "loop")
