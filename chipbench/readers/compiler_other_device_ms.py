"""Device milliseconds a step of the compiler's operations of kind ``other``:
operations of no other kind: the guard of ``trace.compiler_kind``'s table (a
high reading: an opcode it should learn). One of the five parts of
``unnamed_device_ms`` (chipbench/compiler_trace.py: each nameless instant
goes to the innermost nameless operation running then)."""

from chipbench import compiler_trace


def read(records):
    return compiler_trace.kind_ms(records, "other")
