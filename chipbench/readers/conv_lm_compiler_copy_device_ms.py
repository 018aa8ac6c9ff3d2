"""Device milliseconds a step of the compiler's operations of kind ``copy``:
moves of a whole array (``copy``, the asynchronous pair ``copy-start`` /
``copy-done``, ``transpose``). One of the five parts of
``unnamed_device_ms`` (chipbench/compiler_trace.py: each nameless instant
goes to the innermost nameless operation running then).

``compiler_copy_device_ms`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""

from chipbench import compiler_trace


def read(records):
    return compiler_trace.kind_ms(records, "copy")
