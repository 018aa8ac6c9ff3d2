"""Device milliseconds a step in which the device was busy and no
operation ran that carries a name of the program's (``trace.layer_of`` is
None for it): what the compiler made: copies, slices, layout changes,
loops' bookkeeping. With ``trace.SCOPES`` closed over the step's source
(tests/unit/test_trace_layers.py) nothing the program wrote is in here but
what XLA fused under a nameless root.

``unnamed_device_ms`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""

from chipbench import layer_trace


def read(records):
    return layer_trace.unnamed_ms(records)
