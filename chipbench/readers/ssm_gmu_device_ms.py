"""Device milliseconds a step under the scope ``gmu``: a gated memory unit
(``models/lm.py::GatedMemoryUnit``), forward, second run and backward:
its input projection (2560 -> 5120), the gate on layer 16's scan output
(float32, 168 MB a row of 8192) and the output projection."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "gmu", "train_step")
