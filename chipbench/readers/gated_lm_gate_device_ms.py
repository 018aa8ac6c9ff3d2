"""Device milliseconds a step under the scope ``attention.gate``: the gate
on attention's output (``models/lm_layers.py::GroupedAttention`` with
``spec.gate``): its projection (2048 -> 48 or 64, float32 at precision
highest), the sigmoid, the product a head over (8192, heads, 128) and
their backward, in a rematerialised block's second run too. A part of
``attention_proj_device_ms``, as ``moe.experts`` is of ``moe``;
``None`` on a program without the scope."""

from chipbench import program_trace


def read(records):
    trace = program_trace.program_trace()
    if trace is None or "attention.gate" not in trace.SCOPES:
        return None
    return program_trace.scope_ms_a_step(records, "attention.gate", "train_step")
