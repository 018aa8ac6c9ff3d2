"""Device milliseconds a step under the scope ``moe.shared``: the shared
experts of every routed layer, one gated feed-forward that every token
meets (``models/lm_layers.py::GatedFeedForward`` inside ``DroplessMoE``),
forward, second run and backward, with AdamW's update where XLA fuses it
into a weight-gradient matmul. A part of ``moe_device_ms``, as
``moe.experts`` is.

``moe_shared_device_ms`` under this name for ``nemotron-3-nano.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "moe.shared", "train_step")
