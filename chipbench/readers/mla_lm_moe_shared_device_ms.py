"""Device milliseconds a step under the scope ``moe.shared``:
the shared experts of every routed layer, one gated feed-forward 1536
wide that every token meets (``models/lm.py::GatedFeedForward`` inside
``DroplessMoE``), forward, second run and backward, with AdamW's update
where XLA fuses it into a weight-gradient matmul."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "moe.shared", "train_step")
