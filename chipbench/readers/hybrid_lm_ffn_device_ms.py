"""Device milliseconds a step under the scope ``ffn``: the dense gated
feed-forward of every layer (``models/lm.py::GatedFeedForward``), forward,
its second run under remat and backward, with AdamW's update where XLA
fuses it into a weight-gradient matmul. No accepted cell runs this layer;
in a hybrid decoder's it is the largest single share of the step."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "ffn", "train_step")
