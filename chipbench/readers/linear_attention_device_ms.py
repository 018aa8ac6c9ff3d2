"""Device milliseconds a step under the scope ``linear_attention``: a
linear-attention mixer whole, forward and backward: its seven projections
(with AdamW's update where XLA fuses it into a weight-gradient matmul), the
three short convolutions, the L2 norms, the gates, the scan, the gated RMS
norm and the output projection (chipbench/program_trace.py)."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "linear_attention",
                                         "train_step")
