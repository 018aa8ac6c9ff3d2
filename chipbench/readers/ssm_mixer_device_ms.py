"""Device milliseconds a step under the scope ``ssm``: a state-space mixer
(``models/lm.py::StateSpaceMixer``) whole, forward, a rematerialised
block's second run and backward: the input projection (2560 -> 2 x 5120),
the causal convolution and its SiLU, x_proj and dt_proj (float32 at
precision highest), the softplus, the selective scan (``ssm.core``), D x,
the gate and the output projection, with AdamW's update where XLA fuses
it into a weight-gradient matmul (chipbench/program_trace.py)."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "ssm", "train_step")
