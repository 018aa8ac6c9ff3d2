"""Device milliseconds a step under the scope ``short_conv``: a gated short
convolution as a mixer (``models/lm_layers.py::ShortConvMixer``) whole,
forward, a rematerialised block's second run and backward: the input
projection (2048 -> 6144, the thirds B, C, X), the element-wise core
(``short_conv.core``) and the output projection, with AdamW's update where
XLA fuses it into a weight-gradient matmul (chipbench/program_trace.py).
``None`` on a program without the scope."""

from chipbench import program_trace


def read(records):
    trace = program_trace.program_trace()
    if trace is None or "short_conv" not in trace.SCOPES:
        return None
    return program_trace.scope_ms_a_step(records, "short_conv", "train_step")
