"""Device milliseconds a step under the scope ``ssd``: a Mamba-2 mixer
(``models/lm_layers.py::ScalarDecayMixer``) whole, forward, a rematerialised
block's second run and backward: the input projection (2688 -> 10240, and
dt's 64 columns float32 at precision highest), the causal convolution with
its bias and SiLU, the softplus, the chunked scan (``ssd.core``), D x, the
norm gated over groups of 512 and the output projection, with AdamW's
update where XLA fuses it into a weight-gradient matmul
(chipbench/program_trace.py). ``None`` on a program without the scope."""

from chipbench import program_trace


def read(records):
    trace = program_trace.program_trace()
    if trace is None or "ssd" not in trace.SCOPES:
        return None
    return program_trace.scope_ms_a_step(records, "ssd", "train_step")
