"""Device milliseconds a step under the scope ``short_conv.core``: the
mixer's element-wise core ``C * conv3(B * X)`` (ops/short_conv.py), forward
(once more where a rematerialised block makes it again) and backward: the
Pallas calls ``short_conv_fwd`` and ``short_conv_bwd`` on the product where
the input projection left it. ``None`` on a program without the scope."""

from chipbench import program_trace


def read(records):
    trace = program_trace.program_trace()
    if trace is None or "short_conv.core" not in trace.SCOPES:
        return None
    return program_trace.scope_ms_a_step(records, "short_conv.core",
                                         "train_step")
