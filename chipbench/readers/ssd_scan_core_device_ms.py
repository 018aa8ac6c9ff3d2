"""Device milliseconds a step under the scope ``ssd.core``: the scalar
decay rule's chunked scan (ops/linear_attention.py::scalar_decay_rule),
forward and backward: the Pallas calls ``ssd_scan_fwd`` (once a step a
Mamba-2 block: a rematerialised block keeps its output and the chunks'
entering states) and ``ssd_scan_bwd``, and what prepares their operands
(groups first, the chunks' running log decays) where XLA does not fuse it
elsewhere. ``None`` on a program without the scope."""

from chipbench import program_trace


def read(records):
    trace = program_trace.program_trace()
    if trace is None or "ssd.core" not in trace.SCOPES:
        return None
    return program_trace.scope_ms_a_step(records, "ssd.core", "train_step")
