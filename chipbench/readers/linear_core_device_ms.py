"""Device milliseconds a step under the scope ``linear_attention.core``:
the gated delta rule's chunked scan (ops/linear_attention.py), forward and
backward: the Pallas calls ``linear_scan_fwd`` (once a step a linear layer:
a rematerialised block keeps its output and the chunks' entering states)
and ``linear_scan_bwd``, and what prepares their operands (heads first,
the chunks' running log decays) where XLA does not fuse it elsewhere."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "linear_attention.core",
                                         "train_step")
