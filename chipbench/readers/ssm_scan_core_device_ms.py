"""Device milliseconds a step under the scope ``ssm.core``: the selective
scan (ops/selective_scan.py), forward and backward: the Pallas calls
``selective_scan_fwd`` (once a step a Mamba layer: a rematerialised block
keeps its output and the chunks' entering states) and
``selective_scan_bwd``, and what lays their operands out (channels in
tiles of 8 x 128, B and C joined for SMEM) and sums dA, dB and dC over
rows and tiles where XLA does not fuse it elsewhere."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "ssm.core", "train_step")
