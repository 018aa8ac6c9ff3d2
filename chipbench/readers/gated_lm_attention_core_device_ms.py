"""Device milliseconds a step under the scope ``attention.core``: the
attention kernels of both kinds of layer, ``flash_fwd`` (once a step a
layer: a rematerialised block keeps its ``out`` and ``lse``) and
``flash_bwd`` at 128 wide, 64 query heads under the window of 512 on the
three window layers and 48 under the causal mask on the two full layers, 8
K/V heads under both, and the backward's delta
(chipbench/program_trace.py)."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "attention.core", "train_step")
