"""Device milliseconds a step under the scope ``attention.core``:
the attention kernels of the latent layers, ``flash_fwd`` (once a step a
layer: a rematerialised block keeps its ``out`` and ``lse``) and
``flash_bwd`` at q.k 192 wide (128 + the 64 of the one shared rotary key)
and v 128 wide, and the backward's delta (chipbench/program_trace.py)."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "attention.core", "train_step")
