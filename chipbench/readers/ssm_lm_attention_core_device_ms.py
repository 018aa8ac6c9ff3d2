"""Device milliseconds a step under the scope ``attention.core``: the
attention kernels of the differential layers, ``flash_fwd`` (twice a
step a layer, q_1 on k_1 and q_2 on k_2: a rematerialised block keeps
their ``out`` and ``lse``) and ``flash_bwd`` at q.k 64 and v 128 wide,
20 query heads on 10 K/V heads a call, under the window of 512 and
without, and the backward's delta (chipbench/program_trace.py)."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "attention.core", "train_step")
