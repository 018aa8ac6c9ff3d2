"""Share of the device's busy time, in %, spent in operations named by any
of the program's scopes (metaopt_tpu/utils/trace.py ``SCOPES``). A guard:
a low reading means the names were lost (a compile cache warmed by a
program without them, or a step rewritten outside them), and the
``*_device_ms`` metrics then read low for that reason alone.

``scoped_device_share`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""

from chipbench import program_trace


def read(records):
    return program_trace.scoped_share(records)
