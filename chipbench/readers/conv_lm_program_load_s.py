"""Seconds of set-up spent getting the program's own jitted functions
(``init_fn``, ``train_step``) ready to run: their ``compile`` spans, from
the start of the retrace to the executable, compiled or found in the
cache.

``program_load_s`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""

from chipbench import program_trace


def read(records):
    compiles = program_trace.compiles_of(("init_fn", "train_step"))
    if compiles is None:
        return None
    return sum(map(program_trace.program_trace().seconds, compiles))
