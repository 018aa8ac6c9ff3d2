"""Seconds of set-up spent getting the program's own jitted functions
(``init_fn``, ``train_step``) ready to run: their ``compile`` spans, from
the start of the retrace to the executable, compiled or found in the
cache."""

from chipbench import program_trace


def read(records):
    compiles = program_trace.compiles_of(("init_fn", "train_step"))
    if compiles is None:
        return None
    return sum(map(program_trace.program_trace().seconds, compiles))
