"""Seconds of set-up the host spent making the trial's data: the program's
span ``trial.data`` (``synthetic_seq2seq``, ``synthetic_lm``) in this
process's ring."""

from chipbench import program_trace


def read(records):
    spans = program_trace.one_setup()
    if spans is None:
        return None
    return program_trace.program_trace().seconds(spans["trial.data"][0])
