"""Seconds of set-up in ``init_sharded`` that are its own: the program's
span ``trial.init`` less what its children cover (the ``compile`` of
``init_fn``, which ``program_load_s`` counts)."""

from chipbench import program_trace


def read(records):
    spans = program_trace.one_setup()
    if spans is None:
        return None
    init = spans["trial.init"][0]
    children = [s for recs in spans.values() for s in recs
                if s["parent"] == init["id"]]
    return program_trace.program_trace().self_ns(
        init, {init["id"]: children}) * 1e-9
