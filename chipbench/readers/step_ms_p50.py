"""Median time of one train step, in ms: the host clock between two steps'
completions (each call of a ``steady_steps`` loop waits for the step
before the one it dispatched)."""

import statistics


def read(records):
    if not records.get("step_s"):
        return None
    return statistics.median(records["step_s"]) * 1e3
