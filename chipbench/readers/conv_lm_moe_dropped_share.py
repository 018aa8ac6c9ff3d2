"""% of the (token, choice) items routed to held experts over the window
that the expert layers dropped, from the program's own counts
(models/lm.py::LMTrial.read_counts): 0 for a dropless layer.

``moe_dropped_share`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""


def read(records):
    counts = records.get("moe_counts")
    routed = counts and sum(map(sum, counts["items"]))
    if not routed:
        return None
    dropped = sum(counts["dropped"])
    return 100.0 * dropped / (routed + dropped)
