"""The fullest held expert's items over the mean of the held experts,
over the window, of the layer where that ratio is largest: from the
program's own counts (models/lm.py::LMTrial.read_counts).

``moe_held_load_max_over_mean`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""


def read(records):
    counts = records.get("moe_counts")
    layers = counts and [row for row in counts["items"] if sum(row)]
    if not layers:
        return None
    return max(max(row) * len(row) / sum(row) for row in layers)
