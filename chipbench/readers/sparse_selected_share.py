"""% of the causal (query, key) pairs that the layers' indexers selected
over the window, from the program's own counts, summed on the device and
read once before and after the window (models/lm.py::LMTrial.read_counts):
(2048 x 2049 / 2 + (L - 2048) x 2048) / (L (L + 1) / 2) by arithmetic,
23.4 % at L 16 384; 100 % means the selection never engaged."""


def read(records):
    counts = records.get("selection_counts")
    causal = counts and sum(counts["causal_pairs"])
    if not causal:
        return None
    return 100.0 * sum(counts["selected_pairs"]) / causal
