"""% of the window's (token, routed layer) choices in which the routing's
correction bias changed the chosen experts: the tokens whose chosen four
are not the four largest scores without the bias, from the program's own
count, summed on the device beside the expert counts and read once before
and after the window (models/lm.py::LMTrial.read_counts, ``bias_moved``).
0 = the bias never engaged; 100 = no token kept its plain top-k.

``mla_lm_moe_choice_bias_share`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""


def read(records):
    counts = records.get("choice_counts")
    if not counts or not counts["tokens"] or not counts["bias_moved"]:
        return None
    return 100.0 * sum(counts["bias_moved"]) \
        / (counts["tokens"] * len(counts["bias_moved"]))
