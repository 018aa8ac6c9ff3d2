"""Share, in %, of the compile requests for the program's own jitted
functions (``init_fn``, ``train_step``) that the persistent cache served:
100 in a warm checkout, 0 in its first run.

``compile_cache_hit_share`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""

from chipbench import program_trace


def read(records):
    compiles = program_trace.compiles_of(("init_fn", "train_step"))
    if compiles is None:
        return None
    return 100.0 * sum(c["attrs"]["cache_hit"] for c in compiles) \
        / len(compiles)
