"""Share, in %, of the compile requests for the program's own jitted
functions (``init_fn``, ``train_step``) that the persistent cache served:
100 in a warm checkout, 0 in its first run."""

from chipbench import program_trace


def read(records):
    compiles = program_trace.compiles_of(("init_fn", "train_step"))
    if compiles is None:
        return None
    return 100.0 * sum(c["attrs"]["cache_hit"] for c in compiles) \
        / len(compiles)
