"""How ``data/layer_ops.json`` was made: the device operations of the first
step of a traced run of ``smallthinker-21b.steady-8k`` as
``program_trace.load`` returns them (those of 10 us or more; every
``op_name`` once, in ``paths``), with what the readers of the step's
partition make of them (the test holds them to that).

    PYTHONPATH=. python3 chipbench/tests/record_layer_ops.py RUN_DIR OUT.json
"""

import json
import sys

import record_cell_step
from chipbench import program_trace
from chipbench.run import _reader

READERS = ("embed_device_ms", "attention_proj_device_ms", "trunk_device_ms",
           "unnamed_device_ms", "forward_again_device_ms",
           "backward_device_ms", "attention_core_device_ms",
           "moe_device_ms", "readout_xent_device_ms",
           "optimizer_device_ms", "scoped_device_share")


def main(run_dir, out, steps="1"):
    loaded = program_trace.load(run_dir)
    plane, = loaded["ops"]
    paths, ops, programs = record_cell_step.cut(loaded, int(steps))
    cut = {"ops": {plane: [(paths[p], s, d) for p, s, d in ops]},
           "programs": {plane: programs}}
    program_trace.load = lambda directory: cut
    program_trace.run_dir = lambda: run_dir
    rec = {"trace": {"busy_s": 1.0, "window_s": 1.0}}
    doc = {"recorded": f"the first {steps} step(s) of {run_dir}, ops >= 10 us",
           "paths": paths, "ops": ops, "programs": programs,
           "expected": {name: _reader(name).read(rec) for name in READERS}}
    with open(out, "w") as f:
        json.dump(doc, f, separators=(",", ":"))
    print(f"{len(ops)} operations, {len(paths)} paths -> {out}: "
          f"{doc['expected']}")


if __name__ == "__main__":
    main(*sys.argv[1:])
