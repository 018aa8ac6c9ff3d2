"""How ``data/layer_ops.json`` was made: the device operations of the first
step of a traced run of ``smallthinker-21b.steady-8k`` as
``program_trace.load`` returns them (those of 10 us or more; every
``op_name`` once, in ``paths``), with what the readers of the step's
partition make of them (the test holds them to that).

    PYTHONPATH=. python3 chipbench/tests/record_layer_ops.py RUN_DIR OUT.json
"""

import json
import sys

from chipbench import program_trace
from chipbench.run import _reader

READERS = ("embed_device_ms", "attention_proj_device_ms", "trunk_device_ms",
           "unnamed_device_ms", "forward_again_device_ms",
           "backward_device_ms", "lm_attention_core_device_ms",
           "moe_device_ms", "lm_readout_xent_device_ms",
           "lm_optimizer_device_ms", "lm_scoped_device_share")


def main(run_dir, out, steps="1"):
    loaded = program_trace.load(run_dir)
    (plane, ops), = loaded["ops"].items()
    runs = [n for n in loaded["programs"][plane] if "train_step" in n]
    first = min(s for _, s, _ in ops)
    last = max(s + d for _, s, d in ops)
    until = first + (last - first) * int(steps) / len(runs)
    kept = [(p, round(s - first, 9), round(d, 9)) for p, s, d in ops
            if s + d <= until and d >= 10e-6]
    paths = sorted({p for p, _, _ in kept})
    index = {p: i for i, p in enumerate(paths)}
    cut = {"ops": {plane: kept}, "programs": {plane: runs[:int(steps)]}}
    program_trace.load = lambda directory: cut
    program_trace.run_dir = lambda: run_dir
    rec = {"trace": {"busy_s": 1.0, "window_s": 1.0}}
    doc = {"recorded": f"the first {steps} step(s) of {run_dir}, ops >= 10 us",
           "paths": paths, "ops": [[index[p], s, d] for p, s, d in kept],
           "programs": runs[:int(steps)],
           "expected": {name: _reader(name).read(rec) for name in READERS}}
    with open(out, "w") as f:
        json.dump(doc, f, separators=(",", ":"))
    print(f"{len(kept)} operations, {len(paths)} paths -> {out}: "
          f"{doc['expected']}")


if __name__ == "__main__":
    main(*sys.argv[1:])
