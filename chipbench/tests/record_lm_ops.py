"""How ``data/lm_ops.json`` was made: the device operations of the first two
steps of a traced run of ``smallthinker-21b.steady-8k`` as
``program_trace.load`` returns them (those of 20 us or more), with what the
cell's trace readers make of them (the test holds them to that).

    PYTHONPATH=. python3 chipbench/tests/record_lm_ops.py RUN_DIR OUT.json
"""

import json
import sys

from chipbench import program_trace
from chipbench.run import _reader

READERS = ("attention_core_device_ms", "moe_device_ms",
           "moe_experts_device_ms", "moe_route_device_ms",
           "flash_fwd_roofline", "flash_bwd_roofline", "moe_experts_roofline")


def main(run_dir, out, steps="2"):
    loaded = program_trace.load(run_dir)
    (plane, ops), = loaded["ops"].items()
    runs = [n for n in loaded["programs"][plane] if "train_step" in n]
    first = min(s for _, s, _ in ops)
    last = max(s + d for _, s, d in ops)
    until = first + (last - first) * int(steps) / len(runs)
    kept = [[p, round(s - first, 9), round(d, 9)] for p, s, d in ops
            if s + d <= until and d >= 20e-6]
    with open(run_dir + "/result.json") as f:
        records = json.load(f)["records"]
    cut = {"ops": {plane: [tuple(e) for e in kept]},
           "programs": {plane: runs[:int(steps)]}}
    program_trace.load = lambda directory: cut
    program_trace.run_dir = lambda: run_dir
    rec = {"trace": {"busy_s": 1.0, "window_s": 1.0},
           "device_kind": records["device_kind"],
           "kernel_work": records["kernel_work"]}
    doc = {"recorded": f"the first {steps} steps of {run_dir}, ops >= 20 us",
           "ops": kept, "programs": runs[:int(steps)],
           "kernel_work": records["kernel_work"],
           "expected": {name: _reader(name).read(rec) for name in READERS}}
    with open(out, "w") as f:
        json.dump(doc, f, separators=(",", ":"))
    print(f"{len(kept)} operations -> {out}: {doc['expected']}")


if __name__ == "__main__":
    main(*sys.argv[1:])
