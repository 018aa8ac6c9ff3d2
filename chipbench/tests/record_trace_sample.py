"""How ``data/trace_events.json`` was made: the first ``seconds`` of a
traced run's events, as ``trace_reduce.load`` returns them, with what
``trace_reduce`` makes of them (the test holds it to that).

    PYTHONPATH=. python3 chipbench/tests/record_trace_sample.py TRACE_DIR OUT.json [S]
"""

import json
import sys

from chipbench import trace_reduce
from chipbench.runners.steady_steps import SPANS


def main(trace_dir, out, seconds="0.14"):
    loaded = trace_reduce.load(trace_dir, SPANS)
    start = min(s for evs in loaded["devices"].values() for _, s, _ in evs)
    keep = lambda evs: [(n, round(s, 9), round(d, 9))  # noqa: E731
                        for n, s, d in evs if s + d <= start + float(seconds)]
    devices = {k: keep(v) for k, v in loaded["devices"].items()}
    doc = {"recorded": f"the first {seconds} s of {trace_dir}",
           "devices": devices, "spans": keep(loaded["spans"]),
           "expected": {"busy_s": trace_reduce.busy_seconds(devices),
                        "top_op": trace_reduce.op_table(devices)[0][0]}}
    with open(out, "w") as f:
        json.dump(doc, f, separators=(",", ":"))
    print(f"{sum(map(len, devices.values()))} device events, "
          f"{len(doc['spans'])} spans -> {out}")


if __name__ == "__main__":
    main(*sys.argv[1:])
