"""How ``data/step_<cell>.json`` were made: one ``--trace 1`` run of a cell on
the chip, and in the same process, before the line is printed, what every
per-layer reader of the cell is handed, cut to what a test can hold:

- ``records``: the runner kind's records (less the output check's and the
  spans a step: no reader reads them; of the trace, ``busy_s`` and
  ``window_s``);
- ``paths``, ``ops``, ``programs``: the device operations of the traced
  slice's first step as ``program_trace.load`` returns them (those of 10 us
  or more; every ``op_name`` once, in ``paths``);
- ``ring``: this process's ``trial.setup``, ``trial.data``, ``trial.init``
  and ``compile`` spans (metaopt_tpu/utils/trace.py);
- ``expected``: what each metric that lists the cell reads from exactly
  those three (a device reader from the cut step, not the whole slice).

    python3 chipbench/tests/record_cell_step.py OUT_DIR \
        --workload W --seed N --seconds S --trace 1

``record`` takes the entries and the way to a reader as arguments: PR 48
recorded the seven files with PR 47's 128 entries and reader files in their
place, so ``expected`` there is keyed by PR 47's names and
``test_readers.py`` holds the folded entries to it.
"""

import time

T_START = time.time()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RING = ("trial.setup", "trial.data", "trial.init", "compile")
LEFT_OUT = ("check", "step_spans_s", "dispatch_s")


def cut(loaded, steps=1, least_s=10e-6):
    """(paths, ops by path index, the programs' runs) of the first
    ``steps`` runs of ``train_step`` in a loaded trace."""
    (plane, ops), = loaded["ops"].items()
    runs = [n for n in loaded["programs"][plane] if "train_step" in n]
    first = min(s for _, s, _ in ops)
    last = max(s + d for _, s, d in ops)
    until = first + (last - first) * steps / len(runs)
    kept = [(p, round(s - first, 9), round(d, 9)) for p, s, d in ops
            if s + d <= until and d >= least_s]
    paths = sorted({p for p, _, _ in kept})
    index = {p: i for i, p in enumerate(paths)}
    return paths, [[index[p], s, d] for p, s, d in kept], runs[:steps]


def hand_out(doc, monkey_setattr):
    """Put a recorded file in the place of this run's trace and ring:
    ``monkey_setattr(object, name, value)`` is pytest's, or ``setattr``."""
    from chipbench import program_trace
    from metaopt_tpu.utils import trace

    ops = [(doc["paths"][p], s, d) for p, s, d in doc["ops"]]
    loaded = {"ops": {"/device:TPU:0": ops},
              "programs": {"/device:TPU:0": doc["programs"]}}
    monkey_setattr(program_trace, "load", lambda directory: loaded)
    monkey_setattr(program_trace, "run_dir", lambda: "recorded")
    monkey_setattr(trace, "_ring", list(doc["ring"]))


def record(cell, records, per_layer, reader, out, said=""):
    """Write ``out``: the cut of this process's traced run of ``cell`` and
    what ``reader(name).read`` makes of it for every entry of ``per_layer``
    that lists the cell."""
    from chipbench import program_trace
    from metaopt_tpu.utils import trace

    paths, ops, programs = cut(program_trace.load(program_trace.run_dir()))
    kept = {k: v for k, v in records.items() if k not in LEFT_OUT}
    kept["trace"] = {k: records["trace"][k] for k in ("busy_s", "window_s")}
    doc = {"recorded": f"the first step of a traced run of {cell} "
                       f"({' '.join(sys.argv[1:])}), ops >= 10 us{said}",
           "cell": cell, "records": kept, "paths": paths, "ops": ops,
           "programs": programs,
           "ring": [r for r in trace.spans() if r["name"] in RING]}
    real = (program_trace.load, program_trace.run_dir, trace._ring)
    hand_out(doc, setattr)
    try:
        doc["expected"] = {
            m["name"]: reader(m["name"]).read(kept) for m in per_layer
            if "workloads" not in m or cell in m["workloads"]}
    finally:
        program_trace.load, program_trace.run_dir, trace._ring = real
    with open(out, "w") as f:
        json.dump(doc, f, separators=(",", ":"), default=str)
    print(f"{len(ops)} operations, {len(paths)} paths, {len(doc['ring'])} "
          f"spans -> {out}", file=sys.stderr, flush=True)


def main():
    from chipbench import run

    out_dir = sys.argv.pop(1)
    read_line = run.per_layer_metrics

    def and_record(bench, cell, records):
        record(cell, records, bench["per_layer"], run._reader,
               os.path.join(out_dir, f"step_{cell}.json"))
        return read_line(bench, cell, records)

    run.per_layer_metrics = and_record
    return run.main(T_START)


if __name__ == "__main__":
    raise SystemExit(main())
