#!/usr/bin/env python
"""Coordination-plane scale check: a 1024-trial ASHA sweep per backend.

The BASELINE north star is a 1024-trial ASHA ResNet sweep on a v4-32; the
chips do the training, but the FRAMEWORK's own ceiling is the coordination
plane — produce/reserve/report round-trips through the ledger. This
driver runs the full workon loop (real Producer, real ASHA, real backend)
with an instant in-process objective, so the measured trials/hour is the
pure coordination throughput: the upper bound the framework imposes on any
sweep, and the number that must dwarf per-trial training time.

    python benchmarks/sweep_scale.py [--backends memory file native coord]
                                     [--max-trials 1024] [--save]

Emits one JSON line per backend:
  {"backend": ..., "trials": N, "wall_s": ..., "coord_trials_per_hour": ...,
   "reserve_p50_ms": ..., "produce_ms_per_cycle": ..., "best": ...}
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


SPACE = {
    "lr": "loguniform(1e-5, 1e-1)",
    "mom": "uniform(0, 1)",
    "wd": "loguniform(1e-6, 1e-2)",
    "epochs": "fidelity(1, 16, base=4)",
}


def objective(params):
    # instant surrogate for ResNet/CIFAR validation error: smooth in the
    # hparams, improves with budget — exercises ASHA's promotion logic
    import math

    lr, mom, ep = params["lr"], params["mom"], params["epochs"]
    return (
        (math.log10(lr) + 2.5) ** 2 * 0.1
        + (mom - 0.9) ** 2
        + 0.5 / ep
    )


def run_backend(kind: str, root: str, max_trials: int) -> dict:
    from metaopt_tpu.executor import InProcessExecutor
    from metaopt_tpu.ledger import Experiment
    from metaopt_tpu.ledger.backends import make_ledger
    from metaopt_tpu.space import build_space
    from metaopt_tpu.worker import workon

    server = None
    if kind == "memory":
        ledger = make_ledger({"type": "memory"})
    elif kind == "file":
        ledger = make_ledger({"type": "file", "path": os.path.join(root, "f")})
    elif kind == "native":
        ledger = make_ledger({"type": "native", "path": os.path.join(root, "n")})
    elif kind == "coord":
        from metaopt_tpu.coord import CoordLedgerClient, CoordServer

        server = CoordServer()
        server.start()
        host, port = server.address
        ledger = CoordLedgerClient(host=host, port=port)
    else:
        raise ValueError(kind)

    reserve_ms = []

    class TimingLedger:
        """Transparent proxy timing the hot reserve path (produce latency
        comes from the Producer's own suggest_s/cycles aggregates)."""

        def __init__(self, inner):
            self._inner = inner

        def reserve(self, *a, **kw):
            t0 = time.perf_counter()
            out = self._inner.reserve(*a, **kw)
            reserve_ms.append((time.perf_counter() - t0) * 1000)
            return out

        def __getattr__(self, name):
            return getattr(self._inner, name)

    try:
        exp = Experiment(
            f"scale-{kind}",
            TimingLedger(ledger),
            space=build_space(SPACE),
            algorithm={"asha": {"seed": 0, "reduction_factor": 4}},
            max_trials=max_trials,
            pool_size=16,
        ).configure()

        t0 = time.perf_counter()
        stats = workon(
            exp, InProcessExecutor(objective), worker_id="scale-w0",
            max_idle_cycles=2000,
        )
        wall = time.perf_counter() - t0
        produce_s = stats.producer_timings.get("suggest_s", 0.0)
        cycles = max(1, stats.producer_timings.get("cycles", 1))
        completed = exp.count("completed")
        return {
            "backend": kind,
            "trials": completed,
            "wall_s": round(wall, 2),
            "coord_trials_per_hour": round(completed / wall * 3600),
            "reserve_p50_ms": round(statistics.median(reserve_ms), 3)
            if reserve_ms else None,
            "produce_ms_per_cycle": round(produce_s * 1000 / cycles, 3),
            "best": round(exp.stats["best"]["objective"], 4),
        }
    finally:
        # a failing backend must not leave the coordinator's threads
        # running to skew the remaining backends' numbers
        if server is not None:
            server.stop()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backends", nargs="*",
                    default=["memory", "file", "native", "coord"])
    ap.add_argument("--max-trials", type=int, default=1024)
    ap.add_argument("--save", action="store_true")
    args = ap.parse_args()

    from metaopt_tpu.utils.provenance import provenance

    rows = []
    with tempfile.TemporaryDirectory(prefix="mtpu_scale_") as root:
        for kind in args.backends:
            try:
                row = run_backend(kind, root, args.max_trials)
            except Exception as err:  # a missing toolchain must not sink all
                row = {"backend": kind, "error": f"{type(err).__name__}: {err}"}
            # rows self-describe (the two coord rows 100x apart in the r4
            # record straddled an optimization commit, undetectably)
            row.update(provenance())
            print(json.dumps(row), flush=True)
            rows.append(row)
    if args.save:
        stamp = time.strftime("%Y-%m-%d")
        path = os.path.join(REPO, "benchmarks", "results",
                            f"sweep_scale_{stamp}.jsonl")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "a") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
        print(f"saved -> {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
