#!/usr/bin/env python
"""ASHA at 10k+ trials on the coord backend, with a mid-sweep restart.

BASELINE.md's north star claims the coordination plane stays sound past
10 000 trials; VERDICT r4 #6 asks for the ASHA half of that proof — rung
bookkeeping at scale on the coordinator, with rung state INTACT across a
coordinator stop/restore (the snapshot + observe-replay resume doctrine,
SURVEY.md §5 checkpoint/resume).

Phase 1 runs hosted-ASHA workers (producer_mode="coord") to ~half the
target, snapshots the rung table (client-side observe-replay — the same
reconstruction `mtpu status --rungs` performs), and stops the coordinator.
Phase 2 starts a FRESH CoordServer from the snapshot, asserts the replayed
rung table matches byte-for-byte, and drives the sweep past the target.

Emits one provenance-stamped JSON row; --save appends it to
benchmarks/results/asha_restart_<date>.jsonl. CPU-only by design: this
measures the coordination plane, not the chip.

    JAX_PLATFORMS=cpu python benchmarks/asha_restart.py [--trials 10000]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def rung_snapshot(ledger, name: str, space, algo_cfg) -> list:
    """Client-side rung reconstruction: fresh algo + observe-replay."""
    from metaopt_tpu.algo.base import make_algorithm

    algo = make_algorithm(space, algo_cfg)
    from metaopt_tpu.ledger.experiment import Experiment

    exp = Experiment(name, ledger).configure()
    algo.observe(exp.fetch_completed_trials())
    return algo.rung_table


def run_workers(exp_name, host, port, space, algo_cfg, n_workers, stop_at,
                cap_per_worker=None):
    """Drive hosted-producer workers until ``stop_at`` completions.

    ``cap_per_worker`` (phase 1) bounds each worker via ``worker_trials``
    so the restart really happens MID-sweep — in-process trials complete
    faster than any polling watcher could stop them.
    """
    from metaopt_tpu.coord import CoordLedgerClient
    from metaopt_tpu.executor import InProcessExecutor
    from metaopt_tpu.ledger.experiment import Experiment
    from metaopt_tpu.worker import workon

    stop_event = threading.Event()

    def objective(p):
        # cheap, fidelity-aware, deterministic: higher budget refines the
        # noisy low-fidelity estimate (the shape ASHA promotion rewards)
        x = p["x"]
        f = p.get("epochs", 1)
        return [{"name": "o", "type": "objective",
                 "value": (x - 0.7) ** 2 + 0.1 / float(f)}]

    def one(i):
        ledger = CoordLedgerClient(host=host, port=port)
        exp = Experiment(exp_name, ledger).configure()
        workon(exp, InProcessExecutor(objective),
               worker_id=f"w{i}", producer_mode="coord",
               max_broken=50, stop_event=stop_event,
               worker_trials=cap_per_worker)

    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(n_workers)]
    for t in threads:
        t.start()
    from metaopt_tpu.coord import CoordLedgerClient as C

    probe = C(host=host, port=port)
    try:
        while probe.count(exp_name, "completed") < stop_at:
            if not any(t.is_alive() for t in threads):
                break
            time.sleep(0.5)
    finally:
        stop_event.set()
        for t in threads:
            t.join(timeout=60)
        done = probe.count(exp_name, "completed")
    return done


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=10_000)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--save", action="store_true")
    args = ap.parse_args()

    from metaopt_tpu.coord import CoordLedgerClient, CoordServer
    from metaopt_tpu.ledger.experiment import Experiment
    from metaopt_tpu.space import build_space
    from metaopt_tpu.utils.provenance import provenance

    space = build_space({
        "x": "uniform(0, 1)",
        "lr": "loguniform(1e-5, 1e-1)",
        "epochs": "fidelity(1, 27, base=3)",
    })
    algo_cfg = {"asha": {"seed": 0, "num_brackets": 1}}
    target = args.trials
    snap = os.path.join(tempfile.mkdtemp(prefix="asha_restart_"), "snap.mp")

    t0 = time.time()
    server = CoordServer(snapshot_path=snap).start()
    host, port = server.address
    ledger = CoordLedgerClient(host=host, port=port)
    Experiment("asha10k", ledger, space=space, algorithm=algo_cfg,
               max_trials=target, pool_size=max(4, args.workers)).configure()
    done_1 = run_workers("asha10k", host, port, space, algo_cfg,
                         args.workers, stop_at=target // 2,
                         cap_per_worker=(target // 2) // args.workers)
    rungs_before = rung_snapshot(ledger, "asha10k", space, algo_cfg)
    server.stop()  # writes the snapshot

    # --- restart: fresh server, restored ledger --------------------------
    server2 = CoordServer(snapshot_path=snap).start()
    host2, port2 = server2.address
    ledger2 = CoordLedgerClient(host=host2, port=port2)
    rungs_after = rung_snapshot(ledger2, "asha10k", space, algo_cfg)
    intact = rungs_before == rungs_after
    done_2 = run_workers("asha10k", host2, port2, space, algo_cfg,
                         args.workers, stop_at=target)
    wall = time.time() - t0
    completed = ledger2.count("asha10k", "completed")
    rungs_final = rung_snapshot(ledger2, "asha10k", space, algo_cfg)
    server2.stop()

    row = {
        "metric": "asha_coord_restart",
        "target_trials": target,
        "completed": completed,
        "completed_before_restart": done_1,
        "rungs_intact_after_restart": intact,
        "rungs_before": [
            {"budget": r["budget"], "n": r["n"]} for r in rungs_before],
        "rungs_final": [
            {"budget": r["budget"], "n": r["n"]} for r in rungs_final],
        "wall_s": round(wall, 1),
        "trials_per_hour": round(3600 * completed / wall, 1),
        "workers": args.workers,
        **provenance(),
    }
    print(json.dumps(row), flush=True)
    if args.save:
        stamp = time.strftime("%Y-%m-%d", time.gmtime())
        path = os.path.join(REPO, "benchmarks", "results",
                            f"asha_restart_{stamp}.jsonl")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(f"saved -> {path}", file=sys.stderr)
    return 0 if (intact and completed >= target) else 1


if __name__ == "__main__":
    sys.exit(main())
