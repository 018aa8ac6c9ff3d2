#!/usr/bin/env python
"""Scale-simulator certification driver: the 100k-worker numbers.

Runs the discrete-event simulator (``metaopt_tpu/sim``) at certification
scale and emits one JSONL row per scenario plus a ``summary`` row
carrying the headline keys:

- ``sim_asha_promotion_violations`` / ``sim_acked_write_losses`` /
  ``sim_exactly_once_violations``: acceptance bars, zero or the run
  fails (a certification failure is never "drift").
- ``sim_jain_100k_workers``: tenant fairness at the headline scale,
  floor 0.9 (same bar as the live multi-tenant benchmark's
  ``coord_fairness_jain_1k``).
- ``sim_recovery_s_per_10k_wal``: recovery wall time normalized per 10k
  replayed WAL records — drift watch, informational.
- ``sim_regret_parity``: best-objective ratio of the simulated ASHA run
  vs an UNSIMULATED sequential run of the same algorithm/seed/task — the
  sanity check that the simulator's completion-order chaos preserves
  optimization quality (informational; stochastic orders mean parity,
  not equality).

The simulated fleet drives the REAL CoordServer dispatch (WAL, reply
cache, hosted algorithms, fair scheduler), so these are control-plane
certification numbers, not a model of one.

    python benchmarks/sim_scale.py [--workers 100000] [--seed 0] [--save]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from metaopt_tpu.sim.engine import (  # noqa: E402
    DEFAULT_FAULTS, SimConfig, Simulation,
)


def unsimulated_best(task_name: str, algo: str, seed: int,
                     max_trials: int) -> float:
    """Best objective of a plain sequential loop: same algorithm config,
    same seeded space, no coordinator, no chaos — the regret-parity
    reference the simulated run is compared against."""
    from metaopt_tpu.algo.base import make_algorithm
    from metaopt_tpu.benchmark.tasks import task_registry
    from metaopt_tpu.ledger.trial import Trial
    from metaopt_tpu.space import build_space

    task = task_registry.get(task_name)()
    spec = dict(task.space)
    spec["epochs"] = "fidelity(1, 16, base=4)"
    space = build_space(spec)
    algo_inst = make_algorithm(space, {algo: {"seed": seed}})
    best = float("inf")
    n = 0
    while n < max_trials:
        pts = algo_inst.suggest(1)
        if not pts:
            break
        params = pts[0]
        point = {k: v for k, v in params.items() if k != "epochs"}
        budget = float(params.get("epochs", 1) or 1)
        # identical objective shaping to sim/engine.py _objective
        obj = float(task(point)[0]["value"]) * (
            1.0 + 0.25 / max(1.0, budget))
        best = min(best, obj)
        t = Trial(params=params, experiment="ref")
        t.lineage = space.hash_point(params)
        t.transition("reserved")
        t.attach_results([
            {"name": "objective", "type": "objective", "value": obj}])
        t.transition("completed")
        algo_inst.observe([t])
        n += 1
    return best


def run_scenario(workers: int, seed: int, faults: str) -> dict:
    cfg = SimConfig(workers=workers, seed=seed, faults=faults)
    rep = Simulation(cfg).run()
    asha_best = [v for k, v in sorted(rep.best_by_experiment.items())
                 if "-asha-" in k]
    row = {
        "kind": "sim", "workers": workers, "seed": seed,
        "experiments": rep.experiments,
        "virtual_s": rep.virtual_s, "wall_s": rep.wall_s,
        "dispatches": rep.dispatches,
        "acked_completions": rep.acked_completions,
        "cas_rejected_completions": rep.cas_rejected_completions,
        "worker_deaths": rep.worker_deaths,
        "crashes": rep.crashes,
        "jain": rep.jain,
        "promotion_violations": len(rep.promotion_violations),
        "acked_write_losses": len(rep.acked_write_losses),
        "exactly_once_violations": len(rep.exactly_once_violations),
        "recovery_s_per_10k_wal": rep.recovery_s_per_10k_wal,
        "event_log_sha256": rep.event_log_sha256,
        "sim_best_asha": min(asha_best) if asha_best else None,
        "ok": rep.ok,
    }
    if rep.promotion_violations:
        row["promotion_violation_detail"] = rep.promotion_violations[:5]
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workers", type=int, default=100_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--faults", default=None,
                    help="fault spec (default: the simulator's standard "
                         "chaos schedule)")
    ap.add_argument("--save", action="store_true",
                    help="append rows to benchmarks/results/"
                         "sim_scale_<date>.jsonl")
    args = ap.parse_args()

    from metaopt_tpu.utils.provenance import provenance

    faults = DEFAULT_FAULTS if args.faults is None else args.faults
    rows = []
    row = run_scenario(args.workers, args.seed, faults)
    row.update(provenance())
    print(json.dumps(row), flush=True)
    rows.append(row)

    # regret parity: simulated ASHA vs the plain sequential reference
    ref_best = unsimulated_best("sphere", "asha",
                                seed=args.seed * 1009, max_trials=64)
    sim_best = row.get("sim_best_asha")
    parity = (round(sim_best / ref_best, 3)
              if sim_best and ref_best else None)

    summary = {
        "kind": "summary", "workers": args.workers, "seed": args.seed,
        # the headline keys
        "sim_asha_promotion_violations": row["promotion_violations"],
        "sim_acked_write_losses": row["acked_write_losses"],
        "sim_exactly_once_violations": row["exactly_once_violations"],
        "sim_jain_100k_workers": row["jain"],
        "sim_recovery_s_per_10k_wal": row["recovery_s_per_10k_wal"],
        "sim_wall_s": row["wall_s"],
        "sim_regret_parity": parity,
        "sim_best_ref": round(ref_best, 6),
        "event_log_sha256": row["event_log_sha256"],
    }
    summary.update(provenance())
    print(json.dumps(summary), flush=True)
    rows.append(summary)

    if args.save:
        stamp = time.strftime("%Y-%m-%d")
        path = os.path.join(REPO, "benchmarks", "results",
                            f"sim_scale_{stamp}.jsonl")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "a") as fh:
            for r in rows:
                fh.write(json.dumps(r) + "\n")
        print(f"saved -> {path}", file=sys.stderr)
    return 0 if row["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
