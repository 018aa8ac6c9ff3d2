#!/usr/bin/env python
"""Batched trial evaluation benchmark: pooled vmap launch vs per-trial dispatch.

The batched-executor claim is a *dispatch* claim: a pool of k trials
evaluated as ONE jitted vmap program should beat k per-trial launches of
the same jitted math, because the per-trial path pays Python→XLA dispatch,
host sync and result unpacking once per trial while the pooled path pays
them once per pool. This driver measures both sides in the SAME invocation
(same-run ratio doctrine from the coord benches — absolute trials/s drifts
>10% between sessions on the one-core CI box, ratios don't):

- **serial**: the task's math jitted as a scalar program, dispatched once
  per trial through ``InProcessExecutor.execute`` — exactly what
  ``mtpu hunt`` does without ``--batch-size``.
- **batched**: the same trials through ``BatchedExecutor.execute_batch``
  (stack → one vmap launch → per-row unpack), what ``--batch-size k``
  does.

Both sides run the full executor path (Trial objects in, typed result
dicts out), so the ratio includes the stacking/unstacking tax the batched
path actually pays — not just raw kernel time. Launch-count telemetry
confirms the pooled side really is one device program per pool; a figure
measured against a silently chunked pool would flatter nothing but would
not be the claim.

The objective is cheap on purpose: batching is a dispatch-overhead
optimization, and the honest CPU figure is the one where the kernel does
not hide the dispatch. Compute-bound objectives only widen the pooled win
on real accelerators (one launch amortizes better the more rows ride it).

    python benchmarks/batch_eval.py [--pools 8 64] [--reps 5] [--save]

Emits one JSON line per pool size:
  {"pool": k, "batched_trials_per_s": ..., "serial_trials_per_s": ...,
   "speedup": ..., "launches_per_pool": ...}
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from typing import Any, Dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def run_batch_eval(
    pool: int = 64,
    reps: int = 5,
    task_name: str = "rastrigin",
    dim: int = 4,
) -> Dict[str, Any]:
    """Median-of-``reps`` trials/s for both dispatch modes, same points."""
    import jax
    import jax.numpy as jnp

    from metaopt_tpu.benchmark.tasks import task_registry
    from metaopt_tpu.executor import BatchedExecutor, InProcessExecutor
    from metaopt_tpu.ledger.trial import Trial
    from metaopt_tpu.space import build_space

    task = task_registry.get(task_name)(dim=dim)
    space = build_space(task.space)
    trials = [
        Trial(params=p, experiment="bench")
        for p in space.sample(pool, seed=17)
    ]
    names = sorted(task.space)

    # the per-trial side jits the SAME batch math at batch=1 so both modes
    # run identical XLA code per row — the measured delta is dispatch, not
    # kernel quality
    scalar_kernel = jax.jit(
        lambda row: task.batch(jnp.reshape(row, (1, -1)))[0]
    )

    def scalar_fn(params: Dict[str, Any]) -> float:
        row = jnp.asarray([float(params[n]) for n in names], jnp.float32)
        return float(scalar_kernel(row))

    serial_ex = InProcessExecutor(scalar_fn)
    batched_ex = BatchedExecutor(task.batch, space)

    # compile both programs outside the timed region
    serial_ex.execute(trials[0])
    batched_ex.execute_batch(trials)

    serial_s = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for t in trials:
            r = serial_ex.execute(t)
            assert r.status == "completed", r.note
        serial_s.append(time.perf_counter() - t0)

    launches_before = batched_ex.telemetry()["kernel_launches"]
    batched_s = []
    for _ in range(reps):
        t0 = time.perf_counter()
        results = batched_ex.execute_batch(trials)
        assert all(r.status == "completed" for r in results)
        batched_s.append(time.perf_counter() - t0)
    launches = batched_ex.telemetry()["kernel_launches"] - launches_before

    serial_med = statistics.median(serial_s)
    batched_med = statistics.median(batched_s)
    return {
        "pool": pool,
        "task": task_name,
        "dim": dim,
        "reps": reps,
        "serial_trials_per_s": round(pool / serial_med, 1),
        "batched_trials_per_s": round(pool / batched_med, 1),
        "speedup": round(serial_med / batched_med, 2),
        # the claim under the number: one device program per pool
        "launches_per_pool": round(launches / reps, 2),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--pools", type=int, nargs="+", default=[8, 64])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--task", default="rastrigin")
    ap.add_argument("--dim", type=int, default=4)
    ap.add_argument("--save", action="store_true",
                    help="append rows to benchmarks/results/")
    args = ap.parse_args()

    import jax

    from metaopt_tpu.utils.provenance import provenance

    rows = []
    for pool in args.pools:
        row = run_batch_eval(pool, reps=args.reps, task_name=args.task,
                             dim=args.dim)
        # one process, on whatever device it owns: every row says which
        row.update(provenance(backend=jax.default_backend()))
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.save:
        stamp = time.strftime("%Y-%m-%d")
        path = os.path.join(REPO, "benchmarks", "results",
                            f"batch_eval_{stamp}.jsonl")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "a") as fh:
            for row in rows:
                fh.write(json.dumps(row) + "\n")
        print(f"saved -> {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
