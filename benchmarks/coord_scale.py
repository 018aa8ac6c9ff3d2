#!/usr/bin/env python
"""Coordinator RPC-plane scale benchmark: trials/sec at 1/8/32 workers.

`sweep_scale.py` measures one worker's coordination throughput per ledger
backend; THIS driver measures the coordinator's RPC plane under worker
fan-in — N threaded workers against one in-process CoordServer — and the
effect of the fused `worker_cycle` fast path against the serial wire
sequence it replaced (release_stale → produce → reserve →
should_suspend → doc + count reads, ~5-9 round-trips per trial).

The server is hosted in-process rather than in a subprocess: CI boxes
for this repo expose ONE core, where a second interpreter cannot run in
parallel and only adds context-switch noise (measured: cross-process
inflated fused p99 from ~6 ms to 420 ms). On one core the fused/serial
ratio is a pure total-work comparison — per-message framing, JSON,
dispatch, locking and thread handoffs — which is the conservative floor
of the win; real multi-core deployments add the round-trip savings on
top.

Both modes run the SAME workon loop. "serial" reproduces the pre-change
deployment end to end: the client's capability set is pinned so it
composes each cycle from individual RPCs, and the server runs legacy
dispatch (one global lock around every ledger op, no preserialized-reply
cache) — what `_LockedLedger` did before lock sharding. "fused" is the
shipped configuration.

The objective is instant and the algorithm is random search (no surrogate
fit), so the measured trials/sec is pure control-plane: framing, JSON,
dispatch, locking. The produce group-commit window defaults to 0 to keep
the comparison free of a fixed sleep floor both modes would pay
identically (coalescing is covered by sweep_scale + the
coalesced-vs-serial property tests).

"fused+wal" is the shipped configuration with the write-ahead log on
(snapshot+WAL in a tempdir, group-commit fsync on every mutating reply);
the fused vs fused+wal delta is the durability tax. `--recovery` additionally times a crash
restart (restore + replay of a 2000-trial WAL).

"sharded" (via --shards) is the multi-process deployment: a
ShardSupervisor hosting N subprocess CoordServer shards, each with its
own WAL, clients routing directly by the consistent-hash shard map. The
workload spreads `--shard-experiments` experiments across the shards
(workers split evenly), and the SAME multi-experiment workload runs
against the in-process durable server in the SAME invocation — every
reported ratio is same-run/same-machine, because PR 3 showed absolute
trials/s drifts >10% between sessions on the CI box and poisons
cross-session comparisons. On a one-core box sharding cannot scale (the
shards time-slice one core); the honest figure there is the 1-shard
overhead vs the in-process server.

    python benchmarks/coord_scale.py [--workers 1 8 32]
                                     [--modes serial fused fused+wal]
                                     [--shards 1 2 4]
                                     [--shard-experiments 4]
                                     [--trials-per-worker 16]
                                     [--recovery] [--save]
                                     [--fused-suggest] [--residents 64 256]

When the binary wire (protocol v2) is available, a "fused-json" config
rides along automatically: the same fused deployment with the client
pinned to the JSON codec, interleaved in the same repeat loop, so the
`wire_v2_vs_json` summary (throughput speedup + bytes/trial both ways)
is a same-run ratio like every other headline here.

Emits one JSON line per (mode, workers) config:
  {"mode": ..., "workers": N, "wire": "v1"|"v2", "trials": ...,
   "wall_s": ..., "trials_per_s": ..., "rpc_p50_ms": ...,
   "rpc_p99_ms": ..., "rpcs_per_trial": ..., "wire_bytes": ...,
   "wire_bytes_per_trial": ..., "op_counts": {...}}
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

SPACE = {
    "lr": "loguniform(1e-5, 1e-1)",
    "mom": "uniform(0, 1)",
}


def objective(params):
    # instant: the benchmark must measure the RPC plane, not the trial
    return (params["mom"] - 0.9) ** 2


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return None
    i = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def _balanced_names(shard_map, count: int):
    """``count`` experiment names spread as evenly as the ring allows
    across the map's shards — the sharded workload must exercise every
    shard, not land all its experiments on one by hash accident."""
    from metaopt_tpu.coord.shards import ring_of

    ring = ring_of(shard_map)
    n = len(shard_map["shards"])
    cap = -(-count // n)  # ceil
    per: dict = {}
    names = []
    i = 0
    while len(names) < count and i < 100000:
        nm = f"cs-exp{i}"
        sid = ring.owner(nm)
        if per.get(sid, 0) < cap:
            per[sid] = per.get(sid, 0) + 1
            names.append(nm)
        i += 1
    return names


def _make_server(mode: str, produce_coalesce_ms: float, shards=None):
    """The coordinator under test; ``serial`` gets the pre-fast-path
    dispatch shape so the baseline is the pre-change server, not the new
    server driven serially. ``fused+wal`` is the shipped server with the
    write-ahead log on (group-commit fsync before every mutating reply) —
    the fused/fused+wal ratio is the durability tax. ``sharded`` is the multi-process deployment: N
    subprocess shards, one WAL each, under a ShardSupervisor."""
    import shutil
    import tempfile

    from metaopt_tpu.coord import CoordServer

    if mode == "sharded":
        from metaopt_tpu.coord.shards import ShardSupervisor

        wal_dir = tempfile.mkdtemp(prefix="coordscale-shards-")
        sup = ShardSupervisor(shards or 1, snapshot_dir=wal_dir,
                              produce_coalesce_ms=produce_coalesce_ms)
        sup._bench_cleanup = lambda: shutil.rmtree(wal_dir, True)
        return sup
    if mode == "fused+wal":
        wal_dir = tempfile.mkdtemp(prefix="coordscale-wal-")
        server = CoordServer(
            produce_coalesce_ms=produce_coalesce_ms,
            snapshot_path=os.path.join(wal_dir, "snap.json"),
        )
        # benched state is throwaway: drop snapshot+WAL with the server
        server._bench_cleanup = lambda: shutil.rmtree(wal_dir, True)
        return server
    if mode == "fused":
        return CoordServer(produce_coalesce_ms=produce_coalesce_ms)

    class LegacyServer(CoordServer):
        """PR-1 dispatch: ONE global lock serializing every ledger op
        (reads included) and no preserialized-reply cache — what
        `_LockedLedger` did before lock sharding."""

        _CACHED_READS = frozenset()

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            # reads queue behind writers again, as _LockedLedger's did
            self.ledger._LOCK_FREE = frozenset()

        def _exp_lock(self, name):
            return self._lock

    return LegacyServer(produce_coalesce_ms=produce_coalesce_ms)


def run_scale(
    workers: int,
    mode: str = "fused",
    trials_per_worker: int = 16,
    pool_size: int = 8,
    produce_coalesce_ms: float = 0.0,
    seed: int = 0,
    shards: int = None,
    experiments: int = 1,
    wire: str = "auto",
) -> dict:
    """One config: N threaded workers drain ``experiments`` experiments
    through one coordinator deployment; returns the throughput/latency
    row.

    ``mode="serial"`` is the pre-change deployment (legacy-dispatch
    server + per-op wire sequence); ``mode="fused"`` the shipped one —
    same machine, same run, which is what makes the fused/serial ratio a
    like-for-like RPC-plane comparison. ``mode="sharded"`` runs
    ``shards`` subprocess shards (one WAL each) under a ShardSupervisor,
    clients routing directly by the shard map; compare it against an
    in-process mode at the SAME ``experiments`` in the same invocation.

    ``wire`` selects the client codec: ``"auto"`` negotiates the binary
    v2 wire when the server advertises it, ``"v1"`` pins JSON — the
    binary-vs-JSON figure is run_scale(wire="auto") against
    run_scale(wire="v1") in the SAME invocation (serial mode always pins
    JSON: the pre-change deployment had no binary wire).
    """
    from metaopt_tpu.coord import CoordLedgerClient
    from metaopt_tpu.executor import InProcessExecutor
    from metaopt_tpu.ledger import Experiment
    from metaopt_tpu.space import build_space
    from metaopt_tpu.worker import workon

    if mode not in ("serial", "fused", "fused+wal", "sharded"):
        raise ValueError(f"unknown mode {mode!r}")
    # an experiment with zero workers would deadlock its drain
    experiments = max(1, min(experiments, workers))

    lat_lock = threading.Lock()
    latencies: list = []
    op_counts: dict = {}

    class TimingClient(CoordLedgerClient):
        """Per-RPC wall-clock over every worker thread (client sockets are
        per-thread, so one shared instance serves all workers)."""

        def _call(self, op, **args):
            t0 = time.perf_counter()
            try:
                return super()._call(op, **args)
            finally:
                dt = time.perf_counter() - t0
                with lat_lock:
                    latencies.append(dt)
                    op_counts[op] = op_counts.get(op, 0) + 1

    server = _make_server(mode, produce_coalesce_ms, shards)
    server.start()
    try:
        host, port = server.address
        # the serial baseline is the pre-change deployment end to end:
        # JSON wire, no negotiation
        client = TimingClient(host=host, port=port,
                              wire="v1" if mode == "serial" else wire)
        if mode == "serial":
            # a pre-worker_cycle coordinator advertises only these; the
            # client then composes cycles from the serial RPC sequence
            client._caps = ("count", "fetch_completed_since")
        if mode == "sharded":
            # learn the shard map before the clock so the measured window
            # is direct-routed, and spread the experiments across shards
            client.ping()
            assert client._ring is not None, "shard map not learned"
            names = _balanced_names(server.shard_map, experiments)
        else:
            names = [f"coordscale-{mode}-{workers}w-{e}"
                     for e in range(experiments)]
        # workers round-robin over experiments; each experiment's budget
        # matches its worker count so every mode drains the same totals
        exp_workers = [
            sum(1 for i in range(workers) if i % len(names) == e)
            for e in range(len(names))
        ]
        for e, name in enumerate(names):
            Experiment(
                name,
                client,
                space=build_space(SPACE),
                algorithm={"random": {"seed": seed + e}},
                max_trials=exp_workers[e] * trials_per_worker,
                pool_size=pool_size,
            ).configure()
            # warm the hosted-producer path (algorithm construction + its
            # imports) before the clock: the first produce of a fresh
            # process otherwise pays a one-time ~100s-of-ms setup inside
            # whichever mode's window runs first — registers one normal
            # pool that the workers then drain as part of the run
            client.produce(name, pool_size)

        # worker Experiments are built (1 doc load each) before the clock
        # starts; the measured window is pure drain
        worker_exps = [
            Experiment(names[i % len(names)], client).configure()
            for i in range(workers)
        ]
        threads = []
        # start the window with an empty collector debt: on a one-core box
        # a GC pause lands entirely inside whichever mode's window it hits
        gc.collect()
        bytes0 = client.bytes_sent + client.bytes_recv
        t0 = time.perf_counter()
        for i, wexp in enumerate(worker_exps):
            w = threading.Thread(
                target=workon,
                args=(wexp, InProcessExecutor(objective)),
                kwargs={
                    "worker_id": f"cs-w{i}",
                    "producer_mode": "coord",
                    "max_idle_cycles": 2000,
                    "idle_sleep_s": 0.002,
                },
                daemon=True,
            )
            w.start()
            threads.append(w)
        for w in threads:
            w.join(timeout=300)
        wall = time.perf_counter() - t0
        # on-wire volume of the measured window (both directions, framing
        # headers included); the post-window count reads are excluded
        wire_bytes = client.bytes_sent + client.bytes_recv - bytes0

        # measurement reads (this count + the lat snapshot) come AFTER the
        # window closes and are excluded from the RPC accounting
        with lat_lock:
            lat_sorted = sorted(latencies)
            ops = dict(op_counts)
        n_calls = sum(ops.values())
        completed = sum(client.count(nm, "completed") for nm in names)
        # steady-state RPCs per trial: one-time ramp excluded — the caps
        # probe ping, the experiment create/config round-trips, each
        # experiment's configure load + warmup produce, and each worker's
        # bootstrap (configure's doc load + the first loop iteration's
        # full is_done evaluation: doc load + 2 counts) — an identical
        # allowance for every mode
        ramp = (ops.get("ping", 0) + ops.get("create_experiment", 0)
                + ops.get("update_experiment", 0) + 2 * len(names)
                + 4 * workers)
        steady = max(0, n_calls - ramp)
        return {
            "mode": mode,
            "workers": workers,
            "wire": client._wire_for(client._seed),
            **({"shards": shards or 1} if mode == "sharded" else {}),
            **({"experiments": len(names)} if len(names) > 1 else {}),
            "trials": completed,
            "wall_s": round(wall, 3),
            "trials_per_s": round(completed / wall, 2) if wall else None,
            "rpc_p50_ms": round(
                1e3 * statistics.median(lat_sorted), 3) if lat_sorted else None,
            "rpc_p99_ms": round(
                1e3 * _percentile(lat_sorted, 0.99), 3) if lat_sorted else None,
            "rpcs": n_calls,
            "rpcs_per_trial": round(steady / completed, 2) if completed else None,
            "wire_bytes": wire_bytes,
            "wire_bytes_per_trial": (round(wire_bytes / completed, 1)
                                     if completed else None),
            "op_counts": ops,
            "enc_cache_hits": (server._enc_hits
                               if mode.startswith("fused") else None),
            "wal_batches": (server._wal.batches
                            if getattr(server, "_wal", None) else None),
            "wal_records": (server._wal.records
                            if getattr(server, "_wal", None) else None),
        }
    finally:
        server.stop()
        cleanup = getattr(server, "_bench_cleanup", None)
        if cleanup:
            cleanup()


def run_recovery(trials: int = 2000, seed: int = 0) -> dict:
    """Crash-recovery latency: load a durable coordinator with ``trials``
    registered trials, kill it without the shutdown snapshot (the WAL is
    the only record), and time the restart's restore + WAL replay.

    The reported ``recovery_s`` is the window a restarting coordinator is
    unreachable on top of process spawn — the figure the runbook quotes.
    """
    import shutil
    import tempfile

    from metaopt_tpu.coord import CoordServer
    from metaopt_tpu.ledger import Trial

    wal_dir = tempfile.mkdtemp(prefix="coordscale-recovery-")
    snap = os.path.join(wal_dir, "snap.json")
    try:
        server = CoordServer(snapshot_path=snap)
        server.start()
        try:
            # straight through the ledger facade: the workload here is the
            # WAL/replay volume, not the RPC plane run_scale already covers
            server.ledger.create_experiment(
                {"name": "recov", "max_trials": trials + 1})
            for i in range(trials):
                server.ledger.register(
                    Trial(params={"x": float(i)}, experiment="recov"))
            wal_path = server.wal_path
            wal_records = server._wal.records + len(server._wal._pending)
        finally:
            server.snapshot_path = None  # crash: skip the final snapshot
            server.stop()
        wal_bytes = os.path.getsize(wal_path)

        t0 = time.perf_counter()
        restarted = CoordServer(snapshot_path=snap)
        restarted.start()
        recovery_s = time.perf_counter() - t0
        try:
            recovered = restarted.ledger.count("recov")
        finally:
            restarted.snapshot_path = None
            restarted.stop()
        if recovered != trials:
            raise RuntimeError(
                f"recovery dropped trials: {recovered}/{trials}")
        return {
            "mode": "recovery",
            "trials": trials,
            "wal_bytes": wal_bytes,
            "wal_records": wal_records,
            "recovery_s": round(recovery_s, 3),
            "trials_per_s_replayed": round(trials / recovery_s, 1),
        }
    finally:
        shutil.rmtree(wal_dir, True)


def run_handoff(trials: int = 48, seed: int = 0) -> dict:
    """Live hand-off + failover latency on a 2-shard pod.

    ``coord_handoff_ms`` is the wall time of one `sup.handoff` of a
    live experiment carrying ``trials`` completed trials (fence + drain
    + capture + ship + ownership commit — the window the migrating
    experiment's writers see ``Migrating`` retries). ``coord_failover_
    time_s`` is the supervisor's own death-to-redistributed figure for a
    killed shard whose experiment is recovered from snapshot+WAL on
    disk. Both are quoted by the runbook.
    """
    import shutil
    import tempfile

    from metaopt_tpu.coord import CoordLedgerClient
    from metaopt_tpu.coord.shards import ShardSupervisor, ring_of
    from metaopt_tpu.executor import InProcessExecutor
    from metaopt_tpu.ledger import Experiment
    from metaopt_tpu.space import build_space
    from metaopt_tpu.worker import workon

    snap_dir = tempfile.mkdtemp(prefix="coordscale-handoff-")
    try:
        with ShardSupervisor(2, snapshot_dir=snap_dir,
                             snapshot_interval_s=0.5,
                             failover=True) as sup:
            host, port = sup.address
            # a reconnect window: post-kill reads must reroute off the
            # dead shard's address instead of failing fast
            client = CoordLedgerClient(host=host, port=port,
                                       reconnect_window_s=30.0)
            client.ping()
            # two experiments on shard s0: one to migrate live, one to
            # leave behind for the failover kill
            ring = ring_of(sup.shard_map)
            names = []
            i = 0
            while len(names) < 2:
                nm = f"ho-exp{i}"
                if ring.owner(nm) == "s0":
                    names.append(nm)
                i += 1
            for e, nm in enumerate(names):
                Experiment(
                    nm, client, space=build_space(SPACE),
                    algorithm={"random": {"seed": seed + e}},
                    max_trials=trials, pool_size=8,
                ).configure()
                workon(Experiment(nm, client).configure(),
                       InProcessExecutor(objective),
                       worker_id=f"ho-w{e}", producer_mode="coord",
                       max_idle_cycles=2000, idle_sleep_s=0.002)

            t0 = time.perf_counter()
            sup.handoff(names[0], "s1")
            handoff_s = time.perf_counter() - t0
            moved = client.count(names[0], "completed")

            # failover: kill s0 (still owning names[1]); the supervisor
            # recovers it from disk and hands it to the survivor
            sup.kill_shard(0)
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline and not sup.failover_times:
                time.sleep(0.02)
            if not sup.failover_times:
                raise RuntimeError("failover never completed")
            recovered = client.count(names[1], "completed")
            if moved != trials or recovered != trials:
                raise RuntimeError(
                    f"hand-off/failover dropped trials: "
                    f"{moved}/{recovered} of {trials}")
            return {
                "mode": "handoff",
                "trials_per_experiment": trials,
                "coord_handoff_ms": round(1e3 * handoff_s, 1),
                "coord_failover_time_s": round(sup.failover_times[0], 3),
            }
    finally:
        shutil.rmtree(snap_dir, True)


# subprocess probe for run_multitenant's RSS phase: RSS of a fresh process
# is only meaningful measured IN a fresh process (the benchmark driver's
# own heap — jax, prior phases — would swamp the delta). argv:
#   <repo> build   <dir> <n_exp> <n_trials> <evict 0|1>
#   <repo> measure <dir> <n_exp> <n_trials> <evict 0|1>
_RSS_SRC = r"""
import gc, json, os, sys
sys.path.insert(0, sys.argv[1])
os.environ.setdefault("JAX_PLATFORMS", "cpu")
role, root = sys.argv[2], sys.argv[3]
n_exp, n_trials, evict = int(sys.argv[4]), int(sys.argv[5]), sys.argv[6] == "1"


def rss_kb():
    with open("/proc/self/status") as fh:
        for line in fh:
            if line.startswith("VmRSS:"):
                return int(line.split()[1])
    return 0


from metaopt_tpu.coord import CoordServer
from metaopt_tpu.ledger import Trial

snap = os.path.join(root, "snap.json")
evict_dir = os.path.join(root, "evict")
SPACE = {"lr": "loguniform(1e-5, 1e-1)", "mom": "uniform(0, 1)"}
if role == "build":
    server = CoordServer(snapshot_path=snap, evict_dir=evict_dir)
    server.start()
    try:
        for e in range(n_exp):
            name = "rss-exp%d" % e
            server.ledger.create_experiment({
                "name": name, "tenant": "t%d" % (e % 4), "space": SPACE,
                "algorithm": {"random": {"seed": e}},
                "max_trials": 10 ** 6, "pool_size": 8,
            })
            for i in range(n_trials):
                server.ledger.register(Trial(
                    params={"lr": 1e-3 * (1.0 + 1e-6 * i), "mom": 0.5},
                    experiment=name))
            if evict and not server.evict_experiment(name):
                raise RuntimeError("evict refused for %s" % name)
    finally:
        server.stop()
    print(json.dumps({"built": n_exp, "evicted": evict}))
else:
    gc.collect()
    rss0 = rss_kb()
    server = CoordServer(snapshot_path=snap, evict_dir=evict_dir)
    server.start()
    gc.collect()
    rss1 = rss_kb()
    try:
        st = server._tenant_stats({})
    finally:
        server.snapshot_path = None  # measurement only: no rewrite
        server.stop()
    print(json.dumps({"rss0_kb": rss0, "rss1_kb": rss1,
                      "resident": st["resident"], "evicted": st["evicted"]}))
"""

#: warm-vs-cold transfer study space — a plain quadratic bowl; enough
#: dimensions that 50 cold TPE trials do NOT solve it by accident
_T_SPACE = {
    "x0": "uniform(0, 1)",
    "x1": "uniform(0, 1)",
    "x2": "uniform(0, 1)",
    "x3": "uniform(0, 1)",
}
_T_CENTER = (0.32, 0.58, 0.41, 0.67)


def _transfer_study(led, name, center, budget, seed,
                    transfer_from=None, stop_at=None):
    """Run a sequential TPE study on the quadratic bowl; returns
    ``(best, trials_used, wall_s)``. ``stop_at`` ends the study the
    moment the best objective reaches it (the warm run's clock)."""
    from metaopt_tpu.algo.tpe import TPE
    from metaopt_tpu.ledger import Experiment
    from metaopt_tpu.space import build_space
    from metaopt_tpu.worker.producer import Producer

    meta = {"transfer_from": transfer_from} if transfer_from else {}
    exp = Experiment(
        name, led, space=build_space(_T_SPACE),
        algorithm={"tpe": {"seed": seed, "n_initial_points": 5}},
        max_trials=budget + 8, pool_size=1, metadata=meta,
    ).configure()
    producer = Producer(exp, TPE(exp.space, seed=seed, n_initial_points=5))
    best, used = float("inf"), 0
    t0 = time.perf_counter()
    for _ in range(budget):
        producer.produce(1)
        trial = exp.reserve_trial("mt-transfer")
        if trial is None:
            break
        val = sum((trial.params[f"x{d}"] - center[d]) ** 2
                  for d in range(len(center)))
        exp.push_results(trial, [
            {"type": "objective", "name": "loss", "value": val}])
        best = min(best, val)
        used += 1
        if stop_at is not None and best <= stop_at:
            break
    return best, used, time.perf_counter() - t0


def run_multitenant(experiments: int = 1000, window_s: float = 5.0,
                    rss_trials: int = 48, transfer_budget: int = 50,
                    seed: int = 0) -> dict:
    """The 1k-experiment multi-tenant service row (ISSUE 16d): fair
    scheduling + residency + transfer priors, all same-run figures.

    Three phases, one row:

    1. **fairness/throughput** — ``experiments`` experiments registered
       round-robin over 4 equal-weight tenants against one coordinator
       with an LRU residency budget; a hot tenant (8 driver threads)
       competes with 3 small tenants (2 threads each) over a fixed
       ``worker_cycle`` window. ``coord_fairness_jain_1k`` is Jain's
       index over per-tenant produce grants per weight unit — without
       the deficit scheduler the demand imbalance pins it near 0.64;
       fair sharing holds it ≥0.9. ``coord_trials_per_s_1k_exp`` is the
       window's completed-trials throughput with the full experiment
       fleet registered (most of it evicted to its residency budget).
       ``status_scan_ms_1k`` times the O(1)-per-experiment status-count
       scan (``tenant_stats(include_experiments=True)``) — the
       no-hydration satellite's figure.
    2. **RSS probe** — two build/measure subprocess pairs (fresh
       interpreters: the delta must not include this driver's heap):
       the same ``experiments`` x ``rss_trials`` fleet recovered
       all-resident vs all-evicted; ``coord_evict_rss_ratio`` =
       resident-delta / evicted-delta, gated ≥3x.
    3. **transfer warm-start** — cold TPE vs transfer-prior-seeded TPE
       on a quadratic bowl whose optimum sits 0.02 from the ancestor's;
       ``transfer_warm_trials_ratio`` = trials the warm study needs to
       reach the cold study's best-by-``transfer_budget``, over that
       budget (gate: ≤0.5). ``transfer_time_to_good_s`` is the warm
       study's wall clock to that bar.
    """
    import random
    import shutil
    import subprocess
    import tempfile

    from metaopt_tpu.coord import CoordLedgerClient, CoordServer
    from metaopt_tpu.coord.tenancy import jain_index
    from metaopt_tpu.ledger import Experiment, MemoryLedger
    from metaopt_tpu.space import build_space

    tenants = ["acme", "beta", "gamma", "delta"]
    row: dict = {"mode": "multitenant", "experiments": experiments}

    # -- phase 1: fairness + throughput at full fleet size ---------------
    snap_dir = tempfile.mkdtemp(prefix="coordscale-mt-")
    try:
        server = CoordServer(
            snapshot_path=os.path.join(snap_dir, "snap.json"),
            max_resident=128,
            tenant_weights={t: 1.0 for t in tenants},
        )
        server.start()
        try:
            host, port = server.address
            client = CoordLedgerClient(host=host, port=port)
            space_cfg = build_space(SPACE).configuration
            t0 = time.perf_counter()
            for i in range(experiments):
                client.create_experiment({
                    "name": f"mt-exp{i}",
                    "tenant": tenants[i % len(tenants)],
                    "space": space_cfg,
                    "algorithm": {"random": {"seed": seed + i}},
                    "max_trials": 10 ** 6,
                    "pool_size": 8,
                })
            row["register_fleet_s"] = round(time.perf_counter() - t0, 2)

            # let the residency sweep drain the fleet to its budget BEFORE
            # the measured window (the evict fsync burst is setup, not
            # steady-state service)
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                st = client.tenant_stats()
                if st["resident"] <= 128:
                    break
                time.sleep(0.25)

            # O(1)-per-experiment status counts, no hydration: the whole
            # fleet scanned from stubs in one op
            hyd0 = client.tenant_stats()["hydrations"]
            t0 = time.perf_counter()
            scan = client.tenant_stats(include_experiments=True)
            row["status_scan_ms_1k"] = round(
                1e3 * (time.perf_counter() - t0), 1)
            if len(scan.get("experiments", {})) != experiments:
                raise RuntimeError(
                    f"status scan saw {len(scan.get('experiments', {}))}"
                    f"/{experiments} experiments")
            if client.tenant_stats()["hydrations"] != hyd0:
                raise RuntimeError("status scan hydrated experiments")

            # hot tenant: 8 drivers; small tenants: 2 each. One experiment
            # per driver so per-experiment locks never serialize tenants
            # against each other — contention is purely for produce grants.
            demand = [8, 2, 2, 2]
            drivers = []  # (tenant_idx, experiment_name, worker_id)
            for t_i, n in enumerate(demand):
                for k in range(n):
                    drivers.append(
                        (t_i, f"mt-exp{t_i + len(tenants) * k}",
                         f"mt-w{t_i}-{k}"))
            stop = threading.Event()
            completed = [0] * len(drivers)
            throttled = [0] * len(drivers)

            def drive(slot, name, wid):
                done = None
                while not stop.is_set():
                    try:
                        out = client.worker_cycle(
                            name, wid, pool_size=4, complete=done)
                    except Exception:
                        if stop.is_set():
                            return
                        raise
                    done = None
                    if out.get("throttled"):
                        throttled[slot] += 1
                    trial = out.get("trial")
                    if trial is None:
                        time.sleep(0.001)
                        continue
                    trial.attach_results([{
                        "type": "objective", "name": "loss",
                        "value": objective(trial.params)}])
                    trial.transition("completed")
                    done = {"trial": trial.to_dict(),
                            "expected_status": "reserved",
                            "expected_worker": wid}
                    completed[slot] += 1

            threads = [
                threading.Thread(target=drive, args=(s, nm, wid), daemon=True)
                for s, (_, nm, wid) in enumerate(drivers)
            ]
            gc.collect()
            for t in threads:
                t.start()
            time.sleep(1.0)  # warm-up: hydrate actives, fill pools
            s0 = client.tenant_stats()
            c0 = sum(completed)
            t0 = time.perf_counter()
            time.sleep(window_s)
            s1 = client.tenant_stats()
            c1 = sum(completed)
            wall = time.perf_counter() - t0
            stop.set()
            for t in threads:
                t.join(timeout=30)

            grants = []
            for t_i, tenant in enumerate(tenants):
                g1 = (s1["tenants"].get(tenant) or {}).get("granted", 0)
                g0 = (s0["tenants"].get(tenant) or {}).get("granted", 0)
                grants.append(float(g1 - g0))
            row["coord_trials_per_s_1k_exp"] = round((c1 - c0) / wall, 2)
            row["coord_fairness_jain_1k"] = round(jain_index(grants), 4)
            row["tenant_grants_window"] = [int(g) for g in grants]
            row["throttled_cycles_window"] = int(sum(throttled))
            row["coord_evictions_1k"] = s1["evictions"]
            row["coord_hydrations_1k"] = s1["hydrations"]
            row["resident_after_window"] = s1["resident"]
        finally:
            server.snapshot_path = None  # benched state is throwaway
            server.stop()
    finally:
        shutil.rmtree(snap_dir, True)

    # -- phase 2: evicted-vs-resident RSS, fresh subprocesses ------------
    rss = {}
    for label, evict in (("resident", "0"), ("evicted", "1")):
        root = tempfile.mkdtemp(prefix=f"coordscale-mt-rss-{label}-")
        try:
            argv_tail = [REPO, "", root, str(experiments),
                         str(rss_trials), evict]
            for role in ("build", "measure"):
                argv_tail[1] = role
                proc = subprocess.run(
                    [sys.executable, "-c", _RSS_SRC] + argv_tail,
                    capture_output=True, text=True, timeout=600)
                if proc.returncode != 0:
                    raise RuntimeError(
                        f"rss {label}/{role} failed: {proc.stderr[-2000:]}")
                out = json.loads(proc.stdout.strip().splitlines()[-1])
            if label == "evicted" and out["evicted"] != experiments:
                raise RuntimeError(
                    f"rss probe: {out['evicted']}/{experiments} evicted")
            rss[label] = max(1, out["rss1_kb"] - out["rss0_kb"])
        finally:
            shutil.rmtree(root, True)
    row["coord_resident_rss_mb"] = round(rss["resident"] / 1024.0, 1)
    row["coord_evict_rss_mb"] = round(rss["evicted"] / 1024.0, 1)
    row["coord_evict_rss_ratio"] = round(rss["resident"] / rss["evicted"], 2)

    # -- phase 3: transfer priors, warm vs cold --------------------------
    led = MemoryLedger()
    anc_center = tuple(c + 0.02 for c in _T_CENTER)
    anc = Experiment(
        "mt-anc", led, space=build_space(_T_SPACE),
        algorithm={"random": {"seed": seed}}, max_trials=80, pool_size=1,
    ).configure()
    rng = random.Random(seed)
    for _ in range(64):
        params = {
            f"x{d}": min(1.0, max(0.0, anc_center[d] + rng.gauss(0.0, 0.1)))
            for d in range(len(anc_center))
        }
        try:
            anc.ledger.register(anc.make_trial(params))
        except Exception:
            continue  # duplicate sample: 63 ancestors serve as well as 64
    while True:
        trial = anc.reserve_trial("mt-anc-w")
        if trial is None:
            break
        val = sum((trial.params[f"x{d}"] - anc_center[d]) ** 2
                  for d in range(len(anc_center)))
        anc.push_results(trial, [
            {"type": "objective", "name": "loss", "value": val}])

    cold_best, cold_used, cold_s = _transfer_study(
        led, "mt-cold", _T_CENTER, transfer_budget, seed + 1)
    warm_best, warm_used, warm_s = _transfer_study(
        led, "mt-warm", _T_CENTER, transfer_budget, seed + 2,
        transfer_from=["mt-anc"], stop_at=cold_best)
    row["transfer_cold_best"] = round(cold_best, 6)
    row["transfer_warm_best"] = round(warm_best, 6)
    row["transfer_cold_trials"] = cold_used
    row["transfer_warm_trials"] = warm_used
    row["transfer_warm_trials_ratio"] = round(
        warm_used / max(1, cold_used), 3)
    row["transfer_time_to_good_s"] = round(warm_s, 3)
    row["transfer_cold_time_s"] = round(cold_s, 3)
    return row


def run_fused_suggest(residents: int = 256, rounds: int = 4,
                      bucket_max: int = 32, n_obs: int = 10,
                      seed: int = 0) -> dict:
    """Fleet-fused suggest plane vs per-experiment launches, same run.

    ``residents`` bare TPE instances (no server, no RPC — the suggest
    plane alone) share one space and one observation count, so they all
    land in ONE static bucket key and the fused plane's launch count per
    sweep is ceil(residents / bucket_max). Each measured round creates
    identical demand on both legs (the prefetch pool is emptied at the
    live fit, exactly the post-``observe`` state SuggestAhead races to
    refill), then serves one suggestion per experiment:

    - **serial** — the shipped per-experiment plane, reproduced
      faithfully: each experiment's demand is served by its OWN
      SuggestAhead refill (``_suggest_ahead_work`` on its own thread —
      exactly what ``observe()`` fires), each paying one
      ``pool_prefetch``-wide launch + blocking readback: O(residents)
      threads and launches per tick.
    - **fused** — ONE ``SuggestFuser.fuse`` sweep column-stacks every
      snapshot and launches once per pow2 bucket, then every experiment
      serves from its refilled pool: O(buckets) launches, zero spawned
      threads.

    The automatic post-observe refill firing is suppressed on every
    instance so neither leg races a stray background thread for the
    demand — the serial leg then spawns the refill threads itself,
    deterministically, which is the same stampede with the same
    per-experiment work bodies. Both legs end with every pool refilled
    at the same width and one suggestion served per experiment.
    Bit-identity of the fused pool is the property suite's job
    (tests/unit/test_fused_suggest.py); this driver asserts every
    experiment actually fused (zero fallbacks) so the speedup is never
    quietly measuring the fallback path.

      fleet_suggest_speedup     serial_wall / fused_wall (gate: >=3 at
                                256 residents)
      suggest_launches_per_tick fused launches per sweep (gate: <=
                                2 * buckets)
    """
    from metaopt_tpu.algo import TPE
    from metaopt_tpu.coord.fuser import SuggestFuser
    from metaopt_tpu.ledger.trial import Trial
    from metaopt_tpu.space import build_space

    rng = __import__("random").Random(seed)
    space = build_space(SPACE)
    named = []
    for i in range(residents):
        algo = TPE(space, seed=seed + i, n_initial_points=5,
                   pool_prefetch=8)
        # deterministic demand: the background refill must not race the
        # measured legs for it (instance attr shadows the class method)
        algo._suggest_ahead_ready = lambda: False
        trials = []
        for _ in range(n_obs):
            params = {"lr": 10 ** rng.uniform(-5, -1),
                      "mom": rng.uniform(0, 1)}
            t = Trial(params=params, experiment=f"fs-exp{i}")
            t.lineage = space.hash_point(params)
            t.transition("reserved")
            t.attach_results([{
                "name": "loss", "type": "objective",
                "value": (params["mom"] - 0.9) ** 2,
            }])
            t.transition("completed")
            trials.append(t)
        algo.observe(trials)
        named.append((f"fs-exp{i}", algo))

    fuser = SuggestFuser(bucket_max=bucket_max)

    def make_demand():
        # the post-observe state: pool empty at the live fit — exactly
        # what fuse_snapshot treats as demand and suggest() refills
        for _, a in named:
            with a._kernel_lock:
                a._prefetch = []
                a._prefetch_n_obs = len(a._y)

    def serial_leg():
        make_demand()
        t0 = time.perf_counter()
        refills = [threading.Thread(target=a._suggest_ahead_work,
                                    daemon=True) for _, a in named]
        for th in refills:
            th.start()
        for th in refills:
            th.join()
        for _, a in named:
            a.suggest(1)
        return time.perf_counter() - t0

    def fused_leg():
        make_demand()
        t0 = time.perf_counter()
        stats = fuser.fuse(named)
        for _, a in named:
            a.suggest(1)
        return time.perf_counter() - t0, stats

    # warmup: compile the solo and the fleet kernel variants outside the
    # measured window (one-time tracing would otherwise dominate round 0)
    serial_leg()
    _, warm_stats = fused_leg()
    if warm_stats["fallback"] or warm_stats["fused"] != residents:
        raise RuntimeError(
            f"fused sweep fell back: {warm_stats} for {residents} "
            "residents — the speedup would measure the fallback path")

    serial_s, fused_s, launches = 0.0, 0.0, []
    base_launches = sum(a._launches for _, a in named)
    for r in range(rounds):
        # alternate which leg goes first: allocator/cache warm-up inside
        # one process would otherwise favor the later-scheduled leg
        if r % 2 == 0:
            serial_s += serial_leg()
            dt, stats = fused_leg()
        else:
            dt, stats = fused_leg()
            serial_s += serial_leg()
        fused_s += dt
        launches.append(stats["launches"])
    # _launches counts per-experiment kernel launches only — the fused
    # plane's bucket launches live in the fuser's own telemetry
    serial_launches = (sum(a._launches for _, a in named)
                       - base_launches) / rounds

    buckets = -(-residents // max(1, fuser.bucket_max))
    tel = fuser.telemetry()
    return {
        "mode": "fused-suggest",
        "residents": residents,
        "rounds": rounds,
        "bucket_max": fuser.bucket_max,
        "n_obs": n_obs,
        "serial_wall_s": round(serial_s, 4),
        "fused_wall_s": round(fused_s, 4),
        "fleet_suggest_speedup": round(serial_s / max(fused_s, 1e-9), 2),
        "suggest_launches_per_tick": max(launches),
        "serial_launches_per_tick": round(serial_launches, 1),
        "buckets_per_tick": buckets,
        "bucket_occupancy": tel["last_occupancy"],
        "fused_experiments": tel["fused_experiments"],
        "fallback_experiments": tel["fallback_experiments"],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", nargs="*", type=int, default=[1, 8, 32])
    ap.add_argument("--modes", nargs="*", default=["serial", "fused"])
    ap.add_argument(
        "--shards", nargs="*", type=int, default=None, metavar="N",
        help="also run the sharded deployment at these shard counts; "
             "implies a fused+wal in-process baseline at the same "
             "multi-experiment workload in the SAME run (ratios, not "
             "cross-session absolutes)",
    )
    ap.add_argument(
        "--shard-experiments", type=int, default=4,
        help="experiments the sharded (and its baseline) workload spreads "
             "across shards — one experiment lives on one shard, so "
             "sharding can only scale a multi-experiment pod",
    )
    ap.add_argument("--trials-per-worker", type=int, default=16)
    ap.add_argument("--produce-coalesce-ms", type=float, default=0.0)
    ap.add_argument(
        "--repeats", type=int, default=1,
        help="runs per config; the median-throughput row is reported "
             "(one-core boxes jitter ±10%% run to run)",
    )
    ap.add_argument(
        "--recovery", action="store_true",
        help="also time crash recovery (restore + WAL replay) of a "
             "2000-trial log",
    )
    ap.add_argument(
        "--handoff", action="store_true",
        help="also time a live experiment hand-off between 2 shards and "
             "a kill-triggered failover redistribution",
    )
    ap.add_argument(
        "--multitenant", action="store_true",
        help="also run the 1k-experiment multi-tenant service row: "
             "fairness under a hot tenant, evicted-vs-resident RSS, "
             "warm-vs-cold transfer priors (all same-run figures)",
    )
    ap.add_argument(
        "--experiments", type=int, default=1000,
        help="fleet size for --multitenant (default 1000)",
    )
    ap.add_argument(
        "--fused-suggest", action="store_true",
        help="also run the fleet-fused suggest plane rows: one "
             "SuggestFuser sweep (O(buckets) launches) vs per-experiment "
             "inline launches (O(residents)) over the same demand, "
             "same-run ratio per resident count",
    )
    ap.add_argument(
        "--residents", nargs="*", type=int, default=[64, 256],
        help="resident-experiment counts for --fused-suggest "
             "(default 64 256; the >=3x gate rides the 256 row)",
    )
    ap.add_argument(
        "--fuse-bucket-max", type=int, default=32,
        help="fused-suggest bucket width cap (rounded down to pow2; 32 "
             "is the one-core sweet spot — wider buckets amortize "
             "launch overhead further but lengthen each program)",
    )
    ap.add_argument("--save", action="store_true")
    args = ap.parse_args()

    from metaopt_tpu.utils.provenance import provenance

    # each config is (key, mode, extra run_scale kwargs); the sharded
    # configs ride as pseudo-modes so they interleave with the in-process
    # baselines inside the SAME repeat loop (ratio doctrine: never compare
    # a sharded number against a baseline from a different invocation)
    configs = [(m, m, {}) for m in args.modes]
    # binary-vs-JSON: the same fused deployment with the client pinned to
    # the v1 JSON codec, interleaved in the same repeat loop — the wire
    # speedup is a same-run ratio like every other headline here
    from metaopt_tpu.coord.protocol import HAVE_WIRE_V2
    if HAVE_WIRE_V2 and "fused" in args.modes:
        configs.append(("fused-json", "fused", {"wire": "v1"}))
    if args.shards:
        exp = args.shard_experiments
        # the sharded figure is meaningless without the same-durability
        # in-process baseline at the same multi-experiment workload — a
        # dedicated config even when fused+wal is also listed in --modes,
        # because that one runs the single-experiment workload
        configs.append(("wal-base", "fused+wal", {"experiments": exp}))
        for s in args.shards:
            configs.append((f"shard{s}", "sharded",
                            {"shards": s, "experiments": exp}))

    rows = []
    by: dict = {}
    for n in args.workers:
        # interleave the configs within each repeat, alternating which goes
        # first: a long-lived process speeds up run over run (allocator and
        # cache warm-up), so consecutive same-mode repeats would hand the
        # later-scheduled mode a systematic advantage
        per_key: dict = {k: [] for k, _, _ in configs}
        errors: dict = {}
        for r in range(max(1, args.repeats)):
            order = (list(configs) if r % 2 == 0
                     else list(reversed(configs)))
            for key, mode, extra in order:
                try:
                    per_key[key].append(run_scale(
                        n, mode=mode,
                        trials_per_worker=args.trials_per_worker,
                        produce_coalesce_ms=args.produce_coalesce_ms,
                        **extra,
                    ))
                except Exception as err:
                    errors[key] = f"{type(err).__name__}: {err}"
        for key, mode, _ in configs:
            reps = sorted(per_key[key],
                          key=lambda r: r["trials_per_s"] or 0)
            if not reps:
                row = {"mode": mode, "workers": n,
                       "error": errors.get(key, "no successful runs")}
            else:
                row = reps[len(reps) // 2]  # median by throughput
                if len(reps) > 1:
                    row["repeats"] = len(reps)
                    row["trials_per_s_all"] = [
                        r["trials_per_s"] for r in reps
                    ]
            row.update(provenance())
            print(json.dumps(row), flush=True)
            rows.append(row)
            by[(key, n)] = row
    # the headline ratio: fused vs serial at
    # the widest fan-in measured in the SAME run on the SAME machine
    widest = max(args.workers) if args.workers else 0
    f, s = by.get(("fused", widest)), by.get(("serial", widest))
    if f and s and f.get("trials_per_s") and s.get("trials_per_s"):
        print(json.dumps({
            "summary": f"fused_vs_serial_{widest}w",
            "speedup": round(f["trials_per_s"] / s["trials_per_s"], 2),
            "fused_trials_per_s": f["trials_per_s"],
            "serial_trials_per_s": s["trials_per_s"],
            "fused_rpcs_per_trial": f.get("rpcs_per_trial"),
            "serial_rpcs_per_trial": s.get("rpcs_per_trial"),
        }), flush=True)
    # the wire tax: binary (negotiated v2) vs pinned-JSON on the same
    # fused deployment in the same run; bytes/trial rides along so the
    # size win is visible next to the throughput win
    j = by.get(("fused-json", widest))
    if f and j and f.get("trials_per_s") and j.get("trials_per_s"):
        print(json.dumps({
            "summary": f"wire_v2_vs_json_{widest}w",
            "speedup": round(f["trials_per_s"] / j["trials_per_s"], 2),
            "binary_trials_per_s": f["trials_per_s"],
            "json_trials_per_s": j["trials_per_s"],
            "coord_wire_bytes_per_trial": f.get("wire_bytes_per_trial"),
            "json_wire_bytes_per_trial": j.get("wire_bytes_per_trial"),
        }), flush=True)
    # the durability tax: fused+wal vs fused in the same run
    w = by.get(("fused+wal", widest))
    if f and w and f.get("trials_per_s") and w.get("trials_per_s"):
        print(json.dumps({
            "summary": f"wal_overhead_{widest}w",
            "wal_overhead_pct": round(
                100.0 * (1.0 - w["trials_per_s"] / f["trials_per_s"]), 1),
            "fused_trials_per_s": f["trials_per_s"],
            "fused_wal_trials_per_s": w["trials_per_s"],
            "wal_batches": w.get("wal_batches"),
            "wal_records": w.get("wal_records"),
        }), flush=True)
    if args.shards:
        base = by.get(("wal-base", widest))
        one = by.get(("shard1", widest))
        # the process tax: 1 sharded subprocess (WAL on) vs the in-process
        # durable server on the SAME multi-experiment workload — the figure
        # to read on a one-core box, where scaling can't show
        if (base and one and base.get("trials_per_s")
                and one.get("trials_per_s")):
            print(json.dumps({
                "summary": f"shard_overhead_{widest}w",
                "shard_overhead_pct": round(
                    100.0 * (1.0 - one["trials_per_s"]
                             / base["trials_per_s"]), 1),
                "inproc_wal_trials_per_s": base["trials_per_s"],
                "shard1_trials_per_s": one["trials_per_s"],
                "experiments": args.shard_experiments,
            }), flush=True)
        # shard scaling: every count vs shard1, same run (≥1.7x at 2 shards
        # is the multi-core acceptance figure; ~1.0x expected on one core)
        if one and one.get("trials_per_s"):
            for s in sorted(set(args.shards)):
                if s == 1:
                    continue
                rs = by.get((f"shard{s}", widest))
                if rs and rs.get("trials_per_s"):
                    print(json.dumps({
                        "summary": f"shard_scaling_{s}x_{widest}w",
                        "speedup_vs_shard1": round(
                            rs["trials_per_s"] / one["trials_per_s"], 2),
                        "shard1_trials_per_s": one["trials_per_s"],
                        f"shard{s}_trials_per_s": rs["trials_per_s"],
                        "experiments": args.shard_experiments,
                    }), flush=True)
    if args.recovery:
        row = run_recovery()
        row.update(provenance())
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.handoff:
        row = run_handoff()
        row.update(provenance())
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.multitenant:
        row = run_multitenant(experiments=args.experiments)
        row.update(provenance())
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.fused_suggest:
        fs_by = {}
        for n in sorted(set(args.residents)):
            row = run_fused_suggest(
                residents=n, bucket_max=args.fuse_bucket_max)
            row.update(provenance())
            print(json.dumps(row), flush=True)
            rows.append(row)
            fs_by[n] = row
        # the headline: the widest fleet's
        # same-run fused-vs-serial ratio and its launch amortization
        top = fs_by[max(fs_by)]
        print(json.dumps({
            "summary": f"fleet_suggest_{top['residents']}r",
            "fleet_suggest_speedup": top["fleet_suggest_speedup"],
            "suggest_launches_per_tick": top["suggest_launches_per_tick"],
            "serial_launches_per_tick": top["serial_launches_per_tick"],
            "buckets_per_tick": top["buckets_per_tick"],
            "residents": top["residents"],
        }), flush=True)
    if args.save:
        stamp = time.strftime("%Y-%m-%d")
        path = os.path.join(REPO, "benchmarks", "results",
                            f"coord_scale_{stamp}.jsonl")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "a") as fh:
            for row in rows:
                fh.write(json.dumps(row) + "\n")
        print(f"saved -> {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
