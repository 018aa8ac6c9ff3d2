#!/usr/bin/env python
"""Columnar trial-archive scale benchmark: RSS, snapshot latency, serve
pauses, and observe ingest at 100k / 1M completed trials.

The archive (`metaopt_tpu/ledger/archive.py`) exists for exactly three
numbers, and this driver measures all of them same-run:

* **RSS per completed trial** — archived (structure-of-arrays segments)
  vs all-resident (`--no-trial-archive` equivalent), each in its OWN
  subprocess so the interpreter baseline and allocator state cannot
  bleed between configs. The headline `coord_archive_rss_ratio` is
  resident-bytes-per-trial / archived-bytes-per-trial at the largest
  scale.
* **Snapshot latency** — the first v2 snapshot (every sealed segment
  written once), a dirty-tail incremental snapshot (only the mutable
  head + dirty sections reserialize; `coord_snapshot_ms_1m`), and a
  forced v1 full dump of the same state; `coord_snapshot_incr_speedup`
  is full/incremental. A prober thread hammers `count()` over TCP
  through every snapshot and reports the p99 RPC latency
  (`coord_serve_pause_ms_p99`) — the serve-loop pause bound.
* **Observe ingest** — `fetch_completed_since` batches fed to TPE via
  the columnar `_observe_batch` fast path vs the same data observed
  through the per-trial dict path; the columnar path must not be
  slower (it skips per-trial doc materialization entirely).

Ingest goes straight into the inner ledger (the RPC plane is
coord_scale.py's subject, not this one's); snapshots and the pause probe
run against the real started server.

    python benchmarks/archive_scale.py [--scales 100000 1000000]
                                       [--observe-n 20000] [--save]

Emits one JSON line per (mode, scale) probe plus an `observe` row and a
`summary` row carrying the headline keys.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SEGMENT_ROWS = 4096


def _rss_bytes() -> int:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) * 1024
    return 0


def _dir_bytes(path: str) -> int:
    total = 0
    for root, _, files in os.walk(path):
        for fn in files:
            try:
                total += os.path.getsize(os.path.join(root, fn))
            except OSError:
                pass
    return total


def _ingest(server, name: str, n: int, start: int = 0) -> float:
    """Register n completed trials directly into the inner ledger and
    mark the experiment dirty for the snapshot section cache."""
    from metaopt_tpu.ledger import Trial

    t0 = time.perf_counter()
    for i in range(start, start + n):
        # unique params per row: trial ids are content-derived
        t = Trial(params={"x": i / 2e9}, experiment=name)
        t.status = "completed"
        t.results = []
        t.attach_results([
            {"name": "objective", "type": "objective", "value": float(i)}
        ])
        server.inner.register(t)
    wall = time.perf_counter() - t0
    with server._exp_lock(name):
        server._mutated(name)
    return wall


def probe(mode: str, n: int) -> dict:
    """One (mode, scale) measurement — run in a fresh subprocess."""
    from metaopt_tpu.coord import CoordLedgerClient, CoordServer

    archived = mode == "archived"
    rss0 = _rss_bytes()
    row: dict = {"kind": "probe", "mode": mode, "trials": n}
    with tempfile.TemporaryDirectory() as td:
        snap = os.path.join(td, "arch.snap")
        with CoordServer(snapshot_path=snap, snapshot_interval_s=3600.0,
                         stale_timeout_s=None,
                         archive_completed=archived,
                         archive_segment_rows=SEGMENT_ROWS) as server:
            server.inner.create_experiment({
                "name": "arch", "space": {"x": "uniform(0, 1)"},
                "algorithm": {"random": {"seed": 0}}, "max_trials": n * 2,
            })
            ingest_s = _ingest(server, "arch", n)
            row["ingest_s"] = round(ingest_s, 3)
            row["ingest_trials_per_s"] = round(n / ingest_s, 1)
            rss1 = _rss_bytes()
            row["rss_bytes"] = rss1 - rss0
            row["rss_bytes_per_trial"] = round((rss1 - rss0) / n, 1)
            if archived:
                row["archive_stats"] = server.inner.archive_stats("arch")

            # pause probe: count() latency over TCP through every
            # snapshot below (the serve loop must stay interactive)
            host, port = server.address
            client = CoordLedgerClient(host=host, port=port)
            stop = threading.Event()
            lat_ms: list = []

            def prober() -> None:
                while not stop.is_set():
                    t0 = time.perf_counter()
                    client.count("arch", "completed")
                    lat_ms.append((time.perf_counter() - t0) * 1e3)

            pt = threading.Thread(target=prober, daemon=True)
            pt.start()

            t0 = time.perf_counter()
            server.snapshot(snap)  # writes every sealed segment once
            row["snap_first_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
            row["snap_bytes"] = (os.path.getsize(snap)
                                 + _dir_bytes(snap + ".segments"))

            # dirty tail: 1000 fresh completions, then the incremental
            # snapshot only reserializes the mutable part
            _ingest(server, "arch", 1000, start=n)
            incr_lo = len(lat_ms)
            t0 = time.perf_counter()
            server.snapshot(snap)
            row["snap_incr_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
            incr_pause = lat_ms[incr_lo:] or [0.0]

            # the counterfactual: a v1 full dump of the same state
            server.snapshot_incremental = False
            t0 = time.perf_counter()
            server.snapshot(snap)
            row["snap_full_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
            server.snapshot_incremental = True
            # leave a v2 manifest behind so stop()'s final snapshot is
            # cheap and the tempdir teardown sees the segment dir
            server.snapshot(snap)

            stop.set()
            pt.join(timeout=10.0)
            row["pause_ms_p99"] = round(
                statistics.quantiles(lat_ms, n=100)[98]
                if len(lat_ms) >= 100 else max(lat_ms), 2)
            row["pause_incr_ms_max"] = round(max(incr_pause), 2)
            row["snap_incr_speedup"] = round(
                row["snap_full_ms"] / max(row["snap_incr_ms"], 0.1), 1)
    return row


def probe_observe(n: int) -> dict:
    """Columnar vs per-trial observe ingest into TPE, same data."""
    from metaopt_tpu.algo import make_algorithm
    from metaopt_tpu.ledger.backends import MemoryLedger
    from metaopt_tpu.ledger import Trial
    from metaopt_tpu.space import build_space

    ledger = MemoryLedger(archive_segment_rows=SEGMENT_ROWS)
    ledger.create_experiment({
        "name": "obs", "space": {"x": "uniform(0, 1)"},
        "algorithm": {"tpe": {"seed": 0}}, "max_trials": n * 2,
    })
    for i in range(n):
        t = Trial(params={"x": (i + 0.5) / n}, experiment="obs")
        t.status = "completed"
        t.results = []
        t.attach_results([
            {"name": "objective", "type": "objective", "value": float(i)}
        ])
        ledger.register(t)
    ledger.seal_archive("obs")
    space = build_space({"x": "uniform(0, 1)"})
    batch, _ = ledger.fetch_completed_since("obs", None)
    assert batch.columns() is not None, "batch must be columnizable"

    algo_col = make_algorithm(space, {"tpe": {"seed": 0}})
    t0 = time.perf_counter()
    algo_col.observe(batch)  # rides TPE._observe_batch off the columns
    col_s = time.perf_counter() - t0

    trials = list(batch)  # materialized per-trial (the dict path)
    algo_dict = make_algorithm(space, {"tpe": {"seed": 0}})
    t0 = time.perf_counter()
    algo_dict.observe(trials)
    dict_s = time.perf_counter() - t0

    assert len(algo_col._X) == len(algo_dict._X) == n
    return {
        "kind": "observe", "trials": n,
        "observe_columnar_trials_per_s": round(n / col_s, 1),
        "observe_dict_trials_per_s": round(n / dict_s, 1),
        "observe_columnar_speedup": round(dict_s / col_s, 2),
    }


def _run_child(mode: str, n: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--probe", mode, str(n)],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=3600,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"probe {mode}@{n} failed rc={out.returncode}:\n{out.stderr}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scales", type=int, nargs="+",
                    default=[100_000, 1_000_000])
    ap.add_argument("--observe-n", type=int, default=20_000)
    ap.add_argument("--save", action="store_true")
    ap.add_argument("--probe", nargs=2, metavar=("MODE", "N"),
                    help="internal: run one (mode, n) probe and exit")
    args = ap.parse_args()

    if args.probe:
        print(json.dumps(probe(args.probe[0], int(args.probe[1]))))
        return 0

    from metaopt_tpu.utils.provenance import provenance

    rows = []
    by: dict = {}
    for n in args.scales:
        for mode in ("archived", "resident"):
            row = _run_child(mode, n)
            row.update(provenance())
            by[(mode, n)] = row
            print(json.dumps(row), flush=True)
            rows.append(row)
    obs = probe_observe(args.observe_n)
    obs.update(provenance())
    print(json.dumps(obs), flush=True)
    rows.append(obs)

    top = max(args.scales)
    a, r = by[("archived", top)], by[("resident", top)]
    summary = {
        "kind": "summary", "trials": top,
        # the headline keys
        "coord_rss_bytes_per_trial_1m": a["rss_bytes_per_trial"],
        "coord_archive_rss_ratio": round(
            r["rss_bytes_per_trial"] / a["rss_bytes_per_trial"], 2),
        "coord_snapshot_ms_1m": a["snap_incr_ms"],
        "coord_snapshot_incr_speedup": a["snap_incr_speedup"],
        "coord_serve_pause_ms_p99": a["pause_ms_p99"],
        "observe_columnar_trials_per_s":
            obs["observe_columnar_trials_per_s"],
        "observe_dict_trials_per_s": obs["observe_dict_trials_per_s"],
        "snap_bytes_archived": a["snap_bytes"],
        "snap_bytes_resident": r["snap_bytes"],
    }
    summary.update(provenance())
    print(json.dumps(summary), flush=True)
    rows.append(summary)

    if args.save:
        stamp = time.strftime("%Y-%m-%d")
        path = os.path.join(REPO, "benchmarks", "results",
                            f"archive_scale_{stamp}.jsonl")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "a") as fh:
            for row in rows:
                fh.write(json.dumps(row) + "\n")
        print(f"saved -> {path}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
