#!/usr/bin/env python
"""Regression gate for the headline suggest-latency metric.

Compares a bench artifact (newest ``benchmarks/results/bench_*.json`` by
default) against the most recent committed round record (``BENCH_r*.json``)
on ``tpe_suggest_ms_per_point_10k_obs_pool8`` and exits non-zero when the
headline regressed by more than ``--threshold`` (default 10%).

Doctrine:

- **Like-for-like substrate**: a CPU artifact is judged ONLY against CPU
  round baselines and a TPU artifact only against TPU ones (``bench.py``
  itself now runs on the TPU only; older CPU artifacts remain readable).
- No matching-substrate baseline → informational pass (nothing to gate
  against; first round on a new substrate must not fail).

Usage::

    python benchmarks/check_regression.py [--artifact PATH] [--threshold 0.10]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

METRIC = "tpe_suggest_ms_per_point_10k_obs_pool8"
#: coordinator control-plane throughput (higher is better, gated inversely)
COORD_METRIC = "coord_trials_per_s_32w"
#: durability metrics (informational until a committed baseline carries
#: them; then the WAL tax gates like a regression — lower is better)
WAL_METRIC = "coord_wal_overhead_pct"
RECOVERY_METRIC = "coord_recovery_time_s"
#: binary wire (protocol v2): on-wire bytes per trial at 32 workers
#: (lower is better, ratio gate — a codec change that bloats frames
#: shows up here before it shows up in throughput) and the same-run
#: binary-vs-JSON throughput speedup, which must hold its absolute
#: acceptance floor wherever the binary wire negotiated at all
WIRE_BYTES_METRIC = "coord_wire_bytes_per_trial"
WIRE_SPEEDUP_METRIC = "coord_wire_speedup_32w"
WIRE_SPEEDUP_FLOOR = 1.15
#: sharded deployment: per-shard-count throughput (higher is better,
#: inverse gate like COORD_METRIC) and the 1-shard process tax vs the
#: in-process durable server (lower is better, pct-point slack like the
#: WAL tax). All informational until a committed baseline carries them.
SHARD_TPS_METRICS = ("coord_trials_per_s_shard1", "coord_trials_per_s_shard2",
                     "coord_trials_per_s_shard4")
SHARD_OVERHEAD_METRIC = "coord_shard_overhead_pct"
#: live hand-off / failover wall-clock (lower is better). Single-shot
#: process-level latencies (fence+drain+ship / death-to-redistributed),
#: so the slack is wider than the throughput threshold — a 20 ms figure
#: jitters far more run-to-run than a 3-rep throughput median does.
#: Informational until a committed baseline carries them.
HANDOFF_METRICS = ("coord_handoff_ms", "coord_failover_time_s")
HANDOFF_SLACK = 0.50
#: GP-BO incremental fast path: per-point suggest latency (lower is
#: better; the key embeds the observation count, which differs by
#: substrate — 10k on TPU, the 1k side key in older CPU artifacts — so the
#: gate matches artifact and baseline on the SAME key)
GP_METRICS = ("gp_suggest_ms_per_point_10k_obs",
              "gp_suggest_ms_per_point_1k_obs")
#: incremental-vs-full-refit ratio (higher is better); CPU artifacts
#: additionally enforce the absolute acceptance floor
GP_SPEEDUP_METRIC = "gp_incremental_speedup_vs_full_refit"
GP_SPEEDUP_FLOOR = 3.0
#: speculative suggest-ahead effectiveness (higher is better)
HIT_RATE_METRICS = ("gp_prefetch_hit_rate", "tpe_prefetch_hit_rate")
#: batched trial evaluation: pooled-vmap throughput at pool 8/64 (higher
#: is better, inverse gate like COORD_METRIC) and the same-run
#: pooled-vs-per-trial speedup (higher is better; CPU artifacts
#: additionally enforce the absolute acceptance floor, like the GP
#: ratio). Informational until a committed baseline carries them.
BATCH_TPS_METRICS = ("batch_eval_trials_per_s_pool8",
                     "batch_eval_trials_per_s_pool64")
BATCH_SPEEDUP_METRIC = "batch_eval_speedup"
BATCH_SPEEDUP_FLOOR = 3.0
#: multi-tenant service plane (ISSUE 16). The fairness floor ENFORCES the
#: moment the artifact carries the metric — fairness under a hot tenant is
#: the tentpole's acceptance bar, not a drift watch, so there is no
#: informational-until-baselined grace for it. Likewise the residency
#: ratio (evicted fleet must cost ≥3x less RSS than all-resident) and the
#: transfer bar (warm start reaches the cold study's best in ≤ half the
#: trials). The 1k-experiment throughput gates inversely once a committed
#: baseline carries it, like every other throughput here.
FAIRNESS_METRIC = "coord_fairness_jain_1k"
FAIRNESS_FLOOR = 0.9
EVICT_RSS_METRIC = "coord_evict_rss_ratio"
EVICT_RSS_FLOOR = 3.0
TRANSFER_METRIC = "transfer_warm_trials_ratio"
TRANSFER_CEILING = 0.5
MT_TPS_METRIC = "coord_trials_per_s_1k_exp"
#: fleet-fused suggest plane (ISSUE 20). The same-run fused-vs-serial
#: wall-clock ratio at the widest resident TPE fleet ENFORCES its
#: absolute floor the moment the artifact carries it — a paired
#: host-CPU ratio (both legs share one process, one fit state, one
#: run), so substrate drift cannot fake a pass. The launch-amortization
#: claim (O(buckets) fleet launches, not O(residents) solo launches)
#: enforces structurally whenever the artifact carries both sides:
#: fused launches per tick must stay within 2x the bucket count.
FLEET_SPEEDUP_METRIC = "fleet_suggest_speedup"
FLEET_SPEEDUP_FLOOR = 3.0
FLEET_LAUNCHES_METRIC = "suggest_launches_per_tick"
FLEET_BUCKETS_METRIC = "buckets_per_tick"
#: columnar completed-trial archive (ISSUE 17). Drift watches (lower is
#: better, informational until a committed baseline carries them): bytes
#: of coordinator RSS per completed trial at 1M, wall-clock of one
#: incremental snapshot at 1M, and the serve-loop p99 pause while
#: snapshots run. Single-shot host figures, so they gate with the wide
#: hand-off-style slack, not the 10% throughput threshold.
ARCHIVE_DRIFT_METRICS = ("coord_rss_bytes_per_trial_1m",
                         "coord_snapshot_ms_1m",
                         "coord_serve_pause_ms_p99")
ARCHIVE_SLACK = 0.50
#: same-run ratio floors that ENFORCE the moment the artifact carries
#: them (the tentpole's acceptance bars, substrate-independent): the
#: archived coordinator must hold ≥5x less RSS than the all-resident
#: control, and an incremental snapshot of a clean-but-one fleet must
#: beat a full dump by ≥10x
ARCHIVE_RSS_METRIC = "coord_archive_rss_ratio"
ARCHIVE_RSS_FLOOR = 5.0
SNAP_SPEEDUP_METRIC = "coord_snapshot_incr_speedup"
SNAP_SPEEDUP_FLOOR = 10.0
#: discrete-event scale simulator (ISSUE 18). The certification counters
#: ENFORCE at zero whenever an artifact carries them — a promotion
#: violation, an acked-write loss, or a duplicated retry effect at 100k
#: simulated workers is a correctness failure, never drift. The Jain
#: fairness index at the headline scale holds the same 0.9 floor as the
#: live multi-tenant benchmark. Recovery seconds per 10k replayed WAL
#: records is a drift watch: a single-shot host figure, so it gates with
#: the wide hand-off-style slack once a committed baseline carries it.
#: Like the 1M-trial archive probes, the 100k run is too heavy for
#: bench.py's live pass — the gate falls back to the newest committed
#: sim_scale summary row when the bench artifact lacks the keys.
SIM_ZERO_METRICS = ("sim_asha_promotion_violations",
                    "sim_acked_write_losses",
                    "sim_exactly_once_violations")
SIM_JAIN_METRIC = "sim_jain_100k_workers"
SIM_RECOVERY_METRIC = "sim_recovery_s_per_10k_wal"
SIM_SLACK = 0.50
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def newest_artifact() -> str:
    paths = glob.glob(os.path.join(REPO, "benchmarks", "results",
                                   "bench_*.json"))
    if not paths:
        raise SystemExit("no bench artifact under benchmarks/results/ — "
                         "run `python bench.py` first")
    return max(paths, key=os.path.getmtime)


def archive_summary() -> dict:
    """Summary row of the newest committed archive_scale artifact.

    Returns the gate-relevant keys plus ``_source`` (the file it came
    from), or ``{}`` when no artifact carries a summary row.
    """
    paths = sorted(glob.glob(os.path.join(REPO, "benchmarks", "results",
                                          "archive_scale_*.jsonl")))
    for path in reversed(paths):
        try:
            with open(path) as f:
                rows = [json.loads(ln) for ln in f if ln.strip()]
        except (OSError, ValueError):
            continue
        for row in reversed(rows):
            if row.get("kind") == "summary":
                keep = {k: row[k] for k in
                        (ARCHIVE_RSS_METRIC, SNAP_SPEEDUP_METRIC,
                         *ARCHIVE_DRIFT_METRICS, "commit", "trials")
                        if k in row}
                keep["_source"] = os.path.basename(path)
                return keep
    return {}


def sim_summary() -> dict:
    """Summary row of the newest committed sim_scale artifact.

    Same shape as :func:`archive_summary`: the gate-relevant keys plus
    ``_source``, or ``{}`` when no artifact carries a summary row.
    """
    paths = sorted(glob.glob(os.path.join(REPO, "benchmarks", "results",
                                          "sim_scale_*.jsonl")))
    for path in reversed(paths):
        try:
            with open(path) as f:
                rows = [json.loads(ln) for ln in f if ln.strip()]
        except (OSError, ValueError):
            continue
        for row in reversed(rows):
            if row.get("kind") == "summary":
                keep = {k: row[k] for k in
                        (*SIM_ZERO_METRICS, SIM_JAIN_METRIC,
                         SIM_RECOVERY_METRIC, "commit", "workers")
                        if k in row}
                keep["_source"] = os.path.basename(path)
                return keep
    return {}


def load_artifact(path: str) -> dict:
    with open(path) as f:
        rec = json.load(f)
    if rec.get("metric") != METRIC or "value" not in rec:
        raise SystemExit(f"{path}: not a {METRIC} bench record")
    extra = rec.get("extra") or {}
    backend = (extra.get("backend") or rec.get("backend")
               or rec.get("platform"))
    coord = extra.get(COORD_METRIC)
    wal = extra.get(WAL_METRIC)
    recovery = extra.get(RECOVERY_METRIC)
    return {"value": float(rec["value"]), "backend": backend or "unknown",
            "coord": float(coord) if coord else None,
            "wal_overhead": float(wal) if wal is not None else None,
            "recovery": float(recovery) if recovery is not None else None,
            "extra": extra,
            "path": path}


def round_baselines() -> list:
    """(round_name, backend, value) for every committed BENCH_r*.json,
    oldest→newest (names embed the round number, so lexical order works).

    ``benchmarks/baseline.json``, when committed, rides last as the
    newest round: a synthetic baseline capturing bench rows the round
    records predate, so their "informational until baselined" gates
    start enforcing without waiting for the next full round."""
    out = []
    paths = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")))
    paths.append(os.path.join(REPO, "benchmarks", "baseline.json"))
    for path in paths:
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = rec.get("parsed") or {}
        if parsed.get("metric") == METRIC and "value" in parsed:
            out.append((os.path.basename(path),
                        parsed.get("backend", "unknown"),
                        float(parsed["value"]),
                        parsed))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--artifact", default=None,
                    help="bench artifact to check (default: newest under "
                         "benchmarks/results/)")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="allowed fractional regression (default 0.10)")
    args = ap.parse_args()

    art = load_artifact(args.artifact or newest_artifact())
    if art["backend"] != "tpu":
        print(f"WARNING: artifact is a {art['backend']} run — not a device "
              "measurement; gating CPU-vs-CPU only")

    rc = 0
    matching = [b for b in round_baselines() if b[1] == art["backend"]]
    if not matching:
        print(f"no committed {art['backend']} baseline in BENCH_r*.json — "
              "nothing to gate against (pass)")
    else:
        base_name, _, base_value, _ = matching[-1]
        ratio = art["value"] / base_value
        verdict = (f"{METRIC}: {art['value']:.3f} ms vs {base_value:.3f} ms "
                   f"({base_name}, {art['backend']}) → {ratio:.3f}x")
        if ratio > 1.0 + args.threshold:
            print(f"FAIL {verdict} — regressed past the "
                  f"{args.threshold:.0%} threshold")
            rc = 1
        else:
            print(f"OK {verdict}")

    # coordinator throughput gate: HIGHER is better, so the fail direction
    # inverts (new < baseline * (1 - threshold)). A baseline round that
    # predates the metric, or an artifact missing it, is an informational
    # pass — the first round recording it must not fail itself
    coord_bases = [b for b in matching if b[3].get(COORD_METRIC)]
    if art.get("coord") is None or not coord_bases:
        print(f"{COORD_METRIC}: artifact or committed baseline missing the "
              "metric — nothing to gate against (pass)")
    else:
        cb_name, _, _, cb_parsed = coord_bases[-1]
        coord_base = float(cb_parsed[COORD_METRIC])
        cratio = art["coord"] / coord_base
        cverdict = (f"{COORD_METRIC}: {art['coord']:.0f} vs {coord_base:.0f} "
                    f"trials/s ({cb_name}, {art['backend']}) → {cratio:.3f}x")
        if cratio < 1.0 - args.threshold:
            print(f"FAIL {cverdict} — throughput regressed past the "
                  f"{args.threshold:.0%} threshold")
            rc = 1
        else:
            print(f"OK {cverdict}")

    # durability metrics: the WAL tax gates against the last committed
    # baseline that carries it (lower is better, absolute pct-point slack
    # of `threshold * 100` — a 5pt tax drifting to 6pt is noise, not a
    # regression); recovery time is informational. Baselines predating
    # the metrics pass informationally
    wal_bases = [b for b in matching if b[3].get(WAL_METRIC) is not None]
    if art.get("wal_overhead") is None or not wal_bases:
        print(f"{WAL_METRIC}: artifact or committed baseline missing the "
              "metric — nothing to gate against (pass)")
    else:
        wb_name, _, _, wb_parsed = wal_bases[-1]
        wal_base = float(wb_parsed[WAL_METRIC])
        wverdict = (f"{WAL_METRIC}: {art['wal_overhead']:.1f}% vs "
                    f"{wal_base:.1f}% ({wb_name}, {art['backend']})")
        if art["wal_overhead"] > wal_base + args.threshold * 100.0:
            print(f"FAIL {wverdict} — WAL tax grew past the baseline by "
                  f"more than {args.threshold * 100:.0f} points")
            rc = 1
        else:
            print(f"OK {wverdict}")
    if art.get("recovery") is not None:
        print(f"{RECOVERY_METRIC}: {art['recovery']:.2f}s "
              "(informational — cold restore + WAL replay)")

    # binary wire: bytes/trial gates like a latency (lower is better,
    # ratio threshold) against the last committed baseline carrying it;
    # the binary-vs-JSON speedup holds its absolute floor whenever the
    # artifact reports it (absent = the wire never negotiated v2: pass)
    art_extra0 = art.get("extra") or {}
    wb_val = art_extra0.get(WIRE_BYTES_METRIC)
    wb_bases = [b for b in matching if b[3].get(WIRE_BYTES_METRIC)]
    if wb_val is None or not wb_bases:
        print(f"{WIRE_BYTES_METRIC}: artifact or committed baseline "
              "missing the metric — nothing to gate against (pass)")
    else:
        wbb_name, _, _, wbb_parsed = wb_bases[-1]
        wb_base = float(wbb_parsed[WIRE_BYTES_METRIC])
        wbratio = float(wb_val) / wb_base
        wbverdict = (f"{WIRE_BYTES_METRIC}: {float(wb_val):.0f} vs "
                     f"{wb_base:.0f} bytes ({wbb_name}, {art['backend']}) "
                     f"→ {wbratio:.3f}x")
        if wbratio > 1.0 + args.threshold:
            print(f"FAIL {wbverdict} — frames bloated past the "
                  f"{args.threshold:.0%} threshold")
            rc = 1
        else:
            print(f"OK {wbverdict}")
    wspeed = art_extra0.get(WIRE_SPEEDUP_METRIC)
    if wspeed is None:
        print(f"{WIRE_SPEEDUP_METRIC}: artifact missing the metric — "
              "nothing to gate against (pass)")
    elif float(wspeed) < WIRE_SPEEDUP_FLOOR:
        print(f"FAIL {WIRE_SPEEDUP_METRIC}: {float(wspeed):.2f}x < the "
              f"{WIRE_SPEEDUP_FLOOR:.2f}x acceptance floor")
        rc = 1
    else:
        print(f"OK {WIRE_SPEEDUP_METRIC}: {float(wspeed):.2f}x "
              f"(floor {WIRE_SPEEDUP_FLOOR:.2f}x)")

    # live hand-off / failover: lower is better, gated with the wider
    # HANDOFF_SLACK against the last committed baseline carrying each
    # metric — informational until one does
    for mkey in HANDOFF_METRICS:
        mval = (art.get("extra") or {}).get(mkey)
        m_bases = [b for b in matching if b[3].get(mkey) is not None]
        if mval is None or not m_bases:
            print(f"{mkey}: artifact or committed baseline missing the "
                  "metric — nothing to gate against (pass)")
            continue
        mb_name, _, _, mb_parsed = m_bases[-1]
        m_base = float(mb_parsed[mkey])
        mratio = float(mval) / m_base if m_base else 0.0
        mverdict = (f"{mkey}: {float(mval):.3g} vs {m_base:.3g} "
                    f"({mb_name}, {art['backend']}) → {mratio:.3f}x")
        if m_base and mratio > 1.0 + HANDOFF_SLACK:
            print(f"FAIL {mverdict} — hand-off latency regressed past the "
                  f"{HANDOFF_SLACK:.0%} slack")
            rc = 1
        else:
            print(f"OK {mverdict}")

    # sharded serving: throughputs gate inversely (higher is better) and
    # the 1-shard process tax gates with pct-point slack, each against the
    # last committed baseline that carries it — informational until then
    art_extra = art.get("extra") or {}
    for skey in SHARD_TPS_METRICS:
        sval = art_extra.get(skey)
        s_bases = [b for b in matching if b[3].get(skey)]
        if sval is None or not s_bases:
            print(f"{skey}: artifact or committed baseline missing the "
                  "metric — nothing to gate against (pass)")
            continue
        sb_name, _, _, sb_parsed = s_bases[-1]
        s_base = float(sb_parsed[skey])
        sratio = float(sval) / s_base
        sverdict = (f"{skey}: {float(sval):.0f} vs {s_base:.0f} trials/s "
                    f"({sb_name}, {art['backend']}) → {sratio:.3f}x")
        if sratio < 1.0 - args.threshold:
            print(f"FAIL {sverdict} — throughput regressed past the "
                  f"{args.threshold:.0%} threshold")
            rc = 1
        else:
            print(f"OK {sverdict}")
    so_val = art_extra.get(SHARD_OVERHEAD_METRIC)
    so_bases = [b for b in matching
                if b[3].get(SHARD_OVERHEAD_METRIC) is not None]
    if so_val is None or not so_bases:
        print(f"{SHARD_OVERHEAD_METRIC}: artifact or committed baseline "
              "missing the metric — nothing to gate against (pass)")
    else:
        sob_name, _, _, sob_parsed = so_bases[-1]
        so_base = float(sob_parsed[SHARD_OVERHEAD_METRIC])
        soverdict = (f"{SHARD_OVERHEAD_METRIC}: {float(so_val):.1f}% vs "
                     f"{so_base:.1f}% ({sob_name}, {art['backend']})")
        if float(so_val) > so_base + args.threshold * 100.0:
            print(f"FAIL {soverdict} — shard process tax grew past the "
                  f"baseline by more than {args.threshold * 100:.0f} points")
            rc = 1
        else:
            print(f"OK {soverdict}")

    # GP-BO incremental fast path: latency gates like the TPE headline
    # (lower is better, same key in artifact and baseline); baselines
    # predating the metric pass informationally
    extra = art.get("extra") or {}
    gp_key = next((k for k in GP_METRICS if extra.get(k) is not None), None)
    gp_bases = ([b for b in matching if b[3].get(gp_key) is not None]
                if gp_key else [])
    if gp_key is None or not gp_bases:
        print("gp_suggest_ms_per_point: artifact or committed baseline "
              "missing the metric — nothing to gate against (pass)")
    else:
        gb_name, _, _, gb_parsed = gp_bases[-1]
        gp_base = float(gb_parsed[gp_key])
        gratio = float(extra[gp_key]) / gp_base
        gverdict = (f"{gp_key}: {float(extra[gp_key]):.3f} ms vs "
                    f"{gp_base:.3f} ms ({gb_name}, {art['backend']}) "
                    f"→ {gratio:.3f}x")
        if gratio > 1.0 + args.threshold:
            print(f"FAIL {gverdict} — regressed past the "
                  f"{args.threshold:.0%} threshold")
            rc = 1
        else:
            print(f"OK {gverdict}")

    # the incremental-vs-full-refit ratio must hold its absolute floor on
    # CPU (the acceptance substrate for the fast path); other substrates
    # report it informationally
    speedup = extra.get(GP_SPEEDUP_METRIC)
    if speedup is None:
        print(f"{GP_SPEEDUP_METRIC}: artifact missing the metric — "
              "nothing to gate against (pass)")
    elif art["backend"] != "tpu" and float(speedup) < GP_SPEEDUP_FLOOR:
        print(f"FAIL {GP_SPEEDUP_METRIC}: {float(speedup):.2f}x < the "
              f"{GP_SPEEDUP_FLOOR:.0f}x acceptance floor")
        rc = 1
    else:
        print(f"OK {GP_SPEEDUP_METRIC}: {float(speedup):.2f}x "
              f"(floor {GP_SPEEDUP_FLOOR:.0f}x on cpu)")

    # batched-eval throughput gates inversely (higher is better) against
    # the last committed baseline carrying each key — informational until
    # one does
    for bkey in BATCH_TPS_METRICS:
        bval = extra.get(bkey)
        b_bases = [b for b in matching if b[3].get(bkey)]
        if bval is None or not b_bases:
            print(f"{bkey}: artifact or committed baseline missing the "
                  "metric — nothing to gate against (pass)")
            continue
        bb_name, _, _, bb_parsed = b_bases[-1]
        b_base = float(bb_parsed[bkey])
        bratio = float(bval) / b_base
        bverdict = (f"{bkey}: {float(bval):.0f} vs {b_base:.0f} trials/s "
                    f"({bb_name}, {art['backend']}) → {bratio:.3f}x")
        if bratio < 1.0 - args.threshold:
            print(f"FAIL {bverdict} — throughput regressed past the "
                  f"{args.threshold:.0%} threshold")
            rc = 1
        else:
            print(f"OK {bverdict}")

    # the pooled-vs-per-trial speedup holds the same absolute-floor shape
    # as the GP ratio: CPU is the acceptance substrate (dispatch overhead
    # is exactly what pooling amortizes; accelerators only widen the win),
    # other substrates report informationally
    bspeed = extra.get(BATCH_SPEEDUP_METRIC)
    if bspeed is None:
        print(f"{BATCH_SPEEDUP_METRIC}: artifact missing the metric — "
              "nothing to gate against (pass)")
    elif art["backend"] != "tpu" and float(bspeed) < BATCH_SPEEDUP_FLOOR:
        print(f"FAIL {BATCH_SPEEDUP_METRIC}: {float(bspeed):.2f}x < the "
              f"{BATCH_SPEEDUP_FLOOR:.0f}x acceptance floor")
        rc = 1
    else:
        print(f"OK {BATCH_SPEEDUP_METRIC}: {float(bspeed):.2f}x "
              f"(floor {BATCH_SPEEDUP_FLOOR:.0f}x on cpu)")

    # suggest-ahead hit rates: higher is better, gated inversely against
    # the last baseline that carries them (informational until then)
    for hkey in HIT_RATE_METRICS:
        hval = extra.get(hkey)
        h_bases = [b for b in matching if b[3].get(hkey) is not None]
        if hval is None or not h_bases:
            print(f"{hkey}: artifact or committed baseline missing the "
                  "metric — nothing to gate against (pass)")
            continue
        hb_name, _, _, hb_parsed = h_bases[-1]
        h_base = float(hb_parsed[hkey])
        hverdict = (f"{hkey}: {float(hval):.3f} vs {h_base:.3f} "
                    f"({hb_name}, {art['backend']})")
        if h_base > 0 and float(hval) < h_base * (1.0 - args.threshold):
            print(f"FAIL {hverdict} — hit rate fell past the "
                  f"{args.threshold:.0%} threshold")
            rc = 1
        else:
            print(f"OK {hverdict}")

    # multi-tenant service plane: three absolute acceptance bars that
    # ENFORCE whenever the artifact carries them (no baseline grace — they
    # are the tentpole's acceptance criteria, all substrate-independent
    # host-CPU figures), plus the 1k-experiment throughput which gates
    # inversely once a committed baseline records it
    jain = extra.get(FAIRNESS_METRIC)
    if jain is None:
        print(f"{FAIRNESS_METRIC}: artifact missing the metric — "
              "nothing to gate against (pass)")
    elif float(jain) < FAIRNESS_FLOOR:
        print(f"FAIL {FAIRNESS_METRIC}: {float(jain):.3f} < the "
              f"{FAIRNESS_FLOOR:.1f} fairness floor (hot tenant starved "
              "the small tenants)")
        rc = 1
    else:
        print(f"OK {FAIRNESS_METRIC}: {float(jain):.3f} "
              f"(floor {FAIRNESS_FLOOR:.1f})")
    rss_ratio = extra.get(EVICT_RSS_METRIC)
    if rss_ratio is None:
        print(f"{EVICT_RSS_METRIC}: artifact missing the metric — "
              "nothing to gate against (pass)")
    elif float(rss_ratio) < EVICT_RSS_FLOOR:
        print(f"FAIL {EVICT_RSS_METRIC}: {float(rss_ratio):.2f}x < the "
              f"{EVICT_RSS_FLOOR:.0f}x residency floor (eviction is not "
              "reclaiming memory)")
        rc = 1
    else:
        print(f"OK {EVICT_RSS_METRIC}: {float(rss_ratio):.2f}x "
              f"(floor {EVICT_RSS_FLOOR:.0f}x)")
    tratio = extra.get(TRANSFER_METRIC)
    if tratio is None:
        print(f"{TRANSFER_METRIC}: artifact missing the metric — "
              "nothing to gate against (pass)")
    elif float(tratio) > TRANSFER_CEILING:
        print(f"FAIL {TRANSFER_METRIC}: {float(tratio):.3f} > the "
              f"{TRANSFER_CEILING:.1f} ceiling (warm start is not "
              "halving time-to-good)")
        rc = 1
    else:
        print(f"OK {TRANSFER_METRIC}: {float(tratio):.3f} "
              f"(ceiling {TRANSFER_CEILING:.1f})")
    mt_val = extra.get(MT_TPS_METRIC)
    mt_bases = [b for b in matching if b[3].get(MT_TPS_METRIC)]
    if mt_val is None or not mt_bases:
        print(f"{MT_TPS_METRIC}: artifact or committed baseline missing "
              "the metric — nothing to gate against (pass)")
    else:
        mtb_name, _, _, mtb_parsed = mt_bases[-1]
        mt_base = float(mtb_parsed[MT_TPS_METRIC])
        mt_ratio = float(mt_val) / mt_base
        mt_verdict = (f"{MT_TPS_METRIC}: {float(mt_val):.0f} vs "
                      f"{mt_base:.0f} trials/s ({mtb_name}, "
                      f"{art['backend']}) → {mt_ratio:.3f}x")
        if mt_ratio < 1.0 - args.threshold:
            print(f"FAIL {mt_verdict} — throughput regressed past the "
                  f"{args.threshold:.0%} threshold")
            rc = 1
        else:
            print(f"OK {mt_verdict}")

    # fleet-fused suggest plane: the same-run speedup enforces its
    # absolute floor whenever the artifact carries it, and the launch
    # count must hold the O(buckets) amortization bound when both sides
    # ride the artifact
    fspd = extra.get(FLEET_SPEEDUP_METRIC)
    if fspd is None:
        print(f"{FLEET_SPEEDUP_METRIC}: artifact missing the metric — "
              "nothing to gate against (pass)")
    elif float(fspd) < FLEET_SPEEDUP_FLOOR:
        print(f"FAIL {FLEET_SPEEDUP_METRIC}: {float(fspd):.2f}x < the "
              f"{FLEET_SPEEDUP_FLOOR:.0f}x fused-vs-serial floor (the "
              "fused plane is not amortizing launches)")
        rc = 1
    else:
        print(f"OK {FLEET_SPEEDUP_METRIC}: {float(fspd):.2f}x "
              f"(floor {FLEET_SPEEDUP_FLOOR:.0f}x)")
    flaunch = extra.get(FLEET_LAUNCHES_METRIC)
    fbuckets = extra.get(FLEET_BUCKETS_METRIC)
    if flaunch is None or not fbuckets:
        print(f"{FLEET_LAUNCHES_METRIC}: artifact missing the metric — "
              "nothing to gate against (pass)")
    elif float(flaunch) > 2.0 * float(fbuckets):
        print(f"FAIL {FLEET_LAUNCHES_METRIC}: {float(flaunch):.0f} "
              f"launches/tick > 2x the {float(fbuckets):.0f} buckets "
              "(per-experiment launches are leaking through the fuser)")
        rc = 1
    else:
        print(f"OK {FLEET_LAUNCHES_METRIC}: {float(flaunch):.0f} "
              f"launches/tick across {float(fbuckets):.0f} buckets")

    # columnar trial archive: the two same-run ratios enforce their
    # absolute floors whenever the artifact carries them; the drift
    # watches gate (lower is better) with the wide slack against the
    # last committed baseline that carries each — informational until one.
    # The 1M-scale probes live in benchmarks/archive_scale.py, far too
    # heavy for bench.py's live pass — so when the bench artifact lacks
    # the keys, fall back to the newest committed archive_scale summary
    # row (same-run ratios, so substrate drift cannot fake a pass)
    aext = archive_summary()
    if aext and any(extra.get(k) is None for k in
                    (ARCHIVE_RSS_METRIC, SNAP_SPEEDUP_METRIC)):
        print(f"archive gates: riding {aext.pop('_source')} "
              f"(commit {aext.get('commit', '?')}, "
              f"{aext.get('trials', '?')} trials)")
        for k, v in aext.items():
            extra.setdefault(k, v)
    arss = extra.get(ARCHIVE_RSS_METRIC)
    if arss is None:
        print(f"{ARCHIVE_RSS_METRIC}: artifact missing the metric — "
              "nothing to gate against (pass)")
    elif float(arss) < ARCHIVE_RSS_FLOOR:
        print(f"FAIL {ARCHIVE_RSS_METRIC}: {float(arss):.2f}x < the "
              f"{ARCHIVE_RSS_FLOOR:.0f}x residency floor (the archive is "
              "not flattening per-trial RSS)")
        rc = 1
    else:
        print(f"OK {ARCHIVE_RSS_METRIC}: {float(arss):.2f}x "
              f"(floor {ARCHIVE_RSS_FLOOR:.0f}x)")
    snsp = extra.get(SNAP_SPEEDUP_METRIC)
    if snsp is None:
        print(f"{SNAP_SPEEDUP_METRIC}: artifact missing the metric — "
              "nothing to gate against (pass)")
    elif float(snsp) < SNAP_SPEEDUP_FLOOR:
        print(f"FAIL {SNAP_SPEEDUP_METRIC}: {float(snsp):.2f}x < the "
              f"{SNAP_SPEEDUP_FLOOR:.0f}x incremental-snapshot floor "
              "(O(dirty) is not beating the full dump)")
        rc = 1
    else:
        print(f"OK {SNAP_SPEEDUP_METRIC}: {float(snsp):.2f}x "
              f"(floor {SNAP_SPEEDUP_FLOOR:.0f}x)")
    for akey in ARCHIVE_DRIFT_METRICS:
        aval = extra.get(akey)
        a_bases = [b for b in matching if b[3].get(akey) is not None]
        if aval is None or not a_bases:
            print(f"{akey}: artifact or committed baseline missing the "
                  "metric — nothing to gate against (pass)")
            continue
        ab_name, _, _, ab_parsed = a_bases[-1]
        a_base = float(ab_parsed[akey])
        aratio = float(aval) / a_base if a_base else 0.0
        averdict = (f"{akey}: {float(aval):.3g} vs {a_base:.3g} "
                    f"({ab_name}, {art['backend']}) → {aratio:.3f}x")
        if a_base and aratio > 1.0 + ARCHIVE_SLACK:
            print(f"FAIL {averdict} — regressed past the "
                  f"{ARCHIVE_SLACK:.0%} slack")
            rc = 1
        else:
            print(f"OK {averdict}")

    # scale-simulator certification: counters enforce at zero and the
    # fairness index holds the multi-tenant floor whenever an artifact
    # carries them; recovery-per-10k-WAL drifts with the wide slack
    # against the last committed baseline carrying it. The 100k run
    # lives in benchmarks/sim_scale.py, so when the bench artifact lacks
    # the keys the gate rides the newest committed sim_scale summary
    sext = sim_summary()
    if sext and any(extra.get(k) is None for k in SIM_ZERO_METRICS):
        print(f"sim gates: riding {sext.pop('_source')} "
              f"(commit {sext.get('commit', '?')}, "
              f"{sext.get('workers', '?')} workers)")
        for k, v in sext.items():
            extra.setdefault(k, v)
    for zkey in SIM_ZERO_METRICS:
        zval = extra.get(zkey)
        if zval is None:
            print(f"{zkey}: artifact missing the metric — "
                  "nothing to gate against (pass)")
        elif int(zval) != 0:
            print(f"FAIL {zkey}: {int(zval)} — the scale simulator "
                  "certifies this at zero, full stop")
            rc = 1
        else:
            print(f"OK {zkey}: 0")
    sjain = extra.get(SIM_JAIN_METRIC)
    if sjain is None:
        print(f"{SIM_JAIN_METRIC}: artifact missing the metric — "
              "nothing to gate against (pass)")
    elif float(sjain) < FAIRNESS_FLOOR:
        print(f"FAIL {SIM_JAIN_METRIC}: {float(sjain):.3f} < the "
              f"{FAIRNESS_FLOOR:.1f} fairness floor at 100k simulated "
              "workers")
        rc = 1
    else:
        print(f"OK {SIM_JAIN_METRIC}: {float(sjain):.3f} "
              f"(floor {FAIRNESS_FLOOR:.1f})")
    srec = extra.get(SIM_RECOVERY_METRIC)
    sr_bases = [b for b in matching
                if b[3].get(SIM_RECOVERY_METRIC) is not None]
    if srec is None or not sr_bases:
        print(f"{SIM_RECOVERY_METRIC}: artifact or committed baseline "
              "missing the metric — nothing to gate against (pass)")
    else:
        srb_name, _, _, srb_parsed = sr_bases[-1]
        sr_base = float(srb_parsed[SIM_RECOVERY_METRIC])
        sr_ratio = float(srec) / sr_base if sr_base else 0.0
        sr_verdict = (f"{SIM_RECOVERY_METRIC}: {float(srec):.3g} vs "
                      f"{sr_base:.3g} ({srb_name}, {art['backend']}) "
                      f"→ {sr_ratio:.3f}x")
        if sr_base and sr_ratio > 1.0 + SIM_SLACK:
            print(f"FAIL {sr_verdict} — recovery slowed past the "
                  f"{SIM_SLACK:.0%} slack")
            rc = 1
        else:
            print(f"OK {sr_verdict}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
