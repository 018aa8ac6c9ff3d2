#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py                 # on a machine with a TPU
    python chip_smoke.py --phases four   # a subset (never prints a result)
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal   # tiny CPU dry run

Drives the main path -- ``python -m metaopt_tpu hunt --n-chips 1`` with
subprocess trials -- through the normal entry points at the full width
of Transformer-base (the shape of the benchmark's first cell: d_model
512, 8 heads, d_ff 2048, vocab 32000, batch 64, sequence 256; random
weights from a seed), and checks what comes out by the repo's own means. Depth
is cut from 6 layers to 2: every trial compiles its own program (lr and
dropout are constants in it), and at 6 layers that is 113 s of XLA
compile in a 160 s trial (PERF.md), which five trials cannot afford.

A chip belongs to one process at a time. This process never initialises
a jax backend: it asks one short-lived child what devices there are and
then runs its phases as sequential children, each the one owner of the
chip while it runs.

  sweep    5-trial TPE hunt over examples/transformer_wmt.py; every trial
           completed on the TPU with a finite loss, the hunt process
           itself on the CPU
  kernels  the Pallas flash forward and backward kernels, masked and
           unmasked, bf16, interpret=False, against the float32 reference;
           one TPE suggest at 10k observations and one GP-BO suggest at 1k
           on the chip
  cache    one trial's program compiled in two successive processes: the
           second adds no cache entry and reports hits
  handoff  a trial that holds the chip past its timeout is killed; the
           next trial, started right after, gets the device
  busy     with the chip held by another process, a one-trial hunt ends
           with that trial broken, the runtime's message, and rc != 0
  four     (hosts with >= 4 chips; otherwise one "skipped" line) four
           concurrent one-chip trials on four distinct chips, and one
           four-chip trial on a dp2 x tp2 mesh

Exit code 0 and a last stdout line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
only if every phase ran and passed on a TPU. With no TPU it exits
non-zero within seconds and prints no result. ``--rehearsal`` shrinks
sizes for a CPU dry run of the control flow; its last line says
``rehearsal``, never a result.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import subprocess
import sys
import time

try:
    from metaopt_tpu.utils.procs import (
        last_json_object,
        probe_devices,
        probe_tpu,
        run_with_deadline,
        xla_cache_dir,
    )
except ImportError as exc:
    sys.exit(f"chip_smoke: the metaopt_tpu package is not beside this "
             f"script ({exc})")

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")
#: trial checkpoints (hundreds of MB each at full width): emptied before and
#: after a run. Trial ids are hashes of seeded params, so the default root
#: (the system temp dir) would hand a re-run the previous run's weights.
CKPT = os.path.join(HERE, ".cache", "chip_smoke_ckpt")
PHASES = ("sweep", "kernels", "cache", "handoff", "busy", "four")

#: Transformer-base as chipbench/configs/transformer-base-wmt.json sizes
#: it, depth cut.
#: GP-BO gets 1k observations: its cold fit at 10k took 566 s on the v5e.
FULL = {"d_model": 512, "n_layers": 2, "d_ff": 2048, "vocab": 32000,
        "batch": 64, "seq": 256, "max_len": 512, "steps": 20,
        "attn": (64, 256, 8, 64), "attn_long_seq": 512,
        "tpe_obs": 10_000, "gp_obs": 1_000,
        "hold_timeout_s": 40.0, "trial_timeout_s": 600.0}
TINY = {"d_model": 128, "n_layers": 2, "d_ff": 128, "vocab": 211,
        "batch": 4, "seq": 16, "max_len": 32, "steps": 2,
        "attn": (2, 32, 2, 16), "attn_long_seq": 64,
        "tpe_obs": 200, "gp_obs": 100,
        "hold_timeout_s": 12.0, "trial_timeout_s": 300.0}

#: a trial for the handoff/busy/four phases: claims its device, says which
#: device files it holds, optionally outstays its welcome
_TRIAL_SRC = '''\
import os, sys, time
import jax, jax.numpy as jnp
from metaopt_tpu.client import report_results
t0 = time.time()
x = float(jnp.ones((256, 256)).sum())          # the device is ours from here
marker, hold_s = sys.argv[1], float(sys.argv[2])
if marker != "-" and not os.path.exists(marker):
    open(marker, "w").close()
    time.sleep(3600)                           # until the executor kills us
time.sleep(hold_s)
held = set()
for fd in os.listdir("/proc/self/fd"):
    try:
        path = os.readlink("/proc/self/fd/" + fd)
    except OSError:
        continue
    if path.startswith(("/dev/accel", "/dev/vfio/")) and path != "/dev/vfio/vfio":
        held.add(path)
d = jax.devices()
report_results([
    {"name": "o", "type": "objective", "value": x},
    {"name": "device", "type": "statistic",
     "value": f"{d[0].platform}:{d[0].device_kind}:{len(d)}"},
    {"name": "held", "type": "statistic", "value": sorted(held)},
    {"name": "span", "type": "statistic", "value": [t0, time.time()]},
])
'''


def log(text: str) -> None:
    """Everything the launcher and its children print, kept on disk too:
    whoever runs this may only see the tail of the output."""
    if os.path.isdir(OUT):
        with open(os.path.join(OUT, "log.txt"), "a") as f:
            f.write(text)


def say(msg: str) -> None:
    print(msg, flush=True)
    if not os.environ.get("_CHIP_SMOKE_CHILD"):  # a child's lines are
        log(msg + "\n")                          # logged by its launcher


class PhaseFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


# ---------------------------------------------------------------------------
# helpers of the launcher (no jax here)


def run_child(argv, timeout_s, env=None):
    """(rc, output) with the child's lines streamed as they appear."""
    rc, out = run_with_deadline(argv, timeout_s=timeout_s, env=env,
                                stream=True, poll_s=0.2)
    log(out)
    return rc, out


def hunt_summary(text: str) -> dict:
    """`mtpu hunt` prints one indented JSON object last on its stdout."""
    start = text.rfind("\n{\n")
    try:
        return json.loads(text[start + 1:text.rindex("\n}") + 2])
    except ValueError:
        raise PhaseFailed("hunt printed no summary") from None


def hunt(name, ledger, user_cmd, *flags, timeout_s, env=None, n_chips=1):
    argv = [sys.executable, "-m", "metaopt_tpu", "hunt", "-n", name,
            "--ledger", ledger, "--n-chips", str(n_chips),
            "--ckpt-root", CKPT, *flags, "--", *user_cmd]
    say(f"$ {' '.join(argv)}")
    t0 = time.time()
    rc, out = run_child(argv, timeout_s, env=env)
    say(f"hunt {name}: rc={rc} in {time.time() - t0:.1f}s")
    return rc, out


def trials_of(ledger_dir: str, name: str):
    from metaopt_tpu.ledger.backends import ledger_from_spec

    return ledger_from_spec(ledger_dir).fetch(name)


def stat(trial, name):
    return next((r.value for r in trial.statistics if r.name == name), None)


def write(path: str, text: str) -> str:
    with open(path, "w") as f:
        f.write(text)
    return path


# ---------------------------------------------------------------------------
# phases, launcher side


def phase_sweep(cfg, dev, rehearsal):
    tpe_yaml = write(os.path.join(OUT, "tpe.yaml"),
                     "algorithm:\n  tpe:\n    seed: 0\n"
                     "    n_initial_points: 2\n")
    ledger = os.path.join(OUT, "ledger_sweep")
    rc, out = hunt(
        "smoke-sweep", f"file:{ledger}",
        [os.path.join(HERE, "examples", "transformer_wmt.py"),
         "--lr~loguniform(1e-4, 5e-3)", "--dropout~uniform(0.0, 0.3)",
         "--tp", "1", "--steps-per-epoch", str(cfg["steps"]),
         "--d-model", str(cfg["d_model"]), "--n-layers", str(cfg["n_layers"]),
         "--d-ff", str(cfg["d_ff"]), "--vocab", str(cfg["vocab"]),
         "--seq-len", str(cfg["seq"]), "--batch-size", str(cfg["batch"]),
         "--max-len", str(cfg["max_len"])],
        "--config", tpe_yaml, "--max-trials", "5", "--exp-max-broken", "1",
        "--timeout-s", str(cfg["trial_timeout_s"]),
        timeout_s=5 * cfg["trial_timeout_s"],
    )
    check(rc == 0, f"hunt exit {rc}")
    summary = hunt_summary(out)
    say(f"sweep: hunt process platform={summary['platform']} "
        f"host_chips={summary['host_chips']} ledger={summary['ledger']} "
        f"jax_cache={summary['jax_cache']} "
        f"producer_timings={summary['producer_timings']}")
    check(summary["platform"] == "cpu",
          f"the hunt process ran jax on {summary['platform']!r}, not cpu")
    total = summary["total"]
    check(total.get("completed") == 5 and not total.get("broken")
          and not total.get("interrupted"), f"trial statuses {total}")
    trials = trials_of(f"file:{ledger}", "smoke-sweep")
    for t in trials:
        loss, where = t.objective, stat(t, "device")
        say(f"sweep: trial {t.id[:8]} {t.status} loss={loss} "
            f"chips={t.resources.get('chips')} device={where} "
            f"jax_cache={stat(t, 'jax_cache')} "
            f"wall={(t.end_time or 0) - (t.start_time or 0):.1f}s")
        check(t.status == "completed" and loss is not None
              and math.isfinite(loss), f"trial {t.id[:8]}: {t.status} {loss}")
        check(bool(t.resources.get("chips")),
              f"trial {t.id[:8]} has no resources.chips")
        check(str(where).startswith(dev["platform"] + ":"),
              f"trial {t.id[:8]} ran on {where!r}")
        check(stat(t, "jax_cache") == summary["jax_cache"] == xla_cache_dir(),
              f"trial cache {stat(t, 'jax_cache')!r}, hunt cache "
              f"{summary['jax_cache']!r}, rule {xla_cache_dir()!r}")
    return {"completed": 5, "broken": 0,
            "suggest_s": summary["producer_timings"].get("suggest_s")}


def phase_child(name):
    """A phase that is one in-process child of this same script."""
    def run(cfg, dev, rehearsal):
        argv = [sys.executable, os.path.abspath(__file__), "--child", name]
        if rehearsal:
            argv.append("--rehearsal")
        rc, out = run_child(argv, timeout_s=900.0,
                            env=dict(os.environ, _CHIP_SMOKE_CHILD="1"))
        check(rc == 0, f"{name} child exit {rc}")
        res = last_json_object(out, "PHASE_RESULT ")
        check(res is not None, f"the {name} child printed no result")
        return res
    return run


def phase_cache(cfg, dev, rehearsal):
    runs = []
    for i in (1, 2):
        say(f"cache: process {i} of 2")
        runs.append(phase_child("cache")(cfg, dev, rehearsal))
    first, second = runs
    check(second["entries_after"] == second["entries_before"]
          and second["writes"] == 0,
          f"the second process added cache entries: {second}")
    check(second["hits"] >= 1, f"the second process hit nothing: {second}")
    check(second["cache_dir"] == first["cache_dir"], "cache directory moved")
    return {"cache_dir": second["cache_dir"], "first": first,
            "second": second}


def phase_handoff(cfg, dev, rehearsal):
    script = write(os.path.join(OUT, "claim_trial.py"), _TRIAL_SRC)
    marker = os.path.join(OUT, "handoff.marker")
    ledger = os.path.join(OUT, "ledger_handoff")  # bare path: native engine
    rc, out = hunt(
        "smoke-handoff", ledger, [script, marker, "0", "-x~uniform(0, 1)"],
        "--max-trials", "1", "--exp-max-broken", "2",
        "--timeout-s", str(cfg["hold_timeout_s"]),
        timeout_s=10 * cfg["hold_timeout_s"],
    )
    check(rc == 0, f"hunt exit {rc}")
    summary = hunt_summary(out)
    say(f"handoff: ledger engine {summary['ledger']}")
    check(summary["ledger"] == "NativeFileLedger",
          f"native ledger engine not built (got {summary['ledger']})")
    total = summary["total"]
    check(total.get("completed") == 1 and total.get("broken") == 1,
          f"expected 1 timed-out + 1 completed trial, got {total}")
    check("timeout after" in out, "no timeout note in the hunt's log")
    trials = trials_of(ledger, "smoke-handoff")
    dead = next(t for t in trials if t.status == "broken")
    live = next(t for t in trials if t.status == "completed")
    gap = live.start_time - dead.end_time
    say(f"handoff: timed-out trial ended, next one reserved {gap:.2f}s later "
        f"and ran on {stat(live, 'device')} holding {stat(live, 'held')}")
    check(str(stat(live, "device")).startswith(dev["platform"] + ":"),
          f"the trial after the kill ran on {stat(live, 'device')!r}")
    return {"gap_s": round(gap, 2)}


def phase_busy(cfg, dev, rehearsal):
    if rehearsal:
        say("busy: skipped in rehearsal (CPU devices are not exclusive)")
        return {"skipped": "rehearsal"}
    script = write(os.path.join(OUT, "claim_trial.py"), _TRIAL_SRC)
    flag = os.path.join(OUT, "holder.ready")
    holder = subprocess.Popen(
        [sys.executable, "-c",
         "import sys, time, jax\njax.devices()\n"
         "open(sys.argv[1], 'w').close()\ntime.sleep(600)\n", flag])
    try:
        deadline = time.time() + 120
        while not os.path.exists(flag):
            check(holder.poll() is None and time.time() < deadline,
                  "the holder process never got the chip")
            time.sleep(0.2)
        say(f"busy: pid {holder.pid} holds the chip")
        ledger = os.path.join(OUT, "ledger_busy")
        # the count is given: a probe child could not get the chip either
        env = dict(os.environ, MTPU_SLICE_CHIPS=str(dev["count"]))
        rc, out = hunt(
            "smoke-busy", f"file:{ledger}",
            [script, "-", "0", "-x~uniform(0, 1)"],
            "--max-trials", "1", "--exp-max-broken", "1",
            "--timeout-s", "120", timeout_s=300.0, env=env,
        )
    finally:
        holder.kill()
        holder.wait()
    check(rc not in (0, None), f"hunt exit {rc} with the chip held elsewhere")
    trials = trials_of(f"file:{ledger}", "smoke-busy")
    check([t.status for t in trials] == ["broken"],
          f"statuses {[t.status for t in trials]}")
    note = next((ln for ln in out.splitlines() if " broken: " in ln), "")
    tail = out[out.find(note):][:3000] if note else ""
    check("timeout after" not in tail, "the trial hung instead of failing")
    check("already in use" in tail or "Unable to initialize backend" in tail,
          f"no runtime message in the broken trial's note: {tail[-400:]!r}")
    say("busy: trial broken with the runtime's message, hunt rc=%d" % rc)
    return {"hunt_rc": rc}


def phase_four(cfg, dev, rehearsal):
    if dev["count"] < 4:
        say(f"four: skipped: {dev['count']} chip(s)")
        return {"skipped": f"{dev['count']} chip(s)"}
    script = write(os.path.join(OUT, "claim_trial.py"), _TRIAL_SRC)
    hold_s = 3.0 if rehearsal else 20.0
    ledger = os.path.join(OUT, "ledger_four_a")
    rc, out = hunt(
        "smoke-four-a", f"file:{ledger}",
        [script, "-", str(hold_s), "-x~uniform(0, 1)"],
        "--n-workers", "4", "--max-trials", "4", "--exp-max-broken", "1",
        "--timeout-s", "300",
        timeout_s=900.0,
    )
    check(rc == 0, f"hunt --n-workers 4 exit {rc}")
    trials = trials_of(f"file:{ledger}", "smoke-four-a")
    done = [t for t in trials if t.status == "completed"]
    check(len(done) == 4, f"statuses {[t.status for t in trials]}")
    spans = [stat(t, "span") for t in done]
    check(max(s[0] for s in spans) < min(s[1] for s in spans),
          f"the four trials did not overlap in time: {spans}")
    chips = sorted(c for t in done for c in t.resources["chips"])
    held = [tuple(stat(t, "held")) for t in done]
    say(f"four: concurrent trials on chips {chips}, device files {held}")
    check(chips == [0, 1, 2, 3], f"chips {chips}")
    check(rehearsal or (all(held) and len(set(held)) == 4),
          f"device files not distinct: {held}")
    for t in done:
        check(rehearsal or str(stat(t, "device")).endswith(":1"),
              f"a one-chip trial saw {stat(t, 'device')}")

    ledger = os.path.join(OUT, "ledger_four_b")
    rc, out = hunt(
        "smoke-four-b", f"file:{ledger}",
        [os.path.join(HERE, "examples", "transformer_wmt.py"),
         "--lr~loguniform(1e-4, 5e-3)", "--tp", "2",
         "--steps-per-epoch", str(cfg["steps"]),
         "--d-model", str(cfg["d_model"]), "--n-layers", str(cfg["n_layers"]),
         "--d-ff", str(cfg["d_ff"]), "--vocab", str(cfg["vocab"]),
         "--seq-len", str(cfg["seq"]), "--batch-size", str(cfg["batch"]),
         "--max-len", str(cfg["max_len"])],
        "--max-trials", "1", "--exp-max-broken", "1",
        "--timeout-s", str(cfg["trial_timeout_s"]),
        timeout_s=2 * cfg["trial_timeout_s"], n_chips=4,
    )
    check(rc == 0, f"hunt --n-chips 4 exit {rc}")
    (t,) = trials_of(f"file:{ledger}", "smoke-four-b")
    say(f"four: 4-chip trial {t.status} loss={t.objective} "
        f"device={stat(t, 'device')} mesh={stat(t, 'mesh')} "
        f"device_ids={stat(t, 'device_ids')}")
    check(t.status == "completed" and math.isfinite(t.objective),
          f"{t.status} {t.objective}")
    check(stat(t, "mesh") == {"dp": 2, "tp": 2}
          and len(set(stat(t, "device_ids"))) == 4,
          f"mesh {stat(t, 'mesh')} over devices {stat(t, 'device_ids')}")
    return {"chips": chips, "mesh": stat(t, "mesh")}


LAUNCH = {"sweep": phase_sweep, "kernels": phase_child("kernels"),
          "cache": phase_cache, "handoff": phase_handoff,
          "busy": phase_busy, "four": phase_four}


# ---------------------------------------------------------------------------
# phases, child side (each is the one process on the chip while it runs)


def _own_device(rehearsal: bool) -> str:
    """Claim the device for this child; returns the cache dir in force."""
    from metaopt_tpu.utils.procs import use_xla_cache

    cache_dir = use_xla_cache()
    import jax

    d = jax.devices()
    say(f"child: platform={d[0].platform} device_kind={d[0].device_kind} "
        f"count={len(d)} jax={jax.__version__} cache_dir={cache_dir}")
    if d[0].platform != "tpu" and not rehearsal:
        raise SystemExit(f"no TPU: this child got platform {d[0].platform}")
    return cache_dir


def _with_observations(algo_cls, n_obs: int, seed: int = 0):
    """``algo_cls`` (TPE, GPBO) over a mixed space of eight dimensions,
    holding ``n_obs`` seeded observations as if it had been told them."""
    import numpy as np

    from metaopt_tpu.space import build_space

    space = build_space(
        {
            "lr": "loguniform(1e-5, 1e-1)",
            "wd": "loguniform(1e-6, 1e-2)",
            "width": "uniform(32, 1024, discrete=True)",
            "depth": "uniform(1, 12, discrete=True)",
            "dropout": "uniform(0.0, 0.5)",
            "momentum": "uniform(0.5, 0.999)",
            "opt": "choices(['adam', 'sgd', 'lamb'])",
            "schedule": "choices(['cosine', 'linear', 'constant'])",
        }
    )
    algo = algo_cls(space, seed=seed, n_initial_points=8)
    rng = np.random.default_rng(seed)
    X = rng.random((n_obs, algo.cube.n_dims))
    y = rng.random(n_obs).tolist()
    algo._X = list(X)
    algo._y = y
    algo._observed = {str(i): y[i] for i in range(n_obs)}
    return algo


def child_kernels(cfg, rehearsal):
    _own_device(rehearsal)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from metaopt_tpu.ops.attention import _reference_attention, flash_attention

    b, s0, h, d = cfg["attn"]
    out = {"kernels": []}
    # one rounding of a bf16 output is 2^-9 relative; 1e-2 of the largest
    # reference value leaves room for the blockwise summation order
    tol = 1e-2
    for seq in (s0, cfg["attn_long_seq"]):
        ks = jax.random.split(jax.random.PRNGKey(seq), 4)
        q = jax.random.normal(ks[0], (b, seq, h, d), jnp.bfloat16) / d ** 0.5
        k = jax.random.normal(ks[1], (b, seq, h, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, seq, h, d), jnp.bfloat16)
        w = jax.random.normal(ks[3], (b, seq, h, d), jnp.float32)
        causal = jnp.broadcast_to(
            jnp.tril(jnp.ones((seq, seq), bool))[None], (b, seq, seq))
        for mask in (None, causal):
            def pallas(q, k, v):
                o = flash_attention(q, k, v, mask, impl="pallas",
                                    interpret=rehearsal)
                return jnp.sum(o.astype(jnp.float32) * w), o

            def reference(q, k, v):
                o = _reference_attention(q, k, v, mask)
                return jnp.sum(o * w), o

            t0 = time.perf_counter()
            fn = jax.jit(jax.value_and_grad(pallas, argnums=(0, 1, 2),
                                            has_aux=True))
            (_, o), grads = jax.block_until_ready(fn(q, k, v))
            t1 = time.perf_counter()
            jax.block_until_ready(fn(q, k, v))
            t2 = time.perf_counter()
            f32 = [x.astype(jnp.float32) for x in (q, k, v)]
            with jax.default_matmul_precision("float32"):
                (_, ro), rgrads = jax.jit(jax.value_and_grad(
                    reference, argnums=(0, 1, 2), has_aux=True))(*f32)
            errs = {}
            for nm, a, r in zip(("out", "dq", "dk", "dv"),
                                (o, *grads), (ro, *rgrads)):
                a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
                check(a.shape == r.shape and np.isfinite(a).all(),
                      f"{nm}: shape {a.shape} or non-finite values")
                errs[nm] = float(np.max(np.abs(a - r)) / np.max(np.abs(r)))
            row = {"shape": [b, seq, h, d], "masked": mask is not None,
                   "interpret": rehearsal, "rel_err": errs,
                   "compile_and_first_run_s": round(t1 - t0, 2),
                   "run_s": round(t2 - t1, 4)}
            say(f"kernels: pallas fwd+bwd {row}")
            check(max(errs.values()) <= tol,
                  f"error {errs} over {tol} at {row['shape']}")
            out["kernels"].append(row)

    # the coordinator-chip deployment: suggest kernels on this device
    from metaopt_tpu.algo import GPBO, TPE

    for nm, algo, n_obs, n in (
            ("tpe", _with_observations(TPE, cfg["tpe_obs"]),
             cfg["tpe_obs"], 8),
            ("gp_bo", _with_observations(GPBO, cfg["gp_obs"]),
             cfg["gp_obs"], 1)):
        t0 = time.perf_counter()
        pts = algo.suggest(n)
        t1 = time.perf_counter()
        algo.suggest(n)
        t2 = time.perf_counter()
        check(len(pts) == n and all(p in algo.space for p in pts),
              f"{nm}.suggest({n}) at {n_obs} observations: {pts}")
        out[nm] = {"n_obs": n_obs, "points": n,
                   "first_call_s": round(t1 - t0, 2),
                   "second_call_s": round(t2 - t1, 4)}
        say(f"kernels: {nm} suggest {out[nm]}")
    return out


def child_cache(cfg, rehearsal):
    cache_dir = _own_device(rehearsal)
    import jax

    counts = {"hits": 0, "writes": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["writes"] += 1  # recorded when an entry is written

    jax.monitoring.register_event_listener(on_event)
    before = len(os.listdir(cache_dir))
    from metaopt_tpu.models.transformer import train_and_eval

    t0 = time.perf_counter()
    loss = train_and_eval(
        {"lr": 1e-3, "dropout": 0.1, "warmup": 10,
         "d_model": cfg["d_model"], "n_layers": cfg["n_layers"],
         "d_ff": cfg["d_ff"], "n_heads": max(1, cfg["d_model"] // 64),
         "vocab": cfg["vocab"], "max_len": cfg["max_len"]},
        steps=2, seq_len=cfg["seq"], batch_size=cfg["batch"],
        n_train=4 * cfg["batch"],
    )
    wall = time.perf_counter() - t0
    check(math.isfinite(loss), f"loss {loss}")
    res = {"cache_dir": cache_dir, "entries_before": before,
           "entries_after": len(os.listdir(cache_dir)), **counts,
           "wall_s": round(wall, 2)}
    say(f"cache: {res}")
    return res


CHILDREN = {"kernels": child_kernels, "cache": child_cache}


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--phases", default=",".join(PHASES))
    ap.add_argument("--child", choices=sorted(CHILDREN))
    args = ap.parse_args()
    cfg = TINY if args.rehearsal else FULL

    if args.child:
        try:
            res = CHILDREN[args.child](cfg, args.rehearsal)
        except PhaseFailed as exc:
            say(f"{args.child}: FAILED: {exc}")
            return 1
        say("PHASE_RESULT " + json.dumps(res))
        return 0

    phases = [p for p in args.phases.split(",") if p]
    unknown = sorted(set(phases) - set(PHASES))
    if unknown:
        ap.error(f"unknown phases {unknown}; choose from {PHASES}")
    try:
        dev = probe_devices() if args.rehearsal else probe_tpu()
    except RuntimeError as exc:
        print(f"chip_smoke: {exc}", file=sys.stderr)
        return 1
    say(f"chip_smoke: platform={dev['platform']} "
        f"device_kind={dev['device_kind']} count={dev['count']} "
        f"ids={dev['ids']} jax={dev['jax']} jaxlib={dev['jaxlib']} "
        f"libtpu={dev['libtpu']} cache_dir={xla_cache_dir()} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})"
        f"{' REHEARSAL' if args.rehearsal else ''}")

    shutil.rmtree(OUT, ignore_errors=True)
    shutil.rmtree(CKPT, ignore_errors=True)
    os.makedirs(OUT)
    t_start = time.time()
    report, failed = {}, []
    try:
        for name in phases:
            say(f"\n=== {name} ===")
            t0 = time.time()
            try:
                report[name] = LAUNCH[name](cfg, dev, args.rehearsal)
                verdict = "ok"
            except PhaseFailed as exc:
                report[name] = {"failed": str(exc)}
                failed.append(name)
                verdict = f"FAILED: {exc}"
            say(f"=== {name}: {verdict} ({time.time() - t0:.1f}s) ===")
    finally:
        shutil.rmtree(CKPT, ignore_errors=True)
    report["wall_s"] = round(time.time() - t_start, 1)
    report["device"] = dev
    write(os.path.join(OUT, "report.json"), json.dumps(report, indent=1))
    say(f"\nchip_smoke: {len(phases) - len(failed)}/{len(phases)} phases "
        f"ok in {report['wall_s']}s; report: {OUT}/report.json")
    if failed:
        say(f"chip_smoke: FAILED phases: {failed}")
        return 1
    if args.rehearsal:
        say(f"rehearsal on {dev['platform']}: control flow only, no result")
        return 0
    if phases != list(PHASES):
        say(f"partial run ({','.join(phases)}): no result")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
