#!/usr/bin/env python
"""Run the five BASELINE graded configs end-to-end and report throughput.

BASELINE.md's graded configs, each driven through the real CLI exactly as a
user would run it (subprocess trials, ~prior DSL, ledger on disk):

  1. random   × Rosenbrock-2D        (CPU objective)
  2. tpe      × MLP/MNIST-shaped     (single chip)
  3. asha     × ResNet/CIFAR-shaped  (multi-fidelity, partial streaming)
  4. hyperband× Transformer seq2seq  (sub-slice shardable)
  5. evolution× PPO                  (population search)

Default is smoke scale (completes in minutes); ``--scale full`` lifts
trial counts/model sizes toward the BASELINE targets. Prints one JSON line
per config plus a summary line:

    {"config": "asha_resnet", "trials": 16, "wall_s": ..., "trials_per_hour":
     ..., "best_objective": ..., "broken": 0}

Usage:
    python benchmarks/run.py [--scale smoke|full] [--only tpe_mlp ...]
                             [--backend tpu|cpu] [--save]

``--backend tpu`` (the default) runs the model configs on the chip: every
hunt passes ``--n-chips``, so each trial subprocess is pinned to its chips
and fails if it cannot get them, and without a TPU the run is an error.
This launcher never imports jax. ``--backend cpu`` is an explicit rehearsal
of the control flow (``JAX_PLATFORMS=cpu`` in every child) and prints no
rate. Rosenbrock's objective is pure CPU on either backend. ``--save``
appends the per-config lines to
benchmarks/results/{scale}_{backend}_{date}.jsonl.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from metaopt_tpu.utils.procs import probe_tpu, run_swept  # noqa: E402

EXAMPLES = os.path.join(REPO, "examples")

#: per-config: (yaml config or None, max_trials by scale, user command)
CONFIGS = {
    "random_rosenbrock": {
        "config": None,
        "cpu_objective": True,  # no tensors: never worth a chip
        "max_trials": {"smoke": 30, "full": 200},
        "cmd": [
            os.path.join(EXAMPLES, "rosenbrock.py"),
            "-x~uniform(-5, 10)", "-y~uniform(-5, 10)",
        ],
    },
    "tpe_mlp": {
        "config": os.path.join(EXAMPLES, "tpe.yaml"),
        "max_trials": {"smoke": 12, "full": 64},
        "cmd": [
            os.path.join(EXAMPLES, "mlp_mnist.py"),
            "--lr~loguniform(1e-4, 1e-1)",
            "--width~uniform(64, 512, discrete=True)",
            "--depth~uniform(1, 4, discrete=True)",
            "--dropout~uniform(0.0, 0.5)",
            "--epochs", "1",
        ],
    },
    "asha_resnet": {
        "config": os.path.join(EXAMPLES, "asha.yaml"),
        "max_trials": {"smoke": 8, "full": 64},
        "cmd": [
            os.path.join(EXAMPLES, "resnet_cifar.py"),
            "--lr~loguniform(1e-3, 1.0)",
            "--momentum~uniform(0.8, 0.99)",
            "--weight-decay~loguniform(1e-6, 1e-2)",
            "--epochs~fidelity(1, 4, base=2)",
            # smoke: tiny ResNet-18 (CPU-compileable); full restores BASELINE
            "--depth", "18", "--n-train", "256", "--n-val", "128",
            "--batch-size", "64", "--width", "16", "--hw", "16",
        ],
        "cmd_full_overrides": {
            "--depth": "50", "--n-train": "4096", "--n-val": "1024",
            "--batch-size": "128", "--width": "64", "--hw": "32",
        },
    },
    "hyperband_transformer": {
        "config": os.path.join(EXAMPLES, "hyperband.yaml"),
        "max_trials": {"smoke": 9, "full": 27},
        # BASELINE config 4 at full scale is a 4-chip sub-slice per trial
        "n_chips": {"smoke": 1, "full": 4},
        "cmd": [
            os.path.join(EXAMPLES, "transformer_wmt.py"),
            "--lr~loguniform(1e-4, 5e-3)",
            "--dropout~uniform(0.0, 0.3)",
            "--warmup~uniform(50, 400, discrete=True)",
            "--epochs~fidelity(1, 4, base=2)",
            "--tp", "1", "--steps-per-epoch", "10",
            "--d-model", "128", "--n-layers", "2", "--d-ff", "256",
        ],
        "cmd_full_overrides": {
            "--tp": "2", "--steps-per-epoch": "50",
            "--d-model": "512", "--n-layers": "6", "--d-ff": "2048",
        },
    },
    "evolution_ppo": {
        "config": os.path.join(EXAMPLES, "evolution.yaml"),
        "max_trials": {"smoke": 10, "full": 60},
        "timeout_scale": 2.0,
        "cmd": [
            os.path.join(EXAMPLES, "ppo_atari.py"),
            "--lr~loguniform(1e-5, 1e-2)",
            "--clip-eps~uniform(0.05, 0.4)",
            "--ent-coef~loguniform(1e-4, 1e-1)",
            "--epochs~fidelity(2, 8, base=2)",
        ],
    },
}


def _partial_progress(ledger_path: str, name: str, wall_s: float,
                      rate: bool) -> dict:
    """What a timed-out config DID finish, read straight off its ledger.

    A timeout line with no numbers hides whether the config was 90% done
    or stuck at trial 1 — the difference between "raise the cap" and
    "debug the compile path".
    """
    try:
        from metaopt_tpu.ledger.backends import make_ledger

        ledger = make_ledger({"type": "file", "path": ledger_path})
        completed = ledger.count(name, "completed")
        return {
            "partial_completed": completed,
            **({"partial_trials_per_hour":
                round(3600 * completed / wall_s, 1)} if rate else {}),
            "partial_statuses": {
                s: ledger.count(name, s)
                for s in ("reserved", "suspended", "broken", "new")
                if ledger.count(name, s)
            },
        }
    except Exception as exc:  # diagnostics must never mask the timeout
        return {"partial_error": str(exc)[:120]}


def run_config(name: str, spec: dict, scale: str, ledger_root: str,
               backend: str, config_timeout_s: float) -> dict:
    max_trials = spec["max_trials"][scale]
    cmd = list(spec["cmd"])
    if scale == "full":
        for flag, val in (spec.get("cmd_full_overrides") or {}).items():
            i = cmd.index(flag)
            cmd[i + 1] = val
    argv = [
        sys.executable, "-m", "metaopt_tpu", "hunt",
        "-n", name,
        "--max-trials", str(max_trials),
        "--ledger", os.path.join(ledger_root, name),
        "--exp-max-broken", "3",
        "--timeout-s", "900",  # a hung trial must not sink the sweep
    ]
    on_cpu = backend == "cpu" or spec.get("cpu_objective")
    if not on_cpu:
        # chip-bound trials: pinned, and broken if they get no chip
        argv += ["--n-chips", str(spec.get("n_chips", {}).get(scale, 1))]
    if spec["config"]:
        argv += ["--config", spec["config"]]
    argv += ["--"] + cmd

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if on_cpu:
        env["JAX_PLATFORMS"] = "cpu"
    t0 = time.time()
    # trials live in their own sessions (executor start_new_session), so a
    # deadline must sweep by env marker, not killpg — run_swept owns that
    rc, stdout, stderr = run_swept(
        argv, config_timeout_s, env=env,
        marker=f"{name}-{os.getpid()}-{int(time.time())}",
    )
    if rc is None:
        out = {"config": name, "trials": max_trials,
               "wall_s": round(time.time() - t0, 1),
               "backend": "cpu" if on_cpu else backend,
               "error": f"config timeout ({config_timeout_s:.0f}s); "
                        f"stderr tail: {stderr[-300:]}"}
        out.update(_partial_progress(
            os.path.join(ledger_root, name), name, config_timeout_s,
            rate=not on_cpu,
        ))
        return out
    wall = time.time() - t0

    out = {"config": name, "trials": max_trials, "wall_s": round(wall, 1),
           "backend": "cpu" if on_cpu else backend}
    if rc != 0:
        out["error"] = stderr[-500:]
        return out
    try:
        summary = json.loads(stdout[stdout.index("{"):])
    except (ValueError, json.JSONDecodeError):
        out["error"] = "unparseable hunt output"
        return out
    completed = summary["total"].get("completed", 0)
    out.update(
        trials=completed,
        best_objective=(summary.get("best") or {}).get("objective"),
        broken=summary["total"].get("broken", 0),
        pruned=summary.get("pruned_by_worker", 0),
    )
    if not on_cpu:  # a CPU wall clock is not a rate anyone pays for
        out["trials_per_hour"] = round(3600 * completed / wall, 1)
    return out


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--scale", choices=("smoke", "full"), default="smoke")
    p.add_argument("--only", nargs="*", choices=sorted(CONFIGS), default=None)
    p.add_argument("--backend", choices=("tpu", "cpu"), default="tpu")
    p.add_argument("--save", action="store_true",
                   help="append results to benchmarks/results/")
    p.add_argument("--config-timeout-s", type=float, default=None,
                   help="wall cap per config (default: 1800 smoke, 7200 full)")
    args = p.parse_args()

    backend = args.backend
    device = None
    if backend == "tpu":
        # one short-lived child, gone before the first hunt starts
        try:
            device = probe_tpu()
        except RuntimeError as exc:
            print(f"run.py: --backend tpu: {exc}", file=sys.stderr)
            return 1
    # per-config timeout_scale stretches only the DEFAULT cap; an explicit
    # --config-timeout-s means exactly what the user said
    explicit_cap = args.config_timeout_s
    cap = explicit_cap or (1800.0 if args.scale == "smoke" else 7200.0)

    from metaopt_tpu.utils.provenance import provenance

    save_path = None
    if args.save:
        stamp = time.strftime("%Y-%m-%d")
        save_path = os.path.join(
            REPO, "benchmarks", "results",
            f"{args.scale}_{backend}_{stamp}.jsonl",
        )
        os.makedirs(os.path.dirname(save_path), exist_ok=True)
    # run id groups one attempt's rows inside the appended-to dated file —
    # a retry on the same day must not double-count
    run_id = f"{int(time.time())}-{os.getpid()}"

    results = []
    with tempfile.TemporaryDirectory(prefix="mtpu_bench_") as root:
        for name, spec in CONFIGS.items():
            if args.only and name not in args.only:
                continue
            scale = 1.0 if explicit_cap else spec.get("timeout_scale", 1.0)
            res = run_config(name, spec, args.scale, root, backend,
                             cap * scale)
            res.update(provenance(run=run_id))
            print(json.dumps(res), flush=True)
            results.append(res)
            if save_path:
                # append the row the moment the config finishes: a crash
                # mid-sweep must not take completed rows with it.
                # Best-effort — the row is already on stdout, and a disk
                # hiccup must not abort the remaining configs
                try:
                    with open(save_path, "a") as f:
                        f.write(json.dumps(res) + "\n")
                except OSError as exc:
                    print(json.dumps({"save_error": str(exc)}), flush=True)

    ok = [r for r in results if "error" not in r]
    summary = {
        "summary": True,
        "scale": args.scale,
        "backend": backend,
        "device": device,
        "configs_ok": len(ok),
        "configs_total": len(results),
        "total_trials": sum(r["trials"] for r in ok),
        "total_wall_s": round(sum(r["wall_s"] for r in results), 1),
        **provenance(run=run_id),
    }
    print(json.dumps(summary))
    if save_path:
        # rows were appended as configs finished; only the summary is new
        with open(save_path, "a") as f:
            f.write(json.dumps(summary) + "\n")
    return 0 if len(ok) == len(results) else 1


if __name__ == "__main__":
    sys.exit(main())
