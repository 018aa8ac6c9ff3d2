"""The one entry point: find the cell's files by name, run, print the line.

``BENCHMARK.json`` names a cell's configuration and traffic mix; the mix's
file names the runner kind; ``readers/<metric>.py`` reads one per-layer
metric each. Nothing here knows a cell, a model or a metric by name.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
from typing import Any, Dict

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: exit codes: 2 the call cannot be served, 3 no accelerator for the cell
EXIT_USAGE, EXIT_NO_CHIP = 2, 3
#: the compile cache: kept from run to run at a fixed path inside the
#: checkout, whatever the environment names
STEADY_CACHE = os.path.join(HERE, ".cache", "xla")


#: the steady cache's cap: room for every program a cell finds again
STEADY_CACHE_BYTES = 768 << 20


def use_cache(env, path: str = STEADY_CACHE,
              max_bytes: int = STEADY_CACHE_BYTES) -> None:
    """Name ``path`` as the compile cache in ``env`` (``os.environ`` or a
    child's). The program's one rule (utils/procs.xla_cache_dir) reads
    this variable first, so it takes the directory the benchmark gives
    it. The cap is the benchmark's too: one that came with the machine
    (192 MiB on the chip tool's) evicts a cell's own programs between
    runs. ``max_bytes`` -1 is jax's default, no cap."""
    os.makedirs(path, exist_ok=True)
    env["JAX_COMPILATION_CACHE_DIR"] = path
    env["JAX_COMPILATION_CACHE_MAX_SIZE"] = str(max_bytes)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Context:
    workload: str
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    chips: int
    run_seconds: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    t_start: float
    run_dir: str

    def use_steady_cache(self) -> None:
        """Call before jax compiles anything in this process."""
        use_cache(os.environ)
        jax = sys.modules.get("jax")
        if jax is not None:
            jax.config.update("jax_compilation_cache_dir", STEADY_CACHE)
            jax.config.update("jax_compilation_cache_max_size",
                              STEADY_CACHE_BYTES)

    def devices(self):
        """This process's devices; raises NoChip unless they are TPU chips,
        as many as the cell asks for (a rehearsal takes what there is)."""
        import jax

        devs = jax.devices()
        if not self.rehearsal and (devs[0].platform != "tpu"
                                   or len(devs) < self.chips):
            raise NoChip(f"the cell needs {self.chips} TPU chip(s); jax "
                         f"found {len(devs)} x {devs[0].platform}")
        return devs


def _load(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def rehearsal_sizes(config: dict) -> None:
    """The configuration at its ``rehearsal`` sizes, with the check's limits
    as read at those sizes (tiny batches are noisier than the cell's)."""
    config["script_args"].update(config.get("rehearsal", {}))
    check = config["check"]
    check["limits"] = check.get("rehearsal_limits", check["limits"])


def _reader(metric: str):
    path = os.path.join(HERE, "readers", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_reader_" + "".join(c if c.isalnum() else "_"
                                      for c in metric), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def per_layer_metrics(bench: dict, cell: str, records: dict) -> dict:
    """Each per-layer metric of the cell through its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in bench["per_layer"]:
        if _applies(m, cell):
            value = _reader(m["name"]).read(records)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class Refused(Exception):
    """The call cannot be served: the message says why."""


def cell_context(workload: str, seed: int, seconds: float, trace: bool,
                 rehearsal: bool, t_start: float):
    """(BENCHMARK.json, the Context of one run of ``workload``): the
    cell's configuration and traffic mix, found by their names."""
    if not os.path.isdir(os.path.join(ROOT, "metaopt_tpu")):
        raise Refused("no metaopt_tpu/ beside chipbench/: nothing to measure")
    bench = _load("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json "
                      f"(has {sorted(cells)})")
    cell = cells[workload]
    config = _load(next(c["file"] for c in bench["configs"]
                        if c["name"] == cell["config"]))
    traffic = _load(os.path.join("chipbench", "traffic",
                                 cell["traffic"] + ".json"))
    if rehearsal:
        rehearsal_sizes(config)
        traffic.update(traffic.get("rehearsal", {}))
    run_dir = os.path.join(
        HERE, ".runs", f"{workload}-trace{int(trace)}"
        + ("-rehearsal" if rehearsal else ""))
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    return bench, Context(
        workload=workload, seed=seed, seconds=seconds, trace=trace,
        rehearsal=rehearsal, chips=int(cell["chips"]),
        run_seconds=int(bench["run_seconds"]), config=config,
        traffic=traffic, t_start=t_start, run_dir=run_dir)


def runner_of(ctx: Context):
    return importlib.import_module("chipbench.runners."
                                   + ctx.traffic["runner"])


def main(t_start: float) -> int:
    p = argparse.ArgumentParser(prog="python3 -m chipbench")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true",
                   help="tiny sizes on whatever device there is; prints "
                        "no metric (a CPU number is never a device's)")
    args = p.parse_args()

    try:
        bench, ctx = cell_context(args.workload, args.seed, args.seconds,
                                  bool(args.trace), args.rehearsal, t_start)
        result = runner_of(ctx).run(ctx)
    except Refused as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return EXIT_USAGE
    except NoChip as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return EXIT_NO_CHIP

    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "device": result["device"]}
    if args.rehearsal:
        line.update(rehearsal=True, metrics={})
    elif args.trace:
        line["metrics"] = per_layer_metrics(bench, args.workload,
                                            result["records"])
        if "breakdown" in result:
            line["breakdown"] = result["breakdown"]
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        line["metrics"] = {k: {"value": v, "unit": units[k]}
                           for k, v in result["end_to_end"].items()}
    with open(os.path.join(ctx.run_dir, "result.json"), "w") as f:
        json.dump({"line": line, "records": result.get("records")}, f,
                  indent=1, default=str)
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0
