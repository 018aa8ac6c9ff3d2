"""``python3 -m chipbench.checks --workload W --seeds a,b,c
[--control-seeds a,b]``: how the limits' readings are taken. For each seed
the cell's runner drives its timed object through the first steps and
frees it (no window), and the output check compares them; on the control
seeds the control is read against the same rows too. One process, so the
programs compile or load once. Last line: ``CHIPBENCH_CHECK {json}``.
"""

import argparse
import dataclasses
import json
import sys
import time

from chipbench import checks, run as harness


def main() -> int:
    p = argparse.ArgumentParser(prog="python3 -m chipbench.checks")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="",
                   help="seeds on which the control is read too")
    p.add_argument("--rehearsal", action="store_true")
    a = p.parse_args()

    _, ctx = harness.cell_context(a.workload, 0, 0.0, False, a.rehearsal,
                                  time.time())
    ctx.use_steady_cache()
    runner = harness.runner_of(ctx)
    try:
        devs = ctx.devices()
    except harness.NoChip as exc:
        print(f"chipbench.checks: {exc}", file=sys.stderr)
        return harness.EXIT_NO_CHIP
    out = {"device": f"{devs[0].platform}:{devs[0].device_kind}:{len(devs)}",
           "runs": []}
    control = {int(s) for s in a.control_seeds.split(",") if s}
    for seed in (int(s) for s in a.seeds.split(",") if s):
        first = runner.readings(dataclasses.replace(ctx, seed=seed))
        rows = first.pop("rows")
        for side in ("program", "control") if seed in control \
                else ("program",):
            r = checks.run(ctx.config, seed, rows,
                           first if side == "program" else None)
            out["runs"].append({
                "seed": seed, "side": side, "correct": r["correct"],
                "seconds": r["seconds"], "losses": r["losses"],
                "numbers": {k: v["value"] for k, v in r["numbers"].items()}})
    print("CHIPBENCH_CHECK " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
