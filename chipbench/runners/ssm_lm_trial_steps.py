"""``ssm_lm_trial_steps``: ``lm_trial_steps`` for a decoder-hybrid-decoder
with state-space layers (Mamba-1's selective scan), differential attention
under a window and without, gated memory units and cross attention on an
earlier layer's K and V (the ``phi4flash`` family's).

The loop, the set-up, the window and ``correct`` are ``lm_trial_steps``'s:
``Loop`` here is that kind's, with the two parts that read the
configuration's file replaced (the description comes from
``ssm_lm_config.py``, the seeded weights from ``weights_ssm_lm.py`` over
``reference/ssm_lm.py``'s shapes) and the first steps chosen as the hybrid
kind chooses them (``judged_steps``: rows that hold an eighth of their
length in distinct tokens; ``runners/hybrid_lm_trial_steps.py`` says why a
row on a short cycle of the data's permutation can be judged by no one
limit); ``run`` is the hybrid kind's ``run`` (no experts: nothing is
counted) with this kind's kernels' work in the records.
``runners/lm_trial_steps.py`` names its configuration and reference
modules in its own imports, which is why a fifth decoder family needs this
file at all and copies ``first_steps`` and ``run`` again (a ``benchmark``
issue's to repair: PERF.md section 7).

What belongs to the kind: the configuration's file keeps the published
config's keys at its top level (``model_type`` ``phi4flash``,
``mb_per_layer``, ``sliding_window``, ``layer_norm_eps`` ...; the cut ones
at the size held here), Mamba-1's sizes under ``assumed.mamba`` and what
the chip holds under ``script_args.share`` (``layers_held``: PUBLISHED
layer numbers, of ``layers_of``; ``vocab_held``); ``ssm_lm_config.py``
turns it into the program's description (``python -m
chipbench.ssm_lm_config FILE`` prints it, for ``examples/lm_causal.py
--model``) and the reference's; ``reference/ssm_lm.py`` (the scans token
by token, attention dense under the mask a head and a block of rows at a
time), ``checks/ssm_lm_train3.py``, ``weights_ssm_lm.py``,
``flops_ssm_lm.py`` (the scan's work counted from the model's shapes,
whatever implements it; attention's two maps at 64 / 128) and
``ssm_kernel_trace.py`` (the scan kernels' roofline shares);
``kernel_trace.py`` is shared with ``lm_trial_steps``. The cell reports
the accepted metrics of the layers it runs under ``ssm_lm_<name>``, each a
reader that calls the accepted one (an accepted ``workloads`` list takes
no new cell), and the new layers' own under ``ssm_<name>``.

The program has to have the mechanism: without ``ops/selective_scan.py``
the call is refused at once (``Refused``), before jax is loaded.
"""

from __future__ import annotations

import importlib.util
import os
import statistics
import time

from chipbench import (checks, flops_ssm_lm, ssm_lm_config, runners,
                       trace_reduce, weights_ssm_lm)
from chipbench.reference import ssm_lm as reference
from chipbench.runners import lm_trial_steps
from chipbench.runners.lm_trial_steps import HOST_SPANS

KIND = "ssm_lm_trial_steps"


class Loop(lm_trial_steps.Loop):
    """``lm_trial_steps.Loop`` over this family's description, reference
    shapes and seeded weights."""

    def __init__(self, config: dict, seed: int):
        import jax

        from metaopt_tpu.models.lm import LMTrial

        a = config["script_args"]
        self._jax = jax
        self.setup_at = [("imports and reaching the chip", time.time())]
        self.trial = LMTrial(
            ssm_lm_config.description(config), tp=a["tp"],
            n_train=a["n_train"], batch_size=a["batch_size"],
            seq_len=a["seq_len"],
            steps=config["hparams"]["schedule_steps"], seed=seed)
        self.setup_at.append(("LMTrial: data and init", time.time()))
        self._last = None
        self.losses, self.done_at, self.dispatch_s = [], [], []

    def first_steps(self, config: dict, seed: int, n: int,
                    distinct_share: float) -> dict:
        """As ``lm_trial_steps.Loop.first_steps``, over the first ``n``
        steps that ``judged_steps`` names; both sides' trees have one form
        (no expert's matrices to stack or split; the blocks carry their
        published numbers on both sides)."""
        import numpy as np
        from flax import linen as nn

        jax, trial = self._jax, self.trial
        boxes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), trial.params)
        jax.tree.map(lambda x: x.delete(), trial.params)  # room for the new
        weights = weights_ssm_lm.make_weights(
            seed, reference.param_shapes(
                ssm_lm_config.reference_cfg(config)))
        trial.params = jax.device_put(
            nn.meta.replace_boxed(boxes, weights), trial.shardings[0])
        del weights
        self.setup_at.append(("seeded weights", time.time()))
        steps = judged_steps(trial, n, distinct_share)
        rows = [np.asarray(jax.device_get(trial.rows(i))) for i in steps]
        if len(np.unique(np.concatenate(rows), axis=0)) \
                != n * trial.batch_size:
            raise ValueError("the first steps' rows do not all differ")
        self.step(steps[0])
        # Adam's first moment after one step is (1 - b1) g, b1 = 0.9 being
        # optax.adamw's default, which trial_setup leaves alone
        grad = jax.tree.map(lambda m: m / (1 - 0.9), jax.device_get(
            nn.meta.unbox(trial.opt_state[0].mu)))
        for i in steps[1:]:
            self.step(i)
        self.drain()
        self.setup_at.append((f"step's compile and {n} steps", time.time()))
        readings = {
            "losses": [float(x) for x in jax.device_get(self.losses)],
            "grad": grad, "rows": rows,
            "params": jax.device_get(nn.meta.unbox(trial.params))}
        self.forget()
        return readings


def judged_steps(trial, n: int, distinct_share: float) -> list:
    """The first ``n`` step numbers of ``trial`` every row of which holds at
    least ``distinct_share`` of its length in distinct tokens: the steps the
    output check can judge (the hybrid kind's rule, this kind's name on
    what it prints)."""
    import numpy as np

    steps, passed_over = [], []
    for i in range(trial.n_train // trial.batch_size):
        rows = np.asarray(trial.rows(i))
        distinct = min(len(np.unique(row)) for row in rows)
        if distinct >= distinct_share * rows.shape[1]:
            steps.append(i)
            if len(steps) == n:
                break
        else:
            passed_over.append((i, distinct))
    else:
        raise ValueError(f"the trial's data has no {n} steps whose rows "
                         f"hold {distinct_share:g} of their length in "
                         "distinct tokens")
    if passed_over:
        print(f"{KIND}: the check follows steps {steps}; passed over "
              + ", ".join(f"step {i} ({d} distinct tokens a row)"
                          for i, d in passed_over), flush=True)
    return steps


def readings(ctx) -> dict:
    """The first steps' readings alone, the loop freed behind them."""
    with Loop(ctx.config, ctx.seed) as loop:
        return loop.first_steps(ctx.config, ctx.seed,
                                ctx.traffic["warm_steps"],
                                ctx.traffic["check_row_distinct_share"])


def kernel_work(config: dict) -> dict:
    """What the roofline readers divide by device time: the operations and
    bytes of one call of each kernel. ``layers`` counts the CALLS of a
    flash kernel in one pass over the model, two a layer of differential
    attention (``kernel_trace.attention_kernel_roofline`` scales the calls
    it finds by it), each with its own seen pairs; ``ssm_layers`` the
    layers that call the scan."""
    cfg = ssm_lm_config.reference_cfg(config)
    a = config["script_args"]
    s, b = a["seq_len"], a["batch_size"]
    kinds = flops_ssm_lm.kinds(cfg)
    maps = [k for k in kinds if k in ("window", "full", "cross")
            for _ in (1, 2)]
    scans = kinds.count("mamba")
    return {
        "layers": len(maps), "ssm_layers": scans, "remat": bool(a["remat"]),
        "flash_fwd": [flops_ssm_lm.flash_fwd_call(cfg, s, k, b)
                      for k in maps],
        "flash_bwd": [flops_ssm_lm.flash_bwd_call(cfg, s, k, b)
                      for k in maps],
        "selective_scan_fwd": [flops_ssm_lm.scan_fwd_call(cfg, s, b)] * scans,
        "selective_scan_bwd": [flops_ssm_lm.scan_bwd_call(cfg, s, b)] * scans,
    }


def run(ctx):
    if importlib.util.find_spec("metaopt_tpu.ops.selective_scan") is None:
        from chipbench.run import Refused

        raise Refused("this program has no ops/selective_scan.py: it "
                      "cannot run a state-space layer")
    ctx.use_steady_cache()
    import jax

    devs = ctx.devices()
    a, t = ctx.config["script_args"], ctx.traffic
    compiles = runners.CompileCounter()
    trace_dir = os.path.join(ctx.run_dir, "trace")
    with Loop(ctx.config, ctx.seed) as loop:
        first = loop.first_steps(ctx.config, ctx.seed, t["warm_steps"],
                                 t["check_row_distinct_share"])
        if ctx.trace:
            jax.profiler.start_trace(trace_dir)
        setup_s = time.time() - ctx.t_start
        compiles.open()
        t0 = runners.now()
        traced = writing = 0.0
        i = t["warm_steps"]
        if ctx.trace:  # the slice: the window's start
            i = loop.run_for(i, min(t["trace_seconds"], ctx.seconds))
            traced = runners.now() - t0
            jax.profiler.stop_trace()
            writing = runners.now() - t0 - traced  # not a step's time
        i = loop.run_for(i, ctx.seconds - traced)
        wall = runners.now() - t0 - writing
        compiles.close()
    peak = runners.peak_bytes(devs)
    finite = [bool(x == x and abs(x) != float("inf"))
              for x in (float(v) for v in jax.device_get(loop.losses))]
    step_s, dispatch_s = loop.step_seconds(), loop.dispatch_s
    steps = len(loop.losses)
    parts = [("start", ctx.t_start)] + loop.setup_at + [
        ("window open", ctx.t_start + setup_s)]
    del loop
    rate = steps * a["batch_size"] * a["seq_len"] / wall
    print(f"{KIND}: {steps} steps in {wall:.3f} s, set-up {setup_s:.2f} s, "
          f"{compiles.in_window} compile requests in the window", flush=True)
    print(f"{KIND}: set-up by part: " + ", ".join(
        f"{name} {at - since:.2f} s" for (_, since), (name, at)
        in zip(parts, parts[1:])), flush=True)
    print(f"{KIND}: between two completions at most "
          f"{max(step_s) * 1e3:.3f} ms, in one dispatch at most "
          f"{max(dispatch_s) * 1e3:.3f} ms", flush=True)
    check = checks.run(ctx.config, ctx.seed, first.pop("rows"), first)
    del first
    records = {
        "step_s": step_s, "dispatch_s": dispatch_s, "items_per_s": rate,
        "flops_per_item": flops_ssm_lm.train_flops_per_item(
            ssm_lm_config.reference_cfg(ctx.config), a["seq_len"]),
        "device_kind": devs[0].device_kind, "chips": len(devs),
        "peak_bytes": peak, "compiles_in_window": compiles.in_window,
        "check": check, "kernel_work": kernel_work(ctx.config),
    }
    result = {
        "correct": check["correct"] and all(finite)
        and compiles.in_window == 0,
        "attempted": steps, "failed": finite.count(False),
        "end_to_end": {"train_items_per_s": rate, "setup_s": setup_s},
        "records": records,
    }
    if ctx.trace:
        reduced = trace_reduce.reduce(trace_dir, traced, HOST_SPANS)
        print("trace lines:", reduced.pop("lines"), flush=True)
        records["trace"] = reduced
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        result["device"] = runners.device_entry(devs, peak, reduced)
    else:
        result["device"] = runners.device_entry(devs, peak)
    print(f"{KIND}: step p50 {statistics.median(step_s) * 1e3:.3f} ms",
          flush=True)
    return result
