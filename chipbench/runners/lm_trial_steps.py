"""``lm_trial_steps``: the train loop of a decoder-only trial, driven
through the program's own step.

``models/lm.py::LMTrial`` is the loop ``train_lm`` runs, handed out step by
step, so nothing of it is copied here: the runner calls ``trial.step(i)``.
``train_lm`` never waits for a step; the runner, like ``steady_steps``,
waits for step ``i - 1`` once it has dispatched step ``i``: the host
prepares the next batch while the device works and is never more than one
step ahead, a step's time is the time between two completions, and a stall
of the host longer than a step idles the device and shows in the rate.
Use this kind for a model ``make_lm`` describes; ``steady_steps`` for the
encoder-decoder, whose loop has no hand-out.

What belongs to the kind (chipbench/README.md, which this kind's PR could
not edit, still names one runner kind): the configuration's file keeps the
published config's keys at its top level (the cut ones at the size held
here) and says what the chip holds under ``script_args.share``;
``lm_config.py`` turns it into the program's description (``python -m
chipbench.lm_config FILE`` prints it, for ``examples/lm_causal.py
--model``) and the reference's; ``reference/lm.py``,
``checks/lm_train3.py``, ``weights_lm.py`` (an expert's matrices are leaves
of their own on both sides, so one lost expert fails a limit),
``flops_lm.py`` and ``kernel_trace.py`` (the roofline readers' arithmetic).
An accepted metric's ``workloads`` list takes no new cell, so the cell
reports the accepted metrics of the layers it runs under ``lm_<name>``,
each a reader that calls the accepted one.

set-up: imports, reaching the chip, data, the sharded init, seeded weights
put into the trial's state, the step's compile and ``warm_steps`` steps,
which are the ones the output check follows (the runner prints where
set-up's seconds went). The window: ``--seconds`` of steps, the last one
waited for before the clock is read; the expert layers' counts are read
once before and once after it. Afterwards, with the trial's state freed:
the reference and the comparison (chipbench/checks, kind ``lm_train3``).
"""

from __future__ import annotations

import os
import statistics
import time

from chipbench import (checks, flops_lm, lm_config, runners, trace_reduce,
                       weights_lm)
from chipbench.reference import lm as reference

#: host spans in a traced run: the program's own two (utils/trace.py
#: annotates them) and this runner's wait
HOST_SPANS = ("slice_and_shard_batch", "dispatch_step", "wait_for_the_device")


class Loop:
    """``LMTrial``, one step a call: ``step(i)`` dispatches step ``i`` and
    then waits for step ``i - 1``; ``drain()`` waits for the last.
    ``losses`` are the finished steps' device scalars, ``done_at`` the
    host's clock at each completion, ``dispatch_s`` each step's seconds in
    ``trial.step``, ``setup_at`` the clock after each part of set-up."""

    def __init__(self, config: dict, seed: int):
        import jax

        from metaopt_tpu.models.lm import LMTrial

        a = config["script_args"]
        self._jax = jax
        self.setup_at = [("imports and reaching the chip", time.time())]
        self.trial = LMTrial(
            lm_config.description(config), tp=a["tp"], n_train=a["n_train"],
            batch_size=a["batch_size"], seq_len=a["seq_len"],
            steps=config["hparams"]["schedule_steps"], seed=seed)
        self.setup_at.append(("LMTrial: data and init", time.time()))
        self._last = None
        self.losses, self.done_at, self.dispatch_s = [], [], []

    def __enter__(self):
        self.trial.__enter__()
        return self

    def __exit__(self, *exc):
        return self.trial.__exit__(*exc)

    def step(self, i: int) -> None:
        t0 = runners.now()
        loss = self.trial.step(i)
        self.dispatch_s.append(runners.now() - t0)
        self.drain()
        self._last = loss

    def drain(self) -> None:
        if self._last is not None:
            with self._jax.profiler.TraceAnnotation("wait_for_the_device"):
                self._last.block_until_ready()
            self.losses.append(self._last)
            self.done_at.append(runners.now())
            self._last = None

    def step_seconds(self) -> list:
        """Seconds between one completion and the next."""
        return [b - a for a, b in zip(self.done_at, self.done_at[1:])]

    def run_for(self, i: int, seconds: float) -> int:
        """Steps from ``i`` on until ``seconds`` of wall have passed, the
        last waited for. Returns the next step's number."""
        t0 = runners.now()
        while runners.now() - t0 < seconds:
            self.step(i)
            i += 1
        self.drain()
        return i

    def forget(self) -> None:
        self.losses.clear()
        self.done_at.clear()
        self.dispatch_s.clear()

    def first_steps(self, config: dict, seed: int, n: int) -> dict:
        """Seeded weights into the trial's state, then its first ``n``
        steps through ``step``: what the output check compares, copied to
        the host so that the device's peak stays the program's."""
        import numpy as np
        from flax import linen as nn

        jax, trial = self._jax, self.trial
        boxes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), trial.params)
        jax.tree.map(lambda x: x.delete(), trial.params)  # room for the new
        weights = weights_lm.make_weights(
            seed, reference.param_shapes(lm_config.reference_cfg(config)),
            stack=True)
        trial.params = jax.device_put(
            nn.meta.replace_boxed(boxes, weights), trial.shardings[0])
        del weights
        self.setup_at.append(("seeded weights", time.time()))
        rows = [np.asarray(jax.device_get(trial.rows(i))) for i in range(n)]
        if len(np.unique(np.concatenate(rows), axis=0)) \
                != n * trial.batch_size:
            raise ValueError("the first steps' rows do not all differ")
        self.step(0)
        # Adam's first moment after one step is (1 - b1) g, b1 = 0.9 being
        # optax.adamw's default, which trial_setup leaves alone
        grad = jax.tree.map(lambda m: m / (1 - 0.9), jax.device_get(
            nn.meta.unbox(trial.opt_state[0].mu)))
        for i in range(1, n):
            self.step(i)
        self.drain()
        self.setup_at.append((f"step's compile and {n} steps", time.time()))
        readings = {
            "losses": [float(x) for x in jax.device_get(self.losses)],
            "grad": weights_lm.split(grad), "rows": rows,
            "params": weights_lm.split(
                jax.device_get(nn.meta.unbox(trial.params)))}
        self.forget()
        return readings


def readings(ctx) -> dict:
    """The first steps' readings alone, the loop freed behind them."""
    with Loop(ctx.config, ctx.seed) as loop:
        return loop.first_steps(ctx.config, ctx.seed,
                                ctx.traffic["warm_steps"])


def kernel_work(config: dict, counts: dict, steps: int) -> dict:
    """What the roofline readers divide by device time: the operations and
    bytes of one call of each attention kernel, a layer, and of one forward
    pass of a layer's grouped products over the items the window's steps
    routed to held experts on average."""
    cfg = lm_config.reference_cfg(config)
    a = config["script_args"]
    windows = flops_lm.layer_windows(cfg)
    items = sum(map(sum, counts["items"])) / (len(windows) * max(steps, 1))
    return {
        "layers": len(windows), "remat": bool(a["remat"]),
        "flash_fwd": [flops_lm.flash_fwd_call(cfg, a["seq_len"], w,
                                              a["batch_size"])
                      for w in windows],
        "flash_bwd": [flops_lm.flash_bwd_call(cfg, a["seq_len"], w,
                                              a["batch_size"])
                      for w in windows],
        "experts_pass": flops_lm.experts_pass(cfg, items),
    }


def run(ctx):
    try:
        from metaopt_tpu.models.lm import LMTrial  # noqa: F401
    except ImportError as exc:
        from chipbench.run import Refused

        raise Refused("this program has no models/lm.py::LMTrial to drive "
                      f"({exc})") from None
    ctx.use_steady_cache()
    import jax

    devs = ctx.devices()
    a, t = ctx.config["script_args"], ctx.traffic
    compiles = runners.CompileCounter()
    trace_dir = os.path.join(ctx.run_dir, "trace")
    with Loop(ctx.config, ctx.seed) as loop:
        first = loop.first_steps(ctx.config, ctx.seed, t["warm_steps"])
        before = loop.trial.read_counts()
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
        after = loop.trial.read_counts()
    peak = runners.peak_bytes(devs)
    finite = [bool(x == x and abs(x) != float("inf"))
              for x in (float(v) for v in jax.device_get(loop.losses))]
    step_s, dispatch_s = loop.step_seconds(), loop.dispatch_s
    steps = len(loop.losses)
    parts = [("start", ctx.t_start)] + loop.setup_at + [
        ("counts read, window open", ctx.t_start + setup_s)]
    del loop
    counts = {"items": [[y - x for x, y in zip(b, e)] for b, e in
                        zip(before["items"], after["items"])],
              "dropped": [e - b for b, e in
                          zip(before["dropped"], after["dropped"])]}
    rate = steps * a["batch_size"] * a["seq_len"] / wall
    print(f"lm_trial_steps: {steps} steps in {wall:.3f} s, set-up "
          f"{setup_s:.2f} s, {compiles.in_window} compile requests in the "
          f"window", flush=True)
    print("lm_trial_steps: set-up by part: " + ", ".join(
        f"{name} {at - since:.2f} s" for (_, since), (name, at)
        in zip(parts, parts[1:])), flush=True)
    print(f"lm_trial_steps: between two completions at most "
          f"{max(step_s) * 1e3:.3f} ms, in one dispatch at most "
          f"{max(dispatch_s) * 1e3:.3f} ms", flush=True)
    print(f"lm_trial_steps: items a held expert over the window, a layer: "
          f"{counts['items']}; dropped {counts['dropped']}", flush=True)
    check = checks.run(ctx.config, ctx.seed, first.pop("rows"), first)
    del first
    records = {
        "step_s": step_s, "dispatch_s": dispatch_s, "items_per_s": rate,
        "flops_per_item": flops_lm.train_flops_per_item(
            lm_config.reference_cfg(ctx.config), a["seq_len"]),
        "device_kind": devs[0].device_kind, "chips": len(devs),
        "peak_bytes": peak, "compiles_in_window": compiles.in_window,
        "check": check, "moe_counts": counts,
        "kernel_work": kernel_work(ctx.config, counts, steps),
    }
    result = {
        "correct": check["correct"] and all(finite)
        and compiles.in_window == 0 and not any(counts["dropped"]),
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
    print(f"lm_trial_steps: step p50 "
          f"{statistics.median(step_s) * 1e3:.3f} ms", flush=True)
    return result
