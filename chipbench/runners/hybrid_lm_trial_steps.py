"""``hybrid_lm_trial_steps``: ``lm_trial_steps`` for a decoder with
linear-attention layers (the gated delta rule) beside full-attention ones
and a dense gated feed-forward.

The loop, the set-up, the window and ``correct`` are ``lm_trial_steps``'s:
``Loop`` here is that kind's, with the two parts that read the
configuration's file replaced (the description comes from
``hybrid_lm_config.py``, the seeded weights from ``weights_hybrid_lm.py``
over ``reference/hybrid_lm.py``'s shapes), and ``run`` is that kind's
``run`` without the expert layers' counters (the model has no experts and
counts nothing) and with this kind's kernels' work in the records.
``runners/lm_trial_steps.py`` names its configuration and reference
modules in its own imports, which is why a third decoder family needs this
file at all (``sparse_lm_trial_steps.py`` says the same).

What belongs to the kind: the configuration's file keeps the published
config's keys at its top level (the Olmo hybrid family's: ``layer_types``,
``linear_*``; the cut ones at the size held here) and says what the chip
holds under ``script_args.share`` (``heads_held`` of ``heads_of``,
``vocab_held``); ``hybrid_lm_config.py`` turns it into the program's
description (``python -m chipbench.hybrid_lm_config FILE`` prints it, for
``examples/lm_causal.py --model``) and the reference's;
``reference/hybrid_lm.py`` (the linear layers token by token),
``checks/hybrid_lm_train3.py``, ``weights_hybrid_lm.py``,
``flops_hybrid_lm.py`` (the scan's work, counted at a chunk of 64 whatever
the program runs) and ``hybrid_kernel_trace.py`` (the scan kernels'
roofline shares); ``kernel_trace.py`` is shared with ``lm_trial_steps``.
The cell reports the accepted metrics of the layers it runs under
``hybrid_lm_<name>``, each a reader that calls the accepted one, and the
scan's own under ``linear_<name>``.

Which steps the output check follows. ``models/data.py::synthetic_lm``
walks one fixed permutation of the held vocabulary, and a permutation has
short cycles: of this cell's 12 542 tokens, 440 lie on cycles of 385, 16,
14, 12, 9, 2, 1 and 1 (3.5 % of the rows start there, a tenth of the seeds
have one among their first three). A row of 8192 tokens on such a cycle is
the same few tokens hundreds of times over: its loss and its gradient are
means over that few predictions, not over 8192, so the rounding of the
configuration's own bfloat16 products no longer averages out (a row on the
cycle of 9 reads a ``loss_gap`` of 5.6e-4 and one on the cycle of 385
1.4e-4 where 56 seeds' other rows read at most 4.4e-5 and the fp8 control
from 1.6e-4; a first row on the cycle of 9 or of 2 fails ``grad_norm_gap``
too: PERF.md section 6, PR 32) and no one limit can tell the two
precisions apart on it. So the check follows the first ``warm_steps`` step
numbers whose rows hold at least ``check_row_distinct_share`` of their
length in distinct tokens (the traffic mix's key; an eighth: 1024 of 8192),
by ``trial.step(i)`` as ever, and the runner prints the numbers it passed
over. Nothing else changes: the window runs every step from ``warm_steps``
on, short cycles and all, as the mix says.

The program has to have the mechanism: without ``ops/linear_attention.py``
the call is refused at once (``Refused``), before jax is loaded.
"""

from __future__ import annotations

import importlib.util
import os
import statistics
import time

from chipbench import (checks, flops_hybrid_lm, hybrid_lm_config, runners,
                       trace_reduce, weights_hybrid_lm)
from chipbench.reference import hybrid_lm as reference
from chipbench.runners import lm_trial_steps
from chipbench.runners.lm_trial_steps import HOST_SPANS

KIND = "hybrid_lm_trial_steps"


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
            hybrid_lm_config.description(config), tp=a["tp"],
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
        (no expert's matrices to stack or split)."""
        import numpy as np
        from flax import linen as nn

        jax, trial = self._jax, self.trial
        boxes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), trial.params)
        jax.tree.map(lambda x: x.delete(), trial.params)  # room for the new
        weights = weights_hybrid_lm.make_weights(
            seed, reference.param_shapes(
                hybrid_lm_config.reference_cfg(config)))
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
    output check can judge (the module's docstring says why a row on a
    short cycle of the data's permutation is none). Prints the numbers it
    passes over."""
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
    bytes of one call of each kernel, a layer that calls it. ``layers``
    counts the full layers (``kernel_trace.attention_kernel_roofline``
    scales the flash kernels' calls by it), ``linear_layers`` the linear
    ones."""
    cfg = hybrid_lm_config.reference_cfg(config)
    a = config["script_args"]
    s, b = a["seq_len"], a["batch_size"]
    linear = sum(cfg["linear"])
    full = len(cfg["linear"]) - linear
    return {
        "layers": full, "linear_layers": linear, "remat": bool(a["remat"]),
        "flash_fwd": [flops_hybrid_lm.flash_fwd_call(cfg, s, b)] * full,
        "flash_bwd": [flops_hybrid_lm.flash_bwd_call(cfg, s, b)] * full,
        "linear_scan_fwd": [flops_hybrid_lm.linear_fwd_call(cfg, s, b)]
        * linear,
        "linear_scan_bwd": [flops_hybrid_lm.linear_bwd_call(cfg, s, b)]
        * linear,
    }


def run(ctx):
    if importlib.util.find_spec("metaopt_tpu.ops.linear_attention") is None:
        from chipbench.run import Refused

        raise Refused("this program has no ops/linear_attention.py: it "
                      "cannot run a linear-attention layer")
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
        "flops_per_item": flops_hybrid_lm.train_flops_per_item(
            hybrid_lm_config.reference_cfg(ctx.config), a["seq_len"]),
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
