"""``sparse_lm_trial_steps``: ``lm_trial_steps`` for a decoder whose
attention runs over the keys an indexer selects.

The loop, the set-up, the window and ``correct`` are ``lm_trial_steps``'s:
``Loop`` here is that kind's, with the two parts that read the
configuration's file replaced (the description comes from
``sparse_lm_config.py``, the seeded weights' shapes from
``reference/sparse_lm.py``), and ``run`` is that kind's ``run`` with the
selection's counters read beside the expert layers' and this kind's
kernels' work in the records. ``runners/lm_trial_steps.py`` names its
configuration and reference modules in its own imports, which is why a
second decoder family needs this file at all.

What belongs to the kind: the configuration's file keeps the published
config's keys at its top level (the Qwen3-MoE family's, plus ``sa_config``;
the cut ones at the size held here) and says what the chip holds under
``script_args.share``; ``sparse_lm_config.py`` turns it into the program's
description (``python -m chipbench.sparse_lm_config FILE`` prints it, for
``examples/lm_causal.py --model``) and the reference's;
``reference/sparse_lm.py``, ``checks/sparse_lm_train3.py``,
``flops_sparse_lm.py``; ``weights_lm.py`` and ``kernel_trace.py`` are
shared with ``lm_trial_steps``. The cell reports the accepted metrics of
the layers it runs under ``sparse_lm_<name>``, each a reader that calls the
accepted one (an accepted ``workloads`` list takes no new cell), and the
selection's own under ``sparse_<name>``.

**A third decoder configuration** whose layers ``make_lm`` can describe
reuses this file as it is if its attention selects its keys, and
``lm_trial_steps`` if it has a window or none: it brings a configuration
file, a traffic mix naming the kind, and its cell's delegating readers. A
family with other published keys brings its own ``*_config.py`` and
reference and, until ``lm_trial_steps`` takes those two as parameters (a
``benchmark`` issue), a runner file like this one.

The program has to have the mechanism: without ``ops/sparse_index.py`` the
call is refused at once (``Refused``), before jax is loaded.
"""

from __future__ import annotations

import importlib.util
import os
import statistics
import time

from chipbench import (checks, flops_lm, flops_sparse_lm, runners,
                       sparse_lm_config, trace_reduce, weights_lm)
from chipbench.reference import sparse_lm as reference
from chipbench.runners import lm_trial_steps
from chipbench.runners.lm_trial_steps import HOST_SPANS


class Loop(lm_trial_steps.Loop):
    """``lm_trial_steps.Loop`` over this family's description and
    reference shapes."""

    def __init__(self, config: dict, seed: int):
        import jax

        from metaopt_tpu.models.lm import LMTrial

        a = config["script_args"]
        self._jax = jax
        self.setup_at = [("imports and reaching the chip", time.time())]
        self.trial = LMTrial(
            sparse_lm_config.description(config), tp=a["tp"],
            n_train=a["n_train"], batch_size=a["batch_size"],
            seq_len=a["seq_len"],
            steps=config["hparams"]["schedule_steps"], seed=seed)
        self.setup_at.append(("LMTrial: data and init", time.time()))
        self._last = None
        self.losses, self.done_at, self.dispatch_s = [], [], []

    def first_steps(self, config: dict, seed: int, n: int) -> dict:
        """As ``lm_trial_steps.Loop.first_steps``; the first gradient names
        the trained leaves alone (AdamW holds no moment for an indexer),
        the parameters after the steps every leaf."""
        import numpy as np
        from flax import linen as nn

        jax, trial = self._jax, self.trial
        boxes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), trial.params)
        jax.tree.map(lambda x: x.delete(), trial.params)  # room for the new
        weights = weights_lm.make_weights(
            seed, reference.param_shapes(
                sparse_lm_config.reference_cfg(config)), stack=True)
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
    bytes of one call of each attention kernel, a layer (the selected pairs
    only), and of one forward pass of a layer's grouped products over the
    items the window's steps routed to held experts on average."""
    cfg = sparse_lm_config.reference_cfg(config)
    a = config["script_args"]
    layers = cfg["n_layers"]
    items = sum(map(sum, counts["items"])) / (layers * max(steps, 1))
    return {
        "layers": layers, "remat": bool(a["remat"]),
        "sparse_fwd": [flops_sparse_lm.sparse_fwd_call(
            cfg, a["seq_len"], a["batch_size"])] * layers,
        "sparse_bwd": [flops_sparse_lm.sparse_bwd_call(
            cfg, a["seq_len"], a["batch_size"])] * layers,
        "experts_pass": flops_lm.experts_pass(cfg, items),
    }


def _over_window(before: dict, after: dict) -> dict:
    """What the program counted between two reads of its counts."""
    diff = lambda b, e: [y - x for x, y in zip(b, e)]  # noqa: E731
    return {"items": [diff(b, e) for b, e in zip(before["items"],
                                                 after["items"])],
            **{k: diff(before[k], after[k])
               for k in ("dropped", "selected_pairs", "causal_pairs")}}


def run(ctx):
    if importlib.util.find_spec("metaopt_tpu.ops.sparse_index") is None:
        from chipbench.run import Refused

        raise Refused("this program has no ops/sparse_index.py: it cannot "
                      "attend over selected keys")
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
    counts = _over_window(before, after)
    rate = steps * a["batch_size"] * a["seq_len"] / wall
    kind = "sparse_lm_trial_steps"
    print(f"{kind}: {steps} steps in {wall:.3f} s, set-up {setup_s:.2f} s, "
          f"{compiles.in_window} compile requests in the window", flush=True)
    print(f"{kind}: set-up by part: " + ", ".join(
        f"{name} {at - since:.2f} s" for (_, since), (name, at)
        in zip(parts, parts[1:])), flush=True)
    print(f"{kind}: between two completions at most "
          f"{max(step_s) * 1e3:.3f} ms, in one dispatch at most "
          f"{max(dispatch_s) * 1e3:.3f} ms", flush=True)
    print(f"{kind}: items a held expert over the window, a layer: "
          f"{counts['items']}; dropped {counts['dropped']}", flush=True)
    print(f"{kind}: pairs selected over the window, a layer: "
          f"{counts['selected_pairs']} of {counts['causal_pairs']} causal",
          flush=True)
    check = checks.run(ctx.config, ctx.seed, first.pop("rows"), first)
    del first
    records = {
        "step_s": step_s, "dispatch_s": dispatch_s, "items_per_s": rate,
        "flops_per_item": flops_sparse_lm.train_flops_per_item(
            sparse_lm_config.reference_cfg(ctx.config), a["seq_len"]),
        "device_kind": devs[0].device_kind, "chips": len(devs),
        "peak_bytes": peak, "compiles_in_window": compiles.in_window,
        "check": check,
        "moe_counts": {k: counts[k] for k in ("items", "dropped")},
        "selection_counts": {k: counts[k] for k in ("selected_pairs",
                                                    "causal_pairs")},
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
    print(f"{kind}: step p50 {statistics.median(step_s) * 1e3:.3f} ms",
          flush=True)
    return result
