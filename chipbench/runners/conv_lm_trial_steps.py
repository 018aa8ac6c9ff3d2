"""``conv_lm_trial_steps``: ``lm_trial_steps`` for a decoder whose mixers are
gated short convolutions beside grouped attention (the ``lfm2_moe``
family's): a convolution of three taps between two gates read from the
layer's own input, no scan and no state beyond two tokens; attention with
q/k norms a head and rotary positions; leading dense layers, then SwiGLU
experts chosen by sigmoid scores with a correction bias, no shared expert;
a tied head.

The loop, the set-up, the window and ``correct`` are ``lm_trial_steps``'s:
``Loop`` here is that kind's, with the two parts that read the
configuration's file replaced (the description comes from
``conv_lm_config.py``, the seeded weights from ``weights_conv_lm.py`` over
``reference/conv_lm.py``'s shapes) and the first steps chosen as the hybrid
kinds choose them (``judged_steps``: rows that hold an eighth of their
length in distinct tokens; ``runners/hybrid_lm_trial_steps.py`` says why a
row on a short cycle of the data's permutation can be judged by no one
limit); ``run`` is the latent kind's ``run`` (the routed layers' counters
and the correction bias's count) with this kind's kernels' work in the
records. ``runners/lm_trial_steps.py`` names its configuration and
reference modules in its own imports, which is why an eighth decoder family
needs this file at all and copies ``first_steps`` and ``run`` again (a
``benchmark`` issue's to repair: PERF.md section 7).

What belongs to the kind (chipbench/README.md is the benchmark's and is not
this kind's PR's to edit): the configuration's file keeps the published
config's keys at its top level (``model_type`` ``lfm2_moe``, ``layer_types``
whole, 40 entries read at the published numbers
``script_args.share.layers_held`` names; the cut ones at the size held
here) and says what the chip holds under ``script_args.share``;
``conv_lm_config.py`` turns it into the program's description (``python -m
chipbench.conv_lm_config FILE`` prints it, for ``examples/lm_causal.py
--model``) and the reference's; ``reference/conv_lm.py`` (the convolution
as three shifted sums; attention dense under the mask, a head at a time and
in blocks of rows; the experts a loop over the held ones; the planted faults
by name), ``checks/conv_lm_train3.py``, ``weights_conv_lm.py``
(``weights_lm.py``'s rules, and the tied table, the taps and the routing's
leaves drawn where the docstring there says), ``flops_conv_lm.py`` (the
core's equations at two bytes a number; the experts' three products) and
``conv_kernel_trace.py`` (the core's roofline shares, and the compiler's
operations made for a mixer with ``short_conv`` among the mixers);
``kernel_trace.py``, ``mla_kernel_trace.py`` and ``weights_lm.py`` are
shared. The cell reports the accepted metrics of the layers it runs under
``conv_lm_<name>``, each a reader with the accepted one's body (an accepted
``workloads`` list takes no new cell from a PR of this kind); the mixer's
own are ``short_conv_mixer_device_ms``, ``short_conv_core_device_ms``,
``short_conv_{fwd,bwd}_roofline``, and the routing's
``conv_lm_moe_choice_bias_share``.

The program has to have the mechanism: where
``models/lm_description.py`` has no ``lfm2_moe`` reader the call is
refused at once (``Refused``), before jax is loaded (the file is parsed,
not imported).
"""

from __future__ import annotations

import ast
import os
import statistics
import time

from chipbench import (checks, conv_lm_config, flops_conv_lm, flops_lm,
                       runners, trace_reduce, weights_conv_lm, weights_lm)
from chipbench.reference import conv_lm as reference
from chipbench.runners import lm_trial_steps
from chipbench.runners.lm_trial_steps import HOST_SPANS
# what the program counted between two reads of its counts
from chipbench.runners.ssd_lm_trial_steps import _over_window

KIND = "conv_lm_trial_steps"
FAMILY = "lfm2_moe"


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
            conv_lm_config.description(config), tp=a["tp"],
            n_train=a["n_train"], batch_size=a["batch_size"],
            seq_len=a["seq_len"],
            steps=config["hparams"]["schedule_steps"], seed=seed)
        self.setup_at.append(("LMTrial: data and init", time.time()))
        self._last = None
        self.losses, self.done_at, self.dispatch_s = [], [], []

    def first_steps(self, config: dict, seed: int, n: int,
                    distinct_share: float) -> dict:
        """As ``lm_trial_steps.Loop.first_steps``, over the first ``n``
        steps that ``judged_steps`` names; the first gradient names the
        trained leaves alone (AdamW holds no moment for a correction
        bias), the parameters after the steps every leaf."""
        import numpy as np
        from flax import linen as nn

        jax, trial = self._jax, self.trial
        boxes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), trial.params)
        jax.tree.map(lambda x: x.delete(), trial.params)  # room for the new
        cfg = conv_lm_config.reference_cfg(config)
        weights = weights_conv_lm.make_weights(
            seed, reference.param_shapes(cfg), stack=True)
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
            "grad": weights_lm.split(grad), "rows": rows,
            "params": weights_lm.split(
                jax.device_get(nn.meta.unbox(trial.params)))}
        self.forget()
        return readings


def judged_steps(trial, n: int, distinct_share: float) -> list:
    """The first ``n`` step numbers of ``trial`` every row of which holds at
    least ``distinct_share`` of its length in distinct tokens (the hybrid
    kind's rule, this kind's name on what it prints)."""
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


def kernel_work(config: dict, counts: dict, steps: int) -> dict:
    """What the roofline readers divide by device time: the operations and
    bytes of one call of each attention kernel (``layers``: the layers that
    call them, the ``full_attention`` ones), of one call of the mixer's core
    in each direction (``conv_layers``: the ``conv`` ones) and of one
    forward pass of a routed layer's three grouped products over the items
    the window's steps routed to held experts on average
    (``routed_layers``: the layers past the dense ones)."""
    cfg = conv_lm_config.reference_cfg(config)
    a = config["script_args"]
    s, b = a["seq_len"], a["batch_size"]
    full = cfg["kinds"].count("full_attention")
    conv = cfg["kinds"].count("conv")
    routed = sum(n >= cfg["dense_layers"] for n in cfg["numbers"])
    items = sum(map(sum, counts["items"])) / (max(routed, 1) * max(steps, 1))
    return {
        "layers": full, "conv_layers": conv, "routed_layers": routed,
        "remat": bool(a["remat"]),
        "flash_fwd": [flops_conv_lm.flash_fwd_call(cfg, s, b)] * full,
        "flash_bwd": [flops_conv_lm.flash_bwd_call(cfg, s, b)] * full,
        "short_conv_fwd": [flops_conv_lm.short_conv_fwd_call(cfg, s, b)]
        * conv,
        "short_conv_bwd": [flops_conv_lm.short_conv_bwd_call(cfg, s, b)]
        * conv,
        "experts_pass": flops_lm.experts_pass(cfg, items),
    }


def has_mechanism() -> bool:
    """Does the program's ``models/lm_description.py`` name a reader for
    the family in ``_FAMILIES``? Read from the file's text: importing it
    would load jax."""
    from chipbench.run import ROOT

    path = os.path.join(ROOT, "metaopt_tpu", "models", "lm_description.py")
    try:
        with open(path) as f:
            tree = ast.parse(f.read())
    except (OSError, SyntaxError):
        return False
    return any(
        isinstance(node, ast.Assign) and isinstance(node.value, ast.Dict)
        and any(getattr(t, "id", None) == "_FAMILIES" for t in node.targets)
        and FAMILY in [getattr(k, "value", None) for k in node.value.keys]
        for node in tree.body)


def run(ctx):
    if not has_mechanism():
        from chipbench.run import Refused

        raise Refused("this program's models/lm_description.py has no "
                      f"{FAMILY!r} reader: it cannot build the family's "
                      "layers")
    ctx.use_steady_cache()
    import jax

    devs = ctx.devices()
    a, t = ctx.config["script_args"], ctx.traffic
    compiles = runners.CompileCounter()
    trace_dir = os.path.join(ctx.run_dir, "trace")
    with Loop(ctx.config, ctx.seed) as loop:
        first = loop.first_steps(ctx.config, ctx.seed, t["warm_steps"],
                                 t["check_row_distinct_share"])
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
    tokens = steps * a["batch_size"] * a["seq_len"]
    rate = tokens / wall
    print(f"{KIND}: {steps} steps in {wall:.3f} s, set-up {setup_s:.2f} s, "
          f"{compiles.in_window} compile requests in the window", flush=True)
    print(f"{KIND}: set-up by part: " + ", ".join(
        f"{name} {at - since:.2f} s" for (_, since), (name, at)
        in zip(parts, parts[1:])), flush=True)
    print(f"{KIND}: between two completions at most "
          f"{max(step_s) * 1e3:.3f} ms, in one dispatch at most "
          f"{max(dispatch_s) * 1e3:.3f} ms", flush=True)
    print(f"{KIND}: items a held expert over the window, a routed layer: "
          f"{counts['items']}; dropped {counts['dropped']}", flush=True)
    print(f"{KIND}: tokens whose chosen experts the bias moved, a routed "
          f"layer: {counts['bias_moved']} of {tokens}", flush=True)
    check = checks.run(ctx.config, ctx.seed, first.pop("rows"), first)
    del first
    records = {
        "step_s": step_s, "dispatch_s": dispatch_s, "items_per_s": rate,
        "flops_per_item": flops_conv_lm.train_flops_per_item(
            conv_lm_config.reference_cfg(ctx.config), a["seq_len"]),
        "device_kind": devs[0].device_kind, "chips": len(devs),
        "peak_bytes": peak, "compiles_in_window": compiles.in_window,
        "check": check,
        "moe_counts": {k: counts[k] for k in ("items", "dropped")},
        "choice_counts": {"bias_moved": counts["bias_moved"],
                          "tokens": tokens},
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
    print(f"{KIND}: step p50 {statistics.median(step_s) * 1e3:.3f} ms",
          flush=True)
    return result
