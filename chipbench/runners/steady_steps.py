"""``steady_steps``: a train loop the runner drives step by step.

For a program whose ``train_and_eval`` has no per-step hook
(models/transformer.py): the runner makes the same calls in the same
order with the same batch slicing as lines 561-583 of that file, so the
window times the program's jitted step, its sharded feed and its host
loop. Each call waits for the step before the one it dispatched: a step's
time is the time between two completions, and the window's last step is
waited for before the clock is read.

set-up: imports, reaching the chip, data, ``init_sharded``, seeded weights
put into the loop's state, the step's compile and ``warm_steps`` steps.
Those first steps are the ones the output check follows: the object they
drive is the object the window then times. The window: ``--seconds`` of
steps. Afterwards, outside both and with the loop's state freed: the
reference and the comparison (chipbench/checks).
"""

from __future__ import annotations

import os
import statistics
import time

from chipbench import checks, flops, runners, trace_reduce
from chipbench.weights import make_weights

SPANS = ("slice_and_shard_batch", "dispatch_step", "wait_for_the_device")


class Loop:
    """The program's train loop, one step a call: the calls, their order
    and the batch slicing of models/transformer.py:561-583. The program's
    loop never waits for a step; ``step(i)`` feeds and dispatches step
    ``i`` and then waits for step ``i - 1``, so the host prepares the next
    batch while the device works, as in the program, and is never more
    than one step ahead. ``drain()`` waits for the last. ``losses`` are
    device scalars, ``spans`` each step's seconds in ``SPANS``' order. Used
    as a context manager: the loop runs inside the trial's mesh, as the
    program's does."""

    def __init__(self, args: dict, hp: dict, seed: int):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from metaopt_tpu.models.data import synthetic_seq2seq
        from metaopt_tpu.models.transformer import (
            init_sharded, make_model, make_train_step, trial_setup,
        )
        from metaopt_tpu.parallel.mesh import use_mesh
        from metaopt_tpu.parallel.sharding import shard_batch

        self._jax, self._shard_batch = jax, shard_batch
        self.batch_size, self.n_train = args["batch_size"], args["n_train"]
        self.mesh, tx = trial_setup(hp, None, args["tp"], 1, 1,
                                    hp["schedule_steps"])
        model = make_model(hp)
        kd, self._kstep = jax.random.split(jax.random.PRNGKey(seed))
        self._src, self._tgt = synthetic_seq2seq(
            kd, self.n_train, args["seq_len"], model.vocab)
        self._use_mesh = use_mesh
        with use_mesh(self.mesh):
            params, opt_state, shardings = init_sharded(
                model, self.mesh, tx, (self.batch_size, args["seq_len"]),
                seed)
            self._step_fn = jax.jit(
                make_train_step(model, tx),
                in_shardings=(shardings[0], shardings[1],
                              NamedSharding(self.mesh, P("dp")), None),
                out_shardings=(shardings[0], shardings[1], None),
                donate_argnums=(0, 1),
            )
        self._state, self._shardings = [params, opt_state], shardings
        self.losses, self.spans = [], []

    def __enter__(self):
        self._mesh_scope = self._use_mesh(self.mesh)
        self._mesh_scope.__enter__()
        return self

    def __exit__(self, *exc):
        return self._mesh_scope.__exit__(*exc)

    def rows(self, i: int):
        """Step ``i``'s (src, tgt), sliced as the program slices them."""
        lo = (i * self.batch_size) % (self.n_train - self.batch_size + 1)
        sl = slice(lo, lo + self.batch_size)
        return self._src[sl], self._tgt[sl]

    def step(self, i: int) -> None:
        annotate = self._jax.profiler.TraceAnnotation
        t0 = runners.now()
        with annotate("slice_and_shard_batch"):
            batch = self._shard_batch(self.mesh, self.rows(i))
        t1 = runners.now()
        with annotate("dispatch_step"):
            self._state[0], self._state[1], loss = self._step_fn(
                self._state[0], self._state[1], batch,
                self._jax.random.fold_in(self._kstep, i))
        t2 = runners.now()
        self.drain()
        self.losses.append(loss)
        self.spans.append((t1 - t0, t2 - t1, runners.now() - t2))

    def drain(self) -> None:
        if self.losses:
            with self._jax.profiler.TraceAnnotation("wait_for_the_device"):
                self.losses[-1].block_until_ready()

    def first_steps(self, seed: int, n: int) -> dict:
        """Seeded weights into this loop's state, then its first ``n``
        steps through ``step``: what the output check compares, copied to
        the host so that the device's peak stays the program's."""
        import numpy as np
        from flax import linen as nn

        jax = self._jax
        params = self._state[0]
        weights = make_weights(seed, jax.eval_shape(nn.meta.unbox, params))
        self._state[0] = jax.device_put(
            nn.meta.replace_boxed(params, weights), self._shardings[0])
        del params, weights
        rows = [jax.device_get(self.rows(i)) for i in range(n)]
        if len(np.unique(np.concatenate([s for s, _ in rows]), axis=0)) \
                != n * self.batch_size:
            raise ValueError("the first steps' rows do not all differ")
        self.step(0)
        # Adam's first moment after one step is (1 - b1) g, b1 = 0.9 being
        # optax.adamw's default, which trial_setup leaves alone
        grad = jax.tree.map(lambda m: m / (1 - 0.9), jax.device_get(
            nn.meta.unbox(self._state[1][0].mu)))
        for i in range(1, n):
            self.step(i)
        self.drain()
        readings = {
            "losses": [float(x) for x in jax.device_get(self.losses)],
            "grad": grad, "rows": rows,
            "params": jax.device_get(nn.meta.unbox(self._state[0]))}
        self.losses.clear()
        self.spans.clear()
        return readings


def model_hparams(config: dict) -> dict:
    a = config["script_args"]
    hp = dict(config["hparams"])
    hp.update(d_model=a["d_model"], n_layers=a["n_layers"], d_ff=a["d_ff"],
              n_heads=max(1, a["d_model"] // 64), vocab=a["vocab"],
              max_len=a["max_len"])
    return hp


def readings(ctx) -> dict:
    """The first steps' readings alone, the loop freed behind them."""
    with Loop(ctx.config["script_args"], model_hparams(ctx.config),
              ctx.seed) as loop:
        return loop.first_steps(ctx.seed, ctx.traffic["warm_steps"])


def run(ctx):
    ctx.use_steady_cache()
    import jax

    devs = ctx.devices()
    a, t = ctx.config["script_args"], ctx.traffic
    batch_size, seq_len = a["batch_size"], a["seq_len"]
    compiles = runners.CompileCounter()
    trace_dir = os.path.join(ctx.run_dir, "trace")
    with Loop(a, model_hparams(ctx.config), ctx.seed) as loop:
        first = loop.first_steps(ctx.seed, t["warm_steps"])
        traced = None
        if ctx.trace:
            jax.profiler.start_trace(trace_dir)
        setup_s = time.time() - ctx.t_start
        compiles.open()
        t0 = runners.now()
        step_s, i, writing = [], t["warm_steps"], 0.0
        while True:
            ts = runners.now()
            if ts - t0 - writing >= ctx.seconds:
                break
            if ctx.trace and traced is None \
                    and ts - t0 >= t["trace_seconds"]:
                loop.drain()
                traced = runners.now() - t0  # the slice: the window's start
                jax.profiler.stop_trace()
                writing = runners.now() - ts  # not a step's time
                continue
            loop.step(i)
            step_s.append(runners.now() - ts)
            i += 1
        loop.drain()
        wall = runners.now() - t0 - writing
        compiles.close()
        if ctx.trace and traced is None:
            jax.profiler.stop_trace()
            traced = wall
    peak = runners.peak_bytes(devs)
    finite = [bool(x == x and abs(x) != float("inf"))
              for x in (float(v) for v in jax.device_get(loop.losses))]
    spans = loop.spans
    del loop
    items = len(step_s) * batch_size * seq_len
    rate = items / wall
    print(f"steady_steps: {len(step_s)} steps in {wall:.3f} s, set-up "
          f"{setup_s:.2f} s, {compiles.in_window} compile requests in the "
          f"window", flush=True)
    slow = max(range(len(step_s)), key=step_s.__getitem__)
    print(f"steady_steps: slowest step {slow} of the window: "
          f"{step_s[slow] * 1e3:.3f} ms = " + ", ".join(
              f"{name} {s * 1e3:.3f}" for name, s in zip(SPANS, spans[slow])),
          flush=True)
    check = checks.run(ctx.config, ctx.seed, first.pop("rows"), first)
    del first
    records = {
        "step_s": step_s, "step_spans_s": spans, "items_per_s": rate,
        "flops_per_item": flops.transformer_train_flops_per_item(
            seq_len, a["d_model"], a["n_layers"], a["d_ff"], a["vocab"]),
        "device_kind": devs[0].device_kind, "chips": len(devs),
        "peak_bytes": peak, "compiles_in_window": compiles.in_window,
        "check": check,
    }
    result = {
        "correct": check["correct"] and all(finite)
        and compiles.in_window == 0,
        "attempted": len(step_s), "failed": finite.count(False),
        "end_to_end": {"train_items_per_s": rate, "setup_s": setup_s},
        "records": records,
    }
    if ctx.trace:
        reduced = trace_reduce.reduce(trace_dir, traced, SPANS)
        print("trace lines:", reduced.pop("lines"), flush=True)
        records["trace"] = reduced
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        result["device"] = runners.device_entry(devs, peak, reduced)
    else:
        result["device"] = runners.device_entry(devs, peak)
    print(f"steady_steps: step p50 {statistics.median(step_s) * 1e3:.3f} ms",
          flush=True)
    return result

