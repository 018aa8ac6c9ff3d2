#!/usr/bin/env python
"""Benchmark: the BASELINE north-star hot path + model-zoo step time/MFU.

Measures, on the TPU only (with no chip it exits non-zero and prints no
number):

1. TPE ``suggest()`` latency with 10 000 observations on an 8-dim mixed
   space — the operation BASELINE.md requires to stay flat past 10k trials —
   with the density kernel XLA-compiled on the chip, compared against a
   faithful numpy implementation of the exact same Parzen/EI math (the
   reference's implementation substrate: pure Python/numpy, SURVEY.md §2.9);
   GP-BO's incremental factor against a cold refit.
2. The flagship trial workloads on the same chip: Transformer-base train-step
   time with analytic-FLOP MFU at seq 256, 512 and 1024 (chunked flash
   attention, the TPU default), and ResNet-50/CIFAR step time (images/s).
3. The Pallas flash kernels compiled and run against the chunked twin
   (status/step_ms/numerics under ``flash_pallas``).
4. Host-side planes: batched trial evaluation, coordinator throughput
   (the ``coord`` stage runs with ``JAX_PLATFORMS=cpu``: it needs no chip).

One process for each chip: this launcher never imports jax. It asks one
short-lived child what devices there are, then runs every section as a
sequential ``--stage`` child that is the chip's one owner while it runs.
A stage that fails or times out is recorded and makes the exit code 1.

Output contract (the driver keeps only a bounded TAIL of stdout, so the
LAST line must be small and self-contained):
- the full record is written to ``benchmarks/results/bench_tpu_<date>.json``;
- the final stdout line is ONE compact JSON object:
    {"metric": "tpe_suggest_ms_per_point_10k_obs_pool8", "value": <ms>,
     "unit": "ms", "vs_baseline": <numpy_ms/jax_ms>, "platform": "tpu",
     "device_kind": ..., "device_count": ..., "stages_ok": [...],
     "stage_errors": {...}, "artifact": <relpath>, "mfu_seq256": ..., ...}
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from metaopt_tpu.utils.procs import (
    last_json_object,
    probe_tpu,
    run_with_deadline,
    use_xla_cache,
)


def build_tpe(n_obs: int, seed: int = 0):
    from metaopt_tpu.algo import TPE
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
    tpe = TPE(space, seed=seed, n_initial_points=8)
    rng = np.random.default_rng(seed)
    X = rng.random((n_obs, tpe.cube.n_dims))
    y = rng.random(n_obs).tolist()
    tpe._X = list(X)
    tpe._y = y
    tpe._observed = {str(i): y[i] for i in range(n_obs)}
    return tpe


def build_gpbo(n_obs: int, seed: int = 0, **kw):
    from metaopt_tpu.algo import GPBO
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
    gp = GPBO(space, seed=seed, n_initial_points=8, **kw)
    rng = np.random.default_rng(seed)
    X = rng.random((n_obs, gp.cube.n_dims))
    y = rng.random(n_obs).tolist()
    gp._X = list(X)
    gp._y = y
    gp._observed = {str(i): y[i] for i in range(n_obs)}
    return gp


def numpy_ei_reference(tpe) -> float:
    """The same split/fit/sample/score pipeline with numpy densities.

    This is what the reference-era implementation does per suggest call
    (Python/numpy KDE evaluation); timing it on the same data is the
    apples-to-apples baseline for the jitted kernel.
    """
    from scipy.special import logsumexp
    from scipy.stats import norm

    below, above = tpe._split()
    good, bad = tpe._fit_set(below), tpe._fit_set(above)
    cand = tpe._sample_from(good, tpe.n_ei_candidates)

    def np_logpdf(fit, x):
        mu, sig, logw = fit["mu"], fit["sigma"], fit["logw"]
        z = (x[:, None, :] - mu[None, :, :]) / sig[None, :, :]
        log_phi = norm.logpdf(z) - np.log(sig[None, :, :])
        mass = norm.cdf((1 - mu) / sig) - norm.cdf((0 - mu) / sig)
        log_mass = np.log(np.clip(mass, 1e-12, 1.0))
        return logsumexp(
            log_phi - log_mass[None, :, :] + logw[None, :, :], axis=1
        )

    log_l = np_logpdf(good, cand)
    log_g = np_logpdf(bad, cand)
    k = np.maximum(tpe.cube.n_choices, 1)
    cat_idx = np.minimum((cand * k[None, :]).astype(int), (k - 1)[None, :])
    d_idx = np.arange(cand.shape[1])[None, :]
    cat_mask = tpe.cube.categorical_mask
    log_l = np.where(cat_mask[None, :], good["cat_logp"][d_idx, cat_idx], log_l)
    log_g = np.where(cat_mask[None, :], bad["cat_logp"][d_idx, cat_idx], log_g)
    scores = np.sum(log_l - log_g, axis=1)
    return cand[int(np.argmax(scores))]


def time_fn(fn, repeats: int = 20) -> float:
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1000)
    return float(np.median(times))


#: peak dense bf16 FLOP/s per chip by device-kind substring
_PEAK_FLOPS = [
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5p", 459e12),
    ("v4", 275e12), ("v6", 918e12),
]


def peak_flops(device) -> float:
    kind = getattr(device, "device_kind", "").lower()
    for sub, peak in _PEAK_FLOPS:
        if sub in kind:
            return peak
    # a device that is not in the table is an error, not a default
    raise ValueError(f"no peak FLOP/s known for device_kind {kind!r}")


def transformer_train_flops(b, s, d, layers, d_ff, vocab) -> float:
    """Analytic FLOPs for one train step (fwd + bwd ≈ 3× fwd matmul FLOPs).

    Per-token matmul FLOPs: encoder layer 8d² (qkv/out) + 4·d·d_ff (ffn)
    + 4·S·d (scores+values); decoder layer adds a cross-attention block;
    readout 2·d·V per target token. Embedding gathers are ignored.
    """
    enc = layers * (8 * d * d + 4 * d * d_ff + 4 * s * d)
    dec = layers * (16 * d * d + 4 * d * d_ff + 8 * s * d)
    readout = 2 * d * vocab
    return 3.0 * b * s * (enc + dec + readout)


def bench_transformer(seq: int = 256, batch: int = 64,
                      force_xent: str = "") -> dict:
    """Train-step time + MFU for the flagship model on the chip.

    Shapes are Transformer-base (BASELINE config 4) at realistic
    sequence lengths — MFU at seq 64 measured mostly fixed overhead, which
    is not the number behind BASELINE's trials/hour north star. Attention
    rides the route ops/attention.attention_route picks (on TPU the Pallas
    kernels, the chunked twin under dropout) so the O(S²) logits tensor
    never exists.

    ``force_xent``: the A/B control — ``"materializing"`` disables the
    blocked online-softmax xent (ops/xent.py) so the f32 (B, T, V) logits
    tensor IS materialized; ``"blocked"`` forces the blocked path even when
    the logits-bytes gate would materialize. Empty = product routing.
    The 2026-08-01 v5e A/B measured materializing FASTER at bench shapes
    (58.5 vs 65.3 ms @seq256), which is why the product gate is now
    logits-bytes, not vocab — the forced stage keeps that verdict honest
    in every future record.
    """
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from metaopt_tpu.models import transformer as transformer_mod
    from metaopt_tpu.models.data import synthetic_seq2seq
    from metaopt_tpu.models.transformer import (
        init_sharded, make_model, make_train_step,
    )
    from metaopt_tpu.parallel.mesh import trial_mesh, use_mesh
    from metaopt_tpu.parallel.sharding import shard_batch

    if force_xent == "materializing":
        # runs in a dedicated --stage child, so the module-global poke
        # cannot leak into any other measurement
        transformer_mod._BLOCKED_XENT_MIN_LOGITS_BYTES = 1 << 62
    elif force_xent == "blocked":
        transformer_mod._BLOCKED_XENT_MIN_LOGITS_BYTES = 1
    elif force_xent:
        # a typo must not record a product-routed run under a forced tag
        raise ValueError(
            f"force_xent={force_xent!r}: expected materializing/blocked")

    # Transformer-base (BASELINE config 4 trial workload)
    cfg = {"d_model": 512, "n_heads": 8, "n_layers": 6, "d_ff": 2048,
           "vocab": 32000, "dropout": 0.1, "max_len": max(512, seq)}

    import jax.numpy as jnp

    model = make_model(cfg)
    tx = optax.adamw(1e-3)
    mesh = trial_mesh(tp=1)
    key = jax.random.PRNGKey(0)
    n_steps = 20
    with use_mesh(mesh):
        params, opt_state, shardings = init_sharded(
            model, mesh, tx, (batch, seq)
        )
        inner = make_train_step(model, tx)

        # the whole timed window is ONE device program (lax.scan over the
        # steps), so per-step host dispatch is not in it — MFU is about
        # the chip
        def run_steps(params, opt_state, batch, key):
            def body(carry, i):
                params, opt_state = carry
                params, opt_state, loss = inner(
                    params, opt_state, batch, jax.random.fold_in(key, i)
                )
                return (params, opt_state), loss

            (params, opt_state), losses = jax.lax.scan(
                body, (params, opt_state), jnp.arange(n_steps)
            )
            return params, opt_state, losses

        scanned = jax.jit(
            run_steps,
            in_shardings=(shardings[0], shardings[1],
                          NamedSharding(mesh, P("dp")), None),
            out_shardings=(shardings[0], shardings[1], None),
            donate_argnums=(0, 1),
        )
        src, tgt = synthetic_seq2seq(key, batch, seq, model.vocab)
        sharded = shard_batch(mesh, (src, tgt))
        # warm-up/compile
        params, opt_state, losses = scanned(params, opt_state, sharded, key)
        jax.block_until_ready(losses)
        t0 = time.perf_counter()
        params, opt_state, losses = scanned(
            params, opt_state, sharded, jax.random.fold_in(key, 1)
        )
        jax.block_until_ready(losses)
        dt_ms = (time.perf_counter() - t0) * 1000 / n_steps

    flops = transformer_train_flops(
        batch, seq, cfg["d_model"], cfg["n_layers"], cfg["d_ff"], cfg["vocab"]
    )
    # the step runs data-parallel over the whole mesh: peak scales with it
    peak = peak_flops(jax.devices()[0]) * mesh.size
    mfu = (flops / (dt_ms / 1000)) / peak
    from metaopt_tpu.ops.attention import attention_impl

    # one predicate, shared with loss_fn: copying the formula here is how
    # the label and the measured routing would silently desync. Forced
    # stages skip it — the gate global is poked, so it would not report
    # product routing anyway
    if force_xent:
        xent = force_xent
    else:
        with use_mesh(mesh):
            xent = ("blocked"
                    if transformer_mod.blocked_xent_enabled(
                        batch, seq, cfg["vocab"])
                    else "materializing")
    tag = f"_seq{seq}"
    if force_xent:
        tag += "_matxent" if force_xent == "materializing" else "_blockedxent"
    return {
        f"transformer_step_ms{tag}": round(dt_ms, 3),
        f"transformer_tokens_per_s{tag}": round(batch * seq / (dt_ms / 1000)),
        f"mfu{tag}": round(mfu, 4),
        f"transformer_config{tag}": {
            **cfg, "batch": batch, "seq": seq,
            "attention": attention_impl() or "reference",
            "xent": xent,
        },
    }


def bench_resnet() -> dict:
    """ResNet-50/CIFAR train-step time (BASELINE config 3 trial workload)."""
    import jax
    import jax.numpy as jnp
    import optax

    from metaopt_tpu.models.data import synthetic_images
    from metaopt_tpu.models.resnet import ResNet

    depth, batch = 50, 256
    model = ResNet(depth=depth)
    key = jax.random.PRNGKey(0)
    x, y = synthetic_images(key, batch, hw=32, channels=3)
    variables = model.init(jax.random.PRNGKey(1), x[:1], train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = optax.sgd(0.1, momentum=0.9, nesterov=True)
    opt_state = tx.init(params)

    def loss_fn(p, bs):
        logits, new_state = model.apply(
            {"params": p, "batch_stats": bs}, x, train=True,
            mutable=["batch_stats"],
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
        return loss, new_state["batch_stats"]

    # NOTE: unlike bench_transformer, this is a python step loop, so
    # per-step host dispatch is inside the timed window.
    @jax.jit
    def step(p, bs, o):
        (loss, bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(p, bs)
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), bs, o, loss

    params, batch_stats, opt_state, loss = step(params, batch_stats, opt_state)
    jax.block_until_ready(loss)
    n_steps = 20
    t0 = time.perf_counter()
    for _ in range(n_steps):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state
        )
    jax.block_until_ready(loss)
    dt_ms = (time.perf_counter() - t0) * 1000 / n_steps
    out = {
        f"resnet{depth}_step_ms": round(dt_ms, 3),
        f"resnet{depth}_images_per_s": round(batch / (dt_ms / 1000)),
    }
    # conv FLOPs come from XLA's own cost model (no hand-derived formula
    # for the CIFAR-stem ResNet variant) → an explicit resnet MFU field,
    # so nobody misreads the transformer MFU as covering this model
    cost = step.lower(params, batch_stats, opt_state).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    flops = float(cost.get("flops", 0.0))
    if flops > 0:
        out[f"resnet{depth}_mfu"] = round(
            (flops / (dt_ms / 1000)) / peak_flops(jax.devices()[0]), 4
        )
    return out


def bench_flash_pallas() -> dict:
    """Compile-and-run the REAL Pallas flash kernel (not a trivial probe).

    Runs ``ops/attention._pallas_forward`` through ``flash_attention(
    impl='pallas', interpret=False)`` at Transformer-base attention shapes,
    checks numerics against the chunked twin, and times the forward from
    the host (PERF.md has the per-call device times that made the Pallas
    route ``attention_impl()``'s TPU default).
    """
    import jax
    import jax.numpy as jnp

    from metaopt_tpu.ops.attention import flash_attention

    b, s, h, d = 4, 256, 8, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.bfloat16) / (d ** 0.5)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.bfloat16)

    pallas_fn = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, impl="pallas", interpret=False))
    chunked_fn = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, impl="chunked"))
    out_p = jax.block_until_ready(pallas_fn(q, k, v))    # Mosaic compile+run
    out_c = jax.block_until_ready(chunked_fn(q, k, v))
    err = float(jnp.max(jnp.abs(out_p.astype(jnp.float32)
                                - out_c.astype(jnp.float32))))
    step_ms = time_fn(lambda: jax.block_until_ready(pallas_fn(q, k, v)),
                      repeats=20)
    chunked_ms = time_fn(lambda: jax.block_until_ready(chunked_fn(q, k, v)),
                         repeats=20)

    # the two-pass Pallas BACKWARD (dKV + dQ kernels): compile via Mosaic,
    # check grads against the chunked blockwise backward, time the full
    # grad step
    def grads(impl):
        def loss(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, impl=impl, interpret=False) ** 2)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    gp_fn, gc_fn = grads("pallas"), grads("chunked")
    gp = jax.block_until_ready(gp_fn(q, k, v))
    gc = jax.block_until_ready(gc_fn(q, k, v))
    gerr = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32)
                              - b.astype(jnp.float32))))
        for a, b in zip(gp, gc)
    )
    gstep_ms = time_fn(lambda: jax.block_until_ready(gp_fn(q, k, v)),
                       repeats=20)
    gchunked_ms = time_fn(lambda: jax.block_until_ready(gc_fn(q, k, v)),
                          repeats=20)
    return {"flash_pallas": {
        "status": "ok",
        "step_ms": round(step_ms, 3),
        "chunked_step_ms": round(chunked_ms, 3),
        "max_abs_err_vs_chunked": err,
        "bwd_step_ms": round(gstep_ms, 3),
        "bwd_chunked_step_ms": round(gchunked_ms, 3),
        "bwd_max_abs_err_vs_chunked": gerr,
        "shape": [b, s, h, d],
    }}


def _completed_on(algo, params, objective):
    from metaopt_tpu.ledger.trial import Trial

    t = Trial(params=params, experiment="bench")
    t.lineage = algo.space.hash_point(params)
    t.transition("reserved")
    t.attach_results([{"name": "o", "type": "objective", "value": objective}])
    t.transition("completed")
    return t


def bench_tpe() -> dict:
    """TPE suggest latency at 10k observations, on this device."""
    n_obs = 10_000
    pool = 8  # a producer pool: one fused kernel launch + one readback
    tpe = build_tpe(n_obs)

    # warm-up: compile the kernels for these padded shapes
    tpe.suggest(pool)
    tpe._suggest_one_ei()
    jax_ms = time_fn(lambda: tpe.suggest(pool), repeats=20) / pool
    # amortized single-suggest: a full prefetch cycle (one launch +
    # pool_prefetch-1 cache pops) divided by the points served — the cost a
    # worker asking for one point at a time actually pays per point — vs
    # the raw one-launch-per-point path
    pp = tpe.pool_prefetch
    single_ms = time_fn(
        lambda: [tpe._suggest_one_ei() for _ in range(pp)], repeats=10
    ) / pp
    single_uncached_ms = time_fn(lambda: tpe._launch_ei(1), repeats=10)

    # the worker-visible "uncached" cost: observe() fires a speculative
    # pool refill, the worker spends ≥100 ms on ledger RPCs + subprocess
    # teardown before its next ask, and suggest(1) blocks only on whatever
    # of the launch+readback is still in flight
    def _observe_gap_suggest(i):
        pt = tpe.space.sample(1, seed=100_000 + i)[0]
        tpe.observe([_completed_on(tpe, pt, float(i))])
        time.sleep(0.1)
        t0 = time.perf_counter()
        tpe.suggest(1)
        return (time.perf_counter() - t0) * 1000

    after_observe_ms = float(np.median(
        [_observe_gap_suggest(i) for i in range(10)]
    ))

    # transfer/launch telemetry: steady-state device traffic of one
    # observe→suggest cycle. Before the incremental buffers every fit
    # re-uploaded the whole padded (N, d) matrix — O(N·d) ≈ 440 KB per
    # suggest at 10k obs on this space; the device-resident buffer appends
    # one donated row per observe, O(d) bytes
    tel0 = tpe.telemetry()
    tel_cycles = 10
    for i in range(tel_cycles):
        pt = tpe.space.sample(1, seed=200_000 + i)[0]
        tpe.observe([_completed_on(tpe, pt, float(1000 + i))])
        tpe.suggest(pool)
    t = tpe._refill_thread
    if t is not None:
        t.join(timeout=60)  # settle in-flight speculative launches
    tel1 = tpe.telemetry()
    h2d_per_suggest = (tel1["h2d_bytes"] - tel0["h2d_bytes"]) / tel_cycles
    launches_per_suggest = (
        tel1["kernel_launches"] - tel0["kernel_launches"]) / tel_cycles
    # speculative suggest-ahead effectiveness over the whole TPE run:
    # fraction of suggest() calls answered from a banked pool
    tpe_hits = tel1.get("prefetch_hits", 0)
    tpe_served = tpe_hits + tel1.get("prefetch_misses", 0)
    from metaopt_tpu.ops.tpe_math import pad_pow2 as _pad_pow2

    rebuild_bytes = _pad_pow2(len(tpe._y) + 1) * (tpe.cube.n_dims + 1) * 4

    # the reference substrate refits + rescores per suggestion (host numpy)
    numpy_ms = time_fn(lambda: numpy_ei_reference(tpe), repeats=5)

    out = {
        "value": round(jax_ms, 3),
        "vs_baseline": round(numpy_ms / jax_ms, 2),
        "numpy_reference_ms_per_point": round(numpy_ms, 3),
        "single_suggest_ms": round(single_ms, 3),
        "single_suggest_uncached_ms": round(single_uncached_ms, 3),
        "suggest_after_observe_100ms_gap_ms": round(after_observe_ms, 3),
        "h2d_bytes_per_suggest": round(h2d_per_suggest, 1),
        "kernel_launches_per_suggest": round(launches_per_suggest, 2),
        "h2d_bytes_full_rebuild_equiv": rebuild_bytes,
    }
    if tpe_served:
        out["tpe_prefetch_hit_rate"] = round(tpe_hits / tpe_served, 3)

    # flatness: per-suggestion latency at 1k, 16k and 32k observations
    # against the 10k headline (the north star claims flat PAST 10k)
    per_point = {}
    for n in (1_000, 16_000, 32_000):
        tpe_n = build_tpe(n)
        tpe_n.suggest(pool)
        per_point[n] = time_fn(lambda: tpe_n.suggest(pool), repeats=10) / pool
        out[f"jax_{n // 1000}k_obs_ms_per_point"] = round(per_point[n], 3)
    out["flatness_10k_over_1k"] = round(jax_ms / per_point[1_000], 2)
    for n in (16_000, 32_000):
        out[f"flatness_{n // 1000}k_over_1k"] = round(
            per_point[n] / per_point[1_000], 2)
    return out


def bench_gp() -> dict:
    """GP-BO at 10k observations: incremental-Cholesky fast path vs the
    legacy cold refit.

    Per-suggest cost of the worker cycle (observe one, ask one) with the
    device-resident factor extended rank-1 per append, against
    incremental=False (full MLL refit + full factorization per launch —
    the pre-fast-path behaviour). Speculation is DISABLED on both so the
    timed suggest pays its launch inline; the prefetch win is measured
    separately below as a hit rate. Its own stage: one cold fit at 10k
    took 566 s on the v5e (PERF.md), and a timeout here must not take the
    TPE headline with it.
    """
    n_obs = 10_000
    out = {}

    def _gp_cycle(gp, i, base):
        pt = gp.space.sample(1, seed=base + i)[0]
        gp.observe([_completed_on(gp, pt, float(i))])
        t0 = time.perf_counter()
        gp.suggest(1)
        return (time.perf_counter() - t0) * 1000.0

    gp_inc = build_gpbo(n_obs)
    gp_cold = build_gpbo(n_obs, incremental=False)
    for gp in (gp_inc, gp_cold):
        gp._suggest_ahead_async = lambda: None
        gp.suggest(1)  # compile + first factor at this padded shape
    inc_ms = float(np.median(
        [_gp_cycle(gp_inc, i, 300_000) for i in range(12)]))
    cold_ms = float(np.median(
        [_gp_cycle(gp_cold, i, 400_000) for i in range(4)]))
    out["gp_suggest_ms_per_point_10k_obs"] = round(inc_ms, 3)
    out["gp_full_refit_ms_per_point_10k_obs"] = round(cold_ms, 3)
    out["gp_incremental_speedup_vs_full_refit"] = round(
        cold_ms / max(inc_ms, 1e-9), 2)
    out.update({f"gp_{k}": v for k, v in gp_inc._factor.telemetry().items()})

    # prefetch effectiveness: speculation ON, the worker-gap cycle —
    # observe() banks the next pool while the worker is away, so
    # suggest(1) blocks only on whatever launch is still in flight
    gp_hot = build_gpbo(n_obs, suggest_prefetch_depth=2)
    gp_hot.suggest(1)

    def _gp_hot_cycle(i):
        pt = gp_hot.space.sample(1, seed=500_000 + i)[0]
        gp_hot.observe([_completed_on(gp_hot, pt, float(i))])
        time.sleep(0.1)
        t0 = time.perf_counter()
        gp_hot.suggest(1)
        return (time.perf_counter() - t0) * 1000.0

    hot_ms = float(np.median([_gp_hot_cycle(i) for i in range(10)]))
    gp_hot.drain_suggest_ahead()
    ahead = gp_hot.suggest_ahead_telemetry()
    served = ahead["prefetch_hits"] + ahead["prefetch_misses"]
    out["gp_suggest_after_observe_100ms_gap_ms"] = round(hot_ms, 3)
    if served:
        out["gp_prefetch_hit_rate"] = round(ahead["prefetch_hits"] / served, 3)
    return out


def bench_coord() -> dict:
    """Coordinator control-plane throughput: fused worker_cycle path at 32
    threaded workers (benchmarks/coord_scale.py). Host-CPU-bound: this stage
    runs with JAX_PLATFORMS=cpu and never touches the chip; median of 3 to
    ride out scheduler jitter."""
    coord_stats = {}
    from benchmarks.coord_scale import run_scale as coord_run_scale

    # the binary-vs-JSON pair is interleaved WITHIN each repeat with
    # alternating order (a long-lived process speeds up run over run,
    # so sequential batches would hand the later codec a systematic
    # advantage — the same discipline coord_scale.py's own repeat
    # loop applies); the speedup is the median of per-repeat ratios
    coord_pairs = []
    for r in range(3):
        rep = {}
        for w in (("auto", "v1") if r % 2 == 0 else ("v1", "auto")):
            rep[w] = coord_run_scale(32, "fused", trials_per_worker=16,
                                     wire=w)
        coord_pairs.append((rep["auto"], rep["v1"]))
    coord_reps = sorted((f for f, _ in coord_pairs),
                        key=lambda row: row["trials_per_s"] or 0)
    coord_row = coord_reps[1]
    coord_stats["coord_trials_per_s_32w"] = coord_row["trials_per_s"]
    coord_stats["coord_rpcs_per_trial_32w"] = coord_row["rpcs_per_trial"]
    coord_stats["coord_wire_bytes_per_trial"] = (
        coord_row.get("wire_bytes_per_trial"))
    if coord_row.get("wire") == "v2":
        ratios = sorted(
            f["trials_per_s"] / j["trials_per_s"]
            for f, j in coord_pairs
            if f["trials_per_s"] and j["trials_per_s"])
        if ratios:
            coord_stats["coord_wire_speedup_32w"] = round(
                ratios[len(ratios) // 2], 2)

    # durability tax + recovery: same fused path with the WAL under
    # it (group-commit fsync before every ack), then a cold restart
    # replaying a 2000-record WAL. Same median-of-3 discipline; the
    # overhead pct pairs this run's OWN fused median so one-core
    # scheduler drift between sessions cancels out
    wal_reps = sorted(
        (coord_run_scale(32, "fused+wal", trials_per_worker=16)
         for _ in range(3)),
        key=lambda row: row["trials_per_s"] or 0,
    )
    wal_tps = wal_reps[1]["trials_per_s"]
    if coord_row["trials_per_s"] and wal_tps:
        coord_stats["coord_wal_overhead_pct"] = round(
            100.0 * (1.0 - wal_tps / coord_row["trials_per_s"]), 1)

    from benchmarks.coord_scale import run_recovery as coord_run_recovery

    coord_stats["coord_recovery_time_s"] = coord_run_recovery(
        trials=2000)["recovery_s"]

    # live hand-off + failover latency on a 2-shard pod (lower is
    # better; informational until a committed baseline carries them)
    from benchmarks.coord_scale import run_handoff as coord_run_handoff

    handoff_row = coord_run_handoff()
    coord_stats["coord_handoff_ms"] = handoff_row["coord_handoff_ms"]
    coord_stats["coord_failover_time_s"] = (
        handoff_row["coord_failover_time_s"])

    # race-detector tax (informational, never gated): the same fused
    # path under full dynrace instrumentation — what `mtpu race
    # --suite coord` costs, paired against this run's OWN fused
    # median like the WAL overhead above
    from metaopt_tpu.analysis import dynrace
    from metaopt_tpu.analysis.registry import (default_config,
                                               default_race_config)

    monitor = dynrace.monitored_classes(default_config(),
                                        default_race_config())

    def _raced_run():
        rt = dynrace.RaceRuntime(monitor)
        with dynrace.instrument(rt):
            return coord_run_scale(32, "fused", trials_per_worker=16)

    race_reps = sorted((_raced_run() for _ in range(3)),
                       key=lambda row: row["trials_per_s"] or 0)
    race_tps = race_reps[1]["trials_per_s"]
    if coord_row["trials_per_s"] and race_tps:
        coord_stats["coord_race_overhead_pct"] = round(
            100.0 * (1.0 - race_tps / coord_row["trials_per_s"]), 1)

    # sharded deployment: subprocess shards (one WAL each) behind the
    # consistent-hash map. The workload spreads 4 experiments across
    # the shards; the overhead pct pairs the 1-shard figure against
    # this run's OWN in-process fused+wal at the SAME multi-experiment
    # workload (same durability, same run — ratio doctrine). On the
    # one-core CI box shard2/shard4 time-slice a single core, so their
    # absolute numbers are informational; the gated figure is the
    # 1-shard process tax
    shard_base_reps = sorted(
        (coord_run_scale(32, "fused+wal", trials_per_worker=16,
                         experiments=4)
         for _ in range(3)),
        key=lambda row: row["trials_per_s"] or 0,
    )
    shard_base_tps = shard_base_reps[1]["trials_per_s"]
    for n_shards in (1, 2, 4):
        shard_reps = sorted(
            (coord_run_scale(32, "sharded", trials_per_worker=16,
                             shards=n_shards, experiments=4)
             for _ in range(3)),
            key=lambda row: row["trials_per_s"] or 0,
        )
        shard_tps = shard_reps[1]["trials_per_s"]
        coord_stats[f"coord_trials_per_s_shard{n_shards}"] = shard_tps
        if n_shards == 1 and shard_base_tps and shard_tps:
            coord_stats["coord_shard_overhead_pct"] = round(
                100.0 * (1.0 - shard_tps / shard_base_tps), 1)

    # multi-tenant service plane at the full 1k-experiment fleet
    # (benchmarks/coord_scale.py run_multitenant): fairness under a
    # hot tenant, evicted-vs-resident RSS (fresh subprocesses), and
    # the warm-vs-cold transfer-prior study. Single shot — the
    # fairness/residency/transfer figures are acceptance bars with
    # wide margins, not drift-sensitive medians
    from benchmarks.coord_scale import run_multitenant

    mt_row = run_multitenant(experiments=1000)
    for mt_key in ("coord_trials_per_s_1k_exp", "coord_fairness_jain_1k",
                   "coord_evict_rss_mb", "coord_resident_rss_mb",
                   "coord_evict_rss_ratio", "coord_evictions_1k",
                   "coord_hydrations_1k", "status_scan_ms_1k",
                   "transfer_warm_trials_ratio",
                   "transfer_time_to_good_s", "transfer_cold_time_s"):
        if mt_row.get(mt_key) is not None:
            coord_stats[mt_key] = mt_row[mt_key]

    # fleet-fused suggest plane: same-run fused-vs-serial at the
    # 256-resident TPE fleet (benchmarks/coord_scale.py
    # run_fused_suggest). Both legs share one process and one fit
    # state, alternating order round to round, so the speedup is a
    # paired ratio — the gated figure plus the launch-count
    # telemetry that proves the O(buckets) claim
    from benchmarks.coord_scale import run_fused_suggest

    fs_row = run_fused_suggest(residents=256, bucket_max=32)
    for fs_key in ("fleet_suggest_speedup", "suggest_launches_per_tick",
                   "serial_launches_per_tick", "buckets_per_tick",
                   "bucket_occupancy"):
        if fs_row.get(fs_key) is not None:
            coord_stats[fs_key] = fs_row[fs_key]
    return coord_stats


def bench_batch() -> dict:
    """Batched trial evaluation: a pool of k trials as ONE jitted vmap
    program vs k per-trial launches of the same math through
    InProcessExecutor (benchmarks/batch_eval.py). The speedup pairs both
    sides from THIS run, and the launch-count telemetry under it confirms
    the pooled side really is one device program per pool."""
    batch_stats = {}
    from benchmarks.batch_eval import run_batch_eval

    for bpool in (8, 64):
        brow = run_batch_eval(bpool, reps=5)
        batch_stats[f"batch_eval_trials_per_s_pool{bpool}"] = (
            brow["batched_trials_per_s"])
        if bpool == 64:
            batch_stats["batch_eval_serial_trials_per_s"] = (
                brow["serial_trials_per_s"])
            batch_stats["batch_eval_speedup"] = brow["speedup"]
            batch_stats["batch_eval_launches_per_pool"] = (
                brow["launches_per_pool"])
    return batch_stats


#: every section, in run order: (name, deadline in seconds). Each runs as
#: one child process that owns the chip while it runs.
STAGES = (
    ("tpe", 600.0),
    ("transformer-256", 600.0), ("transformer-512", 600.0),
    ("transformer-1024", 600.0),
    ("xent-256", 600.0), ("xent-512", 600.0), ("xent-1024", 600.0),
    ("resnet", 600.0), ("flash", 600.0),
    ("batch", 600.0), ("coord", 1800.0),
    # last: three cold fits at 10k observations (566 s for one on the v5e,
    # PERF.md) do not fit any sane deadline; S0/D4 resize or remove it
    ("gp", 1200.0),
)

#: keys of the full record that also ride in the compact last line
_COMPACT_KEYS = (
    "mfu_seq256", "mfu_seq512", "mfu_seq1024", "resnet50_mfu",
    "xent_blocked_step_speedup_seq256", "xent_blocked_step_speedup_seq512",
    "xent_blocked_step_speedup_seq1024",
    "flatness_16k_over_1k", "flatness_32k_over_1k",
    "h2d_bytes_per_suggest", "kernel_launches_per_suggest",
    "gp_suggest_ms_per_point_10k_obs", "gp_full_refit_ms_per_point_10k_obs",
    "gp_incremental_speedup_vs_full_refit", "gp_prefetch_hit_rate",
    "tpe_prefetch_hit_rate",
    "transformer_tokens_per_s_seq512", "resnet50_images_per_s",
    "coord_trials_per_s_32w", "coord_rpcs_per_trial_32w",
    "coord_wal_overhead_pct", "coord_race_overhead_pct",
    "coord_recovery_time_s", "coord_handoff_ms", "coord_failover_time_s",
    "coord_trials_per_s_shard1", "coord_trials_per_s_shard2",
    "coord_trials_per_s_shard4", "coord_shard_overhead_pct",
    "batch_eval_trials_per_s_pool8", "batch_eval_trials_per_s_pool64",
    "batch_eval_speedup", "batch_eval_launches_per_pool",
    "coord_trials_per_s_1k_exp", "coord_fairness_jain_1k",
    "coord_evict_rss_ratio", "transfer_warm_trials_ratio",
    "fleet_suggest_speedup", "suggest_launches_per_tick",
)


def main() -> int:
    use_xla_cache()  # the stage children inherit it
    try:
        dev = probe_tpu()
    except RuntimeError as exc:  # a CPU timing is not a device metric
        print(f"bench.py: {exc}", file=sys.stderr)
        return 1

    stats: dict = {}
    stages_ok, stage_errors = [], {}
    for name, deadline_s in STAGES:
        env = dict(os.environ)
        if name == "coord":
            env["JAX_PLATFORMS"] = "cpu"
        t0 = time.time()
        rc, out = run_with_deadline(
            [sys.executable, os.path.abspath(__file__), "--stage", name],
            timeout_s=deadline_s, env=env, capture=True,
        )
        parsed = last_json_object(out) if rc == 0 else None
        if parsed is None:
            stage_errors[name] = (
                f"timeout after {deadline_s:.0f}s" if rc is None
                else f"rc={rc}: {out[-300:]}")
        else:
            stats.update(parsed)
            stages_ok.append(name)
        print(f"stage {name}: {'ok' if parsed is not None else 'FAILED'} "
              f"in {time.time() - t0:.0f}s", flush=True)

    # the xent A/B verdict: blocked-loss step-time win per seq (>1 = the
    # blocked online-softmax xent is faster than materializing (B, T, V)).
    # The default stage measures product routing (materializing at bench
    # shapes, per the logits-bytes gate); the xent- stage forces blocked
    for s in (256, 512, 1024):
        mat_ms = stats.get(f"transformer_step_ms_seq{s}")
        blocked_ms = stats.get(f"transformer_step_ms_seq{s}_blockedxent")
        routed = stats.get(f"transformer_config_seq{s}", {})
        if mat_ms and blocked_ms and routed.get("xent") == "materializing":
            stats[f"xent_blocked_step_speedup_seq{s}"] = round(
                mat_ms / blocked_ms, 3)

    from metaopt_tpu.utils.provenance import provenance

    device = {"platform": dev["platform"], "device_kind": dev["device_kind"],
              "device_count": dev["count"]}
    result = {
        "metric": "tpe_suggest_ms_per_point_10k_obs_pool8",
        "value": stats.pop("value", None),
        "unit": "ms",
        "vs_baseline": stats.pop("vs_baseline", None),
        **provenance(),
        **device,
        "versions": {k: dev[k] for k in ("jax", "jaxlib", "libtpu")},
        "stages_ok": stages_ok,
        "stage_errors": stage_errors,
        "extra": stats,
    }
    # Full record goes to a file; stdout gets ONE compact line. The driver
    # keeps only a bounded TAIL of output, so a giant single-line record
    # gets its head (the "{"metric": ..." part) truncated and parses as
    # nothing.
    stamp = time.strftime("%Y-%m-%d", time.gmtime())
    here = os.path.dirname(os.path.abspath(__file__))
    results_dir = os.path.join(here, "benchmarks", "results")
    os.makedirs(results_dir, exist_ok=True)
    artifact = os.path.join(results_dir, f"bench_tpu_{stamp}.json")
    with open(artifact, "w") as f:
        json.dump(result, f, indent=1)
    print(f"full record: {artifact}", flush=True)

    compact = {k: result[k] for k in
               ("metric", "value", "unit", "vs_baseline", "commit")}
    compact.update(device)
    compact.update(stages_ok=stages_ok, stage_errors=stage_errors,
                   artifact=os.path.relpath(artifact, here))
    compact.update({k: stats[k] for k in _COMPACT_KEYS if k in stats})
    print(json.dumps(compact))
    return 1 if stage_errors else 0


def stage_main(name: str) -> None:
    """Child entry: run one section, print its stats as one JSON line."""
    import jax

    if name != "coord" and jax.default_backend() != "tpu":
        raise SystemExit(
            f"bench stage {name}: no TPU (platform={jax.default_backend()})")
    if name == "tpe":
        stats = bench_tpe()
    elif name == "gp":
        stats = bench_gp()
    elif name.startswith("transformer"):
        seq = int(name.split("-")[1])
        # equal token count per step (16k): batch trades off against seq
        stats = bench_transformer(seq=seq, batch=16384 // seq)
    elif name.startswith("xent-"):
        # the A/B control: same shapes, blocked xent FORCED — product
        # routing materializes at these shapes (the measured-faster path),
        # so the forced stage is what keeps the blocked kernel measured
        seq = int(name.split("-")[1])
        stats = bench_transformer(seq=seq, batch=16384 // seq,
                                  force_xent="blocked")
    elif name == "resnet":
        stats = bench_resnet()
    elif name == "flash":
        stats = bench_flash_pallas()
    elif name == "batch":
        stats = bench_batch()
    elif name == "coord":
        stats = bench_coord()
    else:
        raise SystemExit(f"unknown stage {name!r}")
    print(json.dumps(stats))


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--stage":
        stage_main(sys.argv[2])
    else:
        sys.exit(main())
