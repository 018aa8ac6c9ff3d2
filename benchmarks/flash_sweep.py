"""Flash-vs-chunked attention sweep: seq × block shapes, fwd + bwd.

This sweep times, from the host, forward and full-grad steps of the Pallas
kernels and the chunked twin at seq 4096→256 (descending — the crossover
data first), causal-masked by default, over a small grid of (block_q,
block_k), and records per-seq ratios plus the crossover. (PERF.md holds
the per-call device times at seq 256/512/1024 behind attention_impl()'s
TPU default.) ``--unmasked`` adds the unmasked study,
``--grid`` the full block grid.

One process, on the chip (without a TPU it exits non-zero):
    python benchmarks/flash_sweep.py [--save] [--quick]

One JSON line per (seq, masked, impl, blocks) config; with --save they land
in benchmarks/results/flash_sweep_<date>.jsonl and a summary line records
the crossover.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from metaopt_tpu.utils.procs import use_xla_cache  # noqa: E402


def time_fn(fn, repeats):
    fn()  # compile + warm
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn()
    import jax

    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1000 / repeats


def main() -> int:
    save = "--save" in sys.argv
    quick = "--quick" in sys.argv
    use_xla_cache()
    import jax
    import jax.numpy as jnp

    from metaopt_tpu.ops.attention import flash_attention
    from metaopt_tpu.utils.provenance import provenance

    if jax.default_backend() != "tpu":
        print(f"flash_sweep: no TPU (platform={jax.default_backend()}); "
              "a CPU timing is not a device metric", file=sys.stderr)
        return 1

    # Decision data first: the crossover question lives at seq >= 1024, so
    # sweep DESCENDING, causal-only by default (the transformer training
    # path), with the block grid trimmed. --unmasked / --grid restore the
    # full study.
    seqs = (2048, 1024, 256) if quick else (4096, 2048, 1024, 512, 256)
    if "--grid" in sys.argv:  # the full study, independent of --quick
        blocks = ((128, 128), (256, 256), (128, 256), (256, 128),
                  (128, 512), (256, 512))
    elif quick:
        blocks = ((256, 256),)
    else:
        blocks = ((128, 128), (256, 256))
    maskeds = (True, False) if "--unmasked" in sys.argv else (True,)
    save_path = None
    # run id: appended-to files can hold a partial run plus its same-day
    # retry — rows group by this, so consumers never double-count
    stamp_now = provenance(backend=jax.default_backend(),
                           run=f"{int(time.time())}-{os.getpid()}")
    if save:
        stamp = time.strftime("%Y-%m-%d", time.gmtime())
        save_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "results",
            f"flash_sweep_{stamp}.jsonl")

    def emit(row) -> None:
        # append to disk the moment a row exists: a crash mid-sweep must
        # not take the already-measured rows with it. Best-effort — the
        # row is on stdout, and a disk hiccup must not kill the sweep
        print(json.dumps(row), flush=True)
        if save_path:
            try:
                with open(save_path, "a") as f:
                    f.write(json.dumps({**row, **stamp_now}) + "\n")
            except OSError as exc:
                print(json.dumps({"save_error": str(exc)}), flush=True)

    h, d = 8, 64
    rows = []
    for seq in seqs:
        b = max(1, 8192 // seq)
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, seq, h, d), jnp.bfloat16) / (d ** 0.5)
        k = jax.random.normal(ks[1], (b, seq, h, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, seq, h, d), jnp.bfloat16)
        causal = jnp.broadcast_to(
            jnp.tril(jnp.ones((seq, seq), bool))[None], (b, seq, seq)
        )
        for masked in maskeds:
            mask = causal if masked else None
            ref = None
            # one chunked baseline config per seq
            configs = [("chunked", 128, 256)]
            if "--grid" in sys.argv:
                configs.insert(0, ("chunked", 128, 128))
            configs += [("pallas", bq, bk) for bq, bk in blocks]
            for impl, bq, bk in configs:
                tag = f"{impl}-{bq}x{bk}"
                try:
                    fwd = jax.jit(lambda q, k, v, m, impl=impl, bq=bq, bk=bk:
                                  flash_attention(q, k, v, m, impl=impl,
                                                  block_q=bq, block_k=bk,
                                                  interpret=False))

                    def loss(q, k, v, m, impl=impl, bq=bq, bk=bk):
                        return jnp.sum(flash_attention(
                            q, k, v, m, impl=impl, block_q=bq, block_k=bk,
                            interpret=False) ** 2)

                    gfn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                    out = jax.block_until_ready(fwd(q, k, v, mask))
                    if impl == "chunked" and ref is None:
                        # the reference is strictly the chunked baseline:
                        # if every chunked config errors, pallas rows get
                        # err None, never a self-referential 0.0
                        ref = out.astype(jnp.float32)
                    err = (float(jnp.max(jnp.abs(
                               out.astype(jnp.float32) - ref)))
                           if ref is not None else None)
                    reps = 5 if quick else 10
                    fwd_ms = time_fn(
                        lambda: jax.block_until_ready(fwd(q, k, v, mask)),
                        reps)
                    bwd_ms = time_fn(
                        lambda: jax.block_until_ready(gfn(q, k, v, mask)),
                        reps)
                    row = {"seq": seq, "batch": b, "masked": masked,
                           "impl": impl, "block_q": bq, "block_k": bk,
                           "fwd_ms": round(fwd_ms, 3),
                           "grad_ms": round(bwd_ms, 3),
                           "max_abs_err":
                               round(err, 5) if err is not None else None}
                except Exception as exc:  # noqa: BLE001 — record, keep sweeping
                    row = {"seq": seq, "batch": b, "masked": masked,
                           "impl": impl, "block_q": bq, "block_k": bk,
                           "error": f"{type(exc).__name__}: {exc}"[:300]}
                rows.append(row)
                emit(row)

    # crossover: per (seq, masked), best pallas grad_ms vs best chunked
    summary = {"metric": "flash_vs_chunked", "points": []}
    for seq in seqs:
        for masked in maskeds:
            sub = [r for r in rows if r["seq"] == seq
                   and r["masked"] == masked and "error" not in r]
            pal = [r for r in sub if r["impl"] == "pallas"]
            chk = [r for r in sub if r["impl"] == "chunked"]
            if not pal or not chk:
                continue
            bp = min(pal, key=lambda r: r["grad_ms"])
            bc = min(chk, key=lambda r: r["grad_ms"])
            summary["points"].append({
                "seq": seq, "masked": masked,
                "pallas_ms": bp["grad_ms"], "pallas_blocks":
                    [bp["block_q"], bp["block_k"]],
                "chunked_ms": bc["grad_ms"],
                "speedup": round(bc["grad_ms"] / bp["grad_ms"], 3),
                "fwd_speedup": round(
                    min(chk, key=lambda r: r["fwd_ms"])["fwd_ms"]
                    / min(pal, key=lambda r: r["fwd_ms"])["fwd_ms"], 3),
            })
    # masked (causal — what transformer training runs) and unmasked cross
    # at different points; one mixed number would let the unmasked case
    # flip the default where masked chunked is still faster
    # only label studies that actually ran: crossover_seq_unmasked: None in
    # a masked-only sweep would read as "swept, pallas never won"
    for label, want_masked in (("masked", True), ("unmasked", False)):
        if want_masked not in maskeds:
            continue
        wins = [p["seq"] for p in summary["points"]
                if p["masked"] == want_masked and p["speedup"] >= 1.15]
        summary[f"crossover_seq_{label}"] = min(wins) if wins else None
    summary.update(stamp_now)
    print(json.dumps(summary), flush=True)
    if save_path:
        # rows were appended as they were measured; only the summary is new
        with open(save_path, "a") as f:
            f.write(json.dumps(summary) + "\n")
        print(f"saved: {save_path}", flush=True)
    return 1 if any("error" in r for r in rows) else 0


if __name__ == "__main__":
    sys.exit(main())
