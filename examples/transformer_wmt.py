#!/usr/bin/env python
"""BASELINE config 4: Hyperband on Transformer-base (4-chip sub-slice).

    python -m metaopt_tpu hunt -n wmt --max-trials 27 --n-chips 4 \
        --config examples/hyperband.yaml \
        examples/transformer_wmt.py \
        --lr~'loguniform(1e-4, 5e-3)' \
        --dropout~'uniform(0.0, 0.3)' \
        --warmup~'uniform(100, 4000, discrete=True)' \
        --epochs~'fidelity(1, 9, base=3)'

The trial shards dp×tp over exactly the chips its sub-slice grant names
(MTPU_ASSIGNED_CHIPS), via metaopt_tpu.parallel.trial_mesh.
"""

import argparse

from metaopt_tpu import client
from metaopt_tpu.client import report_results


def _ckpt_kwargs():
    own, parent = client.checkpoint_paths()
    return {"save_dir": own, "restore_dir": parent or own}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--lr", type=float, required=True)
    p.add_argument("--dropout", type=float, default=0.1)
    p.add_argument("--warmup", type=int, default=400)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--tp", type=int, default=2)
    p.add_argument("--sp", type=int, default=1,
                   help=">1 shards the sequence: ring attention over ICI")
    p.add_argument("--ep", type=int, default=1,
                   help=">1 carves an expert-parallel mesh axis")
    p.add_argument("--n-experts", dest="n_experts", type=int, default=0,
                   help=">0 swaps FFNs for a MoE (shard with --ep)")
    p.add_argument("--steps-per-epoch", type=int, default=50)
    p.add_argument("--d-model", dest="d_model", type=int, default=512,
                   help="model width (smoke runs shrink the base config)")
    p.add_argument("--n-layers", dest="n_layers", type=int, default=6)
    p.add_argument("--d-ff", dest="d_ff", type=int, default=2048)
    # pass-throughs for what train_and_eval / make_model already take, so
    # a sweep can run Transformer-base at bench shape through the CLI
    p.add_argument("--vocab", type=int, default=1000)
    p.add_argument("--max-len", dest="max_len", type=int, default=512)
    p.add_argument("--seq-len", dest="seq_len", type=int, default=64)
    p.add_argument("--batch-size", dest="batch_size", type=int, default=32)
    a = p.parse_args()

    import jax

    from metaopt_tpu.models.transformer import train_and_eval
    from metaopt_tpu.parallel.mesh import trial_mesh

    # dp over the sub-slice, with tp (and sp for ring attention, ep for
    # MoE experts) carved out of it
    mesh = trial_mesh(tp=a.tp, extra_axes=tuple(
        (name, n) for name, n in (("sp", a.sp), ("ep", a.ep)) if n > 1))
    loss = train_and_eval(
        {"lr": a.lr, "dropout": a.dropout, "warmup": a.warmup,
         "d_model": a.d_model, "n_layers": a.n_layers, "d_ff": a.d_ff,
         "n_heads": max(1, a.d_model // 64), "n_experts": a.n_experts,
         "vocab": a.vocab, "max_len": a.max_len},
        mesh=mesh,
        steps=a.epochs * a.steps_per_epoch,
        seq_len=a.seq_len,
        batch_size=a.batch_size,
        # orbax trial checkpoints: a PBT continuation restores its parent's
        # training state; a suspended/re-run trial resumes its OWN
        # (train_and_eval skips restore when the dir has no state yet)
        **(_ckpt_kwargs() if client.IS_ORCHESTRATED else {}),
    )
    devs = list(mesh.devices.flat)
    report_results([
        {"name": "loss", "type": "objective", "value": loss},
        # set-up facts, on the trial's own word: where it really ran, on
        # which mesh, with which compile cache
        {"name": "device", "type": "statistic",
         "value": f"{devs[0].platform}:{devs[0].device_kind}:{len(devs)}"},
        {"name": "device_ids", "type": "statistic",
         "value": [d.id for d in devs]},
        {"name": "mesh", "type": "statistic", "value": dict(mesh.shape)},
        {"name": "jax_cache", "type": "statistic",
         "value": jax.config.jax_compilation_cache_dir},
    ])


if __name__ == "__main__":
    main()
