"""Synthetic on-device datasets (zero-egress environment — no downloads).

Each generator is deterministic in (seed, shapes) and *learnable*: labels come
from a fixed random teacher, so validation loss responds to hyperparameters
the way a real dataset's would — which is what an HPO benchmark needs.
Data is generated directly on device with jax.random (no host→HBM copies).
"""

from __future__ import annotations

from typing import Iterator, Tuple

import jax
import jax.numpy as jnp

from metaopt_tpu.utils import trace


@trace.span("trial.data")
def synthetic_images(
    key: jax.Array,
    n: int,
    hw: int = 28,
    channels: int = 1,
    n_classes: int = 10,
    teacher_seed: int = 7,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MNIST/CIFAR-shaped images with teacher-assigned labels.

    The teacher is keyed by ``teacher_seed``, NOT by ``key`` — train and
    validation draws with different sample keys share one labeling function,
    otherwise generalization would be unmeasurable.
    """
    x = jax.random.normal(key, (n, hw, hw, channels), dtype=jnp.float32)
    kt = jax.random.PRNGKey(teacher_seed)
    teacher = jax.random.normal(kt, (hw * hw * channels, n_classes)) / hw
    logits = x.reshape(n, -1) @ teacher
    y = jnp.argmax(logits, axis=-1)
    return x, y


@trace.span("trial.data")
def synthetic_seq2seq(
    key: jax.Array,
    n: int,
    seq_len: int = 64,
    vocab: int = 1000,
    teacher_seed: int = 7,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Copy-through-permutation task: target is the source mapped through a

    fixed random vocabulary permutation — translation-shaped (WMT stand-in)
    and learnable. The permutation is keyed by ``teacher_seed`` so separate
    train/val draws share one "language".
    """
    src = jax.random.randint(key, (n, seq_len), 2, vocab)  # 0=pad, 1=bos
    perm = jax.random.permutation(jax.random.PRNGKey(teacher_seed), vocab)
    tgt = perm[src]
    return src, tgt


@trace.span("trial.data")
def synthetic_lm(
    key: jax.Array,
    n: int,
    seq_len: int = 64,
    vocab: int = 1000,
    teacher_seed: int = 7,
) -> jnp.ndarray:
    """Permutation-walk token streams: ``x[t+1] = perm[x[t]]`` from a random
    start — next-token prediction is exactly learnable (a one-step Markov
    map over [2, vocab), so 0=pad / 1=bos never appear mid-stream). The
    permutation is keyed by ``teacher_seed`` so train/val draws share one
    "language"."""
    perm = 2 + jax.random.permutation(
        jax.random.PRNGKey(teacher_seed), vocab - 2)
    start = jax.random.randint(key, (n,), 2, vocab)

    def body(tok, _):
        nxt = perm[tok - 2]
        return nxt, nxt

    _, cols = jax.lax.scan(body, start, None, length=seq_len - 1)
    return jnp.concatenate([start[:, None], cols.T], axis=1)


def batches(
    x: jnp.ndarray, y: jnp.ndarray, batch_size: int, key: jax.Array
) -> Iterator[Tuple[jnp.ndarray, jnp.ndarray]]:
    """Shuffled epoch of static-shaped batches (drop remainder)."""
    n = x.shape[0]
    idx = jax.random.permutation(key, n)
    for i in range(n // batch_size):
        sl = idx[i * batch_size : (i + 1) * batch_size]
        yield x[sl], y[sl]
