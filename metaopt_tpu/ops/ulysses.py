"""Ulysses-style sequence parallelism: all-to-all head/sequence exchange.

The second long-context strategy next to :mod:`ring_attention` (DeepSpeed
Ulysses; see PAPERS.md): instead of rotating K/V around the ring while
queries stay put, ONE ``all_to_all`` re-shards the activations from
sequence-sharded to head-sharded, every device runs ordinary full-sequence
attention on its subset of heads, and a second ``all_to_all`` restores the
sequence sharding. Communication is 2 all-to-alls of O(S·H·D / sp) per
device — independent of the number of ring hops — at the price of needing
``heads % sp == 0`` and one full-length sequence resident per device
(attention itself still runs through the chunked flash path, so the
O(S²) logits tensor never materializes; only O(S·d) activations do).

Trade-off vs the ring, honestly stated: the ring's peak activation memory
is O(S/sp · d) (never the full sequence) and it pipelines transfers with
compute — better for the longest contexts; Ulysses has lower collective
count and latency at moderate lengths and maps onto XLA's native
``all_to_all``. Both compose with dp/tp in one ``shard_map``. Neither has
been timed on a chip, so which one an ``sp`` mesh takes is still a
variable, ``METAOPT_TPU_SP_IMPL`` (``ring`` default, ``ulysses`` opt-in;
:func:`sp_impl`), until a four-chip cell decides (ROADMAP D2).

ref: the reference framework has no attention code at all (SURVEY.md §5
long-context: "absent by design"); TPU-native demo-zoo surface.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from metaopt_tpu.ops.attention import flash_attention, shard_map_nocheck


def sp_impl() -> str:
    """Which sequence-parallel attention a mesh with sp>1 takes; asked by
    ops/attention.attention_route alone.

    ``METAOPT_TPU_SP_IMPL``: ``ring`` (default — lowest per-chip memory,
    transfers overlap compute) or ``ulysses`` (2 all-to-alls, needs
    ``local heads % sp == 0``).
    """
    env = (os.environ.get("METAOPT_TPU_SP_IMPL") or "ring").strip().lower()
    if env in ("ring", "ulysses"):
        return env
    raise ValueError(f"METAOPT_TPU_SP_IMPL={env!r}: expected ring/ulysses")


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    *,
    mesh: Mesh,
    seq_axis: str = "sp",
    batch_axis: Optional[str] = "dp",
    head_axis: Optional[str] = "tp",
    dropout_rate: float = 0.0,
    dropout_key: Optional[jnp.ndarray] = None,
    impl: str = "chunked",
) -> jnp.ndarray:
    """Sequence-parallel attention via head/sequence all-to-all exchange.

    q: (B, Sq, H, D) pre-scaled by 1/sqrt(D); k, v: (B, Sk, H, D); mask:
    optional (B, Sq, Sk) bool, True = attend (replicated over the seq
    axis — each device needs full-sequence rows for its heads). Sq/Sk must
    divide the ``seq_axis`` size, and the per-device head count (H, or
    H/tp when ``head_axis`` is in the mesh) must divide it too. Returns
    (B, Sq, H, D) in q's dtype, sequence-sharded like q.

    Differentiable end-to-end: ``all_to_all`` transposes to the inverse
    all-to-all, and the local attention is the chunked flash kernel with
    its blockwise VJP.
    """
    if seq_axis not in mesh.shape:
        raise ValueError(f"mesh has no {seq_axis!r} axis: {dict(mesh.shape)}")
    sp = mesh.shape[seq_axis]
    if q.shape[1] % sp or k.shape[1] % sp:
        raise ValueError(
            f"Sq={q.shape[1]}, Sk={k.shape[1]} must divide seq axis {sp}"
        )
    ab = batch_axis if (batch_axis and batch_axis in mesh.shape) else None
    ah = head_axis if (head_axis and head_axis in mesh.shape) else None
    h_local = q.shape[2] // (mesh.shape[ah] if ah else 1)
    if h_local % sp:
        raise ValueError(
            f"ulysses needs per-device heads ({h_local}) divisible by the "
            f"{seq_axis} axis ({sp}); use ring attention for this layout"
        )
    qs = P(ab, seq_axis, ah, None)
    ms = P(ab, None, None)  # full-sequence mask rows on every seq shard

    def local(q, k, v, mask, key):
        # seq-sharded -> head-sharded: split heads sp ways, gather the
        # full sequence (one all-to-all riding ICI)
        def fwd(x):
            return jax.lax.all_to_all(
                x, seq_axis, split_axis=2, concat_axis=1, tiled=True
            )

        qg, kg, vg = fwd(q), fwd(k), fwd(v)
        if key is not None:
            for ax in (ab, seq_axis, ah):
                if ax is not None:
                    key = jax.random.fold_in(key, jax.lax.axis_index(ax))
        out = flash_attention(
            qg, kg, vg, mask, dropout_rate=dropout_rate, dropout_key=key,
            impl=impl,
        )
        # head-sharded -> seq-sharded: the inverse exchange
        return jax.lax.all_to_all(
            out, seq_axis, split_axis=1, concat_axis=2, tiled=True
        )

    wrapped = shard_map_nocheck(
        local, mesh,
        in_specs=(qs, qs, qs, ms if mask is not None else P(), P()),
        out_specs=qs,
    )
    return wrapped(q, k, v, mask, dropout_key)
