"""Per-trial model checkpointing for the demo zoo (orbax-backed).

ref: SURVEY.md §5 checkpoint/resume — "per-trial model checkpoints stay the
user script's business (orbax in our demo models)". The ledger checkpoints
the SEARCH; this module checkpoints a TRIAL's training state so that

- a suspended/preempted trial resumes mid-run (``mtpu resume``), and
- a PBT continuation inherits its parent's weights
  (``client.checkpoint_paths``).

Trees are flattened to index-keyed arrays before saving: orbax round-trips
nested dicts natively, but optimizer states are namedtuple pytrees whose
field iteration order need not match a restored dict's key order —
index keys make the leaf order explicit and structure-independent.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from metaopt_tpu.utils import trace

log = logging.getLogger(__name__)


def _checkpointer():
    import orbax.checkpoint as ocp

    return ocp.PyTreeCheckpointer()


@trace.span("trial.save")
def save_state(path: str, tree: Any) -> None:
    """Save any pytree of arrays under ``path`` (overwrites)."""
    leaves = jax.tree.leaves(tree)
    payload = {
        f"{i:05d}": np.asarray(jax.device_get(leaf))
        for i, leaf in enumerate(leaves)
    }
    _checkpointer().save(os.path.abspath(path), payload, force=True)


@trace.span("trial.restore")
def restore_state(path: str, like: Any, shardings: Optional[Any] = None) -> Any:
    """Restore a pytree shaped like ``like``; re-shard when given.

    ``shardings``: a matching pytree of ``jax.sharding.Sharding`` (e.g. the
    specs ``init_sharded`` returns) — leaves are placed straight onto their
    mesh positions instead of landing replicated on device 0.
    """
    payload = _checkpointer().restore(os.path.abspath(path))
    leaves = [payload[k] for k in sorted(payload)]
    treedef = jax.tree.structure(like)
    if len(leaves) != treedef.num_leaves:
        raise ValueError(
            f"checkpoint at {path} has {len(leaves)} leaves, expected "
            f"{treedef.num_leaves} — saved from a different architecture?"
        )
    if shardings is not None:
        # zip flattened leaves rather than tree.map: the shardings tree
        # collapses each flax Partitioned box into ONE spec leaf, so its
        # STRUCTURE differs from the params tree even though the leaf
        # counts (one array per box) line up
        sharding_leaves = jax.tree.leaves(shardings)
        if len(sharding_leaves) == len(leaves):
            leaves = [
                jax.device_put(jnp.asarray(x), s)
                for x, s in zip(leaves, sharding_leaves)
            ]
        else:
            # a silent fallback here would land a multi-host restore fully
            # replicated on device 0 with no signal — make it loud
            log.warning(
                "restore_state(%s): shardings tree has %d leaves but the "
                "checkpoint has %d — IGNORING shardings, restoring "
                "unsharded (replicated on the default device)",
                path, len(sharding_leaves), len(leaves),
            )
            leaves = [jnp.asarray(x) for x in leaves]
    else:
        leaves = [jnp.asarray(x) for x in leaves]
    return jax.tree.unflatten(treedef, leaves)


def has_state(path: str) -> bool:
    return os.path.isdir(path) and bool(os.listdir(path))
