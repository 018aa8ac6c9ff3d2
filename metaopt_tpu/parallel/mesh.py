"""Device meshes over a trial's assigned sub-slice."""

from __future__ import annotations

import contextlib
import contextvars
import os
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

_ACTIVE_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "mtpu_active_mesh", default=None
)


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Activate a mesh: legacy ``with mesh:`` semantics + model-layer access.

    The model layer (MHA's shard_map routing) reads the active mesh via
    :func:`active_mesh` rather than probing the deprecated
    ``jax.interpreters.pxla.thread_resources`` — this context is the
    supported registration point, and it works for both activation styles
    (the legacy context manager is entered here; new-style
    ``jax.sharding.use_mesh`` callers are caught by the abstract-mesh
    probe in :func:`active_mesh`).
    """
    token = _ACTIVE_MESH.set(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        _ACTIVE_MESH.reset(token)


def active_mesh() -> Optional[Mesh]:
    """The mesh the current trial runs under, or None outside any mesh."""
    mesh = _ACTIVE_MESH.get()
    if mesh is not None:
        return mesh
    abstract = jax.sharding.get_abstract_mesh()
    if abstract is not None and not abstract.empty:
        return abstract
    return None


def trial_devices() -> List[jax.Device]:
    """The devices this trial process may use.

    The TPU executor pins trials via ``MTPU_ASSIGNED_CHIPS`` (see
    executor/topology.py). On the chip the runtime honours
    ``TPU_VISIBLE_CHIPS``: the process sees exactly its block, renumbered
    from device id 0 with coordinates relative to the block (checked on a
    2x2 v5e host, PR 21: chips "2,3" appear as ids [0, 1]), so the visible
    set IS the assignment. Where nothing is hidden (CPU test meshes) the
    ids index into the visible device list — both cases resolve here.
    """
    devices = jax.devices()
    spec = os.environ.get("MTPU_ASSIGNED_CHIPS")
    if not spec:
        return list(devices)
    want = [int(s) for s in spec.split(",") if s != ""]
    if len(set(want)) != len(want):
        raise ValueError(f"MTPU_ASSIGNED_CHIPS={spec!r} repeats a chip id")
    by_id = {d.id: d for d in devices}
    if all(i in by_id for i in want):
        picked = [by_id[i] for i in want]
    elif all(i < len(devices) for i in want):
        # ids are slice-relative; index into the visible list
        picked = [devices[i] for i in want]
    elif len(want) == len(devices):
        # a pinned runtime honored TPU_VISIBLE_CHIPS and renumbered: the
        # assignment ids are global block ids, but the visible set IS
        # exactly the assignment — take it whole, each device once
        picked = list(devices)
    else:
        # never modulo-wrap: that would silently put the same device into
        # the mesh twice and corrupt every collective on it
        raise ValueError(
            f"MTPU_ASSIGNED_CHIPS={spec!r} matches no visible device id, "
            f"exceeds the visible index range, and its size differs from "
            f"the {len(devices)} visible devices — cannot map safely"
        )
    # a pinned runtime that already hides other chips needs no filtering
    return picked or list(devices)


def make_mesh(
    axes: Sequence[Tuple[str, int]],
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """``make_mesh([("dp", 2), ("tp", 4)])`` → a 2×4 Mesh.

    Axis sizes must multiply to the device count; a size of -1 means "fill
    with whatever remains" (at most one axis).
    """
    devs = list(devices if devices is not None else trial_devices())
    names = [a for a, _ in axes]
    sizes = [int(s) for _, s in axes]
    if sizes.count(-1) > 1:
        raise ValueError("at most one mesh axis may be -1")
    known = int(np.prod([s for s in sizes if s != -1]))
    if -1 in sizes:
        if len(devs) % known:
            raise ValueError(
                f"{len(devs)} devices not divisible by fixed axes {known}"
            )
        sizes[sizes.index(-1)] = len(devs) // known
    if int(np.prod(sizes)) != len(devs):
        raise ValueError(
            f"mesh {dict(zip(names, sizes))} needs {int(np.prod(sizes))} "
            f"devices, have {len(devs)}"
        )
    grid = np.asarray(devs, dtype=object).reshape(sizes)
    return Mesh(grid, tuple(names))


def trial_mesh(tp: int = 1, extra_axes: Sequence[Tuple[str, int]] = ()) -> Mesh:
    """The canonical trial mesh: data-parallel over the sub-slice, with an

    optional tensor-parallel inner axis — ``trial_mesh(tp=2)`` on a 4-chip
    sub-slice gives a ("dp", 2) × ("tp", 2) mesh. Demo-zoo models default to
    pure dp, matching SURVEY.md §2.8's "plain pjit data-parallel" scope.
    """
    axes = [("dp", -1), ("tp", tp), *extra_axes]
    return make_mesh(axes)
