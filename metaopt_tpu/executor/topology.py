"""TPU topology model + sub-slice allocation.

The reference has no equivalent — its "device placement" is whatever
`CUDA_VISIBLE_DEVICES` the user script saw (SURVEY.md §2.7/§2.8). On a pod,
trial placement is a first-class scheduler resource: a trial occupies one chip
or an ICI-contiguous sub-slice, and a broken trial must hand its chips back.

Design: chips are addressed by their linear index in the pod's natural torus
ordering. Sub-slices are power-of-two sized, size-aligned blocks — aligned
blocks of the natural ordering are ICI-contiguous on TPU slices, which makes
a classic **buddy allocator** the right shape: allocate/free are O(log n),
fragmentation is bounded, and every allocation is automatically contiguous
and aligned. Cross-process safety (multiple workon processes on one host
sharing a slice) comes from an optional flock-guarded state file, the same
doctrine as the FileLedger.
"""

from __future__ import annotations

import fcntl
import json
import os
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@dataclass(frozen=True)
class SubSlice:
    """An allocated, ICI-contiguous block of chips."""

    start: int
    size: int

    @property
    def chips(self) -> List[int]:
        return list(range(self.start, self.start + self.size))


class BuddyAllocator:
    """Power-of-two buddy allocator over ``total`` linearly-ordered chips."""

    def __init__(self, total: int):
        if not _is_pow2(total):
            raise ValueError(f"total chips must be a power of two, got {total}")
        self.total = total
        # free lists per block size
        self._free: Dict[int, List[int]] = {total: [0]}
        self._lock = threading.Lock()

    def allocate(self, n: int) -> Optional[SubSlice]:
        """Allocate an aligned block of next_pow2(n) chips, or None if full."""
        size = next_pow2(max(1, n))
        if size > self.total:
            raise ValueError(f"requested {n} chips > slice size {self.total}")
        with self._lock:
            return self._alloc_locked(size)

    def _alloc_locked(self, size: int) -> Optional[SubSlice]:
        s = size
        while s <= self.total and not self._free.get(s):
            s *= 2
        if s > self.total or not self._free.get(s):
            return None
        start = self._free[s].pop(0)
        while s > size:  # split down, keeping the upper buddy free
            s //= 2
            self._free.setdefault(s, []).append(start + s)
        return SubSlice(start, size)

    def free(self, block: SubSlice) -> None:
        """Return a block; coalesce with its buddy where possible."""
        with self._lock:
            start, size = block.start, block.size
            while size < self.total:
                buddy = start ^ size
                lst = self._free.get(size, [])
                if buddy in lst:
                    lst.remove(buddy)
                    start = min(start, buddy)
                    size *= 2
                else:
                    break
            self._free.setdefault(size, []).append(start)
            self._free[size].sort()

    @property
    def n_free_chips(self) -> int:
        with self._lock:
            return sum(s * len(lst) for s, lst in self._free.items())


class ChipRegistry:
    """Cross-process chip accounting for one host/slice.

    State file (flock-guarded JSON) maps claimed blocks to (pid, heartbeat).
    Dead claimants (stale heartbeat or vanished pid) are reaped on every
    allocate — a broken or killed trial can never leak its sub-slice, the
    failure-semantics gap SURVEY.md §2.7 flags in the reference.
    """

    def __init__(self, total: int, state_path: Optional[str] = None,
                 stale_s: float = 120.0):
        if not _is_pow2(total):
            raise ValueError(f"total chips must be a power of two, got {total}")
        self.total = total
        self.state_path = state_path
        self.stale_s = stale_s
        self._local = BuddyAllocator(total) if state_path is None else None

    # -- in-process fast path ---------------------------------------------
    def allocate(self, n: int, owner: str = "") -> Optional[SubSlice]:
        if self._local is not None:
            return self._local.allocate(n)
        return self._file_op("alloc", n=n, owner=owner)

    def free(self, block: SubSlice) -> None:
        if self._local is not None:
            self._local.free(block)
            return
        self._file_op("free", start=block.start, size=block.size)

    def heartbeat(self, block: SubSlice) -> None:
        if self._local is None:
            self._file_op("beat", start=block.start, size=block.size)

    @property
    def n_free_chips(self) -> int:
        if self._local is not None:
            return self._local.n_free_chips
        state = self._file_op("read")
        # block size lives in the claim KEY ("start:size"), not the value
        used = sum(int(key.split(":")[1]) for key in state["claims"])
        return self.total - used

    # -- file-backed path --------------------------------------------------
    def _file_op(self, op: str, **kw):
        assert self.state_path is not None
        os.makedirs(os.path.dirname(self.state_path) or ".", exist_ok=True)
        with open(self.state_path + ".lock", "a+") as lockf:
            fcntl.flock(lockf, fcntl.LOCK_EX)
            try:
                try:
                    with open(self.state_path) as f:
                        state = json.load(f)
                except (FileNotFoundError, json.JSONDecodeError):
                    state = {"claims": {}}
                self._reap(state)
                result = None
                if op == "alloc":
                    result = self._file_alloc(state, kw["n"], kw["owner"])
                elif op == "free":
                    state["claims"].pop(f"{kw['start']}:{kw['size']}", None)
                elif op == "beat":
                    key = f"{kw['start']}:{kw['size']}"
                    if key in state["claims"]:
                        state["claims"][key]["t"] = time.time()
                elif op == "read":
                    return state
                tmp = self.state_path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(state, f)
                # atomic, deliberately not durable: claims are leases —
                # a power-lost registry is healed by _reap() on the next
                # flock'd read (stale heartbeats expire the claims)
                os.replace(tmp, self.state_path)  # mtpu: lint-ok MTP001 lease state, heal-on-read
                return result
            finally:
                fcntl.flock(lockf, fcntl.LOCK_UN)

    def _reap(self, state: Dict) -> None:
        now = time.time()
        dead = []
        for key, claim in state["claims"].items():
            pid_alive = True
            try:
                os.kill(int(claim["pid"]), 0)
            except (ProcessLookupError, ValueError):
                pid_alive = False
            except PermissionError:
                pass
            if not pid_alive or now - claim.get("t", 0) > self.stale_s:
                dead.append(key)
        for key in dead:
            del state["claims"][key]

    def _file_alloc(self, state: Dict, n: int, owner: str) -> Optional[SubSlice]:
        size = next_pow2(max(1, n))
        if size > self.total:
            raise ValueError(f"requested {n} chips > slice size {self.total}")
        used = set()
        for key in state["claims"]:
            start, bsize = (int(v) for v in key.split(":"))
            used.update(range(start, start + bsize))
        for start in range(0, self.total, size):  # aligned scan
            block = range(start, start + size)
            if not used.intersection(block):
                state["claims"][f"{start}:{size}"] = {
                    "pid": os.getpid(),
                    "owner": owner,
                    "t": time.time(),
                }
                return SubSlice(start, size)
        return None


def detect_slice_size() -> int:
    """Chips on this host: ``MTPU_SLICE_CHIPS``, else one short-lived child.

    Never ``jax.devices()`` here: this runs in the hunt process, which
    must not claim the chips its trial children need. The probe child
    has exited before the first trial starts; its failure (no TPU, or
    the chips held by another process) raises instead of defaulting.
    Set ``MTPU_SLICE_CHIPS`` when several hunts share one host, since a
    probe cannot count chips that a running trial holds.
    """
    env = os.environ.get("MTPU_SLICE_CHIPS")
    if env:
        return int(env)
    from metaopt_tpu.utils.procs import probe_devices

    return int(probe_devices()["count"])


#: TPU_CHIPS_PER_PROCESS_BOUNDS for a block of 1, 2 or 4 chips on a 2x2 v5e
#: host. Checked on such a host (jax 0.9.0, libtpu 0.0.34, PR 21): four
#: 1-chip blocks, two 2-chip blocks, a 1- beside a 2-chip block and one
#: 4-chip block all initialise side by side, collectives work inside each
#: block, and every process opens only its own /dev/vfio/N. No per-process
#: port, address or libtpu-lock variable was needed for that.
_BLOCK_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}


def chip_env(block: SubSlice) -> Dict[str, str]:
    """Env vars pinning a trial subprocess to its sub-slice of a host.

    ``JAX_PLATFORMS=tpu`` makes a trial that cannot get its chips raise
    with the runtime's message (and end ``broken``); with the variable
    unset, jax falls back to the CPU quietly and the trial would be
    recorded ``completed``. ``TPU_VISIBLE_CHIPS`` and the bounds are the
    TPU analogue of the reference's ``CUDA_VISIBLE_DEVICES`` story: the
    process sees only its block, renumbered from device id 0.
    ``MTPU_ASSIGNED_CHIPS`` is the framework-level contract
    (``parallel.mesh.trial_devices``) and works on any backend.
    """
    if block.size not in _BLOCK_BOUNDS:
        raise ValueError(
            f"no per-process bounds known for a {block.size}-chip block "
            f"(known: {sorted(_BLOCK_BOUNDS)})"
        )
    ids = ",".join(str(c) for c in block.chips)
    return {
        "JAX_PLATFORMS": "tpu",
        "MTPU_ASSIGNED_CHIPS": ids,
        "TPU_VISIBLE_CHIPS": ids,
        "TPU_CHIPS_PER_PROCESS_BOUNDS": _BLOCK_BOUNDS[block.size],
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }
