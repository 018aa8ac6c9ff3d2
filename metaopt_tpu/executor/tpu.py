"""TPU executor: subprocess trials pinned to chips / ICI sub-slices.

The TPU-native replacement for the reference Consumer's "launch on whatever
GPU the script grabs" (SURVEY.md §2.7 TPU-native equivalent): each trial is
gang-scheduled onto an ICI-contiguous sub-slice via the buddy allocator, the
subprocess sees only its chips (env pinning), and the sub-slice is returned
on ANY exit path — completion, breakage, prune, or executor kill — so a
broken trial never leaks capacity.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Optional

from metaopt_tpu.executor.base import ExecutionResult, HeartbeatFn, JudgeFn
from metaopt_tpu.executor.subproc import SubprocessExecutor
from metaopt_tpu.executor.topology import (
    ChipRegistry,
    SubSlice,
    chip_env,
    detect_slice_size,
)
from metaopt_tpu.ledger.trial import Trial
from metaopt_tpu.space.builder import CommandTemplate

log = logging.getLogger(__name__)


class TPUExecutor(SubprocessExecutor):
    def __init__(
        self,
        template: CommandTemplate,
        n_chips: int = 1,
        total_chips: Optional[int] = None,
        registry: Optional[ChipRegistry] = None,
        registry_path: Optional[str] = None,
        allocate_timeout_s: float = 600.0,
        allocate_poll_s: float = 0.5,
        **kwargs,
    ):
        super().__init__(template, **kwargs)
        self.n_chips = int(n_chips)
        # `hunt --n-workers` counts the chips once and passes the answer
        # in: N executors probing at once would race each other for them
        total = total_chips or detect_slice_size()
        # round the slice size down to a power of two for the buddy allocator
        p = 1
        while p * 2 <= total:
            p *= 2
        if registry is None and registry_path is None:
            # default to the flock'd per-host state file: every executor on
            # this host — other hunt PROCESSES and `--n-workers` threads
            # alike — must arbitrate the same physical chips, not each
            # believe the whole slice is free
            import tempfile

            registry_path = os.path.join(
                tempfile.gettempdir(), f"metaopt_tpu-chips-{p}.json"
            )
        self.registry = registry or ChipRegistry(p, state_path=registry_path)
        self.allocate_timeout_s = allocate_timeout_s
        self.allocate_poll_s = allocate_poll_s

    def execute(
        self,
        trial: Trial,
        heartbeat: Optional[HeartbeatFn] = None,
        judge: Optional[JudgeFn] = None,
    ) -> ExecutionResult:
        block = self._acquire(trial, heartbeat)
        if block is None:
            return ExecutionResult(
                "interrupted",
                note=f"no {self.n_chips}-chip sub-slice became available "
                f"within {self.allocate_timeout_s}s",
            )
        env = chip_env(block)
        if os.environ.get("JAX_PLATFORMS"):
            # an explicit platform choice in the environment (the CPU test
            # harness) outranks the pin; only an UNSET variable lets jax
            # fall back quietly, and chip_env never leaves it unset
            del env["JAX_PLATFORMS"]
        trial.resources.update(
            {
                "chips": block.chips,
                "slice": {"start": block.start, "size": block.size},
                "env": env,
            }
        )
        log.debug("trial %s pinned to chips %s", trial.id[:8], block.chips)

        def beating() -> bool:
            self.registry.heartbeat(block)
            return heartbeat() if heartbeat else True

        try:
            return super().execute(trial, heartbeat=beating, judge=judge)
        finally:
            self.registry.free(block)  # every exit path returns the sub-slice

    def _acquire(
        self, trial: Trial, heartbeat: Optional[HeartbeatFn]
    ) -> Optional[SubSlice]:
        deadline = time.time() + self.allocate_timeout_s
        while time.time() < deadline:
            block = self.registry.allocate(self.n_chips, owner=trial.id)
            if block is not None:
                return block
            if heartbeat and not heartbeat():
                return None
            time.sleep(self.allocate_poll_s)
        return None
