"""Subprocess executor: the reference-parity black-box protocol.

ref: src/metaopt/core/worker/consumer.py (SURVEY.md §2.1, §3.1) — materialize
params into the user's argv (and config file template if present), launch the
script as a subprocess, wait, read the results JSON written via
``client.report_results``. Non-zero exit → broken; SIGINT → interrupted.

TPU-era additions beyond the reference:

- heartbeat callbacks while waiting (the lineage's pacemaker, built in),
- the ``judge`` poll: streams ``client.report_partial`` lines to the
  algorithm's early-stop hook and terminates pruned trials,
- env injection (``METAOPT_TPU_RESULTS_PATH``, ``METAOPT_TPU_TRIAL_INFO``,
  plus any executor extras such as chip pinning from the TPU executor).
"""

from __future__ import annotations

import json
import logging
import os
import signal
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

from metaopt_tpu.client import (
    RESULTS_PATH_ENV,
    STOP_PATH_ENV,
    TRIAL_INFO_ENV,
)
from metaopt_tpu.executor.base import ExecutionResult, Executor, HeartbeatFn, JudgeFn
from metaopt_tpu.executor.faults import faults
from metaopt_tpu.utils import trace
from metaopt_tpu.utils.procs import xla_cache_dir

log = logging.getLogger(__name__)


#: seconds a killed trial gets to exit on SIGTERM, then on SIGKILL
_TERM_GRACE_S = 10.0
_KILL_GRACE_S = 5.0


def _stop_path(results_path: str) -> str:
    """The stop-sentinel path — ONE derivation for the env injection and
    the prune-time touch, so the two can never drift apart."""
    return results_path + ".stop"
from metaopt_tpu.ledger.trial import Trial
from metaopt_tpu.space.builder import CommandTemplate


class SubprocessExecutor(Executor):
    def __init__(
        self,
        template: CommandTemplate,
        working_dir: Optional[str] = None,
        interpreter: Optional[List[str]] = None,
        poll_interval_s: float = 0.2,
        heartbeat_every_s: float = 5.0,
        timeout_s: Optional[float] = None,
        prune_grace_s: float = 1.0,
        extra_env: Optional[Dict[str, str]] = None,
        profile_dir: Optional[str] = None,
        ckpt_root: Optional[str] = None,
        jax_cache_dir: Optional[str] = None,
    ):
        self.template = template
        self.working_dir = working_dir
        self.interpreter = interpreter  # e.g. [sys.executable]; None = direct exec
        self.poll_interval_s = poll_interval_s
        self.heartbeat_every_s = heartbeat_every_s
        self.timeout_s = timeout_s
        self.prune_grace_s = prune_grace_s
        self.extra_env = dict(extra_env or {})
        if profile_dir:
            # every trial leaves its spans there (utils/trace.py), this
            # process too; device traces are the script's to ask for
            # (client.profiled)
            self.extra_env[trace.PROFILE_DIR_ENV] = profile_dir
            trace.dump_under(profile_dir)
        if ckpt_root:  # PBT weight handoff root (client.checkpoint_paths)
            self.extra_env["METAOPT_TPU_CKPT_ROOT"] = ckpt_root
        # Persistent XLA compilation cache, always on: every trial is a
        # fresh process, and trials of a sweep trace the same program
        # wherever hyperparameters do not change it. One rule
        # (utils/procs.xla_cache_dir) decides the directory; an ambient
        # JAX_COMPILATION_CACHE_DIR outranks ``jax_cache_dir``.
        self.jax_cache_dir = xla_cache_dir(jax_cache_dir)

    # -- env/argv assembly -------------------------------------------------
    def _prepare(self, trial: Trial, tmpdir: str) -> tuple[List[str], Dict[str, str], str]:
        results_path = os.path.join(tmpdir, "results.json")
        config_out = None
        if self.template.has_config:
            ext = os.path.splitext(self.template.config_path or "c.yaml")[1]
            config_out = os.path.join(tmpdir, f"trial_config{ext}")
            self.template.materialize_config(trial.params, config_out)
        argv = self.template.format(trial.params, config_out=config_out)
        if self.interpreter:
            argv = list(self.interpreter) + argv
        env = dict(os.environ)
        env.update(self.extra_env)
        env["JAX_COMPILATION_CACHE_DIR"] = self.jax_cache_dir
        env.update(trial.resources.get("env", {}))
        # the trial process must be able to import metaopt_tpu.client even
        # when the framework runs from a source tree rather than site-packages
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
        parts = env.get("PYTHONPATH", "").split(os.pathsep)
        if pkg_root not in parts:
            env["PYTHONPATH"] = os.pathsep.join([pkg_root] + [p for p in parts if p])
        env[RESULTS_PATH_ENV] = results_path
        env[STOP_PATH_ENV] = _stop_path(results_path)
        env[TRIAL_INFO_ENV] = json.dumps(
            {
                "id": trial.id,
                "experiment": trial.experiment,
                "params": trial.params,
                "parent": trial.parent,
                "resources": {k: v for k, v in trial.resources.items() if k != "env"},
                **trace.spawn_info(),
            }
        )
        return argv, env, results_path

    @staticmethod
    def _read_partial(path: str, already: int) -> List[Dict[str, Any]]:
        try:
            with open(path) as f:
                lines = f.readlines()
        except FileNotFoundError:
            return []
        out = []
        for line in lines[already:]:
            line = line.strip()
            if line:
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    pass  # torn tail write; picked up next poll
        return out

    # -- main --------------------------------------------------------------
    def execute(
        self,
        trial: Trial,
        heartbeat: Optional[HeartbeatFn] = None,
        judge: Optional[JudgeFn] = None,
    ) -> ExecutionResult:
        with tempfile.TemporaryDirectory(prefix="mtpu_trial_") as tmpdir:
            # stdout/stderr go to files, not PIPEs: an undrained PIPE deadlocks
            # a chatty script once the ~64KB buffer fills
            stdout_path = os.path.join(tmpdir, "stdout")
            stderr_path = os.path.join(tmpdir, "stderr")
            if faults.fire("spawn_fail"):
                return ExecutionResult("broken", note="spawn failed: injected")
            # the span's id and stamp ride to the child in its trial info,
            # so the environment is made inside the span
            with trace.span("executor.spawn", trial=trial.id):
                argv, env, results_path = self._prepare(trial, tmpdir)
                try:
                    with open(stdout_path, "wb") as so, \
                            open(stderr_path, "wb") as se:
                        proc = subprocess.Popen(
                            argv,
                            env=env,
                            cwd=self.working_dir,
                            stdout=so,
                            stderr=se,
                            start_new_session=True,  # isolate signals (we kill the group)
                        )
                except OSError as e:
                    return ExecutionResult(
                        "broken", note=f"spawn failed: {e}")

            if faults.fire("kill_trial"):  # simulate mid-run preemption
                self._kill(proc)

            partial: List[Dict[str, Any]] = []
            started = time.time()
            last_beat = started
            pruned = False
            spawned = json.loads(env[TRIAL_INFO_ENV])  # stamp, and wait's id
            try:
                while True:
                    rc = proc.poll()
                    if rc is not None:
                        break
                    now = time.time()
                    if self.timeout_s and now - started > self.timeout_s:
                        self._kill(proc)
                        return ExecutionResult(
                            "broken", note=f"timeout after {self.timeout_s}s"
                        )
                    if heartbeat and now - last_beat >= self.heartbeat_every_s:
                        last_beat = now
                        if faults.fire("drop_heartbeat") or not heartbeat():
                            self._kill(proc)
                            return ExecutionResult(
                                "interrupted", note="lost reservation"
                            )
                    new = self._read_partial(results_path + ".partial", len(partial))
                    if new:
                        partial.extend(new)
                        if judge:
                            decision = judge(trial, partial)
                            if decision and decision.get("stop"):
                                pruned = True
                                # cooperative first: touch the stop
                                # sentinel (client.stop_requested) so a
                                # gang-scheduled multi-process trial can
                                # agree-to-stop on its mesh and exit
                                # cleanly; SIGTERM only after the grace —
                                # a kill mid-collective strands the rest
                                # of the gang
                                self._touch(_stop_path(results_path))
                                deadline = time.time() + self.prune_grace_s
                                while (proc.poll() is None
                                       and time.time() < deadline):
                                    # the lease must not lapse during a
                                    # long grace: keep beating (and honor
                                    # the overall timeout) while waiting
                                    now2 = time.time()
                                    if (self.timeout_s
                                            and now2 - started
                                            > self.timeout_s):
                                        break
                                    if (heartbeat
                                            and now2 - last_beat
                                            >= self.heartbeat_every_s):
                                        last_beat = now2
                                        if not heartbeat():
                                            break
                                    time.sleep(self.poll_interval_s)
                                self._kill(proc)
                                break
                    time.sleep(self.poll_interval_s)
            except KeyboardInterrupt:
                self._kill(proc)
                return ExecutionResult("interrupted", note="SIGINT")
            finally:  # spawned -> the poll that saw the exit, or the kill
                trace.record("executor.wait", spawned["spawn_ns"],
                             time.time_ns(), id=spawned["under"],
                             trial=trial.id)

            rc = proc.returncode if not pruned else 0
            with trace.span("executor.collect", trial=trial.id):
                results = self._collect(results_path, partial, pruned)
            if results is None:
                try:
                    with open(stderr_path, "rb") as f:
                        stderr_tail = f.read()[-2000:]
                except OSError:
                    stderr_tail = b""
                return ExecutionResult(
                    "broken",
                    exit_code=rc,
                    note=(
                        f"exit={rc}, no results reported; stderr tail: "
                        f"{stderr_tail.decode(errors='replace')}"
                    ),
                )
            if rc != 0:
                return ExecutionResult(
                    "broken", exit_code=rc, note=f"non-zero exit {rc}"
                )
            note = "pruned by judge" if pruned else ""
            return ExecutionResult("completed", results=results, exit_code=rc, note=note)

    @staticmethod
    def _touch(path: str) -> None:
        try:
            with open(path, "w"):
                pass
        except OSError:
            pass  # sentinel is best-effort; the SIGTERM fallback remains

    @staticmethod
    def _kill(proc: subprocess.Popen) -> None:
        """End the trial's process group and wait until it is gone.

        SIGTERM, then SIGKILL after ``_TERM_GRACE_S``. Returning while
        the group still runs would let the next trial race the dying one
        for the chip: the device is free only once its holder has exited.
        """
        pgid = proc.pid  # start_new_session: the trial leads its own group
        for sig, grace_s in ((signal.SIGTERM, _TERM_GRACE_S),
                             (signal.SIGKILL, _KILL_GRACE_S)):
            try:
                os.killpg(pgid, sig)
            except (ProcessLookupError, PermissionError):
                break
            deadline = time.monotonic() + grace_s
            while time.monotonic() < deadline:
                proc.poll()  # reap the leader, or it counts as alive below
                try:
                    os.killpg(pgid, 0)
                except (ProcessLookupError, PermissionError):
                    return
                time.sleep(0.02)
        if proc.poll() is None:
            log.warning("trial process %d survived SIGKILL for %.0fs",
                        proc.pid, _KILL_GRACE_S)

    @staticmethod
    def _collect(
        results_path: str, partial: List[Dict[str, Any]], pruned: bool
    ) -> Optional[List[Dict[str, Any]]]:
        """Final results file wins; a pruned trial falls back to its last

        partial objective (the rung's measurement, per ASHA semantics).
        """
        try:
            with open(results_path) as f:
                return json.load(f)
        except (FileNotFoundError, json.JSONDecodeError):
            pass
        if partial:
            last = partial[-1]
            return [
                {
                    "name": "objective",
                    "type": "objective",
                    "value": float(last["objective"]),
                },
                {
                    "name": "pruned_at_step" if pruned else "last_step",
                    "type": "statistic",
                    "value": int(last.get("step", -1)),
                },
            ]
        return None
