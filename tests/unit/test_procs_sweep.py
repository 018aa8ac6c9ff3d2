"""run_swept + provenance invariants (driven manually in round 5; pinned).

These behaviors guard the watcher's capture integrity: nested deadline
sweeps must reap whole process trees across sessions, captured output
must survive the kill, and perf rows must attribute their numbers to the
right code state.
"""

import os
import subprocess
import sys
import time

from metaopt_tpu.utils.procs import kill_by_env_marker, run_swept
from metaopt_tpu.utils.provenance import git_commit, provenance


class TestRunSwept:
    def test_markers_accumulate_across_nesting(self, monkeypatch):
        """An outer sweep marker must survive into children launched by
        an inner run_swept — overwriting it would leave the outer
        caller's deadline sweep nothing to match (an outer sweep → run.py
        → trial trees)."""
        monkeypatch.setenv("MTPU_SWEEP_MARKER", "outer-abc")
        rc, out, _ = run_swept(
            [sys.executable, "-c",
             "import os; print(os.environ['MTPU_SWEEP_MARKER'])"], 30)
        assert rc == 0
        assert out.strip().startswith("outer-abc,")

    def test_deadline_preserves_partial_output(self):
        """What a killed child DID print must reach the caller — the
        wedge diagnostics this helper exists to preserve."""
        code = ("import sys, time; print('partial-out', flush=True); "
                "sys.stderr.write('partial-err'); sys.stderr.flush(); "
                "time.sleep(60)")
        rc, out, err = run_swept([sys.executable, "-c", code], 2.0)
        assert rc is None
        assert "partial-out" in out
        assert "partial-err" in err

    def test_sweep_reaps_detached_grandchildren(self):
        """start_new_session'd descendants escape any killpg but inherit
        the env marker; the sweep must reach them."""
        marker = f"sweep-test-{os.getpid()}-{time.time_ns()}"
        code = (
            "import subprocess, sys, time; "
            "subprocess.Popen([sys.executable, '-c', "
            "'import time; time.sleep(120)'], start_new_session=True); "
            "print('spawned', flush=True); time.sleep(120)"
        )
        env = dict(os.environ, MTPU_SWEEP_MARKER=marker)
        proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                                start_new_session=True,
                                stdout=subprocess.DEVNULL)
        try:
            # wait until the grandchild exists (environ visible in /proc);
            # both processes sleep long, so there is no lifetime race
            deadline = time.time() + 30
            marked = []
            while time.time() < deadline and len(marked) < 2:
                marked = []
                for pid_s in os.listdir("/proc"):
                    if not pid_s.isdigit():
                        continue
                    try:
                        with open(f"/proc/{pid_s}/environ", "rb") as f:
                            if marker.encode() in f.read():
                                marked.append(pid_s)
                    except OSError:
                        continue
                time.sleep(0.2)
            assert len(marked) >= 2, "child + detached grandchild expected"
            killed = kill_by_env_marker(marker)
            assert killed >= 2
            proc.wait(timeout=10)
        finally:
            # an assertion above must not leak the detached sleepers
            kill_by_env_marker(marker)
            if proc.poll() is None:
                proc.kill()


class TestProvenance:
    def test_stamp_shape(self):
        p = provenance(backend="cpu")
        assert set(p) == {"commit", "ts", "backend"}
        assert p["backend"] == "cpu"

    def test_dirty_flag_tracks_tracked_files_only(self, tmp_path):
        """An untracked file (the watcher's own logs) must not stamp the
        capture +dirty; a modified TRACKED file must."""
        subprocess.run(["git", "init", "-q", str(tmp_path)], check=True)
        subprocess.run(["git", "-C", str(tmp_path), "config",
                        "user.email", "t@t"], check=True)
        subprocess.run(["git", "-C", str(tmp_path), "config",
                        "user.name", "t"], check=True)
        (tmp_path / "a.txt").write_text("v1")
        subprocess.run(["git", "-C", str(tmp_path), "add", "a.txt"],
                       check=True)
        subprocess.run(["git", "-C", str(tmp_path), "commit", "-q", "-m",
                        "c1"], check=True)
        clean = git_commit(str(tmp_path))
        assert not clean.endswith("+dirty")
        (tmp_path / "untracked.log").write_text("noise")
        assert git_commit(str(tmp_path)) == clean
        (tmp_path / "a.txt").write_text("v2")
        assert git_commit(str(tmp_path)) == clean + "+dirty"
