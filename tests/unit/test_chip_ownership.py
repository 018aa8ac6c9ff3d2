"""One owner per chip, loud device failures, one compile-cache rule.

A TPU chip belongs to one process at a time, and jax falls back to the CPU
quietly when ``JAX_PLATFORMS`` is unset and the TPU cannot be initialised.
These tests pin the decisions that keep a CPU run from passing for a chip
run: who may initialise a backend, what a chip-bound trial's environment
says, where the compile cache lives, and that launchers with no TPU fail.
All CPU, no device work.
"""

import json
import os
import subprocess
import sys
import tempfile
import time

import pytest

from metaopt_tpu.executor import SubprocessExecutor
from metaopt_tpu.executor import topology
from metaopt_tpu.ledger import Trial
from metaopt_tpu.space.builder import SpaceBuilder
from metaopt_tpu.utils import procs

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _template(*argv):
    _, template = SpaceBuilder().build([*argv, "-x~uniform(0, 1)"])
    return template


def _trial():
    return Trial(params={"x": 0.5}, experiment="e")


# -- the compile-cache rule ---------------------------------------------------


class TestCacheRule:
    def test_variable_beats_flag(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/ambient/cache")
        assert procs.xla_cache_dir("/from/flag") == "/ambient/cache"

    def test_flag_beats_checkout_default(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert procs.xla_cache_dir("/from/flag") == "/from/flag"

    def test_default_is_the_checkout_never_a_temporary_name(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = procs.xla_cache_dir()
        assert path == os.path.join(REPO, ".cache", "xla")
        assert not path.startswith(tempfile.gettempdir() + os.sep)
        assert str(os.getpid()) not in path
        assert procs.xla_cache_dir() == path  # no clock in it either

    def test_use_reaches_children_and_the_live_config(
            self, monkeypatch, tmp_path):
        import jax

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            cache = procs.use_xla_cache(str(tmp_path / "jc"))
            assert os.path.isdir(cache)
            assert os.environ["JAX_COMPILATION_CACHE_DIR"] == cache
            assert jax.config.jax_compilation_cache_dir == cache
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

    def test_trial_children_always_get_it(self, monkeypatch, tmp_path):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        ex = SubprocessExecutor(_template("t.py"))  # no flag at all
        _, env, _ = ex._prepare(_trial(), str(tmp_path))
        assert env["JAX_COMPILATION_CACHE_DIR"] == procs.xla_cache_dir()

    def test_nothing_in_code_replaces_the_variable(
            self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/ambient/cache")
        ex = SubprocessExecutor(_template("t.py"),
                                jax_cache_dir=str(tmp_path / "flag"))
        _, env, _ = ex._prepare(_trial(), str(tmp_path))
        assert env["JAX_COMPILATION_CACHE_DIR"] == "/ambient/cache"


# -- who may initialise a backend --------------------------------------------


class TestSliceSize:
    def test_env_override_needs_no_probe(self, monkeypatch):
        monkeypatch.setenv("MTPU_SLICE_CHIPS", "4")
        monkeypatch.setattr(procs, "probe_devices", lambda **_: 1 / 0)
        assert topology.detect_slice_size() == 4

    def test_asks_a_child_never_jax_devices(self, monkeypatch):
        import jax

        monkeypatch.delenv("MTPU_SLICE_CHIPS", raising=False)
        monkeypatch.setattr(jax, "devices", lambda *a, **k: 1 / 0)
        monkeypatch.setattr(procs, "probe_devices",
                            lambda **_: {"platform": "tpu", "count": 4})
        assert topology.detect_slice_size() == 4

    def test_probe_failure_raises_instead_of_defaulting(self, monkeypatch):
        monkeypatch.delenv("MTPU_SLICE_CHIPS", raising=False)

        def busy(**_):
            raise RuntimeError("The TPU is already in use by process 7")

        monkeypatch.setattr(procs, "probe_devices", busy)
        with pytest.raises(RuntimeError, match="already in use"):
            topology.detect_slice_size()


class TestProbeDevices:
    def _fake(self, monkeypatch, rc, out):
        seen = {}

        def run(argv, timeout_s, env=None, **kw):
            seen["env"] = env
            return rc, out

        monkeypatch.setattr(procs, "run_with_deadline", run)
        return seen

    def test_unset_platform_is_pinned_to_tpu(self, monkeypatch):
        doc = {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1}
        seen = self._fake(monkeypatch, 0,
                          "noise\nMTPU_DEVICES " + json.dumps(doc) + "\n")
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        assert procs.probe_devices() == doc
        assert seen["env"]["JAX_PLATFORMS"] == "tpu"

    def test_explicit_platform_is_kept(self, monkeypatch):
        seen = self._fake(monkeypatch, 0, 'MTPU_DEVICES {"platform": "cpu"}')
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert procs.probe_devices()["platform"] == "cpu"
        assert seen["env"]["JAX_PLATFORMS"] == "cpu"

    def test_failed_child_raises_with_the_runtimes_message(self, monkeypatch):
        self._fake(monkeypatch, 1, "RuntimeError: Unable to initialize "
                                   "backend 'tpu': already in use")
        with pytest.raises(RuntimeError, match="already in use"):
            procs.probe_devices()

    def test_timeout_raises(self, monkeypatch):
        self._fake(monkeypatch, None, "")
        with pytest.raises(RuntimeError, match="timed out"):
            procs.probe_devices(timeout_s=3)


class TestTrialPin:
    """TPUExecutor puts chip_env into the trial; only an explicit platform
    choice in the parent's environment (this harness) outranks the pin."""

    def _run(self, monkeypatch):
        from metaopt_tpu.executor.topology import ChipRegistry
        from metaopt_tpu.executor.tpu import TPUExecutor

        monkeypatch.setattr(SubprocessExecutor, "execute",
                            lambda self, trial, **kw: trial)
        ex = TPUExecutor(_template("t.py"), n_chips=1, total_chips=4,
                         registry=ChipRegistry(4))
        return ex.execute(_trial()).resources

    def test_unset_platform_gets_the_pin(self, monkeypatch):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        res = self._run(monkeypatch)
        assert res["env"]["JAX_PLATFORMS"] == "tpu"
        assert res["chips"] == [0]

    def test_explicit_platform_outranks_it(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert "JAX_PLATFORMS" not in self._run(monkeypatch)["env"]


def test_subprocess_hunt_pins_its_own_process_to_the_cpu(
        monkeypatch, tmp_path, capsys):
    """Trials are children, so the hunt process must never initialise a
    non-CPU backend — pinned through the live config before the algorithm
    is built, with the chips counted by a child, not by jax.devices()."""
    import jax

    from metaopt_tpu.cli.main import main

    script = tmp_path / "t.py"
    script.write_text(
        "from metaopt_tpu.client import report_objective\n"
        "report_objective(1.0)\n")
    monkeypatch.delenv("MTPU_SLICE_CHIPS", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(procs, "probe_devices",
                        lambda **_: {"platform": "tpu", "count": 2})
    monkeypatch.setattr(tempfile, "gettempdir", lambda: str(tmp_path))
    before = (jax.config.jax_platforms,
              jax.config.jax_compilation_cache_dir)
    jax.config.update("jax_platforms", None)
    try:
        rc = main(["hunt", "-n", "pin", "--ledger", "memory", "--algo", "tpe",
                   "--n-chips", "1", "--max-trials", "1",
                   "--", str(script), "-x~uniform(0, 1)"])
        assert jax.config.jax_platforms == "cpu"
    finally:
        jax.config.update("jax_platforms", before[0])
        jax.config.update("jax_compilation_cache_dir", before[1])
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    out = capsys.readouterr().out
    summary = json.loads(out[out.index("{"):])
    assert rc == 0
    assert summary["platform"] == "cpu"
    assert summary["host_chips"] == 2
    assert summary["jax_cache"] == os.path.join(REPO, ".cache", "xla")
    assert "requeued_by_worker" not in summary


def test_sibling_shard_processes_stay_off_the_chip(monkeypatch):
    """N shard processes host algorithms on one machine; at most one could
    own a chip, so all run suggest on the host CPU unless told otherwise."""
    from metaopt_tpu.coord import shards

    seen = {}

    class FakeProc:
        stdout = ()

        def __init__(self, argv, env=None, **kw):
            seen["env"] = env

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(shards.subprocess, "Popen", FakeProc)
    monkeypatch.setattr(shards.ShardSupervisor, "_shard_argv",
                        lambda self, i: ["shard", str(i)])
    shards.ShardSupervisor(1)._spawn(0)
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"


# -- a killed trial is gone before the executor returns ----------------------

_SLOW_TO_DIE = """\
import os, signal, sys, time
signal.signal(signal.SIGTERM, lambda *_: (time.sleep(0.4), os._exit(0)))
open(sys.argv[1], "w").write(str(os.getpid()))
time.sleep(60)
"""


def _gone(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return True
    return False


class TestKilledTrialIsAwaited:
    """Returning while the killed group still runs lets the next trial race
    the dying one for the chip."""

    def _executor(self, tmp_path, **kw):
        script = tmp_path / "slow.py"
        script.write_text(_SLOW_TO_DIE)
        pidfile = tmp_path / "pid"
        ex = SubprocessExecutor(
            _template(str(script), str(pidfile)),
            interpreter=[sys.executable], poll_interval_s=0.02, **kw)
        return ex, pidfile

    def test_timeout_path(self, tmp_path):
        ex, pidfile = self._executor(tmp_path, timeout_s=0.5)
        res = ex.execute(_trial())
        assert res.status == "broken" and res.note.startswith("timeout after")
        assert _gone(int(pidfile.read_text()))

    def test_lost_reservation_path(self, tmp_path):
        ex, pidfile = self._executor(tmp_path, heartbeat_every_s=0.0)

        def beat():
            # reservation lost as soon as the child is up and has said who
            # it is (the file exists, empty, a moment before it is written)
            return not (pidfile.exists() and pidfile.read_text())

        res = ex.execute(_trial(), heartbeat=beat)
        assert res.status == "interrupted"
        assert _gone(int(pidfile.read_text()))

    def test_sigterm_deaf_group_is_sigkilled(self, tmp_path, monkeypatch):
        from metaopt_tpu.executor import subproc

        monkeypatch.setattr(subproc, "_TERM_GRACE_S", 0.2)
        script = tmp_path / "deaf.py"
        script.write_text(
            "import os, signal, sys, time\n"
            "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
            "open(sys.argv[1], 'w').write(str(os.getpid()))\n"
            "time.sleep(60)\n")
        pidfile = tmp_path / "pid"
        ex = SubprocessExecutor(
            _template(str(script), str(pidfile)),
            interpreter=[sys.executable], poll_interval_s=0.02,
            timeout_s=0.5)
        t0 = time.monotonic()
        assert ex.execute(_trial()).status == "broken"
        assert _gone(int(pidfile.read_text()))
        assert time.monotonic() - t0 < 5.0


# -- launchers without a TPU fail; no fallback, no quiet interpret mode ------


def _no_tpu(*args, **kwargs):
    raise RuntimeError("Unable to initialize backend 'tpu'")


def _each_way_of_having_no_tpu(monkeypatch):
    """The probe child fails; it finds only CPUs; the environment already
    rules the TPU out (no child is started to learn that)."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(procs, "probe_devices", _no_tpu)
    yield
    monkeypatch.setattr(procs, "probe_devices",
                        lambda *a, **k: {"platform": "cpu", "count": 8})
    yield
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(procs, "probe_devices", lambda *a, **k: 1 / 0)
    yield


def test_chip_smoke_without_a_tpu_fails_with_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=60)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
        capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert "not beside this script" in proc.stderr
    assert proc.stdout == ""


def test_run_py_backend_tpu_without_a_chip_is_an_error(monkeypatch, capsys):
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import run

    monkeypatch.setattr(sys, "argv", ["run.py", "--backend", "tpu"])
    for _ in _each_way_of_having_no_tpu(monkeypatch):
        assert run.main() == 1
        captured = capsys.readouterr()
        assert captured.out == ""
        assert "no TPU" in captured.err


def test_run_py_model_configs_pin_their_chips(monkeypatch, tmp_path):
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import run

    seen = []

    def swept(argv, timeout_s, env=None, **kw):
        seen.append((argv, env))
        return 0, json.dumps({"total": {"completed": 1}, "best": None}), ""

    monkeypatch.setattr(run, "run_swept", swept)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    run.run_config("tpe_mlp", run.CONFIGS["tpe_mlp"], "smoke",
                   str(tmp_path), "tpu", 60.0)
    run.run_config("random_rosenbrock", run.CONFIGS["random_rosenbrock"],
                   "smoke", str(tmp_path), "tpu", 60.0)
    (mlp_argv, mlp_env), (rosen_argv, rosen_env) = seen
    assert mlp_argv[mlp_argv.index("--n-chips") + 1] == "1"
    assert "JAX_PLATFORMS" not in mlp_env and "--jax-cache" not in mlp_argv
    assert "--n-chips" not in rosen_argv
    assert rosen_env["JAX_PLATFORMS"] == "cpu"


def test_pallas_is_never_switched_to_interpret_mode_for_the_caller():
    import jax.numpy as jnp

    from metaopt_tpu.ops.attention import flash_attention

    q = jnp.ones((1, 8, 1, 8), jnp.float32)
    with pytest.raises(ValueError, match="interpret mode"):
        flash_attention(q, q, q, impl="pallas")  # CPU: Mosaic cannot compile
    out = flash_attention(q, q, q, impl="pallas", interpret=True)
    assert out.shape == q.shape


def test_provenance_outside_a_git_checkout_says_unknown(tmp_path):
    from metaopt_tpu.utils.provenance import git_commit

    assert git_commit(str(tmp_path)) == "unknown"
