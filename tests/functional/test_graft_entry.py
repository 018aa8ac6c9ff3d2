"""Driver entry: the dryrun parent stays off jax.

A chip belongs to one process at a time, and ``dryrun_multichip`` runs its
mesh steps in CPU children: the PARENT must never initialise a backend
(it would claim a chip it has no use for), so routing to the children is
decided from env + sys.modules only. SURVEY.md §7 steps 6-7 (the driver's
multi-chip gate).

These tests poison ``import jax`` in a subprocess (a PYTHONPATH shim that
raises) and run the parent in plan-only mode under several driver
environments. If any parent code path imports jax, the child exits
non-zero with the poison marker in its output.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

POISON = "POISONED-JAX-IMPORTED-IN-PARENT"


def _run_parent(tmp_path, extra_env, n_devices=8, timeout_s=60.0):
    """Run dryrun_multichip(n) in a subprocess with poisoned jax import."""
    shim = tmp_path / "shim"
    shim.mkdir(exist_ok=True)
    (shim / "jax.py").write_text(
        f"raise RuntimeError({POISON!r})\n"
    )
    env = dict(os.environ)
    # scrub everything the conftest set, then apply the driver's shape
    env.pop("JAX_PLATFORMS", None)
    env.pop("_METAOPT_TPU_DRYRUN_CHILD", None)
    env["PYTHONPATH"] = str(shim) + os.pathsep + REPO
    env["_METAOPT_TPU_DRYRUN_PLAN_ONLY"] = "1"
    env.update(extra_env)
    code = textwrap.dedent(
        f"""
        import __graft_entry__
        __graft_entry__.dryrun_multichip({n_devices})
        print("PARENT-DONE")
        """
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, timeout=timeout_s,
        capture_output=True, text=True,
    )
    return proc


@pytest.mark.parametrize(
    "driver_env",
    [
        # a machine with a chip: the default backend would be the TPU
        {"JAX_PLATFORMS": "tpu"},
        # no platform hints at all
        {},
        # driver that pre-sets CPU flags but never imported jax: still must
        # not import jax in the parent (routing is env-independent)
        {"JAX_PLATFORMS": "cpu",
         "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
    ],
    ids=["tpu-preset", "bare", "cpu-preset"],
)
def test_dryrun_parent_never_imports_jax(tmp_path, driver_env):
    proc = _run_parent(tmp_path, driver_env)
    out = proc.stdout + proc.stderr
    assert POISON not in out, f"parent imported jax:\n{out}"
    assert proc.returncode == 0, out
    assert "provisioning" in out, out
    assert "PARENT-DONE" in out, out


def test_dryrun_child_env_is_cpu_with_the_cache_rule(tmp_path):
    """The step-child env must force CPU + n-device flag (the children
    never need a chip) and carry the one compile-cache directory."""
    import __graft_entry__ as ge

    captured = {}

    def fake_run_many(jobs, timeout_s, poll_s):
        for name, argv, env in jobs:
            captured[name] = env
        return {name: (0, "") for name, _, _ in jobs}

    from metaopt_tpu.utils import procs

    orig = procs.run_many_with_deadline
    procs.run_many_with_deadline = fake_run_many
    try:
        env_backup = dict(os.environ)
        os.environ["JAX_PLATFORMS"] = "tpu"
        os.environ.pop("_METAOPT_TPU_DRYRUN_PLAN_ONLY", None)
        try:
            ge._dryrun_in_child(8)
        finally:
            os.environ.clear()
            os.environ.update(env_backup)
    finally:
        procs.run_many_with_deadline = orig
    assert set(captured) == {"A", "B", "C", "D"}
    for name, env in captured.items():
        assert env["JAX_PLATFORMS"] == "cpu"
        assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
        assert env["JAX_COMPILATION_CACHE_DIR"] == procs.xla_cache_dir()
        assert env["_METAOPT_TPU_DRYRUN_CHILD"] == "1"
        assert env["_METAOPT_TPU_DRYRUN_STEP"] == name
