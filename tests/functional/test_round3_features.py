"""Round-3 surfaces, end-to-end through the real CLI on one ledger.

hunt --algo gp (no YAML) → plot importance → web API importance +
dashboard → benchmark subcommand. Each piece has unit tests; this pins
the integration: one ledger, real subprocess trials, every new surface
reading the same store.
"""

import json
import os
import subprocess
import sys
import urllib.request

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _mtpu(args, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-m", "metaopt_tpu"] + args,
        capture_output=True, text=True, timeout=timeout, env=env,
    )


def test_gp_hunt_importance_dashboard(tmp_path):
    led = str(tmp_path / "ledger")
    script = os.path.join(REPO, "examples", "rosenbrock.py")
    r = _mtpu([
        "hunt", "-n", "r3", "--algo", "gp", "--max-trials", "8",
        "--ledger", led, "--",
        script, "-x~uniform(-5, 10)", "-y~uniform(-5, 10)",
    ])
    assert r.returncode == 0, r.stderr[-500:]

    # the stored experiment carries the shortcut algorithm
    r = _mtpu(["info", "-n", "r3", "--ledger", led, "--json"])
    assert r.returncode == 0, r.stderr[-300:]
    doc = json.loads(r.stdout)
    algo_cfg = doc.get("algorithm") or doc.get("document", {}).get("algorithm")
    assert list(algo_cfg) == ["gp"]

    # surrogate-based importance over the same ledger
    r = _mtpu(["plot", "importance", "-n", "r3", "--ledger", led, "--json"])
    assert r.returncode == 0, r.stderr[-300:]
    imp = json.loads(r.stdout)["importance"]
    assert set(imp) == {"x", "y"}

    # web API serves the same numbers + the dashboard page
    from metaopt_tpu.cli.main import _make_ledger_from_spec
    from metaopt_tpu.io.webapi import make_server, start_in_thread

    server = make_server(_make_ledger_from_spec(led, {}))
    start_in_thread(server)
    host, port = server.server_address[:2]
    base = f"http://{host}:{port}"
    try:
        with urllib.request.urlopen(
            f"{base}/experiments/r3/importance", timeout=10
        ) as resp:
            served = json.loads(resp.read())["importance"]
        assert set(served) == set(imp)
        with urllib.request.urlopen(f"{base}/dashboard", timeout=10) as resp:
            assert resp.headers["Content-Type"].startswith("text/html")
    finally:
        server.shutdown()
        server.server_close()


def test_benchmark_subcommand_smoke():
    r = _mtpu(["benchmark", "--algos", "random", "--task", "sphere",
               "--max-trials", "5", "--repetitions", "1", "--json"],
              timeout=300)
    assert r.returncode == 0, r.stderr[-300:]
    assert json.loads(r.stdout)["winner"] == "random"
