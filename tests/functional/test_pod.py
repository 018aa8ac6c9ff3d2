"""Multi-process pod tests: jax.distributed + coordinator discovery.

The pod story end-to-end at process fidelity (SURVEY.md §2.7): N OS
processes form a jax.distributed "pod" on CPU, process 0 hosts the
CoordServer, the address is agreed via the pod's collective channel
(broadcast_one_to_all), and all processes run workon against the shared
coordinator — the TPU-native analogue of the reference's "N machines, one
Mongo URL" (SURVEY.md §3.2). The 4-process variant additionally delegates
suggestion to the coordinator-hosted algorithm (producer_mode="coord").

Count assertions are ``>=``: the producer's budget check (max_trials −
completed − pending) is read-then-register racy across processes and a
trial in flight when ``is_done`` flips still pushes its result, so totals
can overshoot by design — the hard invariant is no-duplicate-execution.
"""

import json
import multiprocessing as mp
import os
import socket
import time

import pytest


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _pod_proc(rank: int, nprocs: int, jax_port: int, out_path: str,
              producer_mode: str) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        f"127.0.0.1:{jax_port}", num_processes=nprocs, process_id=rank
    )
    from jax.experimental import multihost_utils

    from metaopt_tpu.coord.client_backend import CoordLedgerClient
    from metaopt_tpu.coord.pod import start_pod_coordinator
    from metaopt_tpu.executor import InProcessExecutor
    from metaopt_tpu.ledger import Experiment
    from metaopt_tpu.space import build_space
    from metaopt_tpu.worker import workon

    host, port, server = start_pod_coordinator(stale_timeout_s=60.0)
    assert (server is not None) == (rank == 0)
    ledger = CoordLedgerClient(host=host, port=port)

    if rank == 0:
        exp = Experiment(
            "podrace", ledger,
            space=build_space({"x": "uniform(-5, 5)"}),
            max_trials=12, pool_size=3,
            algorithm={"random": {"seed": 0}},
        ).configure()
    else:
        for _ in range(100):  # wait for process 0 to create it
            if ledger.load_experiment("podrace") is not None:
                break
            time.sleep(0.1)
        exp = Experiment("podrace", ledger).configure()

    stats = workon(
        exp, InProcessExecutor(lambda p: (p["x"] - 1.0) ** 2),
        worker_id=f"pod-w{rank}",
        producer_mode=producer_mode,
    )
    done = exp.count("completed")
    # barrier over the pod channel: the server host must outlive the others
    multihost_utils.sync_global_devices("podrace-done")
    if server is not None:
        server.stop()
    with open(out_path, "w") as f:
        json.dump(
            {"rank": rank, "completed": stats.completed, "total_done": done,
             "events": [e["trial"] for e in stats.events]},
            f,
        )


@pytest.mark.parametrize(
    "nprocs,producer_mode", [(2, "local"), (4, "coord")],
    ids=["2proc-local", "4proc-coord"],
)
def test_pod_coordinator(tmp_path, nprocs, producer_mode):
    jax_port = _free_port()
    ctx = mp.get_context("spawn")
    outs = [str(tmp_path / f"pod{i}.json") for i in range(nprocs)]
    procs = [
        ctx.Process(
            target=_pod_proc,
            args=(i, nprocs, jax_port, outs[i], producer_mode),
        )
        for i in range(nprocs)
    ]
    for p in procs:
        p.start()
    for p in procs:
        p.join(timeout=240)
        assert p.exitcode == 0, "pod process failed (see captured stderr)"

    results = [json.load(open(o)) for o in outs]
    executed = [t for r in results for t in r["events"]]
    assert len(executed) == len(set(executed)), "a trial ran on two processes"
    assert sum(r["completed"] for r in results) >= 12
    assert all(r["total_done"] >= 12 for r in results)


# ---------------------------------------------------------------------------
# coordinator restart mid-hunt with live workers attached


def _serve_proc(port: int, snap: str) -> None:
    from metaopt_tpu.coord import CoordServer
    from metaopt_tpu.coord.server import serve_forever

    serve_forever(CoordServer(
        port=port, snapshot_path=snap, snapshot_interval_s=0.2,
        # wide enough that a CI box under full CPU contention can't starve
        # a live worker's heartbeat into a spurious stale reclaim
        stale_timeout_s=10.0, sweep_interval_s=0.5,
    ))


def _resilient_worker(port: int, worker_id: str, out_path: str) -> None:
    from metaopt_tpu.coord.client_backend import CoordLedgerClient
    from metaopt_tpu.executor import InProcessExecutor
    from metaopt_tpu.ledger import Experiment
    from metaopt_tpu.worker import workon

    ledger = CoordLedgerClient(
        host="127.0.0.1", port=port, reconnect_window_s=60.0
    )
    exp = Experiment("restart-hunt", ledger).configure()

    def objective(p):
        time.sleep(0.05)  # keep trials in flight across the restart
        return (p["x"] - 1.0) ** 2

    stats = workon(
        exp, InProcessExecutor(objective), worker_id=worker_id,
        producer_mode="coord",
        # outlast the outage + the stale sweep reclaiming orphaned
        # reservations: an idle worker must not give up mid-restart
        max_idle_cycles=600,
        heartbeat_timeout_s=10.0,
    )
    with open(out_path, "w") as f:
        json.dump({"completed": stats.completed,
                   "events": [e["trial"] for e in stats.events]}, f)


def test_coordinator_restart_mid_hunt_with_live_workers(tmp_path):
    """Kill the coordinator while workers are mid-hunt; restart it from the
    snapshot; workers ride the outage on their reconnect window and finish
    the experiment (hosted algorithm rebuilt by observe-replay)."""
    from metaopt_tpu.coord.client_backend import CoordLedgerClient
    from metaopt_tpu.ledger import Experiment
    from metaopt_tpu.space import build_space

    port = _free_port()
    snap = str(tmp_path / "snap.json")
    ctx = mp.get_context("spawn")

    server_a = ctx.Process(target=_serve_proc, args=(port, snap))
    server_a.start()
    client = CoordLedgerClient(
        host="127.0.0.1", port=port, reconnect_window_s=30.0
    )
    for _ in range(100):
        try:
            client.ping()
            break
        except Exception:
            time.sleep(0.1)
    Experiment(
        "restart-hunt", client,
        space=build_space({"x": "uniform(-5, 5)"}),
        max_trials=16, pool_size=4, algorithm={"random": {"seed": 7}},
    ).configure()

    outs = [str(tmp_path / f"rw{i}.json") for i in range(3)]
    workers = [
        ctx.Process(target=_resilient_worker, args=(port, f"rw{i}", outs[i]))
        for i in range(3)
    ]
    for w in workers:
        w.start()

    # let the hunt get going, then yank the coordinator (SIGTERM snapshots)
    deadline = time.time() + 60
    while time.time() < deadline:
        try:
            if len(client.fetch("restart-hunt", "completed")) >= 4:
                break
        except Exception:
            pass
        time.sleep(0.2)
    server_a.terminate()
    server_a.join(timeout=10)
    time.sleep(1.0)  # a real outage window with workers live

    server_b = ctx.Process(target=_serve_proc, args=(port, snap))
    server_b.start()
    try:
        for w in workers:
            w.join(timeout=120)
            assert w.exitcode == 0, "worker died across the restart"

        results = [json.load(open(o)) for o in outs]
        executed = [t for r in results for t in r["events"]]
        assert len(executed) == len(set(executed)), "a trial ran twice"
        done = client.fetch("restart-hunt", "completed")
        assert len(done) >= 16
    finally:
        server_b.terminate()
        server_b.join(timeout=10)


def test_hosted_producer_serves_cohort_and_surrogate_algorithms():
    """The coordinator-hosted producer must drive the generation-cohort
    (CMA-ES: suggest barriers until the cohort's results arrive over RPC)
    and surrogate (GP) algorithms end-to-end, not just the stateless ones."""
    from metaopt_tpu.coord import CoordLedgerClient, CoordServer
    from metaopt_tpu.executor import InProcessExecutor
    from metaopt_tpu.ledger import Experiment
    from metaopt_tpu.space import build_space
    from metaopt_tpu.worker import workon

    server = CoordServer().start()
    host, port = server.address
    try:
        for algo in ({"cmaes": {"seed": 0, "population_size": 6}},
                     {"gp": {"seed": 0, "n_initial_points": 5}}):
            name = list(algo)[0]
            ledger = CoordLedgerClient(host=host, port=port)
            space = build_space({"x": "uniform(-5, 5)", "y": "uniform(-5, 5)"})
            exp = Experiment(name, ledger, space=space, algorithm=algo,
                             max_trials=14, pool_size=2).configure()
            workon(
                exp,
                InProcessExecutor(lambda p: [{
                    "name": "o", "type": "objective",
                    "value": (p["x"] - 1) ** 2 + (p["y"] + 1) ** 2,
                }]),
                worker_id=f"w-{name}",
                producer_mode="coord",
            )
            assert ledger.count(name, "completed") == 14, name
    finally:
        server.stop()


def test_hosted_producer_reports_pending_to_liar_algorithms():
    """producer_mode='coord' + TPE parallel_strategy: the coordinator's
    hosted Producer must feed reserved trials into set_pending — the liar
    mechanism works identically whether the fit is local or hosted."""
    from metaopt_tpu.coord import CoordLedgerClient, CoordServer
    from metaopt_tpu.executor import InProcessExecutor
    from metaopt_tpu.ledger import Experiment
    from metaopt_tpu.space import build_space
    from metaopt_tpu.worker import workon

    server = CoordServer().start()
    host, port = server.address
    try:
        algo = {"tpe": {"seed": 0, "n_initial_points": 3,
                        "parallel_strategy": "mean"}}
        ledger = CoordLedgerClient(host=host, port=port)
        space = build_space({"x": "uniform(-5, 5)"})
        exp = Experiment("liar-coord", ledger, space=space, algorithm=algo,
                         max_trials=10, pool_size=2).configure()
        workon(
            exp,
            InProcessExecutor(lambda p: [{
                "name": "o", "type": "objective",
                "value": (p["x"] - 1) ** 2,
            }]),
            worker_id="w-liar",
            producer_mode="coord",
        )
        assert ledger.count("liar-coord", "completed") == 10
        with server._producers_guard:
            prod, _plock = server._producers["liar-coord"]
        assert prod.algorithm.supports_pending

        # now make the pending set VISIBLE: hold a reservation from a
        # second worker and drive one hosted produce cycle over RPC — the
        # hosted algorithm must receive the in-flight trial as a lie row
        from metaopt_tpu.worker.producer import RemoteProducer

        exp.max_trials = 12  # reopen the budget so produce() suggests
        ledger.update_experiment("liar-coord", {"max_trials": 12})
        held = exp.reserve_trial("holder")
        if held is None:  # everything completed: register one to hold
            t = exp.make_trial({"x": 4.875})
            exp.register_trials([t])
            held = exp.reserve_trial("holder")
        assert held is not None
        RemoteProducer(exp, worker="w-liar").produce(pool_size=1)
        with server._producers_guard:
            prod, _plock = server._producers["liar-coord"]
        assert prod.algorithm._pending_fp == (held.id,), \
            "the hosted Producer must report reserved trials to the liar"
        assert len(prod.algorithm._pending_X) == 1
    finally:
        server.stop()
