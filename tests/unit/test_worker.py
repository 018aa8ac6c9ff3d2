"""Worker runtime tests: Producer pump, workon loop, broken-trial handling.

ref coverage model: Producer/worker unit tests with DumbAlgo (SURVEY.md §4).
"""

import pytest

from metaopt_tpu.executor import InProcessExecutor
from metaopt_tpu.ledger import Experiment, MemoryLedger
from metaopt_tpu.space import build_space
from metaopt_tpu.worker import Producer, workon

from tests.dumbalgo import DumbAlgo


@pytest.fixture
def space():
    return build_space({"x": "uniform(-5, 5)"})


@pytest.fixture
def exp(space):
    return Experiment(
        "w", MemoryLedger(), space=space, max_trials=5,
        algorithm={"dumbalgo": {}}, pool_size=2,
    ).configure()


class TestProducer:
    def test_produce_registers_and_dedups(self, exp, space):
        algo = DumbAlgo(space, value={"x": 1.0})
        prod = Producer(exp, algo)
        assert prod.produce() == 1          # both suggestions identical → 1 kept
        assert prod.produce() == 0          # same point again → duplicate
        assert exp.count() == 1

    def test_produce_respects_max_trials_budget(self, exp, space):
        algo = DumbAlgo(space)
        prod = Producer(exp, algo)
        total = 0
        for _ in range(10):
            total += prod.produce(pool_size=3)
        assert exp.count() == 5             # never floods past max_trials
        assert total == 5

    def test_produce_marks_algo_done(self, exp, space):
        algo = DumbAlgo(space, done_after=0)
        Producer(exp, algo).produce()
        assert exp.is_done

    def test_observe_feeds_completed(self, exp, space):
        algo = DumbAlgo(space)
        prod = Producer(exp, algo)
        prod.produce()
        t = exp.reserve_trial("w")
        exp.push_results(t, [{"name": "o", "type": "objective", "value": 1.0}])
        prod.produce()
        assert algo.n_observed == 1

    def test_parent_key_strips_into_trial_lineage(self, exp, space):
        # PBT continuations carry the reserved _parent key; it must become
        # Trial.parent, never a param (or a hash ingredient)
        algo = DumbAlgo(space, value={"x": 2.0, "_parent": "donor-trial"})
        Producer(exp, algo).produce(pool_size=1)
        (t,) = exp.fetch_trials()
        assert t.parent == "donor-trial"
        assert t.params == {"x": 2.0}
        assert t.id == space.hash_point({"x": 2.0}, with_fidelity=True)


class TestWorkon:
    def test_runs_to_max_trials(self, exp):
        stats = workon(exp, InProcessExecutor(lambda p: p["x"] ** 2), "w0")
        assert stats.completed == 5
        assert exp.is_done
        assert exp.stats["best"]["objective"] >= 0

    def test_broken_trials_dont_kill_worker(self, space):
        exp = Experiment(
            "b", MemoryLedger(), space=space, max_trials=4,
            algorithm={"dumbalgo": {}},
        ).configure()

        calls = {"n": 0}

        def flaky(params):
            calls["n"] += 1
            if calls["n"] % 2 == 0:
                raise RuntimeError("boom")
            return params["x"] ** 2

        stats = workon(exp, InProcessExecutor(flaky), "w0", max_idle_cycles=20)
        assert stats.broken >= 1
        assert stats.completed == 4          # max_trials counts completions only
        assert exp.count("completed") == 4

    def test_warm_start_observes_foreign_completions_once(self, space):
        """metadata["warm_start"] replays another experiment's completed
        trials into the algorithm before the first suggest."""
        ledger = MemoryLedger()
        old = Experiment(
            "old", ledger, space=space, max_trials=3,
            algorithm={"dumbalgo": {}},
        ).configure()
        workon(old, InProcessExecutor(lambda p: p["x"] ** 2), "w-old")
        assert old.count("completed") == 3

        new = Experiment(
            "new", ledger, space=space, max_trials=2,
            algorithm={"dumbalgo": {}},
            metadata={"warm_start": "old"},
        ).configure()
        algo = DumbAlgo(space)
        prod = Producer(new, algo)
        prod.produce()
        foreign = [t for t in algo.observed_trials if t.experiment == "old"]
        assert len(foreign) == 3
        prod.produce()  # warm start happens exactly once
        foreign2 = [t for t in algo.observed_trials if t.experiment == "old"]
        assert len(foreign2) == 3

    def test_should_suspend_parks_trial_without_executing(self, space):
        """The algorithm's should_suspend hook: the trial is parked as
        'suspended', never executed, and doesn't block completion."""
        exp = Experiment(
            "susp", MemoryLedger(), space=space, max_trials=4,
            algorithm={"dumbalgo": {}}, pool_size=1,
        ).configure()
        algo = DumbAlgo(
            space,
            scripted=[{"x": 9.0}, {"x": 1.0}, {"x": 2.0}, {"x": 3.0}],
            suspend_if={"x": 9.0},
            done_after=3,
        )
        ran = []

        def objective(p):
            ran.append(p["x"])
            return p["x"] ** 2

        stats = workon(exp, InProcessExecutor(objective), "w0",
                       algorithm=algo, max_idle_cycles=20)
        assert stats.suspended == 1
        assert 9.0 not in ran
        assert stats.completed == 3
        suspended = exp.fetch_trials("suspended")
        assert len(suspended) == 1 and suspended[0].params == {"x": 9.0}
        assert exp.is_done

        # resume path: suspended → new → reservable and executable again
        t = suspended[0]
        t.transition("new")
        t.worker = None
        assert exp.ledger.update_trial(t, expected_status="suspended")
        algo2 = DumbAlgo(space, done_after=0)  # suggest nothing new
        exp2 = Experiment("susp", exp.ledger, max_trials=4).configure()
        stats2 = workon(exp2, InProcessExecutor(objective), "w1",
                        algorithm=algo2, max_idle_cycles=10)
        assert 9.0 in ran and stats2.completed == 1

    def test_worker_trials_cap(self, exp):
        stats = workon(
            exp, InProcessExecutor(lambda p: 0.0), "w0", worker_trials=2
        )
        assert stats.reserved == 2
        assert not exp.is_done

    def test_two_sequential_workers_share_experiment(self, space):
        ledger = MemoryLedger()
        e1 = Experiment("s", ledger, space=space, max_trials=6,
                        algorithm={"dumbalgo": {}}).configure()
        workon(e1, InProcessExecutor(lambda p: p["x"]), "w1", worker_trials=3)
        e2 = Experiment("s", ledger).configure()   # joins by name, adopts config
        stats = workon(e2, InProcessExecutor(lambda p: p["x"]), "w2")
        assert ledger.count("s", "completed") == 6
        assert stats.completed == 3

    def test_gradient_descent_protocol_end_to_end(self):
        """The typed-results protocol: gradient results drive the algorithm."""
        space = build_space({"x": "uniform(-5, 5)"})
        exp = Experiment(
            "g", MemoryLedger(), space=space, max_trials=12,
            algorithm={"gradient_descent": {"learning_rate": 0.2, "seed": 4}},
        ).configure()

        def objective(p):
            x = p["x"]
            return [
                {"name": "f", "type": "objective", "value": (x - 1.0) ** 2},
                {"name": "df", "type": "gradient", "value": [2 * (x - 1.0)]},
            ]

        workon(exp, InProcessExecutor(objective), "w0")
        best = exp.stats["best"]
        assert best["objective"] < 0.05
        assert abs(best["params"]["x"] - 1.0) < 0.25


class TestStaleSweepThrottle:
    def test_first_cycle_sweeps_then_throttles(self):
        """The pacemaker sweep runs on cycle one (a restarted worker must
        free its dead predecessor's holds before producing) and then at
        most every stale_sweep_interval_s — not per cycle."""
        from metaopt_tpu.executor import InProcessExecutor
        from metaopt_tpu.ledger.backends import make_ledger
        from metaopt_tpu.ledger.experiment import Experiment
        from metaopt_tpu.space import build_space
        from metaopt_tpu.worker import workon

        ledger = make_ledger({"type": "memory"})
        calls = {"n": 0}
        orig = ledger.release_stale

        def counting(name, timeout_s):
            calls["n"] += 1
            return orig(name, timeout_s)

        ledger.release_stale = counting
        exp = Experiment(
            "throttle", ledger,
            space=build_space({"x": "uniform(0, 1)"}),
            max_trials=20, algorithm={"random": {"seed": 0}},
        ).configure()
        stats = workon(
            exp,
            InProcessExecutor(lambda p: [{
                "name": "o", "type": "objective", "value": p["x"]}]),
            worker_id="w0",
            stale_sweep_interval_s=3600.0,  # only the first cycle sweeps
        )
        assert stats.completed == 20
        assert calls["n"] == 1, \
            "one sweep for the whole hunt at a huge interval"
