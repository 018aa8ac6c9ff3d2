"""Parallel strategy ("liar") tests: pending trials bias the TPE fit.

ref: the lineage's parallel-strategy classes (Mean/Max/Stub "liars",
post-v0) — reserved trials join the surrogate with a lie objective so
asynchronous workers don't pile suggestions onto in-flight points.
"""

import numpy as np
import pytest

from metaopt_tpu.algo.tpe import TPE
from metaopt_tpu.ledger import Experiment, MemoryLedger, Trial
from metaopt_tpu.space import build_space
from metaopt_tpu.worker import Producer


def _space():
    return build_space({"x": "uniform(0, 1)", "y": "uniform(0, 1)"})


def _completed(space, params, objective):
    t = Trial(params=dict(params), experiment="e")
    t.id = space.hash_point(params, with_fidelity=True)
    t.transition("reserved")
    t.attach_results([{"name": "o", "type": "objective", "value": objective}])
    t.transition("completed")
    return t


def _reserved(space, params):
    t = Trial(params=dict(params), experiment="e")
    t.id = space.hash_point(params, with_fidelity=True)
    t.transition("reserved")
    return t


def _seeded_tpe(strategy=None, n=12, seed=7):
    space = _space()
    tpe = TPE(space, seed=seed, n_initial_points=4, n_ei_candidates=16,
              pool_prefetch=4, parallel_strategy=strategy)
    rng = np.random.RandomState(0)
    for i in range(n):
        x, y = float(rng.rand()), float(rng.rand())
        tpe.observe([_completed(space, {"x": x, "y": y}, (x - 0.3) ** 2 + y)])
    return space, tpe


class TestStrategyConfig:
    def test_unknown_strategy_rejected(self):
        with pytest.raises(ValueError, match="none\\|mean\\|max"):
            TPE(_space(), parallel_strategy="median")

    def test_supports_pending_flag(self):
        assert TPE(_space()).supports_pending is False
        assert TPE(_space(), parallel_strategy="mean").supports_pending
        # the liar setting must survive the experiment-document round trip
        # (coordinator restart rebuilds algorithms from .configuration)
        cfg = TPE(_space(), parallel_strategy="max").configuration["tpe"]
        assert cfg["parallel_strategy"] == "max"


class TestLies:
    def test_pending_changes_the_suggestion_stream(self):
        space, a = _seeded_tpe(strategy="max")
        _, b = _seeded_tpe(strategy="max")
        pend = [_reserved(space, {"x": 0.3, "y": 0.01})]
        b.set_pending(pend)
        sa = a.suggest(4)
        sb = b.suggest(4)
        assert sa != sb, "lies at the incumbent must alter the fit"

    def test_no_strategy_ignores_pending(self):
        space, a = _seeded_tpe(strategy=None)
        _, b = _seeded_tpe(strategy=None)
        b.set_pending([_reserved(space, {"x": 0.3, "y": 0.01})])
        assert a.suggest(4) == b.suggest(4)

    def test_pending_is_ephemeral_and_uncounted(self):
        space, tpe = _seeded_tpe(strategy="mean")
        n0 = tpe.n_observed
        state0 = tpe.state_dict()
        pend = [_reserved(space, {"x": 0.5, "y": 0.5})]
        tpe.set_pending(pend)
        assert tpe.n_observed == n0, "lies never count as observations"
        assert tpe.state_dict() == state0, "lies never serialize"
        # the same point, now truly completed: observe() takes the truth
        # and the next set_pending drops the lie (id is in _observed)
        done = _completed(space, {"x": 0.5, "y": 0.5}, 0.42)
        tpe.observe([done])
        tpe.set_pending(pend)
        assert tpe._pending_X == []

    def test_pending_invalidates_prefetch_pool(self):
        space, tpe = _seeded_tpe(strategy="max")
        first = tpe.suggest(1)  # fills the prefetch pool
        assert len(tpe._prefetch) > 0
        stale = list(tpe._prefetch)
        tpe.set_pending([_reserved(space, {"x": 0.9, "y": 0.9})])
        # set_pending empties the pool and starts the refill against the new
        # fit on another thread, which may already have landed here
        assert not [p for p in tpe._prefetch if p in stale], \
            "stale-fit points must not be served"
        assert tpe.suggest(1) is not None
        assert first  # silence vulture; stream continuity covered above


class TestProducerIntegration:
    def test_produce_reports_reserved_trials(self):
        ledger = MemoryLedger()
        space = _space()
        exp = Experiment(
            "e", ledger, space=space,
            algorithm={"tpe": {"parallel_strategy": "mean",
                               "n_initial_points": 2, "seed": 1}},
            max_trials=50,
        ).configure()
        from metaopt_tpu.algo.base import make_algorithm

        algo = make_algorithm(exp.space, exp.algorithm)
        prod = Producer(exp, algo)
        # seed two completed + one reserved trial
        for i in range(3):
            exp.register_trials([exp.make_trial({"x": 0.1 * (i + 1),
                                                 "y": 0.2})])
        for _ in range(2):
            t = exp.reserve_trial("w")
            exp.push_results(
                t, [{"name": "o", "type": "objective", "value": 1.0}]
            )
        held = exp.reserve_trial("w")  # stays in flight
        assert held is not None
        prod.produce(pool_size=1)
        assert algo._pending_fp == (held.id,)

    def test_plain_algorithms_skip_the_extra_fetch(self):
        ledger = MemoryLedger()
        exp = Experiment(
            "e2", ledger, space=_space(),
            algorithm={"random": {"seed": 1}}, max_trials=10,
        ).configure()
        from metaopt_tpu.algo.base import make_algorithm

        algo = make_algorithm(exp.space, exp.algorithm)
        assert getattr(algo, "supports_pending", False) is False
        Producer(exp, algo).produce(pool_size=1)  # must not blow up


class TestLieRobustness:
    def test_nan_observation_does_not_poison_the_lie(self):
        space, tpe = _seeded_tpe(strategy="mean")
        tpe.observe([_completed(space, {"x": 0.9, "y": 0.9}, float("nan"))])
        tpe.set_pending([_reserved(space, {"x": 0.2, "y": 0.2})])
        pts = tpe.suggest(2)
        assert len(pts) == 2
        # the cached augmented buffer carries a finite lie
        assert tpe._aug_y is not None
        import numpy as _np
        lie_rows = _np.asarray(tpe._aug_y)[len(tpe._y):tpe._aug_n]
        assert _np.all(_np.isfinite(lie_rows))

    def test_augmented_buffers_cached_per_fit(self):
        space, tpe = _seeded_tpe(strategy="max")
        tpe.set_pending([_reserved(space, {"x": 0.2, "y": 0.2})])
        tpe.suggest(1)
        key1 = tpe._aug_key
        tpe.suggest(1)  # same fit + pending: no rebuild
        assert tpe._aug_key is key1
        tpe.observe([_completed(space, {"x": 0.7, "y": 0.7}, 0.9)])
        tpe.set_pending([_reserved(space, {"x": 0.2, "y": 0.2})])
        tpe.suggest(1)
        assert tpe._aug_key != key1  # fit changed -> rebuilt once


class TestGPConstantLiar:
    def _seeded_gp(self, strategy=None, n=8):
        from metaopt_tpu.algo.gp_bo import GPBO

        space = _space()
        gp = GPBO(space, seed=5, n_initial_points=4, n_candidates=32,
                  fit_iters=8, pool_prefetch=2, parallel_strategy=strategy)
        rng = np.random.RandomState(1)
        for _ in range(n):
            x, y = float(rng.rand()), float(rng.rand())
            gp.observe(
                [_completed(space, {"x": x, "y": y}, (x - 0.4) ** 2 + y)]
            )
        return space, gp

    def test_lies_change_the_stream_and_stay_ephemeral(self):
        space, a = self._seeded_gp(strategy="max")
        _, b = self._seeded_gp(strategy="max")
        assert a.supports_pending and b.supports_pending
        n0 = b.n_observed
        state0 = b.state_dict()
        b.set_pending([_reserved(space, {"x": 0.4, "y": 0.02})])
        assert b.n_observed == n0
        assert b.state_dict() == state0
        assert a.suggest(2) != b.suggest(2)

    def test_unknown_strategy_rejected(self):
        from metaopt_tpu.algo.gp_bo import GPBO

        with pytest.raises(ValueError, match="none\\|mean\\|max"):
            GPBO(_space(), parallel_strategy="kriging")

    def test_nan_observation_excluded_from_fit(self):
        space, gp = self._seeded_gp(strategy="mean")
        gp.observe([_completed(space, {"x": 0.99, "y": 0.99},
                               float("nan"))])
        gp.set_pending([_reserved(space, {"x": 0.2, "y": 0.2})])
        pts = gp.suggest(2)
        assert len(pts) == 2
        # the fit itself must stay finite: every suggested point is a
        # real unit-cube point, not NaN fallout
        for pt in pts:
            assert all(np.isfinite(v) for v in pt.values())
            assert pt in space

    def test_all_nan_observations_fall_back_to_uniform(self):
        from metaopt_tpu.algo.gp_bo import GPBO

        space = _space()
        gp = GPBO(space, seed=5, n_initial_points=2, n_candidates=16,
                  fit_iters=4)
        for i in range(4):
            gp.observe([_completed(space, {"x": 0.1 * (i + 1), "y": 0.5},
                                   float("nan"))])
        pts = gp.suggest(3)
        assert len(pts) == 3 and all(p in space for p in pts)
