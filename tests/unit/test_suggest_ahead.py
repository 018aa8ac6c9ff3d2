"""SuggestAhead mixin: stream equivalence, depth banking, thread hygiene.

The speculative refill thread moved from a TPE-private implementation
into :class:`metaopt_tpu.algo.base.SuggestAhead`, adopted by TPE, GP-BO
and CMA-ES. The binding property: speculation is a LATENCY lever only —
any interleaving of background refills with suggest()/observe() must
serve the IDENTICAL stream a speculation-disabled instance computes
inline (PRNG keying by fit state, never by wall-clock or launch order).
"""

import numpy as np
import pytest

from metaopt_tpu.algo import CMAES, GPBO, TPE
from metaopt_tpu.algo.base import SuggestAhead
from metaopt_tpu.ledger.trial import Trial
from metaopt_tpu.space import build_space


def make_space():
    return build_space({"x": "uniform(-5, 5)", "y": "uniform(-5, 5)"})


def completed(space, params, objective):
    t = Trial(params=params, experiment="e")
    t.lineage = space.hash_point(params)
    t.transition("reserved")
    t.attach_results([{"name": "o", "type": "objective", "value": objective}])
    t.transition("completed")
    return t


def f(p):
    return (p["x"] - 1.0) ** 2 + (p["y"] + 2.0) ** 2


ALGOS = [
    pytest.param(
        lambda s: TPE(s, seed=11, n_initial_points=3,
                      suggest_prefetch_depth=2),
        12, id="tpe"),
    pytest.param(
        lambda s: GPBO(s, seed=11, n_initial_points=3, fit_iters=8,
                       refit_iters=4, suggest_prefetch_depth=2),
        8, id="gp_bo"),
    pytest.param(
        lambda s: CMAES(s, seed=11, population_size=4,
                        suggest_prefetch_depth=2),
        12, id="cmaes"),
]


class TestStreamEquivalence:
    @pytest.mark.parametrize("make,steps", ALGOS)
    def test_speculative_stream_identical_to_serial(self, make, steps):
        space = make_space()
        eager = make(space)
        lazy = make(space)
        lazy._suggest_ahead_async = lambda: None  # inline-only control
        for _ in range(steps):
            pe = eager.suggest(1)
            pl = lazy.suggest(1)
            assert pe == pl
            if not pe:  # CMA-ES generation barrier (both must agree)
                break
            obj = f(pe[0])
            eager.observe([completed(space, pe[0], obj)])
            lazy.observe([completed(space, pl[0], obj)])
            eager.drain_suggest_ahead()
        assert eager._ahead_launches > 0


class TestDepthBanking:
    def test_depth_keeps_pools_banked(self):
        # depth N: the refill worker keeps > pool_prefetch·(N−1) points
        # prepared, so N−1 consecutive produce legs answer from memory
        space = make_space()
        tpe = TPE(space, seed=3, n_initial_points=3,
                  suggest_prefetch_depth=3)
        for i in range(4):
            tpe.observe([completed(space, {"x": float(i) - 2.0,
                                           "y": float(i)}, float(i))])
        tpe.suggest(1)  # enter EI-active state
        tpe.observe([completed(space, {"x": 0.5, "y": -1.5}, -1.0)])
        tpe.drain_suggest_ahead()
        assert len(tpe._prefetch) > tpe.pool_prefetch * 2
        assert tpe.suggest_ahead_telemetry()["ahead_launches"] >= 1
        # the banked pool serves without a fresh launch
        launches0 = tpe.telemetry()["kernel_launches"]
        tpe.suggest(2)
        assert tpe.telemetry()["kernel_launches"] == launches0
        assert tpe.suggest_ahead_telemetry()["prefetch_hits"] >= 1

    def test_depth_one_is_the_historical_refill_semantics(self):
        # depth 1 must not stack extra pools: one speculative launch per
        # fit change, exactly what the old private refill thread did
        space = make_space()
        tpe = TPE(space, seed=5, n_initial_points=3)
        assert tpe.suggest_prefetch_depth == 1
        for i in range(4):
            tpe.observe([completed(space, {"x": float(i) - 2.0,
                                           "y": float(i)}, float(i))])
        tpe.suggest(1)
        tpe.observe([completed(space, {"x": 1.0, "y": 1.0}, 0.5)])
        tpe.drain_suggest_ahead()
        assert len(tpe._prefetch) <= tpe.pool_prefetch

    def test_miss_counted_when_pool_cold(self):
        space = make_space()
        tpe = TPE(space, seed=7, n_initial_points=3)
        tpe._suggest_ahead_async = lambda: None
        for i in range(4):
            tpe.observe([completed(space, {"x": float(i) - 2.0,
                                           "y": float(i)}, float(i))])
        tpe.suggest(1)  # cold pool -> inline launch -> miss
        tel = tpe.suggest_ahead_telemetry()
        assert tel["prefetch_misses"] >= 1 and tel["prefetch_hits"] == 0


class TestMixinHygiene:
    def test_private_refill_hook_is_gone(self):
        # the TPE-private thread was DELETED, not aliased — everything
        # goes through the shared mixin now
        for cls in (TPE, GPBO, CMAES):
            assert issubclass(cls, SuggestAhead)
            assert not hasattr(cls, "_maybe_refill_async")

    def test_instances_registered_for_atexit_drain(self):
        from metaopt_tpu.algo import base as algo_base

        space = make_space()
        tpe = TPE(space, seed=1)
        assert any(a is tpe for a in algo_base._live_instances)

    def test_refill_thread_attr_name_preserved(self):
        # the TPE tests join `_refill_thread` by name
        space = make_space()
        tpe = TPE(space, seed=9, n_initial_points=3)
        for i in range(4):
            tpe.observe([completed(space, {"x": float(i) - 2.0,
                                           "y": float(i)}, float(i))])
        tpe.suggest(1)
        tpe.observe([completed(space, {"x": 0.0, "y": 0.0}, -0.5)])
        tpe.drain_suggest_ahead()
        assert tpe._refill_thread is not None
        assert not tpe._refill_thread.is_alive()

    def test_drain_is_reentrant_and_idempotent(self):
        space = make_space()
        tpe = TPE(space, seed=2)
        tpe.drain_suggest_ahead()  # nothing launched yet: no-op
        tpe.drain_suggest_ahead()
