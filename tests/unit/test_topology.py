"""Gang-scheduling tests: buddy allocator, flock'd chip registry, executor
pinning — the sub-slice machinery SURVEY.md §2.8 maps trial placement onto.
"""

import json
import os
import threading

import pytest

from metaopt_tpu.executor.topology import (
    BuddyAllocator,
    ChipRegistry,
    SubSlice,
    chip_env,
    next_pow2,
)


class TestBuddyAllocator:
    def test_allocate_aligned_contiguous(self):
        a = BuddyAllocator(8)
        b1 = a.allocate(4)
        b2 = a.allocate(2)
        b3 = a.allocate(2)
        assert {tuple(b.chips) for b in (b1, b2, b3)} == {
            (0, 1, 2, 3), (4, 5), (6, 7)
        }
        assert a.n_free_chips == 0
        assert a.allocate(1) is None

    def test_rounds_up_to_pow2(self):
        a = BuddyAllocator(8)
        b = a.allocate(3)  # 3 -> 4
        assert b.size == 4 and b.start % 4 == 0

    def test_free_coalesces_buddies(self):
        a = BuddyAllocator(8)
        blocks = [a.allocate(1) for _ in range(8)]
        for b in blocks:
            a.free(b)
        assert a.n_free_chips == 8
        whole = a.allocate(8)  # only possible if every buddy re-merged
        assert whole.start == 0 and whole.size == 8

    def test_oversized_request_raises(self):
        with pytest.raises(ValueError):
            BuddyAllocator(4).allocate(5)
        with pytest.raises(ValueError):
            BuddyAllocator(3)  # not a power of two


class TestChipRegistryFile:
    def test_two_registries_share_one_slice(self, tmp_path):
        """Two ChipRegistry instances (= two hunt processes / two worker
        threads) over one state file must never hand out overlapping
        chips."""
        path = str(tmp_path / "chips.json")
        r1 = ChipRegistry(8, state_path=path)
        r2 = ChipRegistry(8, state_path=path)
        b1 = r1.allocate(4, owner="t1")
        b2 = r2.allocate(4, owner="t2")
        assert not set(b1.chips) & set(b2.chips)
        assert r1.allocate(1) is None  # slice exhausted, seen by BOTH
        assert r2.n_free_chips == 0
        r1.free(b1)
        assert r2.n_free_chips == 4  # the free is visible cross-instance

    def test_concurrent_allocation_no_overlap(self, tmp_path):
        path = str(tmp_path / "chips.json")
        got, lock = [], threading.Lock()

        def worker():
            r = ChipRegistry(16, state_path=path)
            b = r.allocate(2, owner="w")
            if b is not None:
                with lock:
                    got.append(b)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        chips = [c for b in got for c in b.chips]
        assert len(got) == 8
        assert len(chips) == len(set(chips)) == 16

    def test_dead_pid_claims_are_reaped(self, tmp_path):
        path = str(tmp_path / "chips.json")
        r = ChipRegistry(4, state_path=path)
        # forge a claim from a dead pid occupying the whole slice
        with open(path, "w") as f:
            json.dump({"claims": {"0:4": {"pid": 2 ** 30, "owner": "ghost",
                                          "t": 0}}}, f)
        b = r.allocate(4, owner="fresh")  # reap happens on allocate
        assert b is not None and b.size == 4

    def test_stale_heartbeat_claims_are_reaped(self, tmp_path):
        path = str(tmp_path / "chips.json")
        r = ChipRegistry(4, state_path=path, stale_s=0.0)
        # a LIVE pid whose heartbeat lapsed (hung process): reaped too
        with open(path, "w") as f:
            json.dump({"claims": {"0:4": {"pid": os.getpid(), "owner": "me",
                                          "t": 0}}}, f)
        assert r.allocate(1, owner="fresh") is not None

    def test_heartbeat_refreshes_claim(self, tmp_path):
        path = str(tmp_path / "chips.json")
        r = ChipRegistry(4, state_path=path, stale_s=3600.0)
        b = r.allocate(2, owner="t")
        r.heartbeat(b)
        with open(path) as f:
            state = json.load(f)
        assert state["claims"][f"{b.start}:{b.size}"]["t"] > 0


class TestChipEnv:
    def test_pinning_env(self):
        env = chip_env(SubSlice(2, 2))
        assert env["MTPU_ASSIGNED_CHIPS"] == "2,3"
        assert env["TPU_VISIBLE_CHIPS"] == "2,3"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"

    def test_chip_bound_trial_is_pinned_to_the_tpu(self):
        """Only an UNSET JAX_PLATFORMS lets jax fall back to the CPU
        quietly; the pin makes a trial without its chip raise."""
        for block in (SubSlice(0, 1), SubSlice(3, 1), SubSlice(0, 4)):
            assert chip_env(block)["JAX_PLATFORMS"] == "tpu"

    @pytest.mark.parametrize("size,bounds",
                             [(1, "1,1,1"), (2, "1,2,1"), (4, "2,2,1")])
    def test_per_block_bounds(self, size, bounds):
        """The shapes a 2x2 v5e host accepted side by side (PR 21)."""
        env = chip_env(SubSlice(0, size))
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == bounds

    def test_unknown_block_shape_raises(self):
        with pytest.raises(ValueError, match="8-chip block"):
            chip_env(SubSlice(0, 8))

    def test_next_pow2(self):
        assert [next_pow2(n) for n in (1, 2, 3, 5, 8)] == [1, 2, 4, 8, 8]


class TestTPUExecutorRegistry:
    def test_default_registry_is_shared_per_host(self, tmp_path, monkeypatch):
        """Two executors with no explicit registry must arbitrate the same
        state file — N hunt processes (or --n-workers threads) on one host
        cannot each believe the whole slice is free."""
        import tempfile

        monkeypatch.setenv("MTPU_SLICE_CHIPS", "8")
        monkeypatch.setattr(tempfile, "gettempdir", lambda: str(tmp_path))
        from metaopt_tpu.executor.tpu import TPUExecutor
        from metaopt_tpu.space.builder import SpaceBuilder

        _, template = SpaceBuilder().build(["t.py", "-x~uniform(0, 1)"])
        ex1 = TPUExecutor(template, n_chips=4)
        ex2 = TPUExecutor(template, n_chips=4)
        assert ex1.registry.state_path == ex2.registry.state_path
        b1 = ex1.registry.allocate(4, owner="a")
        b2 = ex2.registry.allocate(4, owner="b")
        assert not set(b1.chips) & set(b2.chips)
        assert ex1.registry.allocate(1) is None
