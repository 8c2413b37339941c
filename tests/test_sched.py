"""Scheduler-layer tests: host greedy placer, level schedule, TPU backend."""

import numpy as np
import pytest

from fleetflow_tpu.core.loader import load_project_from_root_with_stage
from fleetflow_tpu.lower import lower_stage, synthetic_problem
from fleetflow_tpu.sched import (HostGreedyScheduler, TpuSolverScheduler,
                                 level_schedule, pick_scheduler)
from fleetflow_tpu.solver.repair import verify


class TestLevelSchedule:
    def test_levels_follow_depth(self, project):
        root, _ = project
        flow = load_project_from_root_with_stage(str(root), "local")
        pt = lower_stage(flow, "local")
        levels = level_schedule(pt)
        assert levels == [["postgres", "redis"], ["app"]]


class TestHostGreedy:
    def test_local_single_node(self, project):
        root, _ = project
        flow = load_project_from_root_with_stage(str(root), "local")
        pt = lower_stage(flow, "local")
        placement = HostGreedyScheduler().place(pt)
        assert placement.feasible
        assert set(placement.assignment.values()) == {"local"}
        assert placement.node_levels("local") == [["postgres", "redis"], ["app"]]

    def test_synthetic_feasible(self):
        pt = synthetic_problem(100, 10, seed=1)
        placement = HostGreedyScheduler().place(pt)
        assert placement.feasible, placement.violations
        stats = verify(pt, placement.raw)
        assert stats["total"] == 0

    def test_synthetic_with_tenants(self):
        pt = synthetic_problem(200, 20, seed=2, n_tenants=4)
        placement = HostGreedyScheduler().place(pt)
        stats = verify(pt, placement.raw)
        assert stats["total"] == 0

    def test_strategies_differ(self):
        from dataclasses import replace
        from fleetflow_tpu.core.model import PlacementStrategy
        pt = synthetic_problem(60, 8, seed=3, port_fraction=0.0,
                               volume_fraction=0.0)
        spread = HostGreedyScheduler().place(pt).raw
        packed = HostGreedyScheduler().place(
            replace(pt, strategy=PlacementStrategy.PACK_INTO_DEDICATED)).raw
        # packing concentrates on fewer nodes than spreading
        assert len(np.unique(packed)) <= len(np.unique(spread))


class TestTpuScheduler:
    def test_solver_backend(self):
        pt = synthetic_problem(80, 8, seed=4)
        sched = TpuSolverScheduler(chains=2, steps=200)
        placement = sched.place(pt)
        assert placement.feasible
        assert placement.source == "cpu-anneal"  # the platform that ran
        stats = verify(pt, placement.raw)
        assert stats["total"] == 0

    def test_reschedule_warm_start_is_sticky(self):
        from dataclasses import replace
        pt = synthetic_problem(80, 8, seed=5)
        sched = TpuSolverScheduler(chains=2, steps=200)
        first = sched.place(pt)
        # kill node 0 -> only services on node 0 should move
        valid = pt.node_valid.copy()
        valid[0] = False
        pt2 = replace(pt, node_valid=valid)
        second = sched.reschedule(pt2)
        assert second.feasible
        a, b = first.raw, second.raw
        movable = a == 0
        moved_without_cause = np.flatnonzero((a != b) & ~movable)
        # stickiness: the overwhelming majority of unaffected services stay
        assert moved_without_cause.size <= int(0.15 * pt.S)
        assert not np.any(b == 0)


class TestPick:
    def test_policy(self):
        from fleetflow_tpu.native import NativeGreedyScheduler
        assert isinstance(pick_scheduler(3, 1), HostGreedyScheduler)
        assert isinstance(pick_scheduler(1000, 100), TpuSolverScheduler)
        # fleet-scale host path routes to the C++ placer (which itself
        # falls back to host-greedy when the library isn't built)
        assert isinstance(pick_scheduler(1000, 100, prefer_tpu=False),
                          NativeGreedyScheduler)
        assert isinstance(pick_scheduler(100, 4, prefer_tpu=False),
                          HostGreedyScheduler)


class TestStagedCacheInvalidation:
    def test_in_place_node_valid_mutation_is_seen(self):
        """Regression: the CP's node_event mutates pt.node_valid IN PLACE on
        the same ProblemTensors object; the staged DeviceProblem must pick up
        the new mask (round-2 bug: the device kept the stale mask and left
        services on a dead node while reporting feasible)."""
        from dataclasses import replace
        pt = synthetic_problem(40, 8, seed=11)
        sched = TpuSolverScheduler(chains=2, steps=128)
        first = sched.place(pt)
        assert first.feasible
        victims = np.flatnonzero(np.asarray(first.raw) == 0)
        assert victims.size, "nothing on node 0; pick another seed"
        pt.node_valid = pt.node_valid.copy()
        pt.node_valid[0] = False          # same pt object, mutated in place
        second = sched.reschedule(pt)
        assert second.feasible
        assert not np.any(np.asarray(second.raw) == 0), (
            "dead node still occupied: staged mask is stale")


class TestSlotManager:
    """Device-memory slot manager (PR 16): per-stage byte accounting,
    LRU eviction to a budget, and warm re-admission from the host
    snapshot. The two property tests the ISSUE pins: evict -> readmit
    re-solves BIT-IDENTICALLY to the never-evicted path, and a budget
    smaller than one slot degrades to one-at-a-time operation instead
    of deadlocking."""

    def _pts(self, n=3):
        return {k: synthetic_problem(60, 12, seed=i, port_fraction=0.3,
                                     volume_fraction=0.2)
                for i, k in enumerate("ABCDEFGH"[:n])}

    def test_evict_readmit_warm_seeds_bit_identically(self, monkeypatch):
        monkeypatch.setenv("FLEET_SUBSOLVE", "0")
        pts = self._pts()

        # control: all three stages stay resident
        ctl = TpuSolverScheduler(steps=32)
        for k in "ABC":
            ctl.place(pts[k], stage=k)
        ref = ctl.reschedule(pts["A"], stage="A")

        # pressured: 2 slots -> placing C evicts A (LRU); the later
        # reschedule(A) re-admits from A's host snapshot
        monkeypatch.setenv("FLEET_RESIDENT_STAGES", "2")
        hot = TpuSolverScheduler(steps=32)
        for k in "ABC":
            hot.place(pts[k], stage=k)
        st = hot.slots_status()
        assert sorted(s["stage"] for s in st["slots"]) == ["B", "C"]
        assert [e["stage"] for e in st["evicted"]] == ["A"]
        assert st["evicted"][0]["snapshot"]      # warm snapshot captured
        got = hot.reschedule(pts["A"], stage="A")
        assert np.array_equal(ref.raw, got.raw)
        assert got.feasible == ref.feasible

    def test_tiny_byte_budget_never_deadlocks(self, monkeypatch):
        """A 1-byte budget is smaller than any slot: the newly admitted
        slot must never be its own eviction victim, so placement still
        converges with exactly one (over-budget) slot resident."""
        monkeypatch.setenv("FLEET_SUBSOLVE", "0")
        pts = self._pts()
        tiny = TpuSolverScheduler(steps=32, resident_bytes=1)
        for k in "ABC":
            placement = tiny.place(pts[k], stage=k)
            assert placement.feasible
        st = tiny.slots_status()
        assert len(st["slots"]) == 1
        assert st["slots"][0]["stage"] == "C"    # MRU survives
        assert st["budget_bytes"] == 1
        assert st["resident_bytes"] > 0          # accounting is live

    def test_slots_status_shape(self):
        pts = self._pts(2)
        sched = TpuSolverScheduler(steps=32)
        for k in "AB":
            sched.place(pts[k], stage=k)
        st = sched.slots_status()
        assert {"budget_bytes", "max_slots", "resident_bytes",
                "slots", "evicted"} <= set(st)
        for s in st["slots"]:
            assert {"stage", "tier", "bytes", "idle_s", "evictions",
                    "warm"} <= set(s)
            assert s["bytes"] > 0
        total = sum(s["bytes"] for s in st["slots"])
        assert st["resident_bytes"] == total


class TestStagesInTurn:
    """Stages are solved one at a time through one scheduler, each
    through its own resident slot: a second stage of the same tier runs
    the executables the first compiled, another tier compiles its own
    once, and no stage's result depends on which stages ran between its
    solves."""

    @staticmethod
    def _pt(seed, S=60, N=12):
        return synthetic_problem(S, N, seed=seed, port_fraction=0.3,
                                 volume_fraction=0.2)

    @staticmethod
    def _killed(pt, node):
        from dataclasses import replace

        from fleetflow_tpu.solver.resident import ProblemDelta
        valid = np.asarray(pt.node_valid, bool).copy()
        valid[node] = False
        return replace(pt, node_valid=valid), ProblemDelta(node_valid=valid)

    def _cold_then_warm(self, sched, stage, pt, node):
        cold = sched.place(pt, stage=stage)
        cur, delta = self._killed(pt, node)
        return cold, sched.reschedule(cur, delta=delta, stage=stage)

    @staticmethod
    def _routes(sched, monkeypatch):
        """Records each solve's route: (resident warm, sub-solve info)."""
        routes = []
        inner = sched._solve_one

        def solve_one(pt, slot, resident_warm, *a, **kw):
            res = inner(pt, slot, resident_warm, *a, **kw)
            routes.append((resident_warm, res.subsolve))
            return res
        monkeypatch.setattr(sched, "_solve_one", solve_one)
        return routes

    def test_same_tier_stage_adds_no_executable(self, monkeypatch):
        from fleetflow_tpu.solver.api import _refine
        monkeypatch.setenv("FLEET_SUBSOLVE", "0")
        sched = TpuSolverScheduler(steps=32)
        routes = self._routes(sched, monkeypatch)
        self._cold_then_warm(sched, "A", self._pt(0), 0)   # warm-up
        before = _refine._cache_size()
        _, warm = self._cold_then_warm(sched, "B", self._pt(1), 1)
        assert warm.feasible
        # the reschedule ran the resident-warm refine, not the sub-solve
        assert routes[-1] == (True, None)
        assert _refine._cache_size() == before

    def test_other_tier_stage_adds_one_executable(self, monkeypatch):
        from fleetflow_tpu.solver.api import _refine
        monkeypatch.setenv("FLEET_SUBSOLVE", "0")
        sched = TpuSolverScheduler(steps=32)
        routes = self._routes(sched, monkeypatch)
        self._cold_then_warm(sched, "A", self._pt(0), 0)   # warm-up
        small = self._pt(2, S=30, N=7)
        sched.place(small, stage="C")
        cur, delta = self._killed(small, 3)
        before = _refine._cache_size()
        sched.reschedule(cur, delta=delta, stage="C")
        assert routes[-1] == (True, None)
        assert _refine._cache_size() == before + 1
        # and the first stage's tier still needs nothing new
        before = _refine._cache_size()
        self._cold_then_warm(sched, "A", self._pt(0), 2)
        assert _refine._cache_size() == before

    @pytest.mark.parametrize("start", ["cold", "warm", "warm-fused"])
    def test_alternating_stages_match_solo_runs(self, start, monkeypatch):
        if start == "warm-fused":
            # the full warm executable instead of the localized sub-solve
            monkeypatch.setenv("FLEET_SUBSOLVE", "0")
        pts = {"A": self._pt(3), "B": self._pt(4)}
        kill = {"A": 5, "B": 6}
        solo = {k: self._cold_then_warm(TpuSolverScheduler(steps=32, seed=7),
                                        k, pts[k], kill[k])
                for k in "AB"}
        mixed = TpuSolverScheduler(steps=32, seed=7)
        got = {k: [mixed.place(pts[k], stage=k)] for k in "AB"}
        for k in "AB":
            cur, delta = self._killed(pts[k], kill[k])
            got[k].append(mixed.reschedule(cur, delta=delta, stage=k))
        i = 0 if start == "cold" else 1
        for k in "AB":
            assert np.array_equal(solo[k][i].raw, got[k][i].raw), k
            assert solo[k][i].soft == got[k][i].soft, k
            assert solo[k][i].feasible == got[k][i].feasible, k
