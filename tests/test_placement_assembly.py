"""A placement is assembled by array passes (PR 38).

`sched.base.assignment_names` and `level_schedule` turn the solver's array
and the dependency depths into names with a few numpy passes. The two
Python loops over rows that they replace are kept here as the plain
reference, and the array passes are held to them exactly: an equal dict in
the same order, every value a `str`, a `json.dumps` round trip, the
schedule list for list.

The scheduler keeps a stage's level schedule with the stage's slot for as
long as a problem brings the very same `dep_depth` and `service_names`
objects: `fleet_sched_level_schedules_total{outcome}` says which happened.
"""

from __future__ import annotations

import json
from dataclasses import replace

import numpy as np
import pytest

from fleetflow_tpu.lower import synthetic_problem
from fleetflow_tpu.lower.tensors import ProblemTensors
from fleetflow_tpu.obs.metrics import REGISTRY
from fleetflow_tpu.sched import HostGreedyScheduler, TpuSolverScheduler
from fleetflow_tpu.sched import tpu as sched_tpu
from fleetflow_tpu.sched.base import (assemble_placement, assignment_names,
                                      level_schedule)


# --------------------------------------------------------------------------
# the loops as they stood before PR 38: the plain reference
# --------------------------------------------------------------------------

def _ref_assignment(pt: ProblemTensors, raw) -> dict[str, str]:
    return {pt.service_names[i]: pt.node_names[int(raw[i])]
            for i in range(pt.S)}


def _ref_level_schedule(pt: ProblemTensors) -> list[list[str]]:
    depth = np.asarray(pt.dep_depth)
    levels: list[list[str]] = []
    for d in range(int(depth.max()) + 1 if depth.size else 0):
        levels.append([pt.service_names[i] for i in np.flatnonzero(depth == d)])
    return levels


# --------------------------------------------------------------------------
# the stages
# --------------------------------------------------------------------------

def _stage(service_names, node_names, dep_depth) -> ProblemTensors:
    """A problem with the names and depths given and nothing else to say:
    the planes an assembly never reads are zero-stride views."""
    S, N = len(service_names), len(node_names)
    no_ids = np.full((S, 1), -1, np.int32)
    pt = ProblemTensors(
        service_names=list(service_names), node_names=list(node_names),
        demand=np.zeros((S, 3), np.float32),
        capacity=np.ones((N, 3), np.float32),
        dep_adj=np.broadcast_to(np.False_, (S, S)),
        dep_depth=np.asarray(dep_depth, np.int32),
        port_ids=no_ids, volume_ids=no_ids, anti_ids=no_ids,
        coloc_ids=no_ids,
        eligible=np.broadcast_to(np.True_, (S, N)),
        node_valid=np.ones(N, bool), node_topology=np.zeros(N, np.int32))
    pt.validate()
    return pt


def _random_stage():
    """nc's shape: 9,660 rows on 1,000 nodes, depth <= 5, names shaped like
    the generator's."""
    rng = np.random.default_rng(2_147_483_659)
    S, N = 9_660, 1_000
    pt = _stage([f"svc-{i:05d}" for i in range(S)],
                [f"node-{j:04d}" for j in range(N)],
                rng.integers(0, 6, S))
    return pt, rng.integers(0, N, S).astype(np.int32)


def _cases():
    nodes = ["tokyo-1", "tokyo-2", "osaka-1"]
    random_pt, random_raw = _random_stage()
    return {
        "empty": (_stage([], nodes, []), np.empty(0, np.int32)),
        "one-row": (_stage(["db"], nodes, [0]), np.array([2], np.int32)),
        # the dict keeps the last row of a repeated name; the schedule
        # lists the name once per row
        "repeated-name": (_stage(["db", "web", "db", "cache"], nodes,
                                 [0, 1, 1, 0]),
                          np.array([0, 1, 2, 1], np.int32)),
        # no row at depth 1 or 3: those levels are empty lists
        "missing-depth": (_stage(["a", "b", "c", "d"], nodes, [2, 0, 4, 2]),
                          np.array([1, 1, 0, 2], np.int32)),
        # a bucketed result: phantom rows after the stage's own
        "padded-raw": (_stage(["a", "b", "c"], nodes, [1, 0, 1]),
                       np.array([2, 0, 1, 0, 0, 0, 0, 0], np.int32)),
        "9660x1000": (random_pt, random_raw),
    }


CASES = _cases()


@pytest.fixture(params=list(CASES))
def case(request):
    return CASES[request.param]


class TestNameMap:
    def test_equals_the_loop_in_the_loops_order(self, case):
        pt, raw = case
        got, want = assignment_names(pt, raw), _ref_assignment(pt, raw)
        assert got == want
        assert list(got) == list(want)
        assert list(got.values()) == list(want.values())

    def test_is_a_plain_dict_of_str(self, case):
        pt, raw = case
        got = assignment_names(pt, raw)
        assert type(got) is dict
        assert all(type(k) is str and type(v) is str
                   for k, v in got.items())
        # the node names themselves, not copies: what the loop stored
        by_name = {n: n for n in pt.node_names}
        assert all(v is by_name[v] for v in got.values())

    def test_json_round_trip(self, case):
        pt, raw = case
        got = assignment_names(pt, raw)
        assert json.dumps(got) == json.dumps(_ref_assignment(pt, raw))
        assert json.loads(json.dumps(got)) == got

    def test_leaves_its_inputs_alone(self, case):
        pt, raw = case
        before = raw.copy()
        names, nodes = list(pt.service_names), list(pt.node_names)
        assignment_names(pt, raw)
        level_schedule(pt)
        assert np.array_equal(raw, before)
        assert pt.service_names == names and pt.node_names == nodes


class TestLevelSchedule:
    def test_equals_the_loop_list_for_list(self, case):
        pt, _ = case
        got, want = level_schedule(pt), _ref_level_schedule(pt)
        assert got == want
        assert type(got) is list
        assert all(type(lvl) is list for lvl in got)
        assert all(type(s) is str for lvl in got for s in lvl)


class TestDtypes:
    """Whatever holds whole numbers: the loops read rows through `int()`
    and depths through `==`, so they took any dtype; the passes still do."""

    @pytest.mark.parametrize("dtype", [np.int32, np.int64, np.uint8,
                                       np.float32, np.float64])
    def test_raw_of_any_dtype(self, dtype):
        for name in ("empty", "repeated-name", "padded-raw"):
            pt, raw = CASES[name]
            got = assignment_names(pt, raw.astype(dtype))
            assert got == _ref_assignment(pt, raw)
            assert list(got) == list(_ref_assignment(pt, raw))

    def test_an_empty_result_of_numpys_default_dtype(self):
        pt, _ = CASES["empty"]
        assert assignment_names(pt, np.array([])) == {}
        assert assignment_names(pt, []) == {}

    @pytest.mark.parametrize("dtype", [np.int64, np.uint8, np.float32,
                                       np.float64])
    def test_depths_of_any_dtype(self, dtype):
        for name in ("empty", "missing-depth", "9660x1000"):
            pt, _ = CASES[name]
            other = replace(pt, dep_depth=pt.dep_depth.astype(dtype))
            assert level_schedule(other) == _ref_level_schedule(pt)


class TestAssemblePlacement:
    """The host and native greedy backends' assembly is the same one."""

    def test_fields(self, case):
        pt, raw = case
        p = assemble_placement(pt, raw, 0, "host-greedy", 1.5)
        assert p.assignment == _ref_assignment(pt, raw)
        assert list(p.assignment) == list(_ref_assignment(pt, raw))
        assert p.levels == _ref_level_schedule(pt)
        assert p.raw is raw and p.feasible and p.source == "host-greedy"

    def test_host_greedy_backend(self):
        pt = synthetic_problem(120, 9, seed=7)
        p = HostGreedyScheduler().place(pt)
        assert p.assignment == _ref_assignment(pt, p.raw)
        assert p.levels == _ref_level_schedule(pt)

    def test_a_short_result_is_refused(self):
        pt, raw = CASES["missing-depth"]
        with pytest.raises(ValueError):
            assignment_names(pt, raw[:2])


# --------------------------------------------------------------------------
# the schedule kept with the stage (TpuSolverScheduler, on CPU)
# --------------------------------------------------------------------------

def _schedules() -> dict[str, float]:
    m = REGISTRY.get("fleet_sched_level_schedules_total")
    return {o: m.value(outcome=o) for o in ("kept", "built")}


def _since(before: dict[str, float]) -> dict[str, int]:
    return {o: int(v - before[o]) for o, v in _schedules().items()}


def _more_capacity(pt: ProblemTensors) -> ProblemTensors:
    """What `_refresh_capacity` hands the scheduler on a churn re-solve:
    new capacity, the very same graph and names."""
    return replace(pt, capacity=pt.capacity * 1.25)


@pytest.fixture
def finalize_phases(monkeypatch):
    """The `sched.finalize` phases the scheduler opens, as objects."""
    seen, real = [], sched_tpu.phase

    def recording_phase(name, /, **fields):
        ph = real(name, **fields)
        if name == "sched.finalize":
            seen.append(ph)
        return ph

    monkeypatch.setattr(sched_tpu, "phase", recording_phase)
    return seen


class TestScheduleKeptWithTheStage:
    @pytest.mark.parametrize("stage", [None, "flow/live"],
                             ids=["keyless", "keyed"])
    def test_a_churn_resolve_keeps_it(self, stage, finalize_phases):
        pt = synthetic_problem(60, 8, seed=11)
        sched = TpuSolverScheduler(chains=1, steps=64)
        before = _schedules()
        first = sched.reschedule(pt, stage=stage)
        second = sched.reschedule(_more_capacity(pt), stage=stage)
        assert _since(before) == {"built": 1, "kept": 1}
        assert [ph.fields["levels"] for ph in finalize_phases] == [
            "built", "kept"]
        assert first.levels == second.levels == level_schedule(pt)
        assert second.levels == _ref_level_schedule(pt)
        assert second.assignment == _ref_assignment(pt, second.raw)

    def test_a_new_graph_builds_it(self):
        pt = synthetic_problem(60, 8, seed=12)
        sched = TpuSolverScheduler(chains=1, steps=64)
        sched.place(pt, stage="s")
        before = _schedules()
        # an equal array that is another object: identity is the test
        moved = replace(pt, dep_depth=pt.dep_depth.copy())
        p = sched.reschedule(moved, stage="s")
        assert _since(before) == {"built": 1, "kept": 0}
        assert p.levels == level_schedule(pt)
        # and new names over the same depths
        renamed = replace(moved, service_names=list(moved.service_names))
        sched.reschedule(renamed, stage="s")
        sched.reschedule(_more_capacity(renamed), stage="s")
        assert _since(before) == {"built": 2, "kept": 1}

    def test_a_changed_graph_is_never_served_the_old_schedule(self):
        pt = synthetic_problem(60, 8, seed=13)
        sched = TpuSolverScheduler(chains=1, steps=64)
        sched.place(pt, stage="s")
        flat = replace(pt, dep_depth=np.zeros_like(pt.dep_depth))
        p = sched.reschedule(flat, stage="s")
        assert p.levels == [list(pt.service_names)]

    def test_the_shared_schedule_is_read_only(self):
        pt = synthetic_problem(60, 8, seed=14)
        sched = TpuSolverScheduler(chains=1, steps=64)
        first = sched.reschedule(pt, stage="s")
        second = sched.reschedule(_more_capacity(pt), stage="s")
        assert second.levels is first.levels      # one schedule, the stage's
        fresh = _ref_level_schedule(pt)
        for node in pt.node_names:
            mine = first.node_levels(node)
            assert all(mine), "node_levels drops the empty levels"
            first.services_on(node)
        assert first.levels == second.levels == fresh

    def test_an_evicted_stage_rebuilds(self):
        pts = {k: synthetic_problem(40, 6, seed=20 + i)
               for i, k in enumerate("AB")}
        # a budget no slot fits: every admission evicts the other stage
        sched = TpuSolverScheduler(chains=1, steps=64, resident_bytes=1)
        before = _schedules()
        for k in "ABA":
            p = sched.reschedule(pts[k], stage=k)
            assert p.levels == _ref_level_schedule(pts[k])
        assert _since(before) == {"built": 3, "kept": 0}

    def test_stages_in_turn_keep_each_stages_own(self, finalize_phases):
        pts = {k: synthetic_problem(40, 6, seed=30 + i)
               for i, k in enumerate("AB")}
        sched = TpuSolverScheduler(chains=1, steps=64)
        before = _schedules()
        for k in "AB":
            sched.reschedule(pts[k], stage=k)
        again = [sched.reschedule(_more_capacity(pts[k]), stage=k)
                 for k in "AB"]
        assert _since(before) == {"built": 2, "kept": 2}
        assert [ph.fields["levels"] for ph in finalize_phases] == [
            "built", "built", "kept", "kept"]
        for k, p in zip("AB", again):
            assert p.levels == _ref_level_schedule(pts[k])
            assert p.assignment == _ref_assignment(pts[k], p.raw)
        # one stage's graph changes: its phase says a schedule was built,
        # the other stage's is still kept
        sched.reschedule(replace(
            pts["B"], dep_depth=pts["B"].dep_depth.copy()), stage="B")
        sched.reschedule(_more_capacity(pts["A"]), stage="A")
        assert [ph.fields["levels"] for ph in finalize_phases[-2:]] == [
            "built", "kept"]
