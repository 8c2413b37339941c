"""obs.phase (obs/trace.Phase): the one timing primitive on the served path.

What is pinned here:

  * a phase lands in the in-memory ring as (name, t0, t1, thread id) on
    time.perf_counter(), nested phases nest, and self time is a
    subtraction of what the ring holds
  * the ring is bounded; overwrites are counted and a window that lost
    spans refuses to be read
  * a phase opened on a run_in_executor thread carries that thread's id
  * an exception closes the phase (and is not swallowed)
  * a phase inside obs.span lands in the flight recorder under that span
  * phases reach the profiler's trace as fleet/<name>
  * SolveResult.timings_ms, which is filled from the phases, keeps every
    key it had before them on the cold, warm resident and sub-solve paths
  * fleet_store_rows_scanned_total counts what a lookup examined
"""

from __future__ import annotations

import asyncio
import dataclasses
import glob
import os
import threading
import time

import numpy as np
import pytest

from fleetflow_tpu import obs
from fleetflow_tpu.obs import trace as obs_trace
from fleetflow_tpu.obs.metrics import REGISTRY


def _mine(spans, prefix):
    return [s for s in spans if s[0].startswith(prefix)]


# --------------------------------------------------------------------------
# the ring
# --------------------------------------------------------------------------

class TestRing:
    @pytest.mark.parametrize("depth", [1, 2, 3])
    def test_nesting_and_self_time(self, depth):
        names = [f"t.nest{depth}.l{i}" for i in range(depth)]
        t_before = time.perf_counter()

        def descend(i):
            with obs.phase(names[i], level=i) as ph:
                time.sleep(0.002)
                if i + 1 < depth:
                    descend(i + 1)
            return ph

        outer = descend(0)
        spans = _mine(obs_trace.spans_between(t_before, time.perf_counter()),
                      f"t.nest{depth}.")
        # children end first, so the ring holds them innermost first
        assert [s[0] for s in spans] == names[::-1]
        by_name = {s[0]: s for s in spans}
        for parent, child in zip(names, names[1:]):
            assert by_name[parent][1] <= by_name[child][1]
            assert by_name[child][2] <= by_name[parent][2]
        assert {s[3] for s in spans} == {threading.get_ident()}
        # self time: a level's wall time less the level inside it
        dur = {n: by_name[n][2] - by_name[n][1] for n in names}
        for parent, child in zip(names, names[1:]):
            assert dur[parent] - dur[child] >= 0.002
        assert outer.ms == pytest.approx(dur[names[0]] * 1e3)
        assert outer.ms >= 2.0 * depth

    @pytest.mark.parametrize("extra", [1, 5])
    def test_ring_is_bounded_and_counts_what_it_drops(self, extra):
        ring = obs_trace.SpanRing(capacity=8)
        dropped = REGISTRY.get("fleet_obs_spans_dropped_total")
        before = dropped.value()
        for i in range(8 + extra):
            ring.append("t.bounded", float(i), i + 0.5, 1)
        assert dropped.value() - before == extra
        kept = ring.between(float(extra), 1e9)
        assert len(kept) == 8 and kept[0][1] == float(extra)
        # a window that reaches back into what was overwritten is refused:
        # a sum over it would be short without saying so
        with pytest.raises(obs_trace.SpansDropped):
            ring.between(0.0, 1e9)

    def test_the_process_ring_holds_a_traced_window(self):
        assert obs_trace.RING_CAPACITY >= 2 ** 17
        assert obs_trace.RING._spans.maxlen == obs_trace.RING_CAPACITY

    def test_between_takes_whole_spans_only(self):
        ring = obs_trace.SpanRing(capacity=8)
        ring.append("a", 1.0, 2.0, 1)
        ring.append("b", 1.5, 3.5, 1)     # ends after the window
        ring.append("c", 0.5, 1.2, 1)     # starts before it
        assert [s[0] for s in ring.between(1.0, 3.0)] == ["a"]

    def test_executor_thread_is_recorded_with_its_id(self):
        seen = {}

        def work():
            seen["tid"] = threading.get_ident()
            with obs.phase("t.executor"):
                time.sleep(0.001)

        async def go():
            with obs.phase("t.loop"):
                await asyncio.get_running_loop().run_in_executor(None, work)

        t0 = time.perf_counter()
        asyncio.run(go())
        spans = {s[0]: s for s in _mine(
            obs_trace.spans_between(t0, time.perf_counter()), "t.")}
        assert spans["t.executor"][3] == seen["tid"]
        assert spans["t.loop"][3] == threading.get_ident()
        assert seen["tid"] != threading.get_ident()
        assert spans["t.loop"][1] <= spans["t.executor"][1]
        assert spans["t.executor"][2] <= spans["t.loop"][2]

    @pytest.mark.parametrize("how", ["phase", "span"])
    def test_an_exception_closes_the_phase(self, how):
        log = obs.get_logger("test.phase")
        hist = REGISTRY.get("fleet_phase_ms")
        name = f"t.raises.{how}"
        count0 = hist.count(phase=name)
        t0 = time.perf_counter()
        with pytest.raises(KeyError):
            if how == "phase":
                with obs.phase(name):
                    raise KeyError("boom")
            else:
                with obs.span(log, name):
                    raise KeyError("boom")
        spans = _mine(obs_trace.spans_between(t0, time.perf_counter()), name)
        assert len(spans) == 1 and spans[0][2] >= spans[0][1]
        assert hist.count(phase=name) == count0 + 1

    def test_fields_set_in_the_body_are_kept(self):
        with obs.phase("t.fields", table="servers") as ph:
            ph.set(bytes=42)
        assert ph.fields == {"table": "servers", "bytes": 42}


# --------------------------------------------------------------------------
# one emit path: obs.span is a phase, and the flight recorder sees both
# --------------------------------------------------------------------------

class TestFlightRecorder:
    @pytest.mark.parametrize("fails", [False, True])
    def test_phase_inside_span_has_the_span_as_parent(self, tmp_path,
                                                      monkeypatch, fails):
        path = tmp_path / "trace.jsonl"
        monkeypatch.setenv("FLEET_TRACE_FILE", str(path))
        log = obs.get_logger("test.phase")
        try:
            with obs.span(log, "t.outer", stage="s") as sp:
                sp["placed"] = 3
                with obs.phase("t.inner", records=7):
                    if fails:
                        raise ValueError("nope")
        except ValueError:
            assert fails
        events = obs_trace.read_trace_file(str(path))
        begin = next(e for e in events if e["kind"] == "begin")
        outer = next(e for e in events
                     if e["name"] == "t.outer" and e["kind"] != "begin")
        inner = next(e for e in events if e["name"] == "t.inner")
        kind = "fail" if fails else "end"
        assert inner["kind"] == kind and outer["kind"] == kind
        # the phase has no id of its own; its parent is the span
        assert inner["parent"] == outer["span"] == begin["span"]
        assert inner["trace"] == outer["trace"]
        assert inner["span"] == "" and inner["fields"] == {"records": 7}
        assert inner["duration_ms"] <= outer["duration_ms"]
        assert outer["fields"] == {"stage": "s", "placed": 3}
        assert ("error" in inner) == fails

    def test_span_and_phase_share_one_emit_path(self):
        log = obs.get_logger("test.phase")
        hist = REGISTRY.get("fleet_phase_ms")
        c0 = hist.count(phase="t.one_path")
        t0 = time.perf_counter()
        with obs.span(log, "t.one_path"):
            pass
        with obs.phase("t.one_path"):
            pass
        spans = _mine(obs_trace.spans_between(t0, time.perf_counter()),
                      "t.one_path")
        assert len(spans) == 2
        assert hist.count(phase="t.one_path") == c0 + 2
        assert obs.phase is obs_trace.Phase

    def test_a_phase_outside_any_span_is_not_recorded(self, tmp_path,
                                                      monkeypatch):
        path = tmp_path / "trace.jsonl"
        monkeypatch.setenv("FLEET_TRACE_FILE", str(path))
        t0 = time.perf_counter()
        with obs.phase("t.orphan"):
            pass
        assert not path.exists()       # nothing to hang it on
        assert _mine(obs_trace.spans_between(t0, time.perf_counter()),
                     "t.orphan")       # the ring has it all the same

    def test_no_recorder_no_file(self, tmp_path, monkeypatch):
        monkeypatch.delenv("FLEET_TRACE_FILE", raising=False)
        with obs.phase("t.quiet"):
            pass
        assert list(tmp_path.iterdir()) == []


# --------------------------------------------------------------------------
# the served path: solver timings, the profiler's trace, the store counters
# --------------------------------------------------------------------------

SOLVE_KW = dict(steps=32, anneal_block=1, warm_block=1, chains=1)
# what _solve reported before its t() pairs became phases
BASE_KEYS = {"stage_ms", "seed_ms", "anneal_ms", "verify_repair_ms",
             "total_ms"}
WANTED = {
    "cold": (BASE_KEYS, {"solver.stage", "solver.seed", "solver.anneal",
                         "solver.dispatch.refine", "solver.fetch",
                         "solver.verify_repair"}),
    "warm_resident": (BASE_KEYS | {"delta_stage_ms"},
                      {"solver.stage", "solver.seed",
                       "solver.dispatch.refine", "solver.fetch",
                       "solver.verify_repair"}),
    "subsolve": (BASE_KEYS | {"delta_stage_ms", "subsolve_ms"},
                 {"solver.subsolve", "solver.dispatch.subsolve",
                  "solver.fetch", "solver.verify_repair"}),
}


@pytest.fixture(scope="module")
def solves():
    """One cold solve and two warm ones after a node kill each: the
    resident full fused path and the localized sub-solve. Per path:
    (SolveResult, the ring's spans over that solve)."""
    from fleetflow_tpu.lower import synthetic_problem
    from fleetflow_tpu.solver import solve
    from fleetflow_tpu.solver.resident import ProblemDelta, ResidentProblem

    mp = pytest.MonkeyPatch()
    mp.setenv("FLEET_SUBSOLVE_MIN", "16")
    mp.setenv("FLEET_SUBSOLVE_FRAC", "0.6")
    out = {}

    def ringed(name, fn):
        t0 = time.perf_counter()
        res = fn()
        out[name] = (res, obs_trace.spans_between(t0, time.perf_counter()))
        return res

    try:
        pt = synthetic_problem(140, 14, seed=7, port_fraction=0.25)
        rp = ResidentProblem(pt)
        res = ringed("cold", lambda: solve(
            pt, prob=rp.prob, resident=rp, seed=7, bucket=True, **SOLVE_KW))
        valid = pt.node_valid.copy()
        for step, (name, env) in enumerate([
                ("warm_resident", {"FLEET_SUBSOLVE": "0"}),
                ("subsolve", {})]):
            loads = np.bincount(res.assignment[: pt.S],
                                minlength=pt.N).astype(float)
            loads[~valid] = -1.0
            valid = valid.copy()
            valid[int(loads.argmax())] = False
            cur = dataclasses.replace(pt, node_valid=valid)
            rp.apply_delta(cur, ProblemDelta(node_valid=valid))
            with mp.context() as m:
                for k, v in env.items():
                    m.setenv(k, v)
                res = ringed(name, lambda: solve(
                    cur, prob=rp.prob, resident=rp, resident_warm=True,
                    seed=70 + step, bucket=True, **SOLVE_KW))
            pt = cur
        yield out
    finally:
        mp.undo()


class TestSolverTimings:
    @pytest.mark.parametrize("path", sorted(WANTED))
    def test_timings_keep_their_keys_and_come_from_phases(self, solves,
                                                          path):
        res, spans = solves[path]
        keys, phases = WANTED[path]
        assert res.feasible
        assert keys <= set(res.timings_ms), (
            f"{path}: timings_ms lost {keys - set(res.timings_ms)}")
        names = {s[0] for s in spans}
        assert phases <= names, f"{path}: no phase {phases - names}"
        if path == "subsolve":
            assert res.subsolve["outcome"] == "localized"
        else:
            assert res.subsolve is None
        # the numbers are the phases' own: stage_ms is solver.stage
        stage = next(s for s in spans if s[0] == "solver.stage")
        assert res.timings_ms["stage_ms"] == pytest.approx(
            (stage[2] - stage[1]) * 1e3)
        parts = sum(res.timings_ms[k] for k in
                    ("stage_ms", "seed_ms", "anneal_ms", "verify_repair_ms"))
        assert parts <= res.timings_ms["total_ms"] + 1e-6
        assert parts >= 0.8 * res.timings_ms["total_ms"]

    def test_fused_prerepair_reports_no_host_prerepair(self, solves):
        assert "prerepair_ms" not in solves["warm_resident"][0].timings_ms


def _cp(n_servers=3):
    from fleetflow_tpu.core.parser import parse_kdl_string
    from fleetflow_tpu.cp.models import Server, ServerCapacity
    from fleetflow_tpu.cp.placement import PlacementService
    from fleetflow_tpu.cp.store import Store

    store = Store()
    slugs = [f"n{i}" for i in range(n_servers)]
    for slug in slugs:
        store.create("servers", Server(
            slug=slug, status="online", tenant="default",
            capacity=ServerCapacity(cpu=4, memory=8192, disk=99999)))
    servers = "\n".join(
        f'server "{s}" {{ capacity {{ cpu 4; memory 8192; disk 99999 }} }}'
        for s in slugs)
    flow = parse_kdl_string(f"""
project "p"
{servers}
service "a0" {{ image "x"; resources {{ cpu 1; memory 64; disk 1 }} }}
service "a1" {{ image "x"; resources {{ cpu 1; memory 64; disk 1 }} }}
stage "live" {{
    service "a0"
    service "a1"
    servers {" ".join(f'"{s}"' for s in slugs)}
}}
""")
    return store, PlacementService(store, use_tpu=True), flow


class TestServedPath:
    def test_profiler_trace_holds_the_programs_phases(self, tmp_path):
        """A short jax.profiler trace on the CPU: the phases are on the
        profiler's own timeline, as fleet/<name>."""
        import jax
        from jax.profiler import ProfileData

        store, svc, flow = _cp()
        placement, rid = svc.solve_stage(flow, "live")   # compiles outside
        assert placement.feasible and svc.commit(rid)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            placement, rid = svc.solve_stage(flow, "live")
            assert placement.feasible and svc.commit(rid)
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(
            str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
        assert files
        names = {e.name.split("#")[0]
                 for plane in ProfileData.from_file(files[-1]).planes
                 if plane.name == "/host:CPU"
                 for line in plane.lines for e in line.events
                 if e.name.startswith(obs_trace.PROFILER_PREFIX)}
        assert {"fleet/solver.fetch", "fleet/cp.commit.persist",
                "fleet/cp.solve_stage", "fleet/sched.place",
                "fleet/sched.finalize",
                "fleet/cp.commit.apply_allocation"} <= names

    def test_commit_and_churn_phases_nest_in_the_ring(self):
        store, svc, flow = _cp()
        t0 = time.perf_counter()
        placement, rid = svc.solve_stage(flow, "live")
        assert svc.commit(rid)
        victim = placement.assignment["a0"]
        moved = dict(svc.node_events([(victim, False)]))
        assert moved["p/live"].feasible
        assert svc.commit_retained("p/live")
        spans = obs_trace.spans_between(t0, time.perf_counter())
        first = {}
        for s in spans:
            first.setdefault(s[0], s)

        def inside(child, parent):
            return (first[parent][1] <= first[child][1]
                    and first[child][2] <= first[parent][2])

        for child, parent in [
                ("cp.solve_stage.inventory", "cp.solve_stage"),
                ("cp.solve_stage.lower", "cp.solve_stage"),
                ("cp.solve_stage.solve", "cp.solve_stage"),
                ("cp.solve_stage.reserve", "cp.solve_stage"),
                ("sched.place", "cp.solve_stage.solve"),
                ("sched.stage", "sched.place"),
                ("sched.solve", "sched.place"),
                ("sched.finalize", "sched.place"),
                ("solver.fetch", "sched.solve"),
                ("cp.commit.apply_allocation", "cp.commit"),
                ("cp.commit.persist", "cp.commit"),
                ("cp.node_events.mark", "cp.node_events"),
                ("cp.node_events.refresh_capacity", "cp.node_events"),
                ("cp.node_events.solve", "cp.node_events"),
                ("cp.node_events.hold", "cp.node_events")]:
            assert inside(child, parent), (child, parent)
        retained = first["cp.commit_retained"]
        under = {s[0] for s in spans
                 if retained[1] <= s[1] and s[2] <= retained[2]}
        assert {"cp.commit.demand", "cp.commit.apply_allocation",
                "cp.commit.persist"} <= under

    @pytest.mark.parametrize("lookup", ["find_one_miss", "find_one_hit",
                                        "list_where", "list_all",
                                        "indexed_miss", "indexed_hit"])
    def test_rows_scanned_counts_what_a_lookup_examined(self, lookup):
        store, _svc, _flow = _cp(n_servers=5)
        for s in store.list("servers"):
            store.update("servers", s.id, hostname=f"h-{s.slug}")
        scanned = REGISTRY.get("fleet_store_rows_scanned_total")
        before = scanned.value(table="servers")
        if lookup == "find_one_miss":
            assert store.find_one(
                "servers", lambda s: s.hostname == "nope") is None
            want = 5                      # the whole table, for nothing
        elif lookup == "find_one_hit":
            assert store.find_one(
                "servers", lambda s: s.hostname == "h-n1").slug == "n1"
            want = 2                      # up to its hit
        elif lookup == "list_where":
            assert len(store.list("servers",
                                  where=lambda s: s.slug == "n4")) == 1
            want = 5
        elif lookup == "list_all":
            assert len(store.list("servers")) == 5
            want = 0                      # no predicate, nothing examined
        elif lookup == "indexed_miss":
            assert store.server_by_slug("nope") is None
            want = 0                      # the index says so, no row read
        else:
            assert store.server_by_slug("n1").slug == "n1"
            want = 1                      # the one row
        assert scanned.value(table="servers") - before == want

    @pytest.mark.parametrize("journaled", [False, True])
    def test_journal_bytes_stay_zero_in_memory(self, tmp_path, journaled):
        from fleetflow_tpu.cp.models import Server
        from fleetflow_tpu.cp.store import Store

        written = REGISTRY.get("fleet_store_journal_bytes_total")
        before = written.value()
        store = Store(str(tmp_path / "db") if journaled else None)
        rec = store.create("servers", Server(slug="n0", tenant="default"))
        store.update("servers", rec.id, status="offline")
        grew = written.value() - before
        if journaled:
            journal = next(p for p in tmp_path.rglob("*")
                           if p.is_file() and p.stat().st_size)
            # the two entries as serialized, less the newline of each
            assert grew == journal.stat().st_size - 2 > 0
        else:
            assert grew == 0
