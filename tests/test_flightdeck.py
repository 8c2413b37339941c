"""Solver flight deck (ISSUE 15): in-dispatch anneal telemetry.

The contract: telemetry is OBSERVATION ONLY. A telemetry-carrying warm
solve must produce a bit-identical assignment to the pre-telemetry
program (FLEET_SOLVE_TRACE_BLOCKS=0), compile nothing extra across a
warm burst loop, and run under the disallow transfer guard — the buffer
is a static-length output riding the existing fetch, never a feedback
path, never a host transfer, never a donation edge (the compile-contract
golden pins that last part; this file pins the behavior)."""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np
import pytest

from fleetflow_tpu.lower import synthetic_problem
from fleetflow_tpu.obs.metrics import REGISTRY
from fleetflow_tpu.solver import solve
from fleetflow_tpu.solver.anneal import TRACE_COLS, solve_trace_blocks
from fleetflow_tpu.solver.api import _refine, _solve
from fleetflow_tpu.solver.resident import ProblemDelta, ResidentProblem
from fleetflow_tpu.solver.subsolve import subsolve_cache_size

SOLVE_KW = dict(steps=16, anneal_block=1, warm_block=1, chains=1)


def _burst_loop(pt, seed, n_bursts=4, **kw):
    """Cold solve + n_bursts warm resident kill/revive bursts; returns
    the list of assignments and the last SolveResult."""
    rng = np.random.default_rng(seed)
    rp = ResidentProblem(pt)
    res = _solve(pt, prob=rp.prob, resident=rp, seed=seed, bucket=True,
                 **SOLVE_KW, **kw)
    outs = [res.assignment.copy()]
    cur = pt
    valid = pt.node_valid.copy()
    for burst in range(n_bursts):
        j = int(rng.integers(0, pt.N))
        valid = valid.copy()
        valid[j] = ~valid[j]
        if not valid.any():
            valid[j] = True
        cur = dataclasses.replace(cur, node_valid=valid)
        rp.apply_delta(cur, ProblemDelta(node_valid=valid))
        res = _solve(cur, prob=rp.prob, resident=rp, resident_warm=True,
                     seed=100 + burst, bucket=True, **SOLVE_KW, **kw)
        outs.append(res.assignment.copy())
    return outs, res


class TestTelemetryParity:
    """The parity pin the ISSUE names: telemetry on == telemetry off,
    bit for bit, with compiles pinned 0 under the disallow guard across
    a 4-burst loop."""

    def test_warm_burst_parity_zero_compiles_disallow(self, monkeypatch):
        monkeypatch.setenv("FLEET_TRANSFER_GUARD", "disallow")
        pt = synthetic_problem(120, 12, seed=11, port_fraction=0.25,
                               volume_fraction=0.15)

        monkeypatch.setenv("FLEET_SOLVE_TRACE_BLOCKS", "16")
        # warm-up burst pair compiles the telemetry-carrying executables;
        # the MEASURED loop below must then compile nothing
        _burst_loop(pt, seed=11, n_bursts=1)
        cache_before = _refine._cache_size() + subsolve_cache_size()
        with_telem, res_on = _burst_loop(pt, seed=11)
        assert _refine._cache_size() + subsolve_cache_size() \
            == cache_before, "telemetry-carrying warm loop recompiled"

        monkeypatch.setenv("FLEET_SOLVE_TRACE_BLOCKS", "0")
        without, res_off = _burst_loop(pt, seed=11)

        assert len(with_telem) == len(without) == 5
        for a, b in zip(with_telem, without):
            np.testing.assert_array_equal(a, b)
        assert res_on.telemetry is not None
        assert res_off.telemetry is None

    def test_subsolve_path_parity_and_telemetry(self, monkeypatch):
        """The localized dispatch carries the same buffer: parity holds
        through a burst the active-set path serves, and the payload says
        so. Churn shape mirrors tests/test_subsolve.py's parity property
        (kill the busiest node — the closure the planner localizes)."""
        monkeypatch.setenv("FLEET_SUBSOLVE_MIN", "16")
        monkeypatch.setenv("FLEET_SUBSOLVE_FRAC", "0.6")
        kw = dict(steps=32, anneal_block=1, warm_block=1, chains=1)

        def run():
            pt = synthetic_problem(140, 14, seed=0, port_fraction=0.25,
                                   volume_fraction=0.15)
            rp = ResidentProblem(pt)
            res = _solve(pt, prob=rp.prob, resident=rp, seed=0,
                         bucket=True, **kw)
            outs = [res.assignment.copy()]
            valid = pt.node_valid.copy()
            loads = np.bincount(res.assignment[: pt.S],
                                minlength=pt.N).astype(float)
            loads[~valid] = -1.0
            valid = valid.copy()
            valid[int(loads.argmax())] = False
            cur = dataclasses.replace(pt, node_valid=valid)
            rp.apply_delta(cur, ProblemDelta(node_valid=valid))
            res = _solve(cur, prob=rp.prob, resident=rp,
                         resident_warm=True, seed=50, bucket=True, **kw)
            outs.append(res.assignment.copy())
            return outs, res

        monkeypatch.setenv("FLEET_SOLVE_TRACE_BLOCKS", "16")
        on, res_on = run()
        monkeypatch.setenv("FLEET_SOLVE_TRACE_BLOCKS", "0")
        off, res_off = run()
        for a, b in zip(on, off):
            np.testing.assert_array_equal(a, b)
        assert res_on.subsolve is not None
        assert res_on.subsolve["outcome"] == "localized"
        assert res_on.telemetry["path"] == "subsolve"
        assert res_on.telemetry["subsolve"]["tier"] \
            == res_on.subsolve["tier"]
        assert res_off.telemetry is None
        assert res_off.subsolve is not None
        assert res_off.subsolve["outcome"] == "localized"


class TestTelemetryPayload:
    def test_cold_payload_shape(self):
        pt = synthetic_problem(60, 12, seed=0, port_fraction=0.3,
                               volume_fraction=0.2)
        res = solve(pt, steps=16)
        t = res.telemetry
        assert t is not None
        assert t["schema"] == list(TRACE_COLS)
        assert t["trace_blocks"] == solve_trace_blocks()
        assert isinstance(t["prerepair_moves"], int)
        assert t["exit_sweep"] == res.steps
        assert t["path"] == "full"
        assert set(t["init"]) == {"violations", "soft"}
        for row in t["blocks"]:
            assert len(row) == len(TRACE_COLS)
        if t["blocks"]:
            # cumulative sweep column is monotone; the last row's sweep
            # covers the exit sweep
            sweeps = [row[0] for row in t["blocks"]]
            assert sweeps == sorted(sweeps)
            assert sweeps[-1] >= res.steps

    @pytest.mark.parametrize("route", ["cold", "host-warm",
                                       "resident-fused",
                                       "resident-localized"])
    def test_every_route_counts_acceptance_and_names_its_path(
            self, route, monkeypatch):
        """Every single-chip route runs the one anneal loop: it counts
        the moves it accepted (never -1), the counter moves by exactly
        that count, and the payload names the dispatch that ran."""
        kw = dict(steps=32, anneal_block=1, warm_block=1, chains=1)
        pt = synthetic_problem(140, 14, seed=0, port_fraction=0.25,
                               volume_fraction=0.15)
        if route == "resident-localized":
            monkeypatch.setenv("FLEET_SUBSOLVE_MIN", "16")
            monkeypatch.setenv("FLEET_SUBSOLVE_FRAC", "0.6")
        else:
            monkeypatch.setenv("FLEET_SUBSOLVE", "0")
        rp = ResidentProblem(pt) if route.startswith("resident") else None
        cold = _solve(pt, prob=rp.prob if rp else None, resident=rp,
                      seed=0, bucket=True, **kw)
        if route == "cold":
            res, accepted_before = cold, None
        else:
            # kill the busiest node: real churn for the warm sweeps
            loads = np.bincount(cold.assignment, minlength=pt.N)
            valid = pt.node_valid.copy()
            valid[int(loads.argmax())] = False
            cur = dataclasses.replace(pt, node_valid=valid)
            accepted_before = REGISTRY.get(
                "fleet_solver_proposals_accepted_total").value()
            if rp is None:
                res = _solve(cur, init_assignment=cold.assignment, seed=1,
                             bucket=True, **kw)
            else:
                rp.apply_delta(cur, ProblemDelta(node_valid=valid))
                res = _solve(cur, prob=rp.prob, resident=rp,
                             resident_warm=True, seed=1, bucket=True, **kw)
        assert res.accepted_moves >= 0
        if res.steps == 0:
            assert res.accepted_moves == 0
        if accepted_before is not None:
            assert (REGISTRY.get("fleet_solver_proposals_accepted_total")
                    .value() == accepted_before + res.accepted_moves)
        t = res.telemetry
        assert t is not None and t["exit_sweep"] == res.steps
        if route == "resident-localized":
            assert res.subsolve["outcome"] == "localized"
            assert t["path"] == "subsolve"
        else:
            assert res.subsolve is None
            assert t["path"] == "full"

    def test_zero_sweep_exit_keeps_init_story(self, monkeypatch):
        """A 0-sweep feasible-prologue exit has no block rows — the
        payload's init/prerepair fields are the whole story and must
        still be present."""
        pt = synthetic_problem(100, 12, seed=5, port_fraction=0.2)
        rp = ResidentProblem(pt)
        _solve(pt, prob=rp.prob, resident=rp, seed=5, bucket=True,
               **SOLVE_KW)
        valid = pt.node_valid.copy()
        valid[0] = ~valid[0]
        cur = dataclasses.replace(pt, node_valid=valid)
        rp.apply_delta(cur, ProblemDelta(node_valid=valid))
        monkeypatch.setenv("FLEET_SUBSOLVE", "0")   # pin the fused path
        res = _solve(cur, prob=rp.prob, resident=rp,
                     resident_warm=True, seed=6, bucket=True,
                     **SOLVE_KW)
        t = res.telemetry
        assert t is not None
        if res.steps == 0:
            assert t["blocks"] == []
            assert t["init"]["violations"] == 0.0


class TestFlightRecorderIntegration:
    def test_solve_records_telemetry_event(self, tmp_path, monkeypatch):
        path = tmp_path / "flight.jsonl"
        monkeypatch.setenv("FLEET_TRACE_FILE", str(path))
        pt = synthetic_problem(60, 12, seed=2, port_fraction=0.3)
        solve(pt, steps=16)
        from fleetflow_tpu.obs.trace import read_trace_file
        events = [e for e in read_trace_file(str(path))
                  if e.get("kind") == "telemetry"
                  and e.get("name") == "solve.trace"]
        assert len(events) == 1
        f = events[0]["fields"]
        assert f["S"] == 60 and f["N"] == 12
        assert f["telemetry"]["schema"] == list(TRACE_COLS)
        # the payload round-trips through JSON (the CLI's food)
        json.dumps(events[0])

    def test_fleet_solve_trace_renders(self, tmp_path, monkeypatch,
                                       capsys):
        path = tmp_path / "flight.jsonl"
        monkeypatch.setenv("FLEET_TRACE_FILE", str(path))
        pt = synthetic_problem(60, 12, seed=2, port_fraction=0.3)
        solve(pt, steps=16)
        solve(pt, steps=16, seed=9)
        from fleetflow_tpu.cli.main import main
        assert main(["solve", "trace", "--last", "1"]) == 0
        out = capsys.readouterr().out
        assert "seed/prologue" in out
        assert out.count("solve ts=") == 1      # --last honored

    def test_fleet_solve_trace_no_file(self, monkeypatch, capsys):
        monkeypatch.delenv("FLEET_TRACE_FILE", raising=False)
        from fleetflow_tpu.cli.main import main
        assert main(["solve", "trace"]) == 2


class TestFlightRecorderRotation:
    """FLEET_TRACE_MAX_MB keep-1 rollover (the admission bench's
    unbounded-growth fix): spans survive the boundary."""

    def test_rollover_and_spanning_reader(self, tmp_path, monkeypatch):
        from fleetflow_tpu.obs.trace import (flight_recorder,
                                             read_trace_file,
                                             read_trace_files,
                                             record_span_event)
        path = tmp_path / "flight.jsonl"
        monkeypatch.setenv("FLEET_TRACE_FILE", str(path))

        def emit(kind, span_id):
            record_span_event(kind, "op", "fleetflow.test",
                              trace="t0000000000000000", span=span_id)

        # measure one line, then cap at 2.5 lines: events 1-2 fit, the
        # 3rd rotates — DETERMINISTICALLY between span B's begin and end
        emit("begin", "span-A00")
        line_len = os.path.getsize(path)
        flight_recorder().close()
        os.unlink(path)
        cap_mb = (2.5 * line_len) / (1024 * 1024)
        monkeypatch.setenv("FLEET_TRACE_MAX_MB", repr(cap_mb))
        emit("begin", "span-A00")     # line 1
        emit("begin", "span-B00")     # line 2 (fits: 2 <= 2.5)
        emit("end", "span-B00")       # line 3 would cross -> rotates
        rotated = str(path) + ".1"
        assert os.path.exists(rotated), "cap never rotated"
        # both generations are well-formed JSONL on their own
        old = read_trace_file(rotated)
        new = read_trace_file(str(path))
        assert [e["kind"] for e in old] == ["begin", "begin"]
        assert [e["kind"] for e in new] == ["end"]
        # the spanning reader stitches span B back together
        events = read_trace_files(str(path))
        b = [e for e in events if e["span"] == "span-B00"]
        assert [e["kind"] for e in b] == ["begin", "end"]
        flight_recorder().close()

    def test_unset_cap_never_rotates(self, tmp_path, monkeypatch):
        import logging

        from fleetflow_tpu.obs import span
        path = tmp_path / "flight.jsonl"
        monkeypatch.setenv("FLEET_TRACE_FILE", str(path))
        monkeypatch.delenv("FLEET_TRACE_MAX_MB", raising=False)
        log = logging.getLogger("fleetflow.test")
        for i in range(50):
            with span(log, "op"):
                pass
        assert not os.path.exists(str(path) + ".1")
