"""The readers over the program's own span ring (`readers/program_span.py`,
`readers/program_coverage.py`) on a synthetic ring: overlap across
threads, `minus`, a window the ring dropped spans from, and a program
that has no ring."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.readers import program_coverage, program_span   # noqa: E402
from benchmarks.spans import Spans                              # noqa: E402
from fleetflow_tpu.obs import trace as obs_trace                # noqa: E402

MAIN, WORKER = 1, 2


class Run:
    """What a reader is given, as far as these two read it."""

    def __init__(self, ops, solves=None):
        self.spans = Spans()
        self.spans.events = ([("op", t0, t1) for t0, t1 in ops]
                             + [("sched", 0.0, 0.0)] * (solves or 0))
        self.ops = len(ops)

    def count(self, what):
        return {"ops": self.ops,
                "solves": self.spans.total("sched")[1]}[what]


@pytest.fixture
def ring(monkeypatch):
    ring = obs_trace.SpanRing(capacity=16)
    monkeypatch.setattr(obs_trace, "RING", ring)
    return ring


def fill(ring):
    """Two ops, [10, 11] and [12, 13]. The handler runs on a worker thread
    and overlaps the event loop's codec phases on the main one."""
    for name, t0, t1, tid in [
            ("protocol.encode", 10.00, 10.05, MAIN),
            ("sched.place", 10.30, 10.50, WORKER),
            ("cp.handler", 10.10, 10.80, WORKER),
            ("protocol.decode", 10.70, 10.90, MAIN),    # overlaps handler
            ("cp.commit_retained", 10.90, 11.00, MAIN),
            ("cp.handler", 12.20, 12.60, WORKER),
            ("late", 12.90, 13.50, MAIN),               # ends after window
            ("early", 9.50, 10.20, MAIN)]:              # starts before it
        ring.append(name, t0, t1, tid)
    return Run([(10.0, 11.0), (12.0, 13.0)], solves=1)


def test_sum_and_minus_per_op(ring):
    run = fill(ring)
    both = {"spans": ["cp.handler", "cp.commit_retained"], "per": "ops"}
    assert program_span.read(both, run) == pytest.approx(
        (0.70 + 0.40 + 0.10) * 1e3 / 2)
    self_time = dict(both, minus=["sched.place", "agents.send_batch"])
    assert program_span.read(self_time, run) == pytest.approx(
        (1.20 - 0.20) * 1e3 / 2)


def test_per_solve_and_default_divisor(ring):
    run = fill(ring)
    assert program_span.read({"spans": ["sched.place"], "per": "solves"},
                             run) == pytest.approx(200.0)
    assert program_span.read({"spans": ["sched.place"]},
                             run) == pytest.approx(100.0)


def test_spans_that_never_opened_give_nothing(ring):
    run = fill(ring)
    assert program_span.read({"spans": ["cp.node_events.hold"]}, run) is None
    # spans cut by the window's edges are not in it
    assert program_span.read({"spans": ["late", "early"]}, run) is None
    assert program_span.read({"spans": ["cp.handler"]}, Run([])) is None


def test_coverage_unions_threads_and_clips_to_ops(ring):
    run = fill(ring)
    # op 1: [10.00, 10.05] + [10.10, 11.00] = 0.95; op 2: [12.2, 12.6] = 0.4
    assert program_coverage.read({}, run) == pytest.approx(
        100.0 * (0.95 + 0.40) / 2.0)
    ring.append("cp.handler", 10.0, 13.0, WORKER)   # spans the gap too
    assert program_coverage.read({}, run) == pytest.approx(100.0)


@pytest.mark.parametrize("reader, params", [
    (program_span, {"spans": ["cp.handler"]}), (program_coverage, {})])
def test_a_dropped_window_raises(reader, params, monkeypatch):
    ring = obs_trace.SpanRing(capacity=4)
    monkeypatch.setattr(obs_trace, "RING", ring)
    for i in range(6):      # the two oldest, which ended at 10.1 and 10.2, go
        ring.append("cp.handler", 10.0 + i / 10, 10.1 + i / 10, MAIN)
    with pytest.raises(obs_trace.SpansDropped):
        reader.read(params, Run([(10.0, 11.0)]))
    # a window that starts after everything that was dropped is whole
    assert reader.read(params, Run([(10.2, 11.0)])) is not None


@pytest.mark.parametrize("reader, params", [
    (program_span, {"spans": ["cp.handler"]}), (program_coverage, {})])
def test_a_program_without_a_ring_gives_nothing(reader, params, monkeypatch):
    """The parent commit of the PR that brought the ring: the benchmark's
    files are laid over it, and the reader must say nothing, not raise."""
    stub = types.ModuleType("fleetflow_tpu.obs.trace")
    monkeypatch.setitem(sys.modules, "fleetflow_tpu.obs.trace", stub)
    assert reader.read(params, Run([(10.0, 11.0)])) is None


def test_every_program_metric_names_a_reader_and_its_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    seen = 0
    for entry in bench["per_layer"]:
        path = os.path.join(ROOT, "benchmarks", "layer_metrics",
                            entry["name"] + ".json")
        with open(path, encoding="utf-8") as f:
            spec = json.load(f)
        if spec["reader"] not in ("program_span", "program_coverage"):
            continue
        seen += 1
        assert entry["source"] == "program_span"
        assert set(entry["workloads"]) <= cells and entry["workloads"]
        assert (spec["unit"], spec["layer"], spec["moves"]) == (
            entry["unit"], entry["layer"], entry["moves"])
        if spec["reader"] == "program_span":
            assert spec["params"]["spans"]
            assert spec["params"].get("per", "ops") in ("ops", "solves")
    assert seen >= 9
