"""The reader over the program's span tree (`readers/span_tree.py`) on a
synthetic ring — children on another thread, overlapping children, a child
that outlives its parent, a wait recorded before its parent opened, a
program that has no tree, phases that never opened — and the metrics that
read it, present in a traced rehearsal of the cells that list them."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.readers import span_tree                        # noqa: E402
from benchmarks.spans import Spans                              # noqa: E402
from benchmarks.tests.test_rehearsal import BENCH, run_cell     # noqa: E402
from fleetflow_tpu.obs import trace as obs_trace                # noqa: E402

LOOP, POOL = 1, 2
NEW = ["handler_self_ms_per_op", "solve_stage_self_ms_per_op",
       "commit_self_ms_per_op", "node_events_self_ms_per_op",
       "sched_self_ms_per_solve", "solver_stage_ms_per_solve",
       "solver_seed_ms_per_solve", "solver_dispatch_ms_per_solve",
       "solver_anneal_self_ms_per_solve", "verify_repair_ms_per_solve",
       "protocol_loop_ms_per_op", "request_wait_ms_per_op",
       "gc_pause_ms_per_op", "gc_full_collections_per_op"]


class Run:
    """What the reader is given, as far as it reads it."""

    def __init__(self, ops, solves=0):
        self.spans = Spans()
        self.spans.events = ([("op", t0, t1) for t0, t1 in ops]
                             + [("sched", 0.0, 0.0)] * solves)
        self.ops = len(ops)

    def count(self, what):
        return {"ops": self.ops,
                "solves": self.spans.total("sched")[1]}[what]


@pytest.fixture
def ring(monkeypatch):
    ring = obs_trace.SpanRing(capacity=64)
    monkeypatch.setattr(obs_trace, "RING", ring)
    return ring


def fill(ring):
    """Two ops, [10, 11] and [12, 13]. Op 1: the handler on the loop, its
    solve in the pool; op 2: a handler with nothing under it."""
    for name, t0, t1, tid, pid, parent in [
            # op 1 — ids 1..: request > serve > handler > ...
            ("protocol.wait.dispatch", 10.02, 10.05, LOOP, 3, 1),
            ("cp.wait.executor", 10.20, 10.22, POOL, 6, 5),
            ("cp.wait.placement_lock", 10.22, 10.30, POOL, 8, 7),
            ("cp.solve_stage.lower", 10.30, 10.40, POOL, 9, 7),
            ("sched.place", 10.45, 10.60, POOL, 11, 10),
            ("cp.solve_stage.solve", 10.40, 10.62, POOL, 10, 7),
            ("cp.solve_stage", 10.22, 10.70, POOL, 7, 5),
            # a child of the handler that runs past its end: clipped
            ("agents.send_batch", 10.75, 10.95, LOOP, 12, 5),
            ("cp.handler", 10.10, 10.80, LOOP, 5, 4),
            ("protocol.encode", 10.80, 10.84, LOOP, 13, 4),
            ("protocol.serve", 10.06, 10.90, LOOP, 4, 1),
            ("protocol.request", 10.00, 10.98, LOOP, 1, 0),
            # op 2
            ("cp.handler", 12.20, 12.60, LOOP, 21, 20),
            ("protocol.serve", 12.10, 12.70, LOOP, 20, 0),
            # outside the window
            ("cp.handler", 9.50, 10.20, LOOP, 30, 0)]:
        ring.append(name, t0, t1, tid, pid, parent, "t")
    return Run([(10.0, 11.0), (12.0, 13.0)], solves=1)


def test_self_time_with_children_on_another_thread(ring):
    run = fill(ring)
    # op 1's handler: 0.70 less the pool's wait [10.20, 10.22], the solve
    # stage [10.22, 10.70] and the fan-out clipped to [10.75, 10.80];
    # op 2's: 0.40, nothing under it
    assert span_tree.read({"spans": ["cp.handler"]}, run) == pytest.approx(
        ((0.70 - 0.02 - 0.48 - 0.05) + 0.40) * 1e3 / 2)


def test_a_child_that_outlives_its_parent_is_clipped(ring):
    ring.append("child", 10.5, 11.5, POOL, 2, 1, "t")
    ring.append("parent", 10.2, 10.8, LOOP, 1, 0, "t")
    run = Run([(10.0, 12.0)])
    assert span_tree.read({"spans": ["parent"]}, run) == pytest.approx(
        (0.6 - 0.3) * 1e3)
    # and one that began before it (a wait written on the parent's behalf)
    ring.append("early", 10.0, 10.3, POOL, 3, 1, "t")
    assert span_tree.read({"spans": ["parent"]}, run) == pytest.approx(
        (0.6 - 0.3 - 0.1) * 1e3)
    # a child wholly outside covers nothing
    ring.append("before", 10.0, 10.1, POOL, 4, 1, "t")
    assert span_tree.read({"spans": ["parent"]}, run) == pytest.approx(
        200.0)


def test_overlapping_children_are_counted_once(ring):
    for name, t0, t1, tid, pid, parent in [
            ("a", 10.1, 10.5, POOL, 2, 1), ("b", 10.3, 10.7, LOOP, 3, 1),
            ("c", 10.35, 10.4, POOL, 4, 1),     # inside both
            ("grandchild", 10.8, 10.9, POOL, 5, 2),   # not a direct child
            ("parent", 10.0, 11.0, LOOP, 1, 0)]:
        ring.append(name, t0, t1, tid, pid, parent, "t")
    run = Run([(10.0, 11.0)])
    assert span_tree.read({"spans": ["parent"]}, run) == pytest.approx(
        (1.0 - 0.6) * 1e3)
    assert span_tree.read({"spans": ["parent"], "part": "total"},
                          run) == pytest.approx(1000.0)


def test_phases_named_together_count_a_nested_one_once(ring):
    run = fill(ring)
    # cp.solve_stage: 0.48 less the lock wait 0.08, lower 0.10, solve 0.22;
    # cp.solve_stage.solve: 0.22 less sched.place 0.15
    both = {"spans": ["cp.solve_stage", "cp.solve_stage.solve"]}
    assert span_tree.read(both, run) == pytest.approx(
        ((0.48 - 0.08 - 0.10 - 0.22) + (0.22 - 0.15)) * 1e3 / 2)
    assert span_tree.read(dict(both, per="solves"), run) == pytest.approx(
        (0.08 + 0.07) * 1e3)


def test_totals_of_the_waits_and_the_protocols_self_time(ring):
    run = fill(ring)
    waits = {"spans": ["protocol.wait.dispatch", "cp.wait.executor",
                       "cp.wait.placement_lock"], "part": "total"}
    assert span_tree.read(waits, run) == pytest.approx(
        (0.03 + 0.02 + 0.08) * 1e3 / 2)
    # request: 0.98 less the dispatch wait and serve; serve: 0.84 less the
    # handler and the encode; op 2's serve: 0.60 less its handler
    loop = {"spans": ["protocol.request", "protocol.serve"]}
    assert span_tree.read(loop, run) == pytest.approx(
        ((0.98 - 0.03 - 0.84) + (0.84 - 0.70 - 0.04) + (0.60 - 0.40))
        * 1e3 / 2)


def test_phases_that_never_opened_give_nothing(ring):
    run = fill(ring)
    assert span_tree.read({"spans": ["cp.node_events"]}, run) is None
    assert span_tree.read({"spans": ["cp.handler"]}, Run([])) is None
    # per solve, and no solve ran
    assert span_tree.read({"spans": ["cp.handler"], "per": "solves"},
                          Run([(10.0, 11.0)])) is None


def test_a_program_without_a_tree_gives_nothing(monkeypatch):
    """The parent commit of the PR that brought the tree: its trace module
    has a ring and no `tree_between`; the reader says nothing, not 0."""
    stub = types.ModuleType("fleetflow_tpu.obs.trace")
    stub.spans_between = lambda t0, t1: [("cp.handler", 10.1, 10.8, LOOP)]
    monkeypatch.setitem(sys.modules, "fleetflow_tpu.obs.trace", stub)
    assert span_tree.read({"spans": ["cp.handler"]},
                          Run([(10.0, 11.0)])) is None


def test_a_dropped_window_raises(monkeypatch):
    ring = obs_trace.SpanRing(capacity=4)
    monkeypatch.setattr(obs_trace, "RING", ring)
    for i in range(6):
        ring.append("cp.handler", 10.0 + i / 10, 10.1 + i / 10, LOOP,
                    i + 1, 0, "t")
    with pytest.raises(obs_trace.SpansDropped):
        span_tree.read({"spans": ["cp.handler"]}, Run([(10.0, 11.0)]))
    assert span_tree.read({"spans": ["cp.handler"]},
                          Run([(10.2, 11.0)])) is not None


@pytest.mark.parametrize("metric", NEW)
def test_a_new_metric_is_a_file_and_an_appended_entry(metric):
    entries = [m["name"] for m in BENCH["per_layer"]]
    # appended, in this order, after everything that was there
    assert entries[-len(NEW):] == NEW
    entry = BENCH["per_layer"][entries.index(metric)]
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           metric + ".json"), encoding="utf-8") as f:
        spec = json.load(f)
    assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) == (
        metric, entry["unit"], entry["layer"], entry["moves"])
    assert entry["better"] == "lower" and entry["moves"] == "op_p50_ms"
    cells = {w["name"] for w in BENCH["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= cells
    assert entry["source"] == ("program_counter"
                               if spec["reader"] == "counter_ratio"
                               else "program_span")
    if spec["reader"] == "span_tree":
        assert spec["params"]["part"] in ("self", "total")
        assert spec["params"]["per"] in ("ops", "solves")
        assert spec["params"]["spans"]
    assert spec["reader"] in ("span_tree", "program_span", "counter_ratio")


@pytest.mark.parametrize("cell", ["mt10kx1k.node-churn",
                                  "k8s-sp-antiaffinity-5k.measure-pods"])
def test_a_traced_rehearsal_reports_every_new_metric_of_the_cell(cell):
    proc = run_cell(cell, 1, "--cpu-rehearsal")
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["failed"] == 0
    listed = {m["name"] for m in BENCH["per_layer"]
              if m["name"] in NEW and cell in m["workloads"]}
    assert len(listed) >= 12
    metrics = result["metrics"]
    assert listed <= set(metrics)
    for name in listed:
        assert metrics[name]["value"] >= 0.0
    # the self times are parts of what they are self times of
    assert metrics["handler_self_ms_per_op"]["value"] \
        < metrics["cp_self_ms_per_op"]["value"]
    assert metrics["request_wait_ms_per_op"]["value"] > 0.0
    assert metrics["gc_pause_ms_per_op"]["value"] > 0.0
