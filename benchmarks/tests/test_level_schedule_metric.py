"""`level_schedule_kept_share` (PR 38): its file, its entry and its reader
over the counter the scheduler keeps — the program's own registry after
real solves, and a program that lacks the family."""

import json
import os
import sys
import types
from dataclasses import replace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.readers import counter_ratio                    # noqa: E402
from benchmarks.spans import Watch                              # noqa: E402

NAME = "level_schedule_kept_share"
CELLS = ["mt10kx1k.node-churn", "shop-live.redeploy",
         "k8s-sp-antiaffinity-5k.measure-pods",
         "k8s-sp-preemption-5k.preempt-pods", "pod100kx1k.node-churn-moved"]


def _spec():
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           NAME + ".json"), encoding="utf-8") as f:
        return json.load(f)


def _read(counters):
    return counter_ratio.read(_spec()["params"],
                              types.SimpleNamespace(counters=counters))


def test_file_and_entry_agree():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    [entry] = [m for m in bench["per_layer"] if m["name"] == NAME]
    spec = _spec()
    assert spec["name"] == NAME
    assert entry["workloads"] == CELLS
    assert entry["source"] == "program_counter" and entry["better"] == "higher"
    assert (spec["unit"], spec["layer"], spec["moves"]) == (
        entry["unit"], entry["layer"], entry["moves"]) == (
        "%", "scheduler and staging", "op_p50_ms")


def test_reads_the_schedulers_counter():
    from fleetflow_tpu.lower import synthetic_problem
    from fleetflow_tpu.sched import TpuSolverScheduler
    pt = synthetic_problem(40, 6, seed=3)
    sched = TpuSolverScheduler(chains=1, steps=64)
    c0 = Watch.counters()
    sched.place(pt, stage="s")                      # built
    for k in (1.1, 1.2, 1.3):                       # kept, three times
        sched.reschedule(replace(pt, capacity=pt.capacity * k), stage="s")
    c1 = Watch.counters()
    delta = {k: v - c0.get(k, 0.0) for k, v in c1.items()}
    assert _read(delta) == 75.0
    # a window of warm re-solves only, and one of new problems only
    kept = 'fleet_sched_level_schedules_total{outcome="kept"}'
    built = 'fleet_sched_level_schedules_total{outcome="built"}'
    assert _read({kept: 46.0}) == 100.0
    assert _read({built: 93.0}) == 0.0


def test_a_program_without_the_counter_reads_nothing():
    assert _read({"fleet_solver_solves_total": 12.0}) is None
