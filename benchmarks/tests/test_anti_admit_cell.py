"""The cell `k8s-sp-antiaffinity-5k-admit.admit-anti-pods`: its files and
entries, its rehearsal correct end to end with every metric it lists, the
two metrics it adds read on a synthetic run and on a program without what
they read, its reference on hand-made clusters, and the op kind refusing a
program whose admission cannot take an anti-affine arrival — at once, in
set-up, before anything is generated or started."""

import asyncio
import json
import os
import types

import pytest

from benchmarks import generators_k8s_anti_admit as generators
from benchmarks import reference_k8s_anti_admit as reference
from benchmarks.ops import submit_wait_anti
from benchmarks.readers import counter_ratio, program_span
from benchmarks.reference_k8s import INIT, MEASURED
from benchmarks.tests.test_rehearsal import (BENCH, ROOT, check_line,
                                             run_cell)

CELL = "k8s-sp-antiaffinity-5k-admit.admit-anti-pods"
ADM = "k8s-sp-basic-5k.admit-pods"
NC = "mt10kx1k.node-churn"
NEW = ["admit_held_ms_per_solve", "subsolve_closure_rows_per_solve"]
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "k8s-sp-antiaffinity-5k-admit.json"),
          encoding="utf-8") as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "admit-anti-pods.json"), encoding="utf-8") as f:
    TRAFFIC = json.load(f)
BATCH = 128         # the server's default admission_batch


def _spec(name):
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def test_the_configuration_and_the_traffic_state_the_source():
    assert CONFIG["name"] == "k8s-sp-antiaffinity-5k-admit"
    assert CONFIG["architecture"] is None and CONFIG["reduced"] == []
    assert "SchedulingPodAntiAffinity" in CONFIG["source"]
    assert "pod-with-pod-anti-affinity.yaml" in CONFIG["source"]
    dep = CONFIG["deployment"]
    assert (dep["nodes"], dep["init_pods"], dep["measure_pods"]) \
        == (5000, 1000, 1000)
    assert set(CONFIG) >= {"what", "chips", "deployment", "assumed",
                           "guarantees", "rehearsal"}
    reh = CONFIG["rehearsal"]
    # departures in `prepare` localize only where the stage's rows pass
    # the sub-solve's first tier (256)
    assert reh["deployment"]["measure_pods"] > 256 and reh["why"]
    assert TRAFFIC["name"] == "admit-anti-pods"
    assert TRAFFIC["op"] == "submit_wait_anti"
    assert [w["ops"] for w in TRAFFIC["warmup"]] == [3]
    assert TRAFFIC["params"]["wait_s"] > 0


def test_the_entries_are_appended():
    config = next(c for c in BENCH["configs"]
                  if c["name"] == CONFIG["name"])
    assert BENCH["configs"][-1] is config
    assert config["source"] == CONFIG["source"] and len(config["source"]) \
        <= 200
    assert config["reduced"] == [] and config["file"].endswith(
        "k8s-sp-antiaffinity-5k-admit.json")
    # the queue it names sets it apart from the solve-commit configuration
    # of the same test case: no two configurations share source and cuts
    kinds = [(c["source"], tuple(c["reduced"])) for c in BENCH["configs"]]
    assert len(set(kinds)) == len(kinds)
    assert "scheduling_queue" in config["source"]
    entry = BENCH["workloads"][-1]
    assert entry == {"name": CELL, "config": CONFIG["name"],
                     "traffic": "admit-anti-pods", "chips": 1,
                     "why": entry["why"]}
    assert len(entry["why"]) <= 200
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW
    # after everything PR 43 left, whatever a later PR appends after them
    assert at > names.index("placement_record_keys_per_write")
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert by_name[NEW[0]]["workloads"] == [CELL]
    assert by_name[NEW[1]]["workloads"] == [NC, ADM, CELL]
    for name, layer, source, unit in (
            (NEW[0], "CP placement", "program_span", "ms"),
            (NEW[1], "scheduler and staging", "program_counter", "rows")):
        m, spec = by_name[name], _spec(name)
        assert (m["layer"], m["source"], m["unit"], m["moves"]) \
            == (layer, source, unit, "op_p50_ms")
        assert (spec["name"], spec["layer"], spec["unit"]) \
            == (name, layer, unit)
    # joined: every list adm is on, and the held keys' two counters
    for m in BENCH["per_layer"]:
        if ADM in m.get("workloads", ()) or m["name"] in (
                "held_keys_per_op", "barred_cells_per_op"):
            assert m["workloads"][-1] == CELL, m["name"]
    # not joined: the lists tests pin, the held phase of a lowering
    for name in ("level_schedule_kept_share", "relaxed_rungs_per_op",
                 "cp_self_ms_per_op", "held_keys_ms_per_op"):
        assert CELL not in by_name[name]["workloads"]


def test_the_new_metrics_read_a_synthetic_run():
    held = _spec(NEW[0])
    assert held["reader"] == "program_span"
    assert held["params"] == {"spans": ["cp.admit_batch.held"],
                              "per": "solves"}
    closure = _spec(NEW[1])
    assert closure["reader"] == "counter_ratio"
    assert closure["params"] == {
        "num": ["fleet_solver_subsolve_closure_rows_total"],
        "den": ["fleet_solver_subsolve_total"]}
    run = types.SimpleNamespace(counters={
        "fleet_solver_subsolve_closure_rows_total": 2000.0,
        'fleet_solver_subsolve_total{outcome="localized"}': 15.0,
        'fleet_solver_subsolve_total{outcome="fallback_small"}': 1.0})
    assert counter_ratio.read(closure["params"], run) == 125.0
    # a program without the numerator reads 0, without attempts nothing
    run.counters.pop("fleet_solver_subsolve_closure_rows_total")
    assert counter_ratio.read(closure["params"], run) == 0.0
    assert counter_ratio.read(closure["params"], types.SimpleNamespace(
        counters={})) is None
    # a program that never opens the phase reads nothing
    empty = types.SimpleNamespace(spans=types.SimpleNamespace(events=[]),
                                  count=lambda what: 16)
    assert program_span.read(held["params"], empty) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_and_each_micro_solve_is_localized(trace):
    proc = run_cell(CELL, trace, "--cpu-rehearsal")
    result = check_line(proc, CELL, trace)
    info = json.loads(proc.stdout.strip().splitlines()[-2])["info"]
    sizes = CONFIG["rehearsal"]["deployment"]
    assert info["notes"]["reference"] == {
        "placed": {INIT: sizes["init_pods"], MEASURED: sizes["measure_pods"]},
        "check": 0}
    assert info["compile_in_window"]["events"] == 0
    if trace:
        metrics = {k: v["value"] for k, v in result["metrics"].items()}
        assert set(NEW) <= set(metrics)
        passes = sizes["measure_pods"] / BATCH
        # the window as it is: the wave placed, and withdrawn in `prepare`
        assert metrics["admission_solves_per_op"] == 2 * passes
        assert metrics["subsolve_closure_rows_per_solve"] == BATCH
        assert metrics["subsolve_localized_share"] == 100
        assert metrics["resident_delta_share"] == 100
        assert metrics["admission_moved_rows_per_op"] == 0
        assert metrics["host_transfers_per_op"] == 0
        assert metrics["sweeps_per_solve"] == 0
        assert metrics["admit_held_ms_per_solve"] > 0
        # each arrival barred from every server sched-0 holds its key on
        assert metrics["barred_cells_per_op"] \
            == sizes["measure_pods"] * sizes["init_pods"]
        assert metrics["held_keys_per_op"] == passes * 2 * sizes["init_pods"]


def test_the_op_kind_refuses_a_program_that_drops_the_term(monkeypatch):
    """A program whose admission builds the arrival without its term (the
    parent's `make_arrival`) is refused in set-up, before the model is
    generated or a CP started."""
    from fleetflow_tpu.cp.admission import AdmissionController
    from fleetflow_tpu.core.model import ResourceSpec, Service

    assert submit_wait_anti.streams_anti_affinity()

    def dropping(self, spec):
        return Service(name=str(spec["name"]), image="app",
                       resources=ResourceSpec(cpu=0.1, memory=500.0))

    monkeypatch.setattr(AdmissionController, "make_arrival", dropping)
    assert not submit_wait_anti.streams_anti_affinity()
    cell = types.SimpleNamespace(name=CELL, traffic=TRAFFIC, config=CONFIG,
                                 phases={})
    op = submit_wait_anti.Op(cell)
    with pytest.raises(RuntimeError, match="anti-affine"):
        asyncio.run(op.setup())
    assert not hasattr(op, "model") and not hasattr(op, "cp")

    def refusing(self, spec):
        raise ValueError("arrival carries ['anti_affinity_stages']")

    monkeypatch.setattr(AdmissionController, "make_arrival", refusing)
    assert not submit_wait_anti.streams_anti_affinity()


@pytest.mark.parametrize("rehearsal", [True, False],
                         ids=["rehearsal", "source"])
def test_the_reference_places_both_namespaces(rehearsal):
    model = generators.model(CONFIG, 3_000_000_017, rehearsal)
    sizes = dict(CONFIG["deployment"])
    if rehearsal:
        sizes.update(CONFIG["rehearsal"]["deployment"])
    assert len(model["nodes"]) == sizes["nodes"]
    assert len(model["namespaces"][INIT]) == sizes["init_pods"]
    assert len(model["namespaces"][MEASURED]) == sizes["measure_pods"]
    placed = reference.schedule(model)
    assert reference.check(model, {}, placed, placed[MEASURED]) \
        == dict.fromkeys(reference.KINDS, 0) | {"total": 0}
    # one green pod a node over both namespaces
    nodes = list(placed[INIT].values()) + list(placed[MEASURED].values())
    assert len(set(nodes)) == sizes["init_pods"] + sizes["measure_pods"]
    again = reference.wave(model, 7)
    assert not {p["name"] for p in again["namespaces"][MEASURED]} \
        & {p["name"] for p in model["namespaces"][MEASURED]}
    # the wire spec carries the term as core/serialize.py spells it
    spec = generators.arrivals(again["namespaces"][MEASURED][:1])[0]
    assert spec["anti_affinity"] == ["color=green"]
    assert spec["anti_affinity_stages"] == {"color=green": [MEASURED, INIT]}


def test_the_checker_counts_planted_faults():
    model = reference.cluster(1, 6, 2, 3)
    placed = reference.schedule(model)
    init, wave = placed[INIT], placed[MEASURED]
    told = dict(wave)
    before = {INIT: dict(init), MEASURED: {}}

    def check(after=placed, before=before, told=told):
        return reference.check(model, before, after, told)

    assert check()["total"] == 0
    w0, i0 = sorted(wave)[0], sorted(init)[0]
    # two green pods on one node, whatever their namespaces
    found = check({INIT: init, MEASURED: dict(wave, **{w0: init[i0]})},
                  told=dict(told, **{w0: init[i0]}))
    assert found["anti_affinity"] == 1 and found["total"] == 1
    # an init pod moved
    free = next(n for n in model["nodes"]
                if n not in set(init.values()) | set(wave.values()))
    found = check({INIT: dict(init, **{i0: free}), MEASURED: wave})
    assert found["moved"] == 1 and found["total"] == 1
    # told another node than the record holds, or told nothing
    assert check(told=dict(told, **{w0: free}))["untold"] == 1
    assert check(told={n: s for n, s in told.items() if n != w0})[
        "untold"] == 1
    # a departed pod still in view
    found = check({INIT: init, MEASURED: dict(wave, **{"pod-9-0": free})})
    assert found["ghost"] == 1 and found["total"] == 1
    # a pod of the wave missing
    found = check({INIT: init, MEASURED: {n: s for n, s in wave.items()
                                          if n != w0}})
    assert found["unplaced"] == 1 and found["untold"] == 1
