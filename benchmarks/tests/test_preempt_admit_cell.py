"""The cell `k8s-sp-preemption-5k-admit.admit-preempt-pods`: its files and
entries, its rehearsal correct end to end with every metric it lists, the
two metrics it adds read on a synthetic run and on a program without what
they read, its reference on hand-made clusters, and the op kind refusing a
program whose admission cannot take a `priority` on an arrival — at once,
in set-up, before anything is generated or started."""

import asyncio
import json
import os
import types

import pytest

from benchmarks import generators_k8s_preempt_admit as generators
from benchmarks import reference_k8s_preempt_admit as reference
from benchmarks.ops import submit_wait_preempt
from benchmarks.readers import program_span
from benchmarks.reference_k8s_preemption import INIT, MEASURED
from benchmarks.tests.test_rehearsal import (BENCH, ROOT, check_line,
                                             run_cell)

CELL = "k8s-sp-preemption-5k-admit.admit-preempt-pods"
AA = "k8s-sp-antiaffinity-5k-admit.admit-anti-pods"
ADM = "k8s-sp-basic-5k.admit-pods"
NEW = ["admit_preemptible_ms_per_solve", "admit_victims_ms_per_solve"]
SPANS = {NEW[0]: "cp.admit_batch.preemptible",
         NEW[1]: "cp.admit_batch.victims"}
# pre's metrics this cell reports too, and the traced run's own counter
JOINED = ("victims_per_op", "commit_evict_ms_per_op",
          "preemptible_servers_per_op", "relaxed_rungs_per_op")
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "k8s-sp-preemption-5k-admit.json"),
          encoding="utf-8") as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "admit-preempt-pods.json"), encoding="utf-8") as f:
    TRAFFIC = json.load(f)
BATCH = 128         # the server's default admission_batch


def _spec(name):
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def test_the_configuration_and_the_traffic_state_the_source():
    assert CONFIG["name"] == "k8s-sp-preemption-5k-admit"
    assert CONFIG["architecture"] is None
    assert CONFIG["reduced"] == ["measure_pods"]
    assert "time limit" in CONFIG["reduced_why"]["measure_pods"]
    assert "PreemptionBasic/5000Nodes" in CONFIG["source"]
    assert "defaultpreemption" in CONFIG["source"]
    dep = CONFIG["deployment"]
    assert (dep["nodes"], dep["init_pods"], dep["measure_pods"]) \
        == (5000, 20000, 1000)
    assert set(CONFIG) >= {"what", "chips", "deployment", "assumed",
                           "guarantees", "rehearsal"}
    reh = CONFIG["rehearsal"]["deployment"]
    # four low pods a node, and a stream past the sub-solve's first tier
    assert reh["init_pods"] == 4 * reh["nodes"]
    assert reh["measure_pods"] > 256 and CONFIG["rehearsal"]["why"]
    assert TRAFFIC["name"] == "admit-preempt-pods"
    assert TRAFFIC["op"] == "submit_wait_preempt"
    assert [w["ops"] for w in TRAFFIC["warmup"]] == [3]
    assert TRAFFIC["params"]["wait_s"] > 0


def test_the_entries_are_appended():
    names = [c["name"] for c in BENCH["configs"]]
    at = names.index(CONFIG["name"])
    # after everything PR 45 left, whatever a later PR appends after it
    assert at > names.index("k8s-sp-antiaffinity-5k-admit")
    config = BENCH["configs"][at]
    assert config["source"] == CONFIG["source"] and len(config["source"]) \
        <= 200
    assert config["reduced"] == ["measure_pods"] and config[
        "file"].endswith("k8s-sp-preemption-5k-admit.json")
    # the plugin on the queue sets it apart from the solve-commit
    # configuration of the same test case
    kinds = [(c["source"], tuple(c["reduced"])) for c in BENCH["configs"]]
    assert len(set(kinds)) == len(kinds)
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells.index(CELL) > cells.index(AA)
    entry = BENCH["workloads"][cells.index(CELL)]
    assert entry == {"name": CELL, "config": CONFIG["name"],
                     "traffic": "admit-preempt-pods", "chips": 1,
                     "why": entry["why"]}
    assert len(entry["why"]) <= 200
    metrics = [m["name"] for m in BENCH["per_layer"]]
    at = metrics.index(NEW[0])
    assert metrics[at:at + len(NEW)] == NEW
    assert at > metrics.index("subsolve_closure_rows_per_solve")
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        m, spec = by_name[name], _spec(name)
        assert m["workloads"] == [CELL]
        assert (m["layer"], m["source"], m["unit"], m["moves"]) \
            == ("CP placement", "program_span", "ms", "op_p50_ms")
        assert (spec["name"], spec["layer"], spec["unit"]) \
            == (name, "CP placement", "ms")
    # joined: every list adm is on, and pre's that this path feeds
    for m in BENCH["per_layer"]:
        if ADM in m.get("workloads", ()) or m["name"] in JOINED:
            assert CELL in m["workloads"], m["name"]
    # not joined: what a solve_stage feeds, what no key here reads, the
    # lists tests pin
    for name in ("preemptible_ms_per_op", "victims_ms_per_op",
                 "held_keys_per_op", "barred_cells_per_op",
                 "level_schedule_kept_share", "cp_self_ms_per_op",
                 "admit_held_ms_per_solve"):
        assert CELL not in by_name[name]["workloads"], name


def test_the_new_metrics_read_a_synthetic_run():
    for name in NEW:
        spec = _spec(name)
        assert spec["reader"] == "program_span"
        assert spec["params"] == {"spans": [SPANS[name]], "per": "solves"}
        run = types.SimpleNamespace(
            spans=types.SimpleNamespace(events=[]), count=lambda what: 16)
        # a program that never opens the phase reads nothing
        assert program_span.read(spec["params"], run) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_and_each_micro_batch_evicts(trace):
    proc = run_cell(CELL, trace, "--cpu-rehearsal")
    result = check_line(proc, CELL, trace)
    info = json.loads(proc.stdout.strip().splitlines()[-2])["info"]
    sizes = CONFIG["rehearsal"]["deployment"]
    wave = sizes["measure_pods"]
    assert info["notes"]["reference"] == {
        "placed": {INIT: sizes["init_pods"] - 3 * wave, MEASURED: wave},
        "victims": 3 * wave, "check": 0}
    assert info["notes"]["places_per_pass"] == 1.0
    assert info["compile_in_window"]["events"] == 0
    if trace:
        metrics = {k: v["value"] for k, v in result["metrics"].items()}
        assert set(NEW) <= set(metrics)
        passes = wave / BATCH
        # the window as it is: the wave placed, and withdrawn in `prepare`
        assert metrics["admission_solves_per_op"] == 2 * passes
        assert metrics["victims_per_op"] == 3 * wave
        assert metrics["resident_delta_share"] == 100
        assert metrics["admission_moved_rows_per_op"] == 0
        assert metrics["relaxed_rungs_per_op"] == 0
        assert metrics["host_transfers_per_op"] == 0
        assert metrics["sweeps_per_solve"] == 0
        assert metrics["admit_preemptible_ms_per_solve"] > 0
        assert metrics["admit_victims_ms_per_solve"] > 0
        assert metrics["commit_evict_ms_per_op"] > 0


def test_the_op_kind_refuses_a_program_without_priority(monkeypatch):
    """A program whose admission refuses `priority` on an arrival (the
    parent's `make_arrival`), or builds the arrival without it, is refused
    in set-up, before the model is generated or a CP started."""
    from fleetflow_tpu.core.model import ResourceSpec, Service
    from fleetflow_tpu.cp.admission import AdmissionController

    assert submit_wait_preempt.streams_priority()

    def refusing(self, spec):
        raise ValueError("arrival 'high-0-0' carries ['priority'], which a "
                         "streamed arrival cannot")

    monkeypatch.setattr(AdmissionController, "make_arrival", refusing)
    assert not submit_wait_preempt.streams_priority()
    cell = types.SimpleNamespace(name=CELL, traffic=TRAFFIC, config=CONFIG,
                                 phases={})
    op = submit_wait_preempt.Op(cell)
    with pytest.raises(RuntimeError, match="`priority` on a streamed"):
        asyncio.run(op.setup())
    assert not hasattr(op, "model") and not hasattr(op, "cp")

    def dropping(self, spec):
        return Service(name=str(spec["name"]), image="app",
                       resources=ResourceSpec(cpu=3.0, memory=500.0))

    monkeypatch.setattr(AdmissionController, "make_arrival", dropping)
    assert not submit_wait_preempt.streams_priority()


@pytest.mark.parametrize("rehearsal", [True, False],
                         ids=["rehearsal", "source"])
def test_the_reference_places_the_wave_preempting(rehearsal):
    model = generators.model(CONFIG, 3_000_000_017, rehearsal)
    sizes = dict(CONFIG["deployment"])
    if rehearsal:
        sizes.update(CONFIG["rehearsal"]["deployment"])
    assert len(model["nodes"]) == sizes["nodes"]
    assert len(model["namespaces"][INIT]) == sizes["init_pods"]
    assert len(model["namespaces"][MEASURED]) == sizes["measure_pods"]
    if not rehearsal:
        return      # the source's size is scheduled in the cell's set-up
    placed, victims = reference.schedule(model)
    stay = {n: s for n, s in placed[INIT].items() if s is not None}
    before = {INIT: {**stay, **victims[INIT]}}
    after = {INIT: stay, MEASURED: placed[MEASURED]}
    assert reference.check(model, before, after, placed[MEASURED],
                           forced=3 * sizes["measure_pods"]) \
        == dict.fromkeys(reference.KINDS, 0) | {"total": 0}
    again = reference.wave(model, 7)
    assert not {p["name"] for p in again["namespaces"][MEASURED]} \
        & {p["name"] for p in model["namespaces"][MEASURED]}
    # the wire spec carries the pod's priority as plain data
    spec = generators.arrivals(again["namespaces"][MEASURED][:1])[0]
    assert spec["priority"] == 10 and spec["cpu"] == 3.0


def test_the_checker_counts_planted_faults():
    model = reference.cluster(1, 4, 16, 2)
    placed, victims = reference.schedule(model)
    stay = {n: s for n, s in placed[INIT].items() if s is not None}
    gone = victims[INIT]
    before = {INIT: {**stay, **gone}, MEASURED: {}}
    wave = placed[MEASURED]
    pods = {p["name"]: p for ns in model["namespaces"].values() for p in ns}

    def allocated(init, arrivals):
        out = {}
        for held in (init, arrivals):
            for name, node in held.items():
                cpu, mem = out.get(node, (0.0, 0.0))
                out[node] = (cpu + pods[name]["cpu"],
                             mem + pods[name]["memory"])
        return out

    def check(init=stay, arrivals=wave, told=wave, alloc=None, forced=6):
        alloc = allocated(init, arrivals) if alloc is None else alloc
        return reference.check(model, before, {INIT: init,
                                               MEASURED: arrivals},
                               told, alloc, forced)

    assert check()["total"] == 0
    v0 = sorted(gone)[0]
    # a victim too many: a fourth low pod gone from a server
    extra = next(n for n, s in stay.items() if s == gone[v0])
    found = check(init={n: s for n, s in stay.items() if n != extra})
    assert found["victims"] == 1 and found["victim_needless"] >= 1
    # a victim too few: the init pod back, the server over capacity
    found = check(init={**stay, v0: gone[v0]})
    assert found["victims"] == 1 and found["cpu"] == 1
    # a server whose `allocated` still counts the victims
    node = gone[v0]
    alloc = allocated(stay, wave)
    alloc[node] = (alloc[node][0] + 0.9, alloc[node][1])
    assert check(alloc=alloc)["allocated"] == 1
    # a pod told another server than the record holds, or told nothing
    w0 = sorted(wave)[0]
    other = next(s for s in model["nodes"] if s != wave[w0])
    assert check(told={**wave, w0: other})["untold"] == 1
    assert check(told={n: s for n, s in wave.items() if n != w0})[
        "untold"] == 1
    # an init pod that moved, and a departed pod still in view
    i0 = sorted(stay)[0]
    moved = next(s for s in model["nodes"] if s != stay[i0])
    assert check(init={**stay, i0: moved})["moved"] == 1
    found = check(arrivals={**wave, "high-9-0": wave[w0]},
                  alloc=allocated(stay, wave))
    assert found["ghost"] == 1
