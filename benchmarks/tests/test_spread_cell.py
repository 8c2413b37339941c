"""The cell `k8s-sp-topology-spread-5k.spread-pods`: its rehearsal is
correct end to end with every metric it lists, its reference keeps the
source's filter on hand-made clusters and its checker counts planted
faults, and the op kind calls a relaxed, repaired, uncommitted or skewed
reply a fault."""

import json
import os
import types

import pytest

from benchmarks import generators_k8s_spread as generators
from benchmarks import reference_k8s_spread as reference
from benchmarks.ops import solve_commit_spread
from benchmarks.reference_k8s_spread import INIT, MEASURED
from benchmarks.tests.test_rehearsal import (BENCH, ROOT, check_line,
                                             run_cell)

CELL = "k8s-sp-topology-spread-5k.spread-pods"
NEW = ["lower_topology_ms_per_op", "spread_excess_at_seed_per_solve",
       "spread_excess_at_device_per_solve", "spread_repair_moves_per_solve",
       "relaxed_rungs_per_op"]
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "k8s-sp-topology-spread-5k.json"),
          encoding="utf-8") as f:
    CONFIG = json.load(f)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_and_the_bound_is_held_by_the_annealer(trace):
    proc = run_cell(CELL, trace, "--cpu-rehearsal")
    result = check_line(proc, CELL, trace)
    info = json.loads(proc.stdout.strip().splitlines()[-2])["info"]
    sizes = CONFIG["rehearsal"]["deployment"]
    assert info["notes"]["reference"] == {
        "placed": {INIT: sizes["init_pods"], MEASURED: sizes["measure_pods"]},
        "zones": [20, 20, 20], "check": 0}
    assert info["compile_in_window"]["events"] == 0
    if trace:
        metrics = result["metrics"]
        assert metrics["lower_topology_ms_per_op"]["value"] > 0
        for name in NEW[1:]:
            assert metrics[name]["value"] == 0, name
        assert metrics["sweeps_per_solve"]["value"] == 1
        assert metrics["held_keys_per_op"]["value"] == 0
        # every timed op is a first solve of sched-1: a warm start by
        # shape would restage what the slot held and count a transfer
        assert metrics["host_transfers_per_op"]["value"] == 0


def test_the_new_metrics_are_files_and_the_last_entries():
    entries = BENCH["per_layer"]
    assert [m["name"] for m in entries[-len(NEW):]] == NEW
    for m in entries[-len(NEW):]:
        assert m["workloads"] == [CELL] and m["moves"] == "op_p50_ms"
        spec_path = os.path.join(ROOT, "benchmarks", "layer_metrics",
                                 m["name"] + ".json")
        with open(spec_path, encoding="utf-8") as f:
            spec = json.load(f)
        assert spec["reader"] in ("program_span", "counter_ratio")
        assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "spread-pods"
    config = next(c for c in BENCH["configs"]
                  if c["name"] == entry["config"])
    assert config["reduced"] == [] == CONFIG["reduced"]
    assert config["source"] == CONFIG["source"]


@pytest.mark.parametrize("rehearsal", [True, False],
                         ids=["rehearsal", "source"])
def test_the_reference_places_the_whole_cluster(rehearsal):
    model = generators.model(CONFIG, 3_000_000_017, rehearsal)
    sizes = dict(CONFIG["deployment"])
    if rehearsal:
        sizes.update(CONFIG["rehearsal"]["deployment"])
    assert len(model["nodes"]) == sizes["nodes"]
    zones = [n["zone"] for n in model["nodes"].values()]
    assert sorted(zones.count(z) for z in reference.ZONES) == sorted(
        sizes["nodes"] // 3 + (i < sizes["nodes"] % 3) for i in range(3))
    placed = reference.schedule(model, {})
    assert sum(v is not None for v in placed[INIT].values()) \
        == sizes["init_pods"]
    assert sum(v is not None for v in placed[MEASURED].values()) \
        == sizes["measure_pods"]
    found = reference.check(model, placed)
    assert found["total"] == 0
    n = sizes["measure_pods"]
    assert sorted(found["zones"].values()) \
        == sorted(n // 3 + (i < n % 3) for i in range(3))
    if not rehearsal:
        assert sorted(found["zones"].values()) == [666, 667, 667]


def _tiny(zones: dict, pods: int, cpu: float = 4.0) -> dict:
    model = reference.with_zones(
        reference.cluster(1, sum(zones.values()), 0, pods), zones)
    for node in model["nodes"].values():
        node["cpu"] = cpu
    return model


def test_the_reference_keeps_the_sources_filter():
    """One pod a node: zones of 5, 3 and 1 nodes take 2, 2 and 1 of nine
    pods and the source leaves four pending (count + 1 - min <= 1 shuts
    the larger zones once the smallest is full); a node without the label
    takes none and is no zone; LeastAllocated breaks ties by name."""
    model = _tiny({"a": 5, "b": 3, "c": 1}, 9, cpu=0.1)
    placed = reference.schedule(model, {})[MEASURED]
    found = reference.check(model, {MEASURED: {
        k: v for k, v in placed.items() if v is not None}})
    assert found["zones"] == {"a": 2, "b": 2, "c": 1}
    assert sum(v is None for v in placed.values()) == 4
    bare = _tiny({"a": 2, None: 3, "b": 2}, 4)
    placed = reference.schedule(bare, {})[MEASURED]
    labelled = {n for n, node in bare["nodes"].items() if node["zone"]}
    assert set(placed.values()) <= labelled
    assert reference.check(bare, {MEASURED: placed})["zones"] \
        == {"a": 2, "b": 2}
    first = reference.schedule(_tiny({"a": 2, "b": 2}, 1), {})[MEASURED]
    assert list(first.values()) == [min(_tiny({"a": 2, "b": 2},
                                              1)["nodes"])]


def test_the_checker_counts_planted_faults():
    model = _tiny({"a": 3, "b": 3, "c": 3, None: 1}, 9)
    placed = reference.schedule(model, {})[MEASURED]
    assert reference.check(model, {MEASURED: placed})["total"] == 0
    by_zone = {z: [n for n, node in model["nodes"].items()
                   if node["zone"] == z] for z in ("a", "b", "c", None)}
    pods = list(placed)
    moved = dict(placed)
    on_b = [p for p in pods if placed[p] in by_zone["b"]]
    moved[on_b[0]] = by_zone["a"][0]             # a 4, b 2, c 3: one over
    found = reference.check(model, {MEASURED: moved})
    assert found["skew"] == 1 and found["total"] == 1
    moved[on_b[1]] = by_zone["a"][1]             # a 5, b 1: three over
    assert reference.check(model, {MEASURED: moved})["skew"] == 3
    bare = dict(placed, **{pods[0]: by_zone[None][0]})
    found = reference.check(model, {MEASURED: bare})
    assert found["unlabelled"] == 1 and found["total"] >= 1
    assert reference.check(model, {MEASURED: dict(
        placed, **{pods[0]: None})})["unplaced"] == 1
    assert reference.check(model, {MEASURED: dict(
        placed, **{pods[0]: "nowhere"})})["unknown"] == 1
    assert reference.check(model, {MEASURED: placed},
                           offline=[placed[pods[0]]])["offline"] == 1
    crowded = _tiny({"a": 1, "b": 1, "c": 1}, 3, cpu=0.15)
    everyone = {p["name"]: next(iter(crowded["nodes"]))
                for p in crowded["namespaces"][MEASURED]}
    found = reference.check(crowded, {MEASURED: everyone})
    assert found["capacity"] == 1 and found["skew"] == 2
    # a zone whose nodes are all down is no zone
    down = by_zone["c"]
    rest = {p: n for p, n in placed.items() if n not in down}
    assert reference.check(model, {MEASURED: placed},
                           offline=down)["skew"] == 0
    assert reference.check(
        dict(model, namespaces={MEASURED: [
            p for p in model["namespaces"][MEASURED] if p["name"] in rest]}),
        {MEASURED: rest}, offline=down)["total"] == 0
    # the same pods under the next op's names are the same answer
    batch = reference.measured_batch(model, 7)
    renamed = {p["name"]: node for p, node in
               zip(batch["namespaces"][MEASURED], moved.values())}
    assert reference.check(batch, {MEASURED: renamed})["skew"] == 3


class _Rec:
    def __init__(self, assignment):
        self.assignment = assignment


def _op(model: dict, init: dict, committed: dict, zone_counts):
    """The op kind over a stand-in for the CP: only what `verify` reads."""
    op = solve_commit_spread.Op(types.SimpleNamespace(
        device={"platform": "tpu"}))
    op.model, op.init, op.zone_counts = model, init, zone_counts
    op._committed = lambda ns: (_Rec(committed[ns]) if ns in committed
                                else None)
    return op


def test_the_op_calls_a_relaxed_repaired_uncommitted_or_skewed_reply_a_fault(
        monkeypatch):
    model = _tiny({"a": 3, "b": 3, "c": 3}, 9)
    placed = reference.schedule(model, {})[MEASURED]
    counters = {name: 0.0 for name in solve_commit_spread.HOST_DID_IT}
    monkeypatch.setattr(solve_commit_spread, "host_did_it",
                        lambda: dict(counters))
    prepared = {"model": model, "host_did_it": dict(counters)}

    def verify(reply=None, done=None, committed=None, moved=None):
        reply = {"feasible": True, "violations": 0, "source": "tpu-anneal",
                 "assignment": placed, **(reply or {})}
        op = _op(model, {}, {INIT: {}, MEASURED: reply["assignment"],
                             **(committed or {})}, [3, 3, 3])
        counters.update(dict.fromkeys(counters, 0.0), **(moved or {}))
        return op.verify(prepared, (reply, done or {"ok": True}))

    assert verify() == (9, [])
    _n, faults = verify({"source": "tpu-anneal+relaxed:spread"})
    assert any("relaxed:spread" in f for f in faults)
    _n, faults = verify({"source": "host-greedy"})
    assert any("host-greedy" in f for f in faults)
    _n, faults = verify(moved={"fleet_sched_relaxed_total": 1.0})
    assert any("fleet_sched_relaxed_total moved by 1" in f for f in faults)
    _n, faults = verify(
        moved={"fleet_solver_spread_repair_moves_total": 2.0})
    assert any("repair_moves_total moved by 2" in f for f in faults)
    _n, faults = verify(done={"ok": False})
    assert "placement not committed" in faults
    _n, faults = verify(committed={MEASURED: {}})
    assert "placement not committed" in faults
    _n, faults = verify({"feasible": False, "violations": 1})
    assert any(f.startswith("infeasible") for f in faults)
    nodes_a = [n for n, node in model["nodes"].items()
               if node["zone"] == "a"]
    on_b = [p for p, n in placed.items()
            if model["nodes"][n]["zone"] == "b"]
    skewed = dict(placed, **{on_b[0]: nodes_a[0]})
    _n, faults = verify({"assignment": skewed})
    assert any("'skew': 1" in f for f in faults)
    assert any(f.startswith("zone counts") for f in faults)


@pytest.mark.parametrize("has_forget", [True, False],
                         ids=["this-program", "a-program-before-forget"])
def test_prepare_tears_the_last_stage_down_so_that_the_next_solve_is_cold(
        has_forget):
    """On this program by `release_stage(key, forget=True)`; on one from
    before `forget` (the parent the cell is first compared with) the
    retained problem is dropped by hand, so both sides solve cold."""
    import contextlib

    calls, last = [], {"k8s/sched-1": "retained", "k8s/sched-0": "kept"}

    class New:
        def release_stage(self, key, *, forget=False):
            calls.append((key, forget))
            if forget:
                last.pop(key, None)

    class Old:
        _last = last
        _locked = staticmethod(contextlib.nullcontext)

        def release_stage(self, key):
            calls.append((key, None))

    op = solve_commit_spread.Op(types.SimpleNamespace())
    op.cp = types.SimpleNamespace(state=types.SimpleNamespace(
        placement=New() if has_forget else Old()))
    op._tear_down("k8s/sched-1")
    assert calls == [("k8s/sched-1", True if has_forget else None)]
    assert last == {"k8s/sched-0": "kept"}

