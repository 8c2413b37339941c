"""The cell `k8s-sp-basic-5k.admit-pods`: its rehearsal is correct end to
end with every metric it lists, its reference keeps the source's scheduler
on hand-made clusters and its checker counts planted faults, and the op
kind calls a parked, moved, untold, uncommitted or fallback-served wave a
fault."""

import json
import os
import types

import pytest

from benchmarks import generators_k8s_basic as generators
from benchmarks import reference_k8s_basic as reference
from benchmarks.ops import submit_wait
from benchmarks.tests.test_rehearsal import (BENCH, ROOT, check_line,
                                             run_cell)

CELL = "k8s-sp-basic-5k.admit-pods"
NEW = ["admission_submit_ms_per_op", "admission_drain_wait_ms_per_op",
       "admission_step_ms_per_solve", "admission_fold_ms_per_solve",
       "admission_commit_ms_per_solve", "admit_refresh_ms_per_solve",
       "admission_step_self_ms_per_solve", "admission_solves_per_op",
       "admission_events_per_solve", "admission_moved_rows_per_op",
       "admission_unplaced_per_op"]
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "k8s-sp-basic-5k.json"), encoding="utf-8") as f:
    CONFIG = json.load(f)
BATCH = 128         # the server's default admission_batch


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_and_the_queue_drains_at_the_solvers_pace(trace):
    proc = run_cell(CELL, trace, "--cpu-rehearsal")
    result = check_line(proc, CELL, trace)
    info = json.loads(proc.stdout.strip().splitlines()[-2])["info"]
    sizes = CONFIG["rehearsal"]["deployment"]
    assert info["notes"]["reference"] == {
        "placed": sizes["init_pods"] + sizes["measure_pods"], "pending": 0,
        "check": 0}
    assert info["compile_in_window"]["events"] == 0
    if trace:
        metrics = {k: v["value"] for k, v in result["metrics"].items()}
        assert set(NEW) <= set(metrics)
        # the window as it is: the wave placed, and withdrawn in `prepare`
        assert metrics["admission_solves_per_op"] \
            == 2 * sizes["measure_pods"] / BATCH
        assert metrics["admission_events_per_solve"] == BATCH
        assert metrics["admission_moved_rows_per_op"] == 0
        assert metrics["admission_unplaced_per_op"] == 0
        # every micro-solve rode the resident delta, localized
        assert metrics["resident_delta_share"] == 100
        assert metrics["subsolve_localized_share"] == 100
        assert metrics["host_transfers_per_op"] == 0
        assert metrics["program_span_coverage"] >= 95
        for name in NEW[:7]:
            assert metrics[name] > 0, name
        # the parent's loop slept 500 ms after every pass
        assert metrics["admission_drain_wait_ms_per_op"] < 50


def test_the_new_metrics_are_files_and_appended_entries():
    entries = [m["name"] for m in BENCH["per_layer"]]
    at = entries.index(NEW[0])
    assert entries[at:at + len(NEW)] == NEW
    # after everything PR 39 left, whatever a later PR appends after them
    assert at > entries.index("relaxed_rungs_per_op")
    for m in BENCH["per_layer"][at:at + len(NEW)]:
        assert m["workloads"] == [CELL] and m["moves"] == "op_p50_ms"
        spec_path = os.path.join(ROOT, "benchmarks", "layer_metrics",
                                 m["name"] + ".json")
        with open(spec_path, encoding="utf-8") as f:
            spec = json.load(f)
        assert spec["reader"] in ("program_span", "span_tree",
                                  "counter_ratio")
        assert m["source"] == ("program_counter"
                               if spec["reader"] == "counter_ratio"
                               else "program_span")
        assert (spec["name"], spec["layer"], spec["unit"]) == (
            m["name"], m["layer"], m["unit"])
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "admit-pods"
    config = next(c for c in BENCH["configs"]
                  if c["name"] == entry["config"])
    assert config["reduced"] == [] == CONFIG["reduced"]
    assert config["source"] == CONFIG["source"]
    assert len(config["source"]) <= 200 and CONFIG["architecture"] is None
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "admit-pods.json"), encoding="utf-8") as f:
        traffic = json.load(f)
    assert traffic["op"] == "submit_wait"
    assert [w["ops"] for w in traffic["warmup"]] == [3]
    # the cell joined lists, and left the one a test pins alone
    kept = next(m for m in BENCH["per_layer"]
                if m["name"] == "level_schedule_kept_share")
    assert CELL not in kept["workloads"]
    # nor the one that would subtract sixteen passes from one held reply
    self_ms = next(m for m in BENCH["per_layer"]
                   if m["name"] == "cp_self_ms_per_op")
    assert CELL not in self_ms["workloads"]


@pytest.mark.parametrize("rehearsal", [True, False],
                         ids=["rehearsal", "source"])
def test_the_reference_places_the_whole_cluster(rehearsal):
    model = generators.model(CONFIG, 3_000_000_017, rehearsal)
    sizes = dict(CONFIG["deployment"])
    if rehearsal:
        sizes.update(CONFIG["rehearsal"]["deployment"])
    assert len(model["nodes"]) == sizes["nodes"]
    assert len(model["init"]) == sizes["init_pods"]
    assert len(model["wave"]) == sizes["measure_pods"]
    placed = reference.schedule(model, {})
    assert None not in placed.values()
    assert len(placed) == sizes["init_pods"] + sizes["measure_pods"]
    init = {p["name"]: placed[p["name"]] for p in model["init"]}
    found = reference.check(model, init, placed, placed)
    assert found == dict.fromkeys(reference.KINDS, 0) | {"total": 0}
    if not rehearsal:
        assert (sizes["nodes"], sizes["init_pods"],
                sizes["measure_pods"]) == (5000, 1000, 1000)
        # LeastAllocated over equal empty nodes: one pod a node
        assert len(set(placed.values())) == 2000
    # the next op's wave is the same pods under fresh names
    again = reference.wave(model, 7)
    assert len(again["wave"]) == sizes["measure_pods"]
    assert not {p["name"] for p in again["wave"]} \
        & {p["name"] for p in model["wave"]}
    assert again["init"] == model["init"]


def _tiny(nodes: int, init: int, wave: int, cpu: float = 0.2) -> dict:
    return reference.cluster(1, nodes, init, wave,
                             node=dict(reference.NODE, cpu=cpu))


def test_the_reference_keeps_the_sources_scheduler():
    """Two pods a node: least allocated first, ties by name, a pod that
    fits nowhere stays pending, a bound pod is never moved."""
    model = _tiny(3, 2, 5)
    placed = reference.schedule(model, {})
    nodes = sorted(model["nodes"])
    order = [p["name"] for p in reference.pods_of(model)]
    # the first three pods take the three empty nodes in name order
    assert [placed[n] for n in order[:3]] == nodes
    assert [placed[n] for n in order[3:6]] == nodes
    assert placed[order[6]] is None                 # the seventh is pending
    assert sum(v is None for v in placed.values()) == 1
    # bound pods stay where they are, wherever that is
    bound = {order[0]: nodes[2], order[1]: nodes[2]}
    rest = reference.schedule(model, bound)
    assert not set(rest) & set(bound)
    assert nodes[2] not in rest.values()
    assert sorted(v for v in rest.values() if v) == sorted(nodes[:2] * 2)
    # the pod count binds where cpu and memory do not
    few = reference.cluster(1, 1, 0, 3, node=dict(reference.NODE, pods=2))
    assert sum(v is None
               for v in reference.schedule(few, {}).values()) == 1


def test_the_checker_counts_planted_faults():
    model = _tiny(4, 3, 4)
    placed = reference.schedule(model, {})
    assert None not in placed.values()
    init = {p["name"]: placed[p["name"]] for p in model["init"]}
    told = {p["name"]: placed[p["name"]] for p in model["wave"]}
    nodes = sorted(model["nodes"])

    def check(after=placed, before=init, told=told, **kw):
        return reference.check(model, before, after, told, **kw)

    assert check()["total"] == 0
    wave0, init0 = model["wave"][0]["name"], model["init"][0]["name"]
    lost = {n: s for n, s in placed.items() if n != wave0}
    found = check(lost)
    assert found["unplaced"] == 1 and found["untold"] == 1
    # a pod the caller was told is parked may lack a node, and must
    found = check(lost, told={**told, wave0: None}, pending=[wave0])
    assert found["total"] == 0
    assert check(told={**told, wave0: None},
                 pending=[wave0])["untold"] == 1
    assert check(dict(placed, **{wave0: "nowhere"}))["unknown"] == 1
    assert check(offline=[placed[wave0]])["offline"] == 1
    elsewhere = next(n for n in nodes if n != placed[init0])
    found = check(dict(placed, **{init0: elsewhere}))
    assert found["moved"] == 1 and found["untold"] == 0
    other = next(n for n in nodes if n != placed[wave0])
    found = check(dict(placed, **{wave0: other}))
    assert found["untold"] == 1 and found["moved"] == 0
    assert check(told={n: s for n, s in told.items()
                       if n != wave0})["untold"] == 1
    found = check(dict(placed, **{"pod-9-0": nodes[0]}))
    assert found["ghost"] == 1 and found["total"] == 1
    everyone = dict.fromkeys(placed, nodes[0])
    found = check(everyone, before={}, told=dict.fromkeys(told, nodes[0]))
    assert found["capacity"] == 1 and found["total"] == 1
    crowd = reference.cluster(1, 1, 0, 3, node=dict(reference.NODE, pods=2))
    one = next(iter(crowd["nodes"]))
    all_on = {p["name"]: one for p in crowd["wave"]}
    assert reference.check(crowd, {}, all_on, all_on)["pods"] == 1


class _Rec:
    def __init__(self, assignment):
        self.assignment = assignment


def _op(model: dict, record: dict, counters: dict, stats: dict):
    """The op kind over a stand-in for the CP: only what `verify` reads."""
    op = submit_wait.Op(types.SimpleNamespace(
        traffic={"params": {"wait_s": 5}}, name=CELL))
    op.model = model
    store = types.SimpleNamespace(find_one=lambda table, pred: _Rec(record))
    op.cp = types.SimpleNamespace(state=types.SimpleNamespace(
        store=store, admission=types.SimpleNamespace(stats=stats)))
    return op


def test_the_op_calls_a_parked_moved_untold_uncommitted_or_fallback_wave_a_fault(
        monkeypatch):
    model = _tiny(4, 3, 4)
    placed = reference.schedule(model, {})
    init = {p["name"]: placed[p["name"]] for p in model["init"]}
    counters = dict.fromkeys(submit_wait.UNMOVED, 0.0)
    monkeypatch.setattr(submit_wait.Watch, "counters",
                        staticmethod(lambda: dict(counters)))
    nodes = sorted(model["nodes"])

    def verify(record=placed, verdicts=None, moved=None, compactions=0,
               pending=0):
        stats = {"compactions": 0}
        op = _op(model, record, counters, stats)
        counters.update(dict.fromkeys(counters, 0.0))
        prepared = {"model": model, "before": init,
                    "watched": op._watched()}
        counters.update(moved or {})
        stats["compactions"] = compactions
        reply = {"pending": pending, "verdicts": verdicts or [
            {"id": f"adm_{i}", "kind": "arrival", "name": p["name"],
             "state": "placed", "server": placed[p["name"]]}
            for i, p in enumerate(model["wave"])]}
        return op.verify(prepared, reply)

    assert verify() == (4, [])
    wave0, init0 = model["wave"][0]["name"], model["init"][0]["name"]
    parked = [{"id": "adm_0", "kind": "arrival", "name": wave0,
               "state": "parked", "reason": "capacity"}] + [
        {"id": f"adm_{i}", "kind": "arrival", "name": p["name"],
         "state": "placed", "server": placed[p["name"]]}
        for i, p in enumerate(model["wave"]) if i]
    _n, faults = verify(verdicts=parked)
    assert any("not every pod placed" in f for f in faults)
    _n, faults = verify(moved={"fleet_admission_parked_total": 1.0})
    assert any("parked_total moved by 1" in f for f in faults)
    _n, faults = verify(moved={"fleet_admission_sheds_total": 2.0})
    assert any("sheds_total moved by 2" in f for f in faults)
    _n, faults = verify(moved={"fleet_admission_moved_rows_total": 3.0})
    assert any("moved_rows_total moved by 3" in f for f in faults)
    _n, faults = verify(
        moved={"fleet_placement_churn_fallbacks_total": 1.0})
    assert any("churn_fallbacks_total moved by 1" in f for f in faults)
    _n, faults = verify(compactions=1)
    assert any("compactions moved by 1" in f for f in faults)
    elsewhere = next(n for n in nodes if n != placed[init0])
    _n, faults = verify(record=dict(placed, **{init0: elsewhere}))
    assert any("'moved': 1" in f for f in faults)
    other = next(n for n in nodes if n != placed[wave0])
    _n, faults = verify(record=dict(placed, **{wave0: other}))
    assert any("'untold': 1" in f for f in faults)
    # told, and not in the store's record: not committed
    _n, faults = verify(record={n: s for n, s in placed.items()
                                if n != wave0})
    assert any("'unplaced': 1" in f for f in faults)
    _n, faults = verify(record=dict(placed, **{"pod-9-0": nodes[0]}))
    assert any("'ghost': 1" in f for f in faults)
    op = _op(model, placed, counters, {"compactions": 0})
    prepared = {"model": model, "before": init, "watched": op._watched()}
    assert op.verify(prepared, {"accepted": [], "queued": 4}) \
        == (0, ["the reply carries no verdicts"])


def test_the_op_kind_refuses_a_program_that_cannot_tell_a_caller(monkeypatch):
    """Laid over the parent of the PR that brought `wait`, the cell fails
    at once and cleanly: before any server is registered."""
    import asyncio

    from fleetflow_tpu.cp.admission import AdmissionController

    monkeypatch.delattr(AdmissionController, "verdicts")
    op = submit_wait.Op(types.SimpleNamespace(
        traffic={"params": {"wait_s": 5}}, name=CELL))
    with pytest.raises(RuntimeError, match="has no `wait`"):
        asyncio.run(op.setup())


def test_the_timed_part_sends_one_submit_and_no_status():
    import asyncio

    sent = []

    class Conn:
        async def request(self, channel, method, payload, timeout=None):
            sent.append((channel, method, sorted(payload)))
            return {"verdicts": [], "pending": 0}

    op = submit_wait.Op(types.SimpleNamespace(
        traffic={"params": {"wait_s": 5}}, name=CELL))
    op.cp = types.SimpleNamespace(conn=Conn())
    model = _tiny(2, 1, 2)
    asyncio.run(op.request({
        "model": model,
        "request": generators.submit_request(model["wave"], 5)}))
    assert sent == [("deploy", "submit",
                     ["arrivals", "stage", "tenant", "wait"])]
    assert op.last is model
