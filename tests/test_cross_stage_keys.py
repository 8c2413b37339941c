"""Conflict keys reach across stages (PR 29).

A commitment and an open reservation remember which conflict keys — host
ports, exclusive volumes, anti-affinity labels declared to reach another
stage — their rows hold on which server, and every lowering against live
inventory bars a stage's rows from the servers on which ANOTHER stage holds
one of their keys (cp/placement.py, lower/tensors.py).

The two-namespace cases compare the system with the plain reference the
benchmark uses (benchmarks/reference_k8s.py: Kubernetes scheduler_perf's
SchedulingPodAntiAffinity as data, a one-pod-at-a-time scheduler and a
checker), at a size a CPU solves in no time.
"""

from __future__ import annotations

import copy
import dataclasses

import numpy as np
import pytest

from benchmarks import generators_k8s, reference_k8s
from benchmarks.reference_k8s import INIT, MEASURED
from fleetflow_tpu.core.model import ResourceSpec, ServerResource
from fleetflow_tpu.core.parser import parse_kdl_string
from fleetflow_tpu.core.serialize import flow_from_dict, flow_to_dict
from fleetflow_tpu.cp.models import Server, ServerAllocated, ServerCapacity
from fleetflow_tpu.cp.placement import PlacementService
from fleetflow_tpu.cp.store import Store
from fleetflow_tpu.lower.tensors import lower_stage
from fleetflow_tpu.obs import trace as obs_trace
from fleetflow_tpu.obs.metrics import REGISTRY
from fleetflow_tpu.sched.fallback import relax_problem

SCHEDULERS = [pytest.param(False, id="host"), pytest.param(True, id="anneal")]


# --------------------------------------------------------------------------
# two namespaces against the reference
# --------------------------------------------------------------------------

class _Cluster:
    """`reference_k8s.cluster` registered in a store, with a
    PlacementService on it."""

    def __init__(self, nodes: int, init: int, measured: int, *,
                 use_tpu: bool, seed: int = 5):
        self.model = reference_k8s.cluster(seed, nodes, init, measured)
        self.store = Store()
        self.stream: list[tuple[int, str]] = []
        self.store.replication_sink = self.stream.extend
        for slug, node in self.model["nodes"].items():
            self.store.create("servers", Server(
                slug=slug, status="online", tenant="default",
                capacity=ServerCapacity(
                    **generators_k8s.server_capacity(node))))
        self.svc = PlacementService(self.store, use_tpu=use_tpu)

    def place(self, namespace: str, *, reach: bool = True,
              commit: bool = True) -> dict:
        request = generators_k8s.solve_request(self.model, namespace)
        if not reach:
            # the declaration cut to its own stage, as it was before the
            # `stages=` property existed
            for svc in request["flow"]["services"].values():
                del svc["anti_affinity_stages"]
        placement, rid = self.svc.solve_stage(
            flow_from_dict(request["flow"]), namespace)
        assert placement.feasible, placement.violations
        if commit:
            assert self.svc.commit(rid)
        return placement.assignment

    def load(self, slugs, cpu: float) -> None:
        """Other tenants' load on `slugs`, in the store and in the model:
        with it, a scheduler that balances load prefers the OTHER nodes."""
        for slug in slugs:
            s = self.store.server_by_slug(slug)
            self.store.update("servers", s.id,
                              allocated=ServerAllocated(cpu=cpu))
            self.model["nodes"][slug]["cpu"] -= cpu


@pytest.mark.parametrize("use_tpu", SCHEDULERS)
@pytest.mark.parametrize("reach", [True, False], ids=["reach", "cut"])
def test_tight_two_namespace_instance(use_tpu, reach):
    """20 nodes, 10 init pods, 10 measured pods: the measured pods fit
    only on the ten nodes the init pods left free. The free nodes carry
    other load, so nothing but the declaration steers a pod to them: with
    its reach cut to its own stage the same instance collides."""
    c = _Cluster(20, 10, 10, use_tpu=use_tpu)
    init = c.place(INIT, reach=reach)
    assert reference_k8s.check(c.model, {INIT: init})["total"] == 0
    free = set(c.model["nodes"]) - set(init.values())
    c.load(free, cpu=2.0)
    measured = c.place(MEASURED, reach=reach)
    found = reference_k8s.check(c.model, {INIT: init, MEASURED: measured})
    mine = reference_k8s.schedule(c.model, {INIT: init})[MEASURED]
    assert None not in mine.values() and set(mine.values()) == free
    if reach:
        assert found["total"] == 0, found
        assert set(measured.values()) == free
    else:
        assert found["anti_affinity"] > 0
        assert found["total"] == found["anti_affinity"]


def test_reference_checker_counts_a_planted_collision():
    model = reference_k8s.cluster(1, 6, 2, 2)
    mine = reference_k8s.schedule(model, {})
    assert reference_k8s.check(model, mine)["total"] == 0
    victim = next(iter(mine[INIT].values()))
    pod = next(iter(mine[MEASURED]))
    planted = {INIT: mine[INIT], MEASURED: dict(mine[MEASURED],
                                                **{pod: victim})}
    found = reference_k8s.check(model, planted)
    assert found["anti_affinity"] == 1 and found["total"] == 1
    assert reference_k8s.check(
        model, {MEASURED: {pod: "nowhere"}})["unknown"] == 1


@pytest.mark.parametrize("use_tpu", SCHEDULERS)
@pytest.mark.parametrize("first", [INIT, MEASURED])
def test_churn_resolve_keeps_the_other_namespace_apart(use_tpu, first):
    """21 nodes, 10 + 10 pods, one spare: kill the server under a measured
    pod and the churn re-solve may only use the spare node. With the
    measured namespace placed FIRST its retained problem was lowered with
    nothing held, so only the bars added at the re-solve keep it off the
    init pods' nodes."""
    c = _Cluster(21, 10, 10, use_tpu=use_tpu)
    second = INIT if first == MEASURED else MEASURED
    placed = {first: c.place(first)}
    placed[second] = c.place(second)
    assert reference_k8s.check(c.model, placed)["total"] == 0
    spare = set(c.model["nodes"]) - {n for a in placed.values()
                                     for n in a.values()}
    assert len(spare) == 1
    victim = next(iter(placed[MEASURED].values()))
    moved = dict(c.svc.node_events([(victim, False)]))
    key = f"{generators_k8s.FLOW}/{MEASURED}"
    assert list(moved) == [key] and moved[key].feasible
    after = {INIT: placed[INIT], MEASURED: moved[key].assignment}
    found = reference_k8s.check(c.model, after, offline=[victim])
    assert found["total"] == 0, found
    assert set(after[MEASURED].values()) - set(placed[MEASURED].values()) \
        == spare
    # the churn hold carries the keys to the new server, and the commit
    # moves them off the dead one
    held = c.svc._held_by_others(f"{generators_k8s.FLOW}/{INIT}")
    assert spare <= {n for slugs in held.values() for n in slugs}
    assert c.svc.commit_retained(key)
    held = c.svc._held_by_others(f"{generators_k8s.FLOW}/{INIT}")
    assert victim not in {n for slugs in held.values() for n in slugs}
    rec = c.store.find_one("placements", lambda p: p.stage_key == key)
    assert rec.held_keys == c.svc._committed[key].held_keys
    assert all(victim not in slugs for slugs in rec.held_keys.values())


def test_standby_reloads_the_keys_and_refuses_the_collision():
    """The replication stream replayed on a second Store, and a
    PlacementService promoted on it, bar the measured pods as the primary
    does (tests/test_commit_retained.py does the same for `allocated`)."""
    c = _Cluster(20, 10, 10, use_tpu=False)
    init = c.place(INIT)
    standby = Store()
    assert standby.apply_replicated(c.stream) == len(c.stream)
    promoted = PlacementService(standby, use_tpu=False)
    key = f"{generators_k8s.FLOW}/{INIT}"
    assert promoted._committed[key].held_keys \
        == c.svc._committed[key].held_keys != {}
    request = generators_k8s.solve_request(c.model, MEASURED)
    placement, _rid = promoted.solve_stage(
        flow_from_dict(request["flow"]), MEASURED)
    assert placement.feasible
    found = reference_k8s.check(
        c.model, {INIT: init, MEASURED: placement.assignment})
    assert found["total"] == 0, found
    assert not set(placement.assignment.values()) & set(init.values())


def test_rehydrate_adopts_the_placement_with_the_bars_in_place():
    """A promoted CP adopts the committed sched-1 placement; the problem
    it retains for later churn already bars the init pods' nodes."""
    c = _Cluster(21, 10, 10, use_tpu=False)
    init, measured = c.place(INIT), c.place(MEASURED)
    standby = Store()
    standby.apply_replicated(c.stream)
    promoted = PlacementService(standby, use_tpu=False)
    request = generators_k8s.solve_request(c.model, MEASURED)
    key = f"{generators_k8s.FLOW}/{MEASURED}"
    assert promoted.rehydrate(key, flow_from_dict(request["flow"]))
    pt, placement = promoted.retained(key)
    assert placement.assignment == measured
    barred = [n in set(init.values()) for n in pt.node_names]
    assert (~pt.eligible).all(axis=0).tolist() == barred
    victim = next(iter(measured.values()))
    moved = dict(promoted.node_events([(victim, False)]))
    found = reference_k8s.check(
        c.model, {INIT: init, MEASURED: moved[key].assignment},
        offline=[victim])
    assert found["total"] == 0, found


# --------------------------------------------------------------------------
# host ports and exclusive volumes are facts about the host
# --------------------------------------------------------------------------

CONFLICTS = {"port": "ports { port host=5432 container=5432 }",
             "volume": 'volume "/data/pg" "/var/lib/pg"'}


def _flow(project: str, stages: list[str], conflict: str):
    services = "\n".join(
        f'service "db-{s}" {{ image "x"; {CONFLICTS[conflict]}\n'
        f'resources {{ cpu 1; memory 64 }} }}' for s in stages)
    stage_nodes = "\n".join(
        f'stage "{s}" {{ service "db-{s}" }}' for s in stages)
    return parse_kdl_string(
        f'project "{project}"\n{services}\n{stage_nodes}\n')


def _service(servers=("n0", "n1")):
    store = Store()
    for slug in servers:
        store.create("servers", Server(
            slug=slug, status="online", tenant="default",
            capacity=ServerCapacity(cpu=8, memory=8192, disk=8192)))
    return PlacementService(store, use_tpu=False)


@pytest.mark.parametrize("conflict", sorted(CONFLICTS))
@pytest.mark.parametrize("scope", ["two_stages_one_flow", "two_flows"])
def test_host_conflicts_hold_across_stages_and_flows(conflict, scope):
    svc = _service()
    if scope == "two_stages_one_flow":
        flow = _flow("p", ["a", "b", "c"], conflict)
        stages = [(flow, "a"), (flow, "b"), (flow, "c")]
    else:
        stages = [(_flow(p, ["live"], conflict), "live")
                  for p in ("p", "q", "r")]
    used = []
    for flow, stage in stages[:2]:
        placement, rid = svc.solve_stage(flow, stage)
        assert placement.feasible and svc.commit(rid)
        used += placement.assignment.values()
    assert sorted(used) == ["n0", "n1"]
    # both servers now hold the key: the third stage has nowhere to go
    flow, stage = stages[2]
    with pytest.raises(Exception, match="no eligible node"):
        svc.solve_stage(flow, stage)


STEPS = ["reserved", "released", "stage_released", "superseded",
         "commit_undone"]


@pytest.mark.parametrize("step", STEPS)
def test_keys_follow_the_reservation(step):
    """Reserve without commit holds the key; release, release_stage and an
    undone commit return it; a superseding commit moves it."""
    svc = _service()
    flow = _flow("p", ["a", "b"], "port")
    placement, rid = svc.solve_stage(flow, "a")
    first = placement.assignment["db-a"]
    other = ({"n0", "n1"} - {first}).pop()
    barred = {first}
    if step == "released":
        assert svc.release(rid)
        barred = set()
    elif step != "reserved":
        assert svc.commit(rid)
    if step == "stage_released":
        assert svc.release_stage("p/a")
        barred = set()
    if step == "commit_undone":
        assert svc.release(rid, undo_commit=True)
        barred = set()
    if step == "superseded":
        s = svc.store.server_by_slug(first)
        svc.store.update("servers", s.id, status="offline")
        placement, rid2 = svc.solve_stage(flow, "a")
        assert placement.assignment["db-a"] == other
        # reserved on the new server and still committed on the old one
        assert {n for v in svc._held_by_others("p/b").values()
                for n in v} == {first, other}
        assert svc.commit(rid2)
        svc.store.update("servers", s.id, status="online")
        barred = {other}
    held = svc._held_by_others("p/b")
    assert {n for v in held.values() for n in v} == barred
    placement, _ = svc.solve_stage(flow, "b", reserve=False)
    pt, _ = svc.retained("p/b")
    want = [n not in barred for n in pt.node_names]
    assert pt.eligible.tolist() == [want]
    assert placement.assignment["db-b"] not in barred
    rec = svc.store.find_one("placements", lambda p: p.stage_key == "p/a")
    if step in ("reserved", "released", "stage_released", "commit_undone"):
        assert rec is None
    else:
        assert rec.held_keys == {"port:0.0.0.0/5432/tcp": sorted(barred)}


def test_a_stages_own_commitment_does_not_bar_its_redeploy():
    svc = _service(servers=("n0",))
    flow = _flow("p", ["a"], "port")
    for _ in range(2):
        placement, rid = svc.solve_stage(flow, "a")
        assert placement.feasible and svc.commit(rid)
    assert svc._committed["p/a"].held_keys \
        == {"port:0.0.0.0/5432/tcp": ["n0"]}


def test_eligibility_relaxation_keeps_the_bars():
    """A held key is physical: the fallback ladder's eligibility rung
    lifts tier and label gates, never another stage's port."""
    nodes = [ServerResource(name=n, capacity=ResourceSpec(cpu=8, memory=8192))
             for n in ("n0", "n1", "n2")]
    flow = _flow("p", ["a"], "port")
    held = {"port:0.0.0.0/5432/tcp": ["n1"]}
    pt = lower_stage(flow, "a", nodes=nodes, held=held)
    assert pt.eligible.tolist() == [[True, False, True]]
    gated = dataclasses.replace(pt, eligible=np.array([[True, False, False]]))
    relaxed = relax_problem(gated, "eligibility")
    assert relaxed.eligible.tolist() == [[True, False, True]]
    assert relax_problem(pt, "eligibility") is None


def test_admit_batch_bars_held_keys():
    """A stage's retained problem handed back to admit_batch after another
    stage committed its key on the server one of its rows runs on: the
    row is barred there (every barred row is, since what others hold
    changed) and moves."""
    svc = _service()
    flow_b = _flow("q", ["live"], "port")
    placement, _ = svc.solve_stage(flow_b, "live", reserve=False)
    taken = placement.assignment["db-live"]
    flow_a = _flow("p", ["a"], "port")
    flow_a.stages["a"].servers = [taken]
    flow_a.servers = {}
    placement, rid = svc.solve_stage(flow_a, "a")
    assert placement.assignment["db-a"] == taken and svc.commit(rid)
    pt, _ = svc.retained("q/live")
    again, _rid, _pt = svc.admit_batch("q/live", pt)
    assert again.feasible and again.assignment["db-live"] != taken


# --------------------------------------------------------------------------
# a stage alone pays nothing; the declaration's spelling; the instruments
# --------------------------------------------------------------------------

def test_one_stage_alone_lowers_the_same_with_and_without_held():
    request = generators_k8s.solve_request(
        reference_k8s.cluster(3, 12, 0, 6), MEASURED)
    flow = flow_from_dict(request["flow"])
    flow.services["pod-0-0"].ports = parse_kdl_string(
        'service "x" { ports { port host=80 container=80 } }'
    ).services["x"].ports
    nodes = [ServerResource(name=f"n{j}",
                            capacity=ResourceSpec(cpu=4, memory=32768))
             for j in range(12)]
    plain = lower_stage(flow, MEASURED, nodes=copy.deepcopy(nodes))
    for held in ({}, {"port:0.0.0.0/81/tcp": ["n3"],
                      "anti:k8s:color=blue@sched-0": ["n1"]}):
        again = lower_stage(flow, MEASURED, nodes=copy.deepcopy(nodes),
                            held=held)
        for f in dataclasses.fields(plain):
            a, b = getattr(plain, f.name), getattr(again, f.name)
            if f.name == "held":
                assert b == held
            elif isinstance(a, np.ndarray):
                assert np.array_equal(a, b), f.name
            else:
                assert a == b, f.name
    assert plain.eligible.all()
    assert plain.holds["port:0.0.0.0/80/tcp"] == [0]
    assert plain.holds["anti:k8s:color=green@sched-1"] == list(range(6))
    assert plain.holds["anti:k8s:color=green>sched-0"] == list(range(6))
    assert plain.barred_by["anti:k8s:color=green@sched-0"] == list(range(6))
    assert plain.barred_by["anti:k8s:color=green>sched-1"] == list(range(6))


KDL = '''
project "k8s"
service "pod" {
    image "registry.k8s.io/pause:3.9"
    anti_affinity "color=green" stages="sched-1,sched-0"
    anti_affinity "rack"
}
stage "sched-1" { service "pod" }
'''


def test_declaration_round_trips_and_lints(tmp_path, capsys):
    flow = parse_kdl_string(KDL)
    pod = flow.services["pod"]
    assert pod.anti_affinity == ["color=green", "rack"]
    assert pod.anti_affinity_stages == {"color=green": ["sched-1",
                                                        "sched-0"]}
    wire = flow_to_dict(flow)
    assert wire["services"]["pod"]["anti_affinity_stages"] \
        == {"color=green": ["sched-1", "sched-0"]}
    back = flow_from_dict(wire).services["pod"]
    assert back.anti_affinity_stages == pod.anti_affinity_stages
    assert back.anti_affinity == pod.anti_affinity
    # a service without the property serializes as it always has
    assert "anti_affinity_stages" not in flow_to_dict(parse_kdl_string(
        'project "p"\nservice "s" { image "x"; anti_affinity "rack" }\n'
        'stage "a" { service "s" }\n'))["services"]["s"]

    from fleetflow_tpu.cli.main import main
    cfg = tmp_path / ".fleetflow"
    cfg.mkdir()
    (cfg / "fleet.kdl").write_text(KDL)
    rc = main(["--project-root", str(tmp_path), "lint", "--strict"])
    assert rc == 0, capsys.readouterr().out


def test_held_phase_and_counters():
    held_total = REGISTRY.get("fleet_placement_held_keys_total")
    barred_total = REGISTRY.get("fleet_lower_barred_cells_total")
    c = _Cluster(12, 4, 4, use_tpu=False)
    c.place(INIT)
    h0, b0 = held_total.value(), barred_total.value()
    t0 = obs_trace.time.perf_counter()
    c.place(MEASURED)
    # two keys (the init pods are green in sched-0, and reach into
    # sched-1) on four servers; four rows barred from four servers by
    # each, the second key finding the bits already cleared
    assert held_total.value() - h0 == 8
    assert barred_total.value() - b0 == 16
    spans = obs_trace.spans_between(t0, obs_trace.time.perf_counter())
    names = [s[0] for s in spans]
    assert names.count("cp.solve_stage.held") == 1
    inv = next(s for s in spans if s[0] == "cp.solve_stage.inventory")
    held = next(s for s in spans if s[0] == "cp.solve_stage.held")
    assert inv[1] <= held[1] and held[2] <= inv[2]
