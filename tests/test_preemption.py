"""Priority and preemption (PR 33).

A service has a priority; a stage that fits nowhere evicts committed rows
of other stages that rank strictly below all of its own: the fewest per
server that make room, as part of the acknowledged commit
(cp/placement.py's class docstring has the five rules, lower/tensors.py
the lowering).

The cluster cases compare the system with the plain reference the
benchmark uses (benchmarks/reference_k8s_preemption.py: Kubernetes
scheduler_perf's PreemptionBasic as data, a one-pod-at-a-time scheduler
that preempts, and a checker), at a size a CPU solves in no time, under
the host scheduler and the annealer both.
"""

from __future__ import annotations

import asyncio
import copy
import dataclasses
import json

import numpy as np
import pytest

from benchmarks import generators_k8s_preemption as gen
from benchmarks import reference_k8s_preemption as ref
from benchmarks.reference_k8s_preemption import INIT, MEASURED
from fleetflow_tpu.core.model import ResourceSpec, ServerResource
from fleetflow_tpu.core.parser import parse_kdl_string
from fleetflow_tpu.core.serialize import flow_from_dict, flow_to_dict
from fleetflow_tpu.cp.models import Server, ServerCapacity
from fleetflow_tpu.cp.placement import PlacementService
from fleetflow_tpu.cp.store import Store
from fleetflow_tpu.lower.tensors import lower_stage, preemption_cost
from fleetflow_tpu.obs import trace as obs_trace
from fleetflow_tpu.obs.metrics import REGISTRY

SCHEDULERS = [pytest.param(False, id="host"), pytest.param(True, id="anneal")]
KEY = {INIT: f"{gen.FLOW}/{INIT}", MEASURED: f"{gen.FLOW}/{MEASURED}"}


def _pods(prefix: str, shapes) -> list[dict]:
    """Pods `prefix`-0.. of (cpu, priority) or (cpu, priority, memory)."""
    return [{"name": f"{prefix}-{i}", "cpu": s[0], "priority": s[1],
             "memory": s[2] if len(s) > 2 else 100.0}
            for i, s in enumerate(shapes)]


def _model(nodes: int, init, measured) -> dict:
    return {"nodes": {f"n{j}": dict(ref.NODE) for j in range(nodes)},
            "namespaces": {INIT: _pods("low", init),
                           MEASURED: _pods("high", measured)}}


class _Cluster:
    """A model registered in a store, with a PlacementService on it."""

    def __init__(self, model: dict, *, use_tpu: bool = False):
        self.model = model
        self.store = Store()
        self.stream: list[tuple[int, str]] = []
        self.store.replication_sink = self.stream.extend
        for slug, node in model["nodes"].items():
            self.store.create("servers", Server(
                slug=slug, status="online", tenant="default",
                capacity=ServerCapacity(**gen.server_capacity(node))))
        self.svc = PlacementService(self.store, use_tpu=use_tpu)

    @classmethod
    def basic(cls, nodes=12, measured=6, **kw) -> "_Cluster":
        """PreemptionBasic small: four low pods a node, none fits a
        fifth; the init namespace is committed."""
        c = cls(ref.cluster(5, nodes, 4 * nodes, measured), **kw)
        c.init = c.place(INIT)
        return c

    def flow(self, namespace: str):
        return flow_from_dict(
            gen.solve_request(self.model, namespace)["flow"])

    def solve(self, namespace: str, **kw):
        return self.svc.solve_stage(self.flow(namespace), namespace, **kw)

    def place(self, namespace: str) -> dict:
        placement, rid = self.solve(namespace)
        assert placement.feasible, placement.violations
        assert self.svc.commit(rid)
        return dict(placement.assignment)

    def victims(self, rid: str) -> dict:
        out: dict[str, dict] = {}
        for v in self.svc.victims(rid):
            out.setdefault(v["stage"].split("/", 1)[1],
                           {})[v["service"]] = v["server"]
        return out

    def record(self, namespace: str):
        return self.store.find_one(
            "placements", lambda p: p.stage_key == KEY[namespace])

    def allocated(self) -> dict[str, tuple]:
        return {s.slug: (s.allocated.cpu, s.allocated.memory)
                for s in self.store.list("servers")}

    def booked(self, *namespaces) -> dict[str, tuple]:
        """What the placement records of `namespaces` say each server
        carries, summed from the model's pods."""
        out = {slug: [0.0, 0.0] for slug in self.model["nodes"]}
        for ns in namespaces:
            rec = self.record(ns)
            pods = {p["name"]: p for p in self.model["namespaces"][ns]}
            for name, slug in (rec.assignment if rec else {}).items():
                out[slug][0] += pods[name]["cpu"]
                out[slug][1] += pods[name]["memory"]
        return {k: tuple(v) for k, v in out.items()}


def _close(a: dict, b: dict) -> bool:
    return a.keys() == b.keys() and all(
        np.allclose(a[k], b[k], rtol=1e-5, atol=1e-5) for k in a)


# --------------------------------------------------------------------------
# the PreemptionBasic shape against the reference
# --------------------------------------------------------------------------

@pytest.mark.parametrize("use_tpu", SCHEDULERS)
def test_preemption_basic_small_matches_the_reference(use_tpu):
    """Every node holds four low pods; a high pod fits nowhere and needs
    exactly three of them gone: three victims a pod, as the reference
    finds, and the checker finds no fault over both namespaces."""
    c = _Cluster.basic(12, 6, use_tpu=use_tpu)
    assert sorted(np.bincount(
        [list(c.model["nodes"]).index(n) for n in c.init.values()])) \
        == [4] * 12
    placement, rid = c.solve(MEASURED)
    assert placement.feasible
    assert placement.source == ("cpu-anneal" if use_tpu else "host-greedy")
    victims = c.victims(rid)
    mine, theirs = ref.schedule(c.model, {INIT: c.init})
    assert None not in mine[MEASURED].values()
    assert sum(map(len, victims.values())) == \
        sum(map(len, theirs.values())) == 18
    found = ref.check(c.model, {INIT: c.init,
                                MEASURED: placement.assignment}, victims)
    assert found["total"] == 0, found
    # each node that took a high pod loses three, the others none
    took = set(placement.assignment.values())
    assert len(took) == 6
    per_node = np.unique(list(victims[INIT].values()), return_counts=True)
    assert set(per_node[0]) == took and set(per_node[1]) == {3}


@pytest.mark.parametrize("use_tpu", SCHEDULERS)
def test_commit_is_read_back_from_the_store(use_tpu):
    """Rule 4: when commit answers, the victim stage's record has lost
    exactly the victims, the arriving stage's holds the assignment, every
    touched server's `allocated` is the sum of what remains, and all of
    it went to the journal."""
    c = _Cluster.basic(12, 6, use_tpu=use_tpu)
    before = c.allocated()
    placement, rid = c.solve(MEASURED)
    victims = c.victims(rid)
    assert c.allocated() == before          # a reservation moves nothing
    assert len(c.record(INIT).assignment) == 48
    mark = len(c.stream)
    assert c.svc.commit(rid)
    assert c.svc.victims(rid) == [
        {"stage": KEY[INIT], "service": n, "server": s}
        for n, s in victims[INIT].items()]
    assert dict(c.record(MEASURED).assignment) == placement.assignment
    assert dict(c.record(INIT).assignment) == {
        n: s for n, s in c.init.items() if n not in victims[INIT]}
    assert _close(c.allocated(), c.booked(INIT, MEASURED))
    touched = set(placement.assignment.values())
    for slug, dem in c.record(INIT).demand_by_node.items():
        left = sum(1 for s in c.record(INIT).assignment.values()
                   if s == slug)
        assert np.isclose(dem[0], 0.9 * left, rtol=1e-5)
    # one journaled write a touched server — each once in the commit's
    # `upd` entries — and both placement records
    written = [json.loads(e) for _seq, e in c.stream[mark:]]
    servers = [c.store.get("servers", rid).slug for e in written
               if e["t"] == "servers" for rid in e["u"]]
    assert sorted(servers) == sorted(touched)
    assert sum(e["t"] == "placements" for e in written) == 2
    found = ref.check(c.model, {INIT: c.init,
                                MEASURED: placement.assignment}, victims)
    assert found["total"] == 0, found


def test_release_of_a_reservation_with_victims_leaves_the_book_untouched():
    c = _Cluster.basic()
    before, record = c.allocated(), dict(c.record(INIT).assignment)
    mark = len(c.stream)
    _placement, rid = c.solve(MEASURED)
    assert c.svc.victims(rid)
    assert c.svc.release(rid)
    assert c.allocated() == before
    assert dict(c.record(INIT).assignment) == record == c.init
    assert c.record(MEASURED) is None
    assert len(c.stream) == mark
    assert c.svc.victims(rid) == []
    # and the rows are preemptible again for the next arrival
    _placement, rid = c.solve(MEASURED)
    assert len(c.svc.victims(rid)) == 18


def test_a_reloaded_store_holds_the_post_eviction_book():
    """A second PlacementService on the same store (a restart, a promoted
    standby) reloads the book as the eviction left it, and its next
    commit supersedes instead of stacking."""
    c = _Cluster.basic()
    placement, rid = c.solve(MEASURED)
    victims = c.victims(rid)
    assert c.svc.commit(rid)
    again = PlacementService(c.store)
    book = again.reservations_snapshot()["committed"]
    assert {b["stage"] for b in book} == set(KEY.values())
    assert dict(again._committed[KEY[INIT]].assignment) == {
        n: s for n, s in c.init.items() if n not in victims[INIT]}
    assert again.release_stage(KEY[MEASURED])
    assert _close(c.allocated(), c.booked(INIT))


def test_a_reloaded_commitment_is_no_victim_until_rehydrated():
    c = _Cluster.basic()
    again = PlacementService(c.store)
    flow = c.flow(MEASURED)
    assert not again.solve_stage(flow, MEASURED)[0].feasible
    assert again.rehydrate(KEY[INIT], c.flow(INIT))
    placement, rid = again.solve_stage(flow, MEASURED)
    assert placement.feasible and len(again.victims(rid)) == 18
    assert again.commit(rid)
    assert _close(c.allocated(), c.booked(INIT, MEASURED))


@pytest.mark.parametrize("use_tpu", SCHEDULERS)
def test_reinstate_puts_the_victims_back(use_tpu):
    """`release_stage` of the batch, then `reinstate` of the victims'
    stage: the init state again, record and servers; and not while the
    batch still stands."""
    c = _Cluster.basic(12, 6, use_tpu=use_tpu)
    before = c.allocated()
    for _ in range(2):
        c.place(MEASURED)
        assert len(c.record(INIT).assignment) == 30
        assert c.svc.reinstate(KEY[INIT]) == 0      # no room yet
        assert len(c.record(INIT).assignment) == 30
        assert c.svc.release_stage(KEY[MEASURED])
        assert c.svc.reinstate(KEY[INIT]) == 18
        assert dict(c.record(INIT).assignment) == c.init
        assert _close(c.allocated(), before)
        assert c.svc.reinstate(KEY[INIT]) == 0      # nothing left to do
        retained = c.svc.retained(KEY[INIT])[1]
        assert retained.assignment == c.init


# --------------------------------------------------------------------------
# who is a victim, and who never is
# --------------------------------------------------------------------------

def test_a_batch_that_fits_evicts_nothing():
    """Rule 3: room on some nodes and the batch fits there: no victim,
    and the lowering got no preemptible capacity at all."""
    c = _Cluster(_model(6, [(0.9, 0)] * 6, [(3.0, 10)] * 2))
    c.init = c.place(INIT)
    counter = REGISTRY.get("fleet_placement_victims_total")
    n0 = counter.value()
    placement, rid = c.solve(MEASURED)
    assert placement.feasible and c.svc.victims(rid) == []
    assert c.svc.commit(rid) and counter.value() == n0
    assert dict(c.record(INIT).assignment) == c.init
    found = ref.check(c.model, {INIT: c.init,
                                MEASURED: placement.assignment}, {})
    assert found["total"] == 0, found


@pytest.mark.parametrize("use_tpu", SCHEDULERS)
def test_room_elsewhere_is_preferred_to_eviction(use_tpu):
    """Half the nodes full, half empty. Four high pods fit in what is
    free: solved as lowered, nothing preemptible on the problem, nothing
    evicted. Six do not: the problem carries what is preemptible and its
    price, at least two full nodes lose three pods each, and no victim is
    needless. (How many pods the price steers to the empty nodes is the
    solver's: the device's greedy seed reads the plane, the host's does
    not.)"""
    c = _Cluster(_model(8, [], [(3.0, 10)] * 4), use_tpu=use_tpu)
    c.model["namespaces"][INIT] = _pods("low", [(0.9, 0)] * 16)
    flow = c.flow(INIT)
    flow.stages[INIT].servers = ["n0", "n1", "n2", "n3"]
    placement, rid = c.svc.solve_stage(flow, INIT)
    assert placement.feasible and c.svc.commit(rid)
    c.init = dict(placement.assignment)
    placement, rid = c.solve(MEASURED, reserve=False)
    pt = c.svc.retained(KEY[MEASURED])[0]
    assert pt.preemptible is None and pt.preferred is None
    assert placement.feasible
    assert set(placement.assignment.values()) == {"n4", "n5", "n6", "n7"}
    c.model["namespaces"][MEASURED] = _pods("high", [(3.0, 10)] * 6)
    placement, rid = c.solve(MEASURED)
    pt = c.svc.retained(KEY[MEASURED])[0]
    assert pt.preemptible is not None and pt.preferred is not None
    assert placement.feasible
    victims = c.victims(rid)
    found = ref.check(c.model, {INIT: c.init,
                                MEASURED: placement.assignment}, victims)
    assert found["total"] == 0, found
    full = set(placement.assignment.values()) & {"n0", "n1", "n2", "n3"}
    assert len(full) >= 2 and len(victims[INIT]) == 3 * len(full)
    assert pt.preferred[0].tolist() == pytest.approx(
        [-185 / 256] * 4 + [0.0] * 4)


@pytest.mark.parametrize("priority", [0, 7, 10], ids=lambda p: f"p{p}")
def test_equal_and_higher_priority_are_never_victims(priority):
    """Only strictly lower ranks: a full cluster of priority-10 rows is
    infeasible for a priority-0, -7 or -10 batch, and nothing moves."""
    c = _Cluster(_model(4, [(0.9, 10)] * 16, [(3.0, priority)] * 2))
    c.init = c.place(INIT)
    before = c.allocated()
    placement, rid = c.solve(MEASURED)
    assert not placement.feasible and rid is None
    assert c.svc.retained(KEY[MEASURED])[0].preemptible is None
    assert c.allocated() == before
    assert dict(c.record(INIT).assignment) == c.init


def test_a_mixed_batch_preempts_as_its_lowest_row():
    """Rule 1: rows of priority 10 and 5 in one batch evict only what
    ranks below 5: priority-7 rows stay, priority-0 rows go."""
    init = [(0.9, 7)] * 8 + [(0.9, 0)] * 8
    c = _Cluster(_model(4, init, [(3.0, 10), (3.0, 5)]))
    c.init = c.place(INIT)
    placement, rid = c.solve(MEASURED)
    pods = {p["name"]: p for p in c.model["namespaces"][INIT]}
    victims = c.victims(rid)
    if placement.feasible:
        assert victims and all(pods[n]["priority"] == 0
                               for n in victims[INIT])
        found = ref.check(c.model, {INIT: c.init,
                                    MEASURED: placement.assignment},
                          victims)
        assert found["total"] == 0, found
    # the same cluster, all of the init pods at 7: the priority-10 row
    # alone could evict them, the batch cannot
    c = _Cluster(_model(4, [(0.9, 7)] * 16, [(3.0, 10), (3.0, 5)]))
    c.place(INIT)
    assert not c.solve(MEASURED)[0].feasible
    c.model["namespaces"][MEASURED] = _pods("high", [(3.0, 10), (3.0, 8)])
    placement, rid = c.solve(MEASURED)
    assert placement.feasible and len(c.svc.victims(rid)) == 6


def test_an_open_reservation_is_never_a_victim():
    """The low pods are reserved, not committed: their capacity is taken
    and nobody may evict them."""
    c = _Cluster(ref.cluster(5, 6, 24, 3))
    placement, low_rid = c.solve(INIT)
    assert placement.feasible
    placement, rid = c.solve(MEASURED)
    assert not placement.feasible and rid is None
    # committed, they are; and once claimed by one open reservation they
    # are no other's: a second batch finds the other nodes, then nothing
    assert c.svc.commit(low_rid)
    first, rid1 = c.solve(MEASURED)
    assert first.feasible and len(c.svc.victims(rid1)) == 9
    c.model["namespaces"]["sched-2"] = _pods("more", [(3.0, 10)] * 3)
    second, rid2 = c.solve("sched-2")
    assert second.feasible and len(c.svc.victims(rid2)) == 9
    assert not (set(first.assignment.values())
                & set(second.assignment.values()))
    assert not ({v["service"] for v in c.svc.victims(rid1)}
                & {v["service"] for v in c.svc.victims(rid2)})
    c.model["namespaces"]["sched-3"] = _pods("most", [(3.0, 10)])
    third, rid3 = c.solve("sched-3")
    assert not third.feasible
    assert c.svc.commit(rid1) and c.svc.commit(rid2)
    assert len(c.record(INIT).assignment) == 24 - 18


def test_a_churn_hold_is_never_a_victim():
    """Two low pods of 3 cpu on two of three nodes; one node dies and its
    pod moves to the third under a churn hold. A high pod may evict the
    committed pod that stayed; the held capacity is nobody's to take."""
    c = _Cluster(_model(3, [(3.0, 0)] * 2, [(3.0, 10)]))
    c.init = c.place(INIT)
    dead, stays = c.init["low-0"], c.init["low-1"]
    assert dead != stays
    moved = dict(c.svc.node_events([(dead, False)]))[KEY[INIT]]
    assert moved.feasible and dead not in moved.assignment.values()
    assert any(r["churn"] for r in
               c.svc.reservations_snapshot()["in_flight"])
    # the book still says low-1 is on `stays`: that row may go; the third
    # node is held for whichever pod moved there
    placement, rid = c.solve(MEASURED)
    assert placement.feasible
    assert placement.assignment == {"high-0": stays}
    assert c.victims(rid) == {INIT: {"low-1": stays}}


def test_a_commit_whose_victims_were_committed_anew_is_refused():
    c = _Cluster.basic()
    before = c.allocated()
    _placement, rid = c.solve(MEASURED)
    assert c.svc.victims(rid)
    assert c.svc.commit_retained(KEY[INIT])    # the same rows, a new book
    assert not c.svc.commit(rid)
    assert c.allocated() == before and c.record(MEASURED) is None
    assert dict(c.record(INIT).assignment) == c.init


@pytest.mark.parametrize("use_tpu", SCHEDULERS)
def test_no_needless_victim_among_pods_of_many_sizes(use_tpu):
    """Rule 3 on heterogeneous pods: whatever the packing of the init
    pods came to, no victim can be put back on its server alone."""
    sizes = [(2.0, 0), (1.0, 1), (0.5, 2)] * 6
    c = _Cluster(_model(6, sizes, [(2.5, 10)] * 6), use_tpu=use_tpu)
    c.init = c.place(INIT)
    placement, rid = c.solve(MEASURED)
    assert placement.feasible
    victims = c.victims(rid)
    assert victims
    found = ref.check(c.model, {INIT: c.init,
                                MEASURED: placement.assignment}, victims)
    assert found["total"] == 0, found
    assert c.svc.commit(rid)
    assert _close(c.allocated(), c.booked(INIT, MEASURED))


def test_a_shrunken_node_gains_no_phantom_capacity():
    """A node that shrank under its commitment is in deficit; what its
    low pods hold is added to the deficit, not to zero: the arrival gets
    the node's 3 cpu and all four low pods go."""
    c = _Cluster(_model(1, [(0.9, 0)] * 4, [(3.0, 10)]))
    c.init = c.place(INIT)
    s = c.store.server_by_slug("n0")
    c.store.update("servers", s.id, capacity=ServerCapacity(
        cpu=3.0, memory=s.capacity.memory))
    c.model["nodes"]["n0"]["cpu"] = 3.0
    placement, rid = c.solve(MEASURED)
    pt = c.svc.retained(KEY[MEASURED])[0]
    assert pt.capacity[0, 0] == pytest.approx(3.0)
    assert pt.preemptible[0, 0] == pytest.approx(3.0)
    assert placement.feasible and len(c.victims(rid)[INIT]) == 4
    found = ref.check(c.model, {INIT: c.init,
                                MEASURED: placement.assignment},
                      c.victims(rid))
    assert found["total"] == 0, found
    # a 3.5-cpu arrival does not fit the node at all
    c.model["namespaces"][MEASURED] = _pods("big", [(3.5, 10)])
    assert not c.solve(MEASURED)[0].feasible


def test_reprieve_is_highest_priority_first():
    """One node, low pods of priority 0, 1, 2 and 0.9 cpu each plus one of
    1.2 at priority 3: a 1.9-cpu arrival needs 1.6 freed of 0.1 free: the
    two lowest go, the two highest are reprieved."""
    c = _Cluster(_model(1, [(0.9, 0), (0.9, 1), (0.9, 2), (1.2, 3)],
                        [(1.9, 10)]))
    c.init = c.place(INIT)
    placement, rid = c.solve(MEASURED)
    assert placement.feasible
    assert c.victims(rid) == {INIT: {"low-0": "n0", "low-1": "n0"}}
    mine, theirs = ref.schedule(c.model, {INIT: c.init})
    assert theirs == c.victims(rid)


# --------------------------------------------------------------------------
# the reference's checker
# --------------------------------------------------------------------------

def test_reference_places_the_cluster_and_counts_planted_faults():
    model = ref.cluster(2, 8, 32, 4)
    mine, victims = ref.schedule(model, {})
    was = {INIT: {**mine[INIT], **victims[INIT]}, MEASURED: mine[MEASURED]}
    assert len(victims[INIT]) == 12
    assert ref.check(model, was, victims)["total"] == 0
    # a victim on a node that took no arrival: nothing outranks it there,
    # and it could be put back
    spared = next(n for n in model["nodes"]
                  if n not in mine[MEASURED].values())
    pod = next(p for p, n in was[INIT].items() if n == spared)
    found = ref.check(model, was, {INIT: {**victims[INIT], pod: spared}})
    assert found["victim_priority"] == 1 and found["victim_needless"] == 1
    assert found["total"] == 2
    # a needless victim beside the arrival: the fourth low pod of a node
    # whose other three had to go
    node = next(iter(mine[MEASURED].values()))
    last = next(p for p, n in mine[INIT].items() if n == node)
    found = ref.check(model, was, {INIT: {**victims[INIT], last: node}})
    assert found == {**dict.fromkeys(ref.KINDS, 0), "victim_needless": 4,
                     "total": 4}
    # a victim too few: the node is over capacity
    gone = dict(victims[INIT])
    gone.pop(next(p for p, n in gone.items() if n == node))
    found = ref.check(model, was, {INIT: gone})
    assert found["cpu"] == 1 and found["total"] == 1
    # a victim that was never there
    found = ref.check(model, was, {INIT: {**victims[INIT], "nobody": node}})
    assert found["victim_unknown"] == 1


# --------------------------------------------------------------------------
# the victim stage afterwards
# --------------------------------------------------------------------------

@pytest.mark.parametrize("use_tpu", SCHEDULERS)
def test_a_churn_resolve_does_not_resurrect_victims(use_tpu):
    """Three low pods a node with 1.3 cpu free; two high pods evict two
    each. The node of one high pod dies: the low pod that survived there
    moves into free room, the victims stay gone from every view, from the
    hold and from the commitment that follows."""
    c = _Cluster(_model(4, [(0.9, 0)] * 12, [(3.0, 10)] * 2),
                 use_tpu=use_tpu)
    c.init = c.place(INIT)
    assert sorted(np.unique(list(c.init.values()),
                            return_counts=True)[1]) == [3] * 4
    placement, rid = c.solve(MEASURED)
    victims = c.victims(rid)[INIT]
    assert len(victims) == 4 and c.svc.commit(rid)
    retained = c.svc.retained(KEY[INIT])[1]
    assert set(retained.assignment) == set(c.init) - set(victims)
    dead = placement.assignment["high-0"]
    survivor = next(n for n, s in c.init.items()
                    if s == dead and n not in victims)
    moved = dict(c.svc.node_events([(dead, False)]))[KEY[INIT]]
    assert moved.feasible
    assert set(moved.assignment) == set(c.init) - set(victims)
    assert dead not in moved.assignment.values()
    # the hold books the survivors that moved and nothing of a victim
    holds = [r for r in c.svc.reservations_snapshot()["in_flight"]
             if r["churn"] and r["stage"] == KEY[INIT]]
    assert holds and survivor in [n for n, s in moved.assignment.items()
                                  if s != c.init[n]]
    assert sum(d[0] for h in holds
               for d in h["demand_by_node"].values()) <= 0.9 * 8 + 1e-3
    assert c.svc.commit_retained(KEY[INIT])
    assert dict(c.record(INIT).assignment) == moved.assignment
    found = ref.check(c.model, {INIT: {**victims, **moved.assignment},
                                MEASURED: placement.assignment},
                      {INIT: victims}, offline=[dead])
    # capacity holds over what remains; the pods have moved since, so
    # whether a victim could now be put back is no longer the question
    assert found["cpu"] == found["memory"] == found["unplaced"] == 0, found
    assert _close(c.allocated(), c.booked(INIT, MEASURED))


# --------------------------------------------------------------------------
# lowering, spelling, wire, instruments
# --------------------------------------------------------------------------

def test_a_stage_that_preempts_nothing_lowers_the_same_tensors():
    model = ref.cluster(3, 12, 0, 6)
    flow = flow_from_dict(gen.solve_request(model, MEASURED)["flow"])
    nodes = [ServerResource(name=f"n{j}",
                            capacity=ResourceSpec(cpu=4, memory=32768))
             for j in range(12)]
    plain = lower_stage(flow, MEASURED, nodes=copy.deepcopy(nodes))
    assert plain.preferred is None and plain.preemptible is None
    assert plain.priority.tolist() == [10] * 6
    for pre in (None, np.zeros((12, 3))):
        again = lower_stage(flow, MEASURED, nodes=copy.deepcopy(nodes),
                            preemptible=pre)
        for f in dataclasses.fields(plain):
            a, b = getattr(plain, f.name), getattr(again, f.name)
            if isinstance(a, np.ndarray):
                assert np.array_equal(a, b), f.name
            else:
                assert a == b, f.name
    # default priorities carry no array at all
    for svc in flow.services.values():
        svc.priority = 0
    assert lower_stage(flow, MEASURED, nodes=nodes).priority is None


def test_the_cost_of_preemptible_capacity_goes_into_the_preference_plane():
    model = ref.cluster(3, 4, 0, 2)
    flow = flow_from_dict(gen.solve_request(model, MEASURED)["flow"])
    # what is free, as the CP hands it over, and what lower ranks hold
    free = [0.4, 2.2, 0.4, 4.0]
    nodes = [ServerResource(name=f"n{j}",
                            capacity=ResourceSpec(cpu=cpu, memory=32768))
             for j, cpu in enumerate(free)]
    pre = np.zeros((4, 3))
    pre[0] = [3.6, 2000.0, 0.0]         # n0: full of low pods
    pre[1] = [1.8, 1000.0, 0.0]         # n1: half of it is low pods'
    pt = lower_stage(flow, MEASURED, nodes=nodes, preemptible=pre)
    assert pt.capacity[:, 0].tolist() == pytest.approx([4.0, 4.0, 0.4, 4.0])
    assert np.array_equal(pt.preemptible, pre.astype(np.float32))
    # the row's 3 cpu overflow n0's 0.4 free by 2.6 of 3.6 preemptible,
    # n1's 2.2 free by 0.8 of 1.8; n3 has room; n2 (full of its equals)
    # cannot take it at all
    assert pt.preferred.shape == (2, 4)
    want = -np.rint(np.array([2.6 / 3.6, 0.8 / 1.8, 1.0, 0.0]) * 256) / 256
    assert np.allclose(pt.preferred[0], want)
    # servers filled alike give every cell one cost: no plane
    same = np.tile([3.6, 2000.0, 0.0], (4, 1)).astype(np.float32)
    assert preemption_cost(pt.demand, np.full((4, 3), 0.4, np.float32),
                           same) is None
    for n in nodes:
        n.capacity = ResourceSpec(cpu=0.4, memory=30768)
    alike = lower_stage(flow, MEASURED, nodes=nodes, preemptible=same)
    assert alike.preferred is None and alike.preemptible is not None
    assert alike.capacity[:, 0].tolist() == pytest.approx([4.0] * 4)


KDL = """
project "p"
service "batch" { image "x"; resources { cpu 1; memory 64 } }
service "prod" { image "x"; priority 10; resources { cpu 1; memory 64 } }
stage "a" { service "batch"; service "prod" }
"""


def test_priority_is_spelled_parsed_and_carried_over_the_wire():
    flow = parse_kdl_string(KDL)
    assert flow.services["prod"].priority == 10
    assert flow.services["batch"].priority == 0
    wire = flow_to_dict(flow)
    assert wire["services"]["prod"]["priority"] == 10
    assert "priority" not in wire["services"]["batch"]
    back = flow_from_dict(wire)
    assert back.services["prod"].priority == 10
    assert back.services["batch"].priority == 0
    # a stage override keeps the base's priority unless it names one
    over = parse_kdl_string(KDL + 'stage "b" { service "prod" { '
                            'image "y" } }\n')
    assert over.stage("b").resolved_services(over)[0].priority == 10
    assert lower_stage(flow, "a").priority.tolist() == [0, 10]


def test_a_placement_record_serializes_as_asdict_would():
    from fleetflow_tpu.cp.models import PlacementRecord, Record
    c = _Cluster.basic(6, 2)
    c.place(MEASURED)
    for ns in (INIT, MEASURED):
        rec = c.record(ns)
        mine, plain = rec.to_dict(), Record.to_dict(rec)
        assert mine == plain and list(mine) == list(plain)
        assert PlacementRecord.from_dict(mine) == rec
        mine["assignment"]["x"] = "y"
        mine["demand_by_node"][next(iter(rec.demand_by_node))].append(0.0)
        assert rec.to_dict() == plain       # a copy, one level down too


def test_phases_and_counters_fire():
    victims_total = REGISTRY.get("fleet_placement_victims_total")
    servers_total = REGISTRY.get("fleet_placement_preemptible_servers_total")
    c = _Cluster.basic(12, 6)
    v0, s0 = victims_total.value(), servers_total.value()
    t0 = obs_trace.time.perf_counter()
    _placement, rid = c.solve(MEASURED)
    assert servers_total.value() - s0 == 12
    assert victims_total.value() == v0          # nothing is gone yet
    assert c.svc.commit(rid)
    assert victims_total.value() - v0 == 18
    spans = obs_trace.spans_between(t0, obs_trace.time.perf_counter())

    def one(name):
        found = [s for s in spans if s[0] == name]
        assert len(found) == 1, name
        return found[0]

    inv, pre = one("cp.solve_stage.inventory"), one(
        "cp.solve_stage.preemptible")
    assert inv[1] <= pre[1] and pre[2] <= inv[2]
    whole, picked = one("cp.solve_stage"), one("cp.solve_stage.victims")
    assert whole[1] <= picked[1] and picked[2] <= whole[2]
    assert one("cp.solve_stage.solve")[2] <= picked[1]
    commit, evict = one("cp.commit"), one("cp.commit.evict")
    assert commit[1] <= evict[1] and evict[2] <= commit[2]
    # a stage that ranks above nobody opens none of them
    t0 = obs_trace.time.perf_counter()
    c.svc.release_stage(KEY[MEASURED])
    c.solve(INIT, reserve=False)
    names = {s[0] for s in obs_trace.spans_between(
        t0, obs_trace.time.perf_counter())}
    assert not names & {"cp.solve_stage.preemptible",
                        "cp.solve_stage.victims", "cp.commit.evict"}


def test_victims_ride_the_wire_and_deploy_execute_refuses_them():
    """`placement.solve` replies the victims, `placement.commit` how many
    it evicted; `deploy.execute` of a stage that needs victims is refused
    with the book as it was."""
    from test_cp import FakeAgent, connect, start_cp
    from fleetflow_tpu.cp.protocol import RpcError
    from fleetflow_tpu.runtime.engine import DeployRequest

    model = ref.cluster(5, 4, 16, 2)

    async def go():
        handle = await start_cp()
        agents = [await FakeAgent(slug).connect(handle)
                  for slug in model["nodes"]]
        store = handle.state.store
        for slug, node in model["nodes"].items():
            store.update("servers", store.server_by_slug(slug).id,
                         status="online", capacity=ServerCapacity(
                             **gen.server_capacity(node)))
        conn, _ = await connect(handle)
        low = await conn.request("placement", "solve",
                                 gen.solve_request(model, INIT))
        assert low["feasible"] and low["victims"] == []
        done = await conn.request("placement", "commit",
                                  {"reservation": low["reservation"]})
        assert done == {"ok": True, "evicted": 0}
        allocated = {s.slug: s.allocated.cpu for s in store.list("servers")}

        flow = gen.flow(model, MEASURED)
        for pod in model["namespaces"][MEASURED]:
            flow.services[pod["name"]].priority = pod["priority"]
        flow.stages[MEASURED].servers = list(model["nodes"])
        with pytest.raises(RpcError, match="deploy.execute does not "
                                           "preempt"):
            await conn.request("deploy", "execute", {"request": DeployRequest(
                flow=flow, stage_name=MEASURED).to_dict()}, timeout=10)
        assert all(cmd != "deploy.execute"
                   for a in agents for cmd, _p in a.commands)
        assert {s.slug: s.allocated.cpu
                for s in store.list("servers")} == allocated
        snap = handle.state.placement.reservations_snapshot()
        assert snap["in_flight"] == []

        high = await conn.request("placement", "solve",
                                  gen.solve_request(model, MEASURED))
        assert high["feasible"] and len(high["victims"]) == 6
        assert {v["stage"] for v in high["victims"]} == {KEY[INIT]}
        assert all(low["assignment"][v["service"]] == v["server"]
                   for v in high["victims"])
        done = await conn.request("placement", "commit",
                                  {"reservation": high["reservation"]})
        assert done == {"ok": True, "evicted": 6}
        gone = {INIT: {v["service"]: v["server"] for v in high["victims"]}}
        found = ref.check(model, {INIT: low["assignment"],
                                  MEASURED: high["assignment"]}, gone)
        assert found["total"] == 0, found
        for a in agents:
            await a.conn.close()
        await conn.close()
        await handle.stop()

    asyncio.run(asyncio.wait_for(go(), 60))


def test_admit_batch_preempts():
    c = _Cluster.basic(6, 2)
    preview, _ = c.solve(MEASURED, reserve=False)
    assert preview.feasible
    pt, _ = c.svc.retained(KEY[MEASURED])
    again, rid, _pt = c.svc.admit_batch(KEY[MEASURED], pt)
    assert again.feasible and rid is not None


def test_a_started_cp_settles_the_collector():
    """`cp.server.start` freezes what is alive and looks at the oldest
    generation a tenth as often: a full collection walks every record
    and row the CP keeps, and ran in every second request."""
    import gc

    from test_cp import start_cp

    async def go():
        handle = await start_cp()
        await handle.stop()

    before = gc.get_threshold()
    try:
        gc.unfreeze()
        gc.set_threshold(700, 10, 10)
        asyncio.run(asyncio.wait_for(go(), 30))
        assert gc.get_threshold() == (700, 10, 100)
        assert gc.get_freeze_count() > 10_000
    finally:
        gc.unfreeze()
        gc.set_threshold(*before)
