"""High-priority pods through streaming admission: Kubernetes
scheduler_perf's PreemptionBasic drained from the CP's queue.

A streamed arrival may carry a `priority` (cp/admission.py `make_arrival`;
the fold writes it into the stream's `priority`, a departure clears it).
Where a micro-batch's lowest live row ranks above another stage's committed
rows, `PlacementService.admit_batch` counts what those rows hold as
capacity and prices it (lower/tensors.py `with_price`; the resident delta
carries it and the merge prices on device), solves once, selects the
fewest victims per server after the solve, and the reservation's commit
evicts them in the acknowledged write — eight commits a wave, each on a
book the last one changed.

The served wave and the chunkings are compared with the benchmark's plain
reference (benchmarks/reference_k8s_preempt_admit.py, which imports nothing
of the program) on the same cluster: every pod placed and told, capacity
over the survivors, no victim of no lower priority or needless, three
victims a pod, `allocated` the sum of what remains, nothing moved.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time

import numpy as np
import pytest

from benchmarks import generators_k8s_preempt_admit as gen
from benchmarks import reference_k8s_preempt_admit as ref
from benchmarks.reference_k8s_preemption import INIT, MEASURED
from fleetflow_tpu.core.model import (Flow, ResourceSpec, ServerLabels,
                                      Service, Stage)
from fleetflow_tpu.cp.admission import (AdmissionConfig,
                                        AdmissionController,
                                        AdmissionRequest)
from fleetflow_tpu.cp.models import Server, ServerCapacity
from fleetflow_tpu.cp.placement import PlacementService
from fleetflow_tpu.cp.protocol import ProtocolClient
from fleetflow_tpu.cp.server import ServerConfig, start
from fleetflow_tpu.cp.store import Store
from fleetflow_tpu.lower.tensors import (Node, lower_stage,
                                         preemption_price, with_price)
from fleetflow_tpu.obs import trace as obs_trace
from fleetflow_tpu.obs.metrics import REGISTRY
from fleetflow_tpu.sched.tpu import TpuSolverScheduler

INIT_KEY = f"{gen.FLOW}/{INIT}"
WAIT_S = 120.0


def _counter(name: str, **labels) -> float:
    metric = REGISTRY.get(name)
    return 0.0 if metric is None else metric.value(**labels)


class _World:
    """The reference's cluster registered online in a store, a placement
    service on it with sched-0's low pods solved and committed by
    `solve_stage`, and an admission controller whose passes are driven by
    hand, sched-1 opened empty."""

    def __init__(self, nodes: int, wave: int, *, use_tpu: bool = True,
                 batch: int = 4, seed: int = 5):
        self.model = ref.cluster(seed, nodes, 4 * nodes, wave)
        self.store = Store()
        for slug, node in self.model["nodes"].items():
            self.store.create("servers", Server(
                slug=slug, status="online", tenant="default",
                capacity=ServerCapacity(**gen.server_capacity(node))))
        self.pl = PlacementService(self.store, use_tpu=use_tpu)
        placement, rid = self.pl.solve_stage(gen.flow(self.model, INIT),
                                             INIT)
        assert placement.feasible and self.pl.commit(rid)
        self.init = self.records()[INIT]
        self.adm = AdmissionController(
            self.pl, config=AdmissionConfig(batch_max=batch,
                                            shed_age_s=0.0,
                                            max_queue=100_000))
        flow = Flow(name=gen.FLOW)
        flow.stages[MEASURED] = Stage(name=MEASURED, services=[])
        self.key = self.adm.attach(flow, MEASURED)

    def records(self) -> dict:
        out = {}
        for ns in (INIT, MEASURED):
            rec = self.store.find_one(
                "placements",
                lambda p, k=f"{gen.FLOW}/{ns}": p.stage_key == k)
            out[ns] = dict(rec.assignment) if rec is not None else {}
        return out

    def allocated(self) -> dict:
        return {s.slug: (s.allocated.cpu, s.allocated.memory)
                for s in self.store.list("servers")}

    def drain(self) -> list[dict]:
        out = []
        while self.adm.has_work():
            out.append(self.adm.step())
        return out

    def wave(self, op: int) -> tuple[dict, list[dict]]:
        """Op `op`'s wave submitted and drained: (its model, the passes)."""
        model = ref.wave(self.model, op)
        self.adm.submit("default", stage=self.key, arrivals=gen.arrivals(
            model["namespaces"][MEASURED]))
        return model, self.drain()

    def told(self, model: dict) -> dict:
        names = {p["name"] for p in model["namespaces"][MEASURED]}
        return {r.name: r.server for r in self.adm.requests.values()
                if r.name in names and r.state == "placed"}

    def leave(self, model: dict) -> None:
        self.adm.submit("default", stage=self.key, departures=[
            p["name"] for p in model["namespaces"][MEASURED]])
        self.drain()

    def check(self, model: dict, before: dict) -> dict:
        return ref.check(model, before, self.records(), self.told(model),
                         self.allocated(),
                         3 * len(model["namespaces"][MEASURED]))


# --------------------------------------------------------------------------
# the source's shape, small, on the served path
# --------------------------------------------------------------------------

def test_a_wave_through_the_queue_preempts_as_the_reference_says(
        monkeypatch):
    """24 nodes that 96 low pods fill, sched-1 opened empty, then three
    waves of 16 high pods in one deploy.submit each at admission_batch 4,
    each withdrawn and its victims reinstated in turn: every verdict
    `placed`, the reference's check 0 on both namespaces' records and the
    servers read back, 48 victims a wave, nothing moved, no cold staging
    after the first wave."""
    monkeypatch.setenv("FLEET_SUBSOLVE_MIN", "8")
    nodes, wave = 24, 16

    async def go():
        model = ref.cluster(21, nodes, 4 * nodes, wave)
        mine, gone = ref.schedule(model)
        assert sum(map(len, gone.values())) == 3 * wave
        handle = await start(ServerConfig(use_tpu_solver=True,
                                          admission_batch=4))
        store, pl = handle.state.store, handle.state.placement
        for slug, node in model["nodes"].items():
            rec = store.register_server(slug, tenant="default",
                                        hostname=slug)
            store.update("servers", rec.id, status="online",
                         capacity=ServerCapacity(
                             **gen.server_capacity(node)))
        conn, task = await ProtocolClient.connect(
            handle.host, handle.port, identity="test-client")

        def records() -> dict:
            out = {}
            for ns in (INIT, MEASURED):
                rec = store.find_one(
                    "placements",
                    lambda p, k=f"{gen.FLOW}/{ns}": p.stage_key == k)
                out[ns] = dict(rec.assignment) if rec is not None else {}
            return out

        try:
            placement, rid = pl.solve_stage(gen.flow(model, INIT), INIT)
            assert placement.feasible and pl.commit(rid)
            init = records()[INIT]
            opened = await conn.request("deploy", "submit",
                                        gen.attach_request(), timeout=60)
            assert opened["stage"] == gen.KEY
            for op in range(3):
                w = ref.wave(model, op)
                before = records()
                assert before[INIT] == init
                moved = _counter("fleet_admission_moved_rows_total")
                victims = _counter("fleet_placement_victims_total")
                cold = _counter("fleet_solver_resident_reuse_total",
                                outcome="cold")
                reply = await conn.request(
                    "deploy", "submit",
                    gen.submit_request(w["namespaces"][MEASURED], WAIT_S),
                    timeout=WAIT_S + 30)
                assert {v["state"] for v in reply["verdicts"]} \
                    == {"placed"} and reply["pending"] == 0
                told = {v["name"]: v["server"] for v in reply["verdicts"]}
                allocated = {s.slug: (s.allocated.cpu, s.allocated.memory)
                             for s in store.list("servers")}
                found = ref.check(w, before, records(), told, allocated,
                                  3 * wave)
                assert found["total"] == 0, found
                assert _counter("fleet_placement_victims_total") \
                    == victims + 3 * wave
                assert _counter("fleet_admission_moved_rows_total") == moved
                if op:
                    assert _counter("fleet_solver_resident_reuse_total",
                                    outcome="cold") == cold
                gone = await conn.request(
                    "deploy", "submit",
                    {"tenant": gen.TENANT, "stage": gen.KEY, "wait": WAIT_S,
                     "departures": list(told)}, timeout=WAIT_S + 30)
                assert {v["state"] for v in gone["verdicts"]} \
                    == {"departed"}
                assert pl.reinstate(INIT_KEY) == 3 * wave
                assert records() == {INIT: init, MEASURED: {}}
        finally:
            await conn.close()
            task.cancel()
            await handle.stop()
    asyncio.run(asyncio.wait_for(go(), 300))


# --------------------------------------------------------------------------
# the rules, in-process
# --------------------------------------------------------------------------

@pytest.mark.parametrize("use_tpu", [False, True], ids=["host", "anneal"])
def test_three_victims_a_pod(use_tpu):
    """Each high pod of a wave of eight, at batch 3, leaves exactly three
    of its server's four low pods gone, and each victim stage's record
    lost exactly them; the reference's check reads 0."""
    w = _World(16, 8, use_tpu=use_tpu, batch=3)
    before = w.records()
    model, passes = w.wave(0)
    assert len(passes) == 3
    after = w.records()
    gone = set(before[INIT]) - set(after[INIT])
    assert len(gone) == 24
    per_server: dict[str, int] = {}
    for name in gone:
        per_server[before[INIT][name]] = per_server.get(
            before[INIT][name], 0) + 1
    assert sorted(per_server) == sorted(after[MEASURED].values())
    assert set(per_server.values()) == {3}
    assert w.check(model, before)["total"] == 0


def test_reinstate_puts_back_the_victims_of_every_commit():
    """A wave of ten evicts in four commits (batch 3); once it has left
    through the queue, one `reinstate` puts all thirty victims back where
    they were, the record of sched-0 and every server's `allocated` as
    before the wave; the next wave evicts again from that book."""
    w = _World(16, 10, batch=3)
    allocated = w.allocated()
    model, passes = w.wave(0)
    assert len(passes) == 4
    assert len(w.pl._committed[INIT_KEY].rows.evicted) == 30
    w.leave(model)
    assert w.pl.reinstate(INIT_KEY) == 30
    assert w.records() == {INIT: w.init, MEASURED: {}}
    after = w.allocated()
    assert all(np.allclose(after[s], allocated[s]) for s in allocated)
    before = w.records()
    again, _ = w.wave(1)
    assert w.check(again, before)["total"] == 0


def test_a_stage_that_does_not_outrank_opens_neither_phase():
    """Arrivals of priority 0 beside committed rows of priority 0 (the
    basic and anti-affine cells' case), and a stage with no other stage
    committed: no `cp.admit_batch.preemptible` or `.victims` phase, no
    preemptible server counted, no victim, no price on the candidate."""
    w = _World(8, 0, batch=4)
    servers = _counter("fleet_placement_preemptible_servers_total")
    t0 = time.perf_counter()
    w.adm.submit("default", stage=w.key, arrivals=[
        {"name": f"plain-{i}", "cpu": 0.1, "memory": 10.0}
        for i in range(6)])
    w.drain()
    names = {s[0] for s in obs_trace.spans_between(t0, time.perf_counter())}
    assert "cp.admission.step" in names
    assert not {"cp.admit_batch.preemptible",
                "cp.admit_batch.victims"} & names
    assert _counter("fleet_placement_preemptible_servers_total") == servers
    assert w.records()[INIT] == w.init
    pt, _ = w.pl.retained(w.key)
    assert not pt.priced and pt.preferred is None
    # a stage alone in the book
    store = Store()
    store.create("servers", Server(slug="n0", status="online",
                                   tenant="default",
                                   capacity=ServerCapacity(cpu=4.0,
                                                           memory=1024.0)))
    pl = PlacementService(store, use_tpu=True)
    adm = AdmissionController(pl, config=AdmissionConfig(batch_max=4))
    flow = Flow(name="solo")
    flow.stages["s"] = Stage(name="s", services=[])
    key = adm.attach(flow, "s")
    t0 = time.perf_counter()
    adm.submit("default", stage=key, arrivals=[
        {"name": "hi", "cpu": 1.0, "memory": 10.0, "priority": 10}])
    while adm.has_work():
        adm.step()
    names = {s[0] for s in obs_trace.spans_between(t0, time.perf_counter())}
    assert "cp.admission.step" in names
    assert "cp.admit_batch.preemptible" not in names
    assert adm.requests[next(iter(adm.requests))].state == "placed"


def test_a_preempting_micro_batch_makes_one_device_solve(monkeypatch):
    """Every pass of a wave whose arrivals fit nowhere — the first, and
    those after it, where the stage's own rows stand on the servers it
    has taken — calls the scheduler once, on the resident delta from the
    second wave on, and opens both phases once."""
    w = _World(16, 8, batch=4)
    first, _ = w.wave(0)
    w.leave(first)
    assert w.pl.reinstate(INIT_KEY) == 24
    calls = []
    place = TpuSolverScheduler.place

    def counted(self, pt, **kw):
        calls.append(pt.S)
        return place(self, pt, **kw)

    monkeypatch.setattr(TpuSolverScheduler, "place", counted)
    delta = _counter("fleet_solver_resident_reuse_total", outcome="delta")
    cold = _counter("fleet_solver_resident_reuse_total", outcome="cold")
    t0 = time.perf_counter()
    before = w.records()
    model, passes = w.wave(1)
    assert len(passes) == 2 and len(calls) == 2
    assert _counter("fleet_solver_resident_reuse_total",
                    outcome="delta") == delta + 2
    assert _counter("fleet_solver_resident_reuse_total",
                    outcome="cold") == cold
    spans = obs_trace.spans_between(t0, time.perf_counter())
    count = {n: sum(s[0] == n for s in spans)
             for n in ("cp.admission.step", "cp.admit_batch.preemptible",
                       "cp.admit_batch.victims")}
    assert count == dict.fromkeys(count, 2)
    assert w.check(model, before)["total"] == 0


def test_the_first_wave_into_an_empty_stream_moves_nobody(monkeypatch):
    """The first wave appends its rows, each micro-batch's parked together
    on one server: a priced sub-solve's prologue moves each off it to a
    server of its own, so every pass is localized and no incumbent moves
    (on the full path incumbents moved, and the victims evicted for them
    stayed evicted: needless)."""
    from fleetflow_tpu.cp.admission import subsolve_outcomes

    monkeypatch.setenv("FLEET_SUBSOLVE_MIN", "32")
    w = _World(100, 96, batch=32)
    before = w.records()
    outcomes = subsolve_outcomes()
    model, passes = w.wave(0)
    assert len(passes) == 3
    after = subsolve_outcomes()
    assert after["localized"] - outcomes["localized"] == 2
    assert after["fallback_infeasible"] == outcomes["fallback_infeasible"]
    assert w.adm.stats["moved_rows"] == 0
    assert w.check(model, before)["total"] == 0


@pytest.mark.parametrize("batch", [1, 3, 8, 100])
def test_any_chunking_of_a_wave_evicts_three_a_pod(batch):
    """The same wave of eight drained at batch 1, 3, 8 and in one batch:
    24 victims, the reference's check 0, nothing moved."""
    w = _World(12, 8, batch=batch, use_tpu=False)
    before = w.records()
    model, passes = w.wave(0)
    assert len(passes) == -(-8 // batch)
    assert w.check(model, before)["total"] == 0
    assert w.adm.stats["moved_rows"] == 0


def test_a_streamed_stage_is_no_victim():
    """A stage that admission streams keeps its rows: high pods streamed
    into sched-1 find the servers filled by low pods streamed into
    another stage and evict none of them — they park, and that stage's
    record is untouched — where rows of a stage committed by
    `solve_stage` are victims (the other tests)."""
    store = Store()
    for j in range(3):
        store.create("servers", Server(
            slug=f"n{j}", status="online", tenant="default",
            capacity=ServerCapacity(cpu=4.0, memory=32768.0)))
    pl = PlacementService(store, use_tpu=True)
    adm = AdmissionController(pl, config=AdmissionConfig(
        batch_max=4, shed_age_s=0.0))
    keys = {}
    for ns in ("low", "high"):
        flow = Flow(name=gen.FLOW)
        flow.stages[ns] = Stage(name=ns, services=[])
        keys[ns] = adm.attach(flow, ns)
    adm.submit("default", stage=keys["low"], arrivals=[
        {"name": f"low-{i}", "cpu": 0.9, "memory": 500.0}
        for i in range(12)])
    while adm.has_work():
        adm.step()
    low = dict(pl._committed[keys["low"]].assignment)
    assert len(low) == 12
    adm.submit("default", stage=keys["high"], arrivals=[
        {"name": "high-0", "cpu": 3.0, "memory": 500.0, "priority": 10}])
    adm.step()
    assert adm.requests[max(adm.requests,
                            key=lambda i: int(i.rsplit("_", 1)[1]))
                        ].state == "parked"
    assert dict(pl._committed[keys["low"]].assignment) == low
    assert keys["high"] not in pl._committed


# --------------------------------------------------------------------------
# the arrival's priority, and the price on the resident delta
# --------------------------------------------------------------------------

def test_an_arrival_keeps_its_priority_and_refuses_another_type():
    adm = AdmissionController(None)
    assert adm.make_arrival({"name": "a", "priority": 10}).priority == 10
    assert adm.make_arrival({"name": "b"}).priority == 0
    for bad in (1.5, "10", True, None):
        with pytest.raises(ValueError, match="priority"):
            adm.make_arrival({"name": "c", "priority": bad})


def test_the_fold_writes_priorities_and_a_departure_clears_them():
    w = _World(8, 4, batch=100)
    model, _ = w.wave(0)
    pt = w.adm._streams[w.key].pt
    assert pt.priority.tolist() == [10, 10, 10, 10]
    w.adm.submit("default", stage=w.key, departures=[
        model["namespaces"][MEASURED][1]["name"]])
    w.drain()
    assert w.adm._streams[w.key].pt.priority.tolist() == [10, 0, 10, 10]


def test_a_parked_arrival_keeps_its_priority_through_the_journal():
    store = Store()
    adm = AdmissionController(None, store=store)
    svc = adm.make_arrival({"name": "p", "cpu": 3.0, "priority": 7})
    r = AdmissionRequest(id="adm_1", tenant="t", kind="arrival", name="p",
                         stage_key="k/s", submitted_at=0.0, seq=1,
                         service=svc, demand=np.zeros(3))
    adm._journal_park(r, "capacity")
    again = AdmissionController(None, store=store)
    assert again._parked[0].service.priority == 7


def test_the_price_rides_the_resident_delta():
    """A priced candidate whose lower ranks' hold changed stages on the
    delta path, and the plane the merge writes on device is the host's
    `preemption_price` of the merged rows, to the bit."""
    from fleetflow_tpu.solver.resident import ProblemDelta, ResidentProblem

    flow = Flow(name="f")
    for i in range(6):
        flow.services[f"s{i}"] = Service(
            name=f"s{i}", image="x",
            resources=ResourceSpec(cpu=1.0 + 0.5 * (i % 3), memory=100.0))
    flow.stages["st"] = Stage(name="st", services=list(flow.services))
    nodes = [Node(f"n{j}", ServerLabels()) for j in range(5)]
    base = lower_stage(flow, "st", nodes=nodes,
                       capacity=np.full((5, 3), 1.0, np.float32),
                       valid=np.ones(5, bool))
    pre = np.tile(np.array([[3.0, 512.0, 0.0]], np.float32), (5, 1))
    pt = with_price(base, pre)
    rp = ResidentProblem(pt)
    rp.adopt_host(np.arange(6) % 5, pt.node_valid, warm=False)
    pre2 = pre.copy()
    pre2[2] = [0.5, 100.0, 0.0]
    rows = np.array([1, 4], np.int32)
    demand = np.array(base.demand)
    demand[rows] = [[2.5, 50.0, 0.0], [0.0, 0.0, 0.0]]
    cand = with_price(dataclasses.replace(base, demand=demand), pre2)
    delta = ProblemDelta(demand_rows=(rows, demand[rows]),
                         node_valid=cand.node_valid, capacity=cand.capacity,
                         preemptible=cand.preemptible)
    assert rp.compatible(cand, delta)
    assert not rp.compatible(cand, ProblemDelta(
        demand_rows=(rows, demand[rows])))
    rp.apply_delta(cand, delta)
    plane = np.asarray(rp.prob.preferred)[:cand.S]
    assert np.array_equal(plane, cand.preferred)
    assert np.array_equal(cand.preferred, preemption_price(
        demand, cand.capacity, pre2))
    assert cand.preferred.min() < 0 and (cand.preferred[4] == 0).all()
