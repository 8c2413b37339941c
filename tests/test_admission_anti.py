"""Anti-affine pods through streaming admission (PR 45): Kubernetes
scheduler_perf's SchedulingPodAntiAffinity through the CP's queue.

A streamed arrival may now declare a label-style anti-affinity term and
its reach (`anti_affinity`, `anti_affinity_stages`: the spelling of
core/serialize.py). The fold writes the row's group id and the stage's
keys (lower/tensors.py `anti_keys`), a departure clears them,
`PlacementService.admit_batch` bars the arrivals from the servers on which
another stage holds their key, the reservation records what they hold, and
the micro-solve rides the resident delta (the ids scattered by
`ProblemDelta.conflict_rows`), localized to the arrivals: a fresh arrival
pulls in no conflict partner (solver/subsolve.py).

The served scenario is compared with the benchmark's plain reference
(benchmarks/reference_k8s_anti_admit.py, which imports nothing of the
program) on the same cluster. The others drive a controller and a
placement service in-process, and make the answer unique where they can:
eligible nodes narrowed so that exactly the servers a key leaves free
remain.
"""

from __future__ import annotations

import asyncio
import os

import numpy as np
import pytest

from benchmarks import generators_k8s_anti_admit as gen
from benchmarks import reference_k8s_anti_admit as ref
from benchmarks.reference_k8s import INIT, MEASURED
from fleetflow_tpu.core.model import Flow, ResourceSpec, Service, Stage
from fleetflow_tpu.cp.admission import (AdmissionConfig,
                                        AdmissionController,
                                        subsolve_outcomes)
from fleetflow_tpu.cp.models import Server, ServerCapacity
from fleetflow_tpu.cp.placement import PlacementService
from fleetflow_tpu.cp.protocol import ProtocolClient
from fleetflow_tpu.cp.server import ServerConfig, start
from fleetflow_tpu.cp.store import Store
from fleetflow_tpu.lower.tensors import lower_stage
from fleetflow_tpu.obs.metrics import REGISTRY

LABEL = "color=green"
REACH = {LABEL: [MEASURED, INIT]}
WAIT_S = 120.0


def _counter(name: str, **labels) -> float:
    metric = REGISTRY.get(name)
    assert metric is not None, f"the program has no counter {name}"
    return metric.value(**labels)


def _pod(name: str, **extra) -> dict:
    """A green pod's wire spec, anti-affine to green pods of both
    namespaces."""
    return {"name": name, "cpu": 0.1, "memory": 500.0,
            "labels": {"color": "green"}, "anti_affinity": [LABEL],
            "anti_affinity_stages": dict(REACH), **extra}


class _World:
    """`nodes` registered online in a store, a placement service on it and
    an admission controller whose passes are driven by hand; sched-1 is
    opened empty."""

    def __init__(self, nodes: int, *, use_tpu: bool = True, batch: int = 4,
                 cpu: float = 4.0):
        self.store = Store()
        self.nodes = [f"n{j:03d}" for j in range(nodes)]
        for slug in self.nodes:
            self.store.create("servers", Server(
                slug=slug, status="online", tenant="default",
                capacity=ServerCapacity(cpu=cpu, memory=32768.0)))
        self.pl = PlacementService(self.store, use_tpu=use_tpu)
        self.adm = AdmissionController(
            self.pl, config=AdmissionConfig(batch_max=batch,
                                            shed_age_s=0.0,
                                            max_queue=100_000))
        flow = Flow(name=gen.FLOW)
        flow.stages[MEASURED] = Stage(name=MEASURED, services=[])
        self.key = self.adm.attach(flow, MEASURED)

    def drain(self) -> None:
        while self.adm.has_work():
            self.adm.step()

    def record(self, key: str) -> dict:
        rec = self.store.find_one("placements",
                                  lambda p: p.stage_key == key)
        return dict(rec.assignment) if rec is not None else {}

    def place_other(self, names: list[str], servers: list[str],
                    stage: str = INIT) -> dict:
        """A stage of green pods anti-affine over both namespaces, solved
        and committed on `servers` by `solve_stage`."""
        flow = Flow(name=gen.FLOW)
        for n in names:
            flow.services[n] = Service(
                name=n, image="x", anti_affinity=[LABEL],
                anti_affinity_stages=dict(REACH),
                resources=ResourceSpec(cpu=0.1, memory=500.0, disk=0.0))
        flow.stages[stage] = Stage(name=stage, services=list(names),
                                   servers=list(servers))
        placement, rid = self.pl.solve_stage(flow, stage)
        assert placement.feasible and self.pl.commit(rid)
        return placement.assignment


# --------------------------------------------------------------------------
# the source's shape, small, on the served path
# --------------------------------------------------------------------------

NODES, INIT_PODS, WAVE, BATCH = 60, 12, 24, 8


def test_the_sources_shape_small_against_the_reference(monkeypatch):
    """60 nodes, 12 init pods in sched-0 placed by placement.solve, sched-1
    opened empty, then three waves of 24 anti-affine pods in one
    deploy.submit each at batch_max 8, every wave withdrawn in turn: every
    verdict `placed`, the reference's check 0 on both namespaces' records
    read back (no two green pods a server, told = committed, moved 0,
    ghost 0), the rows reused from the second wave on."""
    monkeypatch.setenv("FLEET_SUBSOLVE_MIN", "8")

    async def go():
        model = ref.cluster(21, NODES, INIT_PODS, WAVE)
        mine = ref.schedule(model)
        assert ref.check(model, {}, mine, mine[MEASURED])["total"] == 0
        handle = await start(ServerConfig(use_tpu_solver=True,
                                          admission_batch=BATCH))
        store, adm = handle.state.store, handle.state.admission
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
            reply = await conn.request("placement", "solve",
                                       gen.init_request(model), timeout=60)
            done = await conn.request(
                "placement", "commit",
                {"reservation": reply["reservation"]}, timeout=60)
            assert done["ok"]
            opened = await conn.request("deploy", "submit",
                                        gen.attach_request(), timeout=60)
            assert opened == {"accepted": [], "queued": 0,
                              "stage": gen.KEY}
            for op in range(3):
                wave = ref.wave(model, op)
                before = records()
                moved = _counter("fleet_admission_moved_rows_total")
                reply = await conn.request(
                    "deploy", "submit",
                    gen.submit_request(wave["namespaces"][MEASURED],
                                       WAIT_S), timeout=WAIT_S + 30)
                states = {v["state"] for v in reply["verdicts"]}
                assert states == {"placed"} and reply["pending"] == 0
                told = {v["name"]: v["server"] for v in reply["verdicts"]}
                found = ref.check(wave, before, records(), told)
                assert found["total"] == 0, found
                assert _counter("fleet_admission_moved_rows_total") == moved
                gone = await conn.request(
                    "deploy", "submit",
                    {"tenant": gen.TENANT, "stage": gen.KEY, "wait": WAIT_S,
                     "departures": list(told)}, timeout=WAIT_S + 30)
                assert {v["state"] for v in gone["verdicts"]} \
                    == {"departed"}
                assert records()[MEASURED] == {}
            assert adm._streams[gen.KEY].pt.S == WAVE
            assert adm.stats["compactions"] == 0
        finally:
            await conn.close()
            task.cancel()
            await handle.stop()
    asyncio.run(asyncio.wait_for(go(), 300))


# --------------------------------------------------------------------------
# keys across stages, both ways, and a departure's key
# --------------------------------------------------------------------------

@pytest.mark.parametrize("use_tpu", [False, True], ids=["host", "anneal"])
def test_a_key_committed_between_two_micro_solves_bars_the_next(use_tpu):
    """sched-1's first micro-batch lands; another stage then commits green
    pods on four servers; the next micro-batch, whose pods may only use
    those four and two others, lands on the two others."""
    w = _World(10, use_tpu=use_tpu, batch=2)
    w.adm.submit("t0", arrivals=[_pod("a0"), _pod("a1")], stage=w.key)
    w.drain()
    first = set(w.record(w.key).values())
    assert len(first) == 2
    rest = [n for n in w.nodes if n not in first]
    taken, free = rest[:4], rest[4:6]
    held = w.place_other(["i0", "i1", "i2", "i3"], taken)
    assert set(held.values()) == set(taken)
    w.adm.submit("t0", arrivals=[
        _pod("b0", eligible_nodes=taken + free),
        _pod("b1", eligible_nodes=taken + free)], stage=w.key)
    w.drain()
    after = w.record(w.key)
    assert {after["b0"], after["b1"]} == set(free)
    assert {after["a0"], after["a1"]} == first


@pytest.mark.parametrize("use_tpu", [False, True], ids=["host", "anneal"])
def test_a_solve_of_the_other_namespace_is_barred_by_streamed_pods(use_tpu):
    """The reverse direction: sched-1's streamed pods hold their key in the
    reservation they were committed with, so a placement.solve of sched-0
    given their servers and two more lands on the two more."""
    w = _World(8, use_tpu=use_tpu, batch=4)
    w.adm.submit("t0", arrivals=[_pod(f"a{i}") for i in range(4)],
                 stage=w.key)
    w.drain()
    streamed = sorted(set(w.record(w.key).values()))
    assert len(streamed) == 4
    free = [n for n in w.nodes if n not in streamed][:2]
    held = w.pl._committed[w.key].held_keys
    assert held[f"anti:{gen.FLOW}:{LABEL}@{MEASURED}"] == streamed
    placed = w.place_other(["i0", "i1"], streamed + free)
    assert set(placed.values()) == set(free)


@pytest.mark.parametrize("use_tpu", [False, True], ids=["host", "anneal"])
def test_a_departure_frees_its_key(use_tpu):
    """Three pods on the three servers they may use; one leaves, and the
    stage's held keys name the servers its live pods are on; the next
    arrival on the same three servers takes the one left free — on the
    device path the one the departed pod freed, the others staying (the
    host greedy re-places the whole stage)."""
    w = _World(6, use_tpu=use_tpu, batch=4)
    three = w.nodes[:3]
    key = f"anti:{gen.FLOW}:{LABEL}@{MEASURED}"

    def held() -> list[str]:
        return w.pl._committed[w.key].held_keys[key]

    w.adm.submit("t0", arrivals=[_pod(f"a{i}", eligible_nodes=three)
                                 for i in range(3)], stage=w.key)
    w.drain()
    on = w.record(w.key)
    assert sorted(on.values()) == three == held()
    w.adm.submit("t0", departures=["a1"], stage=w.key)
    w.drain()
    left = w.record(w.key)
    assert sorted(left) == ["a0", "a2"]
    assert held() == sorted(set(left.values())) and len(held()) == 2
    w.adm.submit("t0", arrivals=[_pod("b0", eligible_nodes=three)],
                 stage=w.key)
    w.drain()
    after = w.record(w.key)
    assert sorted(after.values()) == three == held()
    if use_tpu:
        assert after == {"a0": on["a0"], "a2": on["a2"], "b0": on["a1"]}
    # the row was reused, its id row rewritten and not left behind
    pt = w.adm._streams[w.key].pt
    assert pt.S == 3 and (pt.anti_ids[:, 0] == 0).all()
    assert sorted(pt.holds[key]) == [0, 1, 2]


# --------------------------------------------------------------------------
# the sub-solve's closure
# --------------------------------------------------------------------------

def test_a_batch_in_a_large_group_is_localized_to_itself():
    """256 green pods running on 400 nodes, 128 green arrivals: the group
    (384 rows) is far above a quarter of the rows, yet the micro-solve is
    localized, its closure the 128 arrivals — relocated up front off the
    server they were parked on together, 0 sweeps — and no running pod
    moves."""
    w = _World(400, use_tpu=True, batch=128)
    w.adm.submit("t0", arrivals=[_pod(f"a{i}") for i in range(256)],
                 stage=w.key)
    w.drain()
    before = w.record(w.key)
    assert len(set(before.values())) == 256
    outcomes = subsolve_outcomes()
    rows = _counter("fleet_solver_subsolve_closure_rows_total")
    moved = w.adm.stats["moved_rows"]
    w.adm.submit("t0", arrivals=[_pod(f"b{i}") for i in range(128)],
                 stage=w.key)
    w.drain()
    took = {k: v - outcomes[k] for k, v in subsolve_outcomes().items()}
    assert took == {"localized": 1, "fallback_closure": 0,
                    "fallback_small": 0, "fallback_infeasible": 0}
    assert _counter("fleet_solver_subsolve_closure_rows_total") - rows \
        == 128
    after = w.record(w.key)
    assert w.adm.stats["moved_rows"] == moved
    assert {n: after[n] for n in before} == before
    assert len(set(after.values())) == 384


def _parents_closure(index, affected: np.ndarray) -> np.ndarray:
    """The closure rule as it stood before fresh arrivals were told apart:
    affected rows, every row sharing a conflict or coloc id with one,
    dependency neighbors, replica siblings."""
    affected = np.unique(affected)
    out = [affected]
    out.append(index._rows_sharing(index._conf_inv,
                                   index.conflict[affected].ravel()))
    out.append(index._rows_sharing(index._coloc_inv,
                                   index.coloc[affected].ravel()))
    nbr = (index._dep[affected].any(axis=0)
           | index._dep[:, affected].any(axis=1))
    out.append(np.nonzero(nbr)[0])
    for i in affected:
        out.append(np.asarray(index._groups[index.pt.replica_of[i]]))
    return np.unique(np.concatenate(out)).astype(np.int64)


@pytest.mark.parametrize("seed", range(3))
def test_churn_closures_are_the_parents_row_for_row(seed):
    """Node churn's shape (mt10kx1k's: every 20th service two replicas
    with hard self-anti-affinity, dependency chains, a few host ports):
    kills strand incumbents, none of them fresh, and the closure of every
    stranded set — and the plan built from it — is the parent rule's."""
    from fleetflow_tpu.core.model import Port, ServerLabels
    from fleetflow_tpu.lower.tensors import Node
    from fleetflow_tpu.solver.subsolve import (ActiveIndex, SubsolveConfig,
                                               plan_active)

    rng = np.random.default_rng(seed)
    flow = Flow(name="nc")
    names = [f"s{i:03d}" for i in range(120)]
    for i, n in enumerate(names):
        flow.services[n] = Service(
            name=n, image="x",
            resources=ResourceSpec(cpu=0.1, memory=64.0, disk=0.0),
            replicas=2 if i % 20 == 0 else 1,
            anti_affinity=[n] if i % 20 == 0 else [],
            depends_on=[names[i - 1]] if i % 5 else [],
            ports=[Port(host=8000 + i % 7, container=80)] if i % 11 == 0
            else [])
    flow.stages["live"] = Stage(name="live", services=list(names))
    N = 40
    pt = lower_stage(flow, "live",
                     nodes=[Node(f"n{j}", ServerLabels()) for j in range(N)],
                     capacity=np.full((N, 3), 100.0))
    index = ActiveIndex(pt)
    mirror = rng.integers(0, N, size=pt.S).astype(np.int32)
    cfg = SubsolveConfig(enabled=True, frac=0.9, min_tier=8)
    for _ in range(10):
        dead = rng.choice(N, size=3, replace=False)
        affected = np.flatnonzero(np.isin(mirror, dead))
        assert np.array_equal(index.closure(affected),
                              _parents_closure(index, affected))
        assert np.array_equal(
            index.closure(affected, np.empty(0, dtype=np.int64)),
            _parents_closure(index, affected))
        plans = [plan_active(index, pt, mirror, pt.S, 1, affected, cfg,
                             fresh_rows=fresh)
                 for fresh in (None, np.empty(0, dtype=np.int64))]
        assert plans[0][1] == plans[1][1]
        if plans[0][0] is not None:
            assert np.array_equal(plans[0][0].rows, plans[1][0].rows)


# --------------------------------------------------------------------------
# the resident delta path carries the ids
# --------------------------------------------------------------------------

def test_the_steady_state_scatters_ids_on_the_delta():
    """After warm-up, every micro-solve of departures and anti-affine
    arrivals rides the donated merge — no cold staging, no host transfer
    (under jax.transfer_guard("disallow")), no compaction — and the
    staged conflict plane holds the stage's ids row for row: the
    arrivals' written, the departed ones' cleared."""
    from fleetflow_tpu.solver.problem import unified_conflict_rows

    w = _World(24, use_tpu=True, batch=8)
    reuse = REGISTRY.get("fleet_solver_resident_reuse_total")
    xfer = REGISTRY.get("fleet_solver_host_transfers_total")
    w.adm.submit("t0", arrivals=[_pod(f"w{i}") for i in range(8)],
                 stage=w.key)
    w.drain()
    w.adm.submit("t0", departures=[f"w{i}" for i in range(4)], stage=w.key)
    w.drain()
    w.adm.submit("t0", arrivals=[_pod(f"v{i}") for i in range(4)],
                 stage=w.key)
    w.drain()
    cold, moved = reuse.value(outcome="cold"), xfer.value()
    prev = os.environ.get("FLEET_TRANSFER_GUARD")
    os.environ["FLEET_TRANSFER_GUARD"] = "disallow"
    try:
        for i in range(3):
            w.adm.submit("t0", departures=[f"v{i}"], stage=w.key)
            w.drain()
            w.adm.submit("t0", arrivals=[_pod(f"s{i}")], stage=w.key)
            w.drain()
    finally:
        if prev is None:
            os.environ.pop("FLEET_TRANSFER_GUARD", None)
        else:
            os.environ["FLEET_TRANSFER_GUARD"] = prev
    assert reuse.value(outcome="cold") == cold
    assert xfer.value() == moved
    assert w.adm.stats["compactions"] == 0
    pt = w.adm._streams[w.key].pt
    slot = next(s for s in w.pl._sched_tpu._residents if s.key == w.key)
    staged = np.asarray(slot.resident.prob.conflict_ids)
    assert np.array_equal(staged[:pt.S], unified_conflict_rows(
        pt, np.arange(pt.S), staged.shape[1]))
    assert (staged[pt.S:] == -1).all()
    live = set(w.record(w.key))
    assert live == {f"w{i}" for i in range(4, 8)} | {"v3"} \
        | {f"s{i}" for i in range(3)}
    rows = [pt.service_names.index(n) for n in live]
    assert (pt.anti_ids[rows, 0] == 0).all()
    assert (np.delete(pt.anti_ids[:, 0], rows) == -1).all()


# --------------------------------------------------------------------------
# the determinism contract, and what is refused
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(3))
def test_any_chunking_commits_the_same_placement_with_keys(seed):
    """A seeded stream of anti-affine arrivals and departures beside a
    committed other namespace, replayed at batch_max 3 and in one batch:
    the same committed placement, the same keys held."""
    import random

    def replay(batch: int) -> tuple:
        w = _World(40, use_tpu=False, batch=batch)
        w.place_other(["i0", "i1", "i2", "i3"], w.nodes[:8])
        rng = random.Random(seed)
        live: list[str] = []
        for i in range(30):
            if live and rng.random() < 0.3:
                w.adm.submit("t0", departures=[
                    live.pop(rng.randrange(len(live)))], stage=w.key)
            else:
                w.adm.submit("t0", arrivals=[_pod(
                    f"p{i:02d}", memory=500.0 + i * 0.125)],
                             stage=w.key)
                live.append(f"p{i:02d}")
        w.drain()
        c = w.pl._committed[w.key]
        return dict(c.assignment), c.held_keys, sorted(live)

    micro, whole = replay(3), replay(10_000)
    assert micro == whole
    assert sorted(micro[0]) == micro[2]


@pytest.mark.parametrize("spec,why", [
    (_pod("x", ports=[{"host": 80, "container": 80}]), "ports"),
    (_pod("x", volumes=[{"host": "/data", "container": "/d"}]), "volumes"),
    (_pod("x", colocate_with=["y"]), "colocate_with"),
    (_pod("x", anti_affinity=["x"]), "names a service"),
    (_pod("x", anti_affinity_stages={"other": [INIT]}), "undeclared"),
    (_pod("x", anti_affinity=LABEL), "list of labels"),
])
def test_what_the_stream_cannot_honour_is_refused_not_dropped(spec, why):
    w = _World(4, use_tpu=False)
    with pytest.raises(ValueError, match=why):
        w.adm.submit("t0", arrivals=[spec], stage=w.key)
    assert not w.adm.has_work() and not w.adm.requests
    # what it can honour is kept: the term, its reach, the eligible nodes
    w.adm.submit("t0", arrivals=[_pod("y", eligible_nodes=["n002"])],
                 stage=w.key)
    r = next(iter(w.adm.requests.values()))
    assert r.service.anti_affinity == [LABEL]
    assert r.service.anti_affinity_stages == REACH
    assert r.eligible_nodes == ["n002"]
    w.drain()
    assert w.record(w.key) == {"y": "n002"}


def test_a_flow_round_trip_lowers_the_streamed_keys_as_the_fold_wrote_them():
    """The fold's keys are the lowering's: sched-1 after a wave, and the
    same pods lowered from the flow the controller keeps, hold and are
    barred by the same keys, row for row by name."""
    w = _World(12, use_tpu=False, batch=4)
    w.adm.submit("t0", arrivals=[_pod(f"a{i}") for i in range(6)],
                 stage=w.key)
    w.drain()
    stream = w.adm._streams[w.key]
    pt = stream.pt
    lowered = lower_stage(stream.flow, MEASURED,
                          capacity=np.full((1, 3), 100.0))

    def by_name(keyed: dict, names: list[str]) -> dict:
        return {k: sorted(names[i] for i in rows)
                for k, rows in keyed.items()}

    assert by_name(pt.holds, pt.service_names) \
        == by_name(lowered.holds, lowered.service_names)
    assert by_name(pt.barred_by, pt.service_names) \
        == by_name(lowered.barred_by, lowered.service_names)
    assert pt.anti_groups == lowered.anti_groups == {LABEL: 0}


def test_a_compaction_renumbers_the_keys_with_the_rows():
    """Growth past the padded tier while tombstones exist compacts the
    stream: the rows are renumbered, and the keys and group ids follow
    them, by name."""
    w = _World(200, use_tpu=False, batch=200)
    w.adm.submit("t0", arrivals=[_pod(f"a{i}") for i in range(40)],
                 stage=w.key)
    w.drain()
    w.adm.submit("t0", departures=[f"a{i}" for i in range(10)],
                 stage=w.key)
    w.drain()
    compactions = w.adm.stats["compactions"]
    w.adm.submit("t0", arrivals=[_pod(f"b{i}") for i in range(40)],
                 stage=w.key)
    w.drain()
    assert w.adm.stats["compactions"] == compactions + 1
    pt = w.adm._streams[w.key].pt
    live = sorted([f"a{i}" for i in range(10, 40)]
                  + [f"b{i}" for i in range(40)])
    assert sorted(w.record(w.key)) == live and pt.S == len(live)
    for key in (f"anti:{gen.FLOW}:{LABEL}@{MEASURED}",
                f"anti:{gen.FLOW}:{LABEL}>{INIT}"):
        assert sorted(pt.service_names[i] for i in pt.holds[key]) == live
    assert (pt.anti_ids[:, 0] == 0).all()
    assert len(set(w.record(w.key).values())) == len(live)
