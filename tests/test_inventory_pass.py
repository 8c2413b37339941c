"""What is free on each server is one array pass (PR 34).

`PlacementService._inventory` and `_refresh_capacity` compute capacity less
what is spoken for over all servers at once. The per-server loops they
replace are kept here as the plain reference, and the array passes are held
to them exactly: the same float64 to the last bit, the same names in the
same order, the same validity mask, the same preemptible capacity.

The worlds are seeded and deliberately untidy: open reservations that
overlap, a churn hold the caller calls its own, servers whose capacity
shrank below what is allocated on them (a deficit must stay a deficit until
the clamp), a tenant's own servers beside the default pool, one slug
registered twice, labelled and unlabelled records, offline and cordoned
ones, commitments with rows of several priorities.

Since PR 42 the passes read the store's columns (`Store.server_columns`)
and no record: the loops below still walk the records, so they are what
the columns are held to — in a fresh world, and in one whose servers are
written between two calls.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from fleetflow_tpu.core.model import (Flow, PlacementPolicy, ResourceSpec,
                                      ServerLabels, ServerResource, Service,
                                      Stage)
from fleetflow_tpu.cp import placement as placement_mod
from fleetflow_tpu.cp import store as store_mod
from fleetflow_tpu.cp.models import (Server, ServerAllocated, ServerCapacity,
                                     ServerLabelsRec)
from fleetflow_tpu.cp.placement import (PlacementService, Reservation, _Rows,
                                        _alloc_vector, _booked_columns)
from fleetflow_tpu.cp.store import Store
from fleetflow_tpu.lower.tensors import Node, lower_stage
from fleetflow_tpu.obs.metrics import REGISTRY

SEEDS = [3, 2_147_483_659, 77]
OWN = "p/own"


# --------------------------------------------------------------------------
# the loops as they stood before PR 34: the plain reference
# --------------------------------------------------------------------------

def _ref_alloc_vector(s: Server) -> np.ndarray:
    return np.array([s.allocated.cpu + s.allocated.reserved_cpu,
                     s.allocated.memory + s.allocated.reserved_memory,
                     s.allocated.disk + s.allocated.reserved_disk],
                    dtype=np.float64)


def _ref_server_to_resource(s: Server) -> ServerResource:
    return ServerResource(
        name=s.slug,
        capacity=ResourceSpec(cpu=s.capacity.cpu, memory=s.capacity.memory,
                              disk=s.capacity.disk),
        labels=ServerLabels(tier=s.labels.tier, region=s.labels.region,
                            clazz=s.labels.clazz, arch=s.labels.arch,
                            extra=dict(s.labels.extra)))


def _ref_inventory(svc: PlacementService, tenant, slugs=None,
                   exclude_demand=None, preemptor=None):
    servers = svc.store.list(
        "servers", lambda s: s.tenant in (tenant, "default")
        and (not slugs or s.slug in slugs))
    if not servers:
        raise ValueError(f"no servers registered for tenant {tenant!r}")
    reserved = svc._reserved_by_node()
    pre = None
    if preemptor is not None:
        pre = svc._preemptible_by_node(*preemptor, [s.slug for s in servers])
    nodes, valid = [], []
    unclamped = None if pre is None else np.empty_like(pre)
    for i, s in enumerate(servers):
        res = _ref_server_to_resource(s)
        alloc = _ref_alloc_vector(s) + reserved.get(s.slug, 0)
        if exclude_demand:
            alloc = alloc - exclude_demand.get(s.slug, 0)
        free = np.array(res.capacity.as_tuple()) - alloc
        if unclamped is not None:
            unclamped[i] = free
        cap = np.maximum(free, 0.0)
        res.capacity = ResourceSpec(cpu=float(cap[0]), memory=float(cap[1]),
                                    disk=float(cap[2]))
        nodes.append(res)
        valid.append(s.schedulable)
    if pre is not None:
        pre = (np.maximum(unclamped + pre, 0.0)
               - np.maximum(unclamped, 0.0))
    return nodes, np.array(valid, dtype=bool), pre


def _ref_refresh_capacity(svc: PlacementService, pt, key, overrides=None,
                          server_map=None) -> np.ndarray:
    own = svc._stage_demand(key)
    reserved = svc._reserved_by_node()
    other = [snap for okey, snap in (overrides or {}).items() if okey != key]
    cap = pt.capacity.copy()
    for j, slug in enumerate(pt.node_names):
        s = (server_map.get(slug) if server_map is not None
             else svc.store.server_by_slug(slug))
        if s is None:
            continue
        alloc = (_ref_alloc_vector(s) + reserved.get(slug, 0)
                 - own.get(slug, 0))
        for old_dem, new_dem in other:
            alloc = alloc - old_dem.get(slug, 0) + new_dem.get(slug, 0)
        raw = np.array([s.capacity.cpu, s.capacity.memory, s.capacity.disk],
                       dtype=np.float64)
        cap[j] = np.maximum(raw - alloc, 0.0)
    return cap


# --------------------------------------------------------------------------
# seeded worlds
# --------------------------------------------------------------------------

def _vec(rng, scale=1.0) -> np.ndarray:
    return rng.uniform(0.0, 1.0, 3) * np.array([4.0, 4096.0, 9000.0]) * scale


def _demand(rng, slugs, k) -> dict[str, np.ndarray]:
    return {str(g): _vec(rng)
            for g in rng.choice(slugs, size=min(k, len(slugs)),
                                replace=False)}


class _World:
    """A store of untidy server records with a PlacementService on it whose
    books (open reservations, a churn hold of stage OWN, commitments with
    rows) are written directly: the passes read them, nothing here solves."""

    def __init__(self, seed: int, n: int = 41):
        rng = self.rng = np.random.default_rng(seed)
        self.store = Store()
        self.slugs = [f"n{j}" for j in range(n)]
        for j, slug in enumerate(self.slugs):
            cap = rng.uniform(1.0, 3.0, 3) * np.array([16.0, 16384.0, 4e4])
            # every third server carries more than it has: it shrank
            load = cap * rng.uniform(0.0, 1.4 if j % 3 == 0 else 0.9, 3)
            held = _vec(rng, 0.1) if rng.random() < 0.3 else np.zeros(3)
            labelled = rng.random() < 0.3
            self.store.create("servers", Server(
                slug=slug,
                # n0 and n3 (shrunken, in OWN's hold) and n1 (registered
                # twice) are in every view
                tenant=("default", "default", "default", "acme", "other")[
                    0 if j in (0, 1, 3) else int(rng.integers(5))],
                status="online" if rng.random() < 0.85 else "offline",
                scheduling_state=("schedulable" if rng.random() < 0.9
                                  else "cordoned"),
                capacity=ServerCapacity(*cap.tolist()),
                allocated=ServerAllocated(*load.tolist(), *held.tolist()),
                labels=(ServerLabelsRec(
                    tier=("gold", "standard", None)[int(rng.integers(3))],
                    region=("tokyo", "osaka", None)[int(rng.integers(3))],
                    extra={"rack": f"r{j % 4}"} if rng.random() < 0.5 else {})
                    if labelled else ServerLabelsRec())))
        # whole numbers, as a benchmark's node states them
        self.store.create("servers", Server(
            slug="whole", tenant="default", status="online",
            capacity=ServerCapacity(cpu=4, memory=32768, disk=40960)))
        self.slugs.append("whole")
        # one slug under two tenants: both records are in acme's view
        self.store.create("servers", Server(
            slug="n1", tenant="acme", status="online",
            capacity=ServerCapacity(8.0, 8192.0, 1e4),
            allocated=ServerAllocated(cpu=1.25, memory=100.0)))
        self.svc = svc = PlacementService(self.store)
        book = svc._reservations
        book["r1"] = Reservation("r1", "p/a", _demand(rng, self.slugs, 9), {})
        book["r2"] = Reservation("r2", "p/b", {
            **_demand(rng, self.slugs, 7), "n1": _vec(rng),
            "gone": _vec(rng)}, {})
        book["r3"] = Reservation("r3", "p/c", _demand(rng, self.slugs, 5),
                                 {}, committed=True)
        # OWN's churn hold sits on shrunken servers too
        self.hold = {**_demand(rng, self.slugs, 6), "n0": _vec(rng),
                     "n3": _vec(rng)}
        book["r4"] = Reservation("r4", OWN, self.hold, {}, churn=True)
        svc._committed[OWN] = Reservation(
            "c0", OWN, _demand(rng, self.slugs, 11), {}, committed=True)
        # two commitments with rows of priorities 0..4: one over the
        # servers in another order (and one the store lacks), one whose
        # rows all rank 2 or above
        for key, lowest, nodes in (
                ("p/low", 0, list(rng.permutation(self.slugs)) + ["gone"]),
                ("p/mid", 2, list(self.slugs))):
            rows = 60
            svc._committed[key] = Reservation(
                f"c-{key}", key, {}, {}, committed=True, rows=_Rows(
                    names=[f"{key}-{i}" for i in range(rows)],
                    nodes=[str(g) for g in nodes],
                    node_of=rng.integers(0, len(nodes), rows),
                    demand=np.stack([_vec(rng, 0.2) for _ in range(rows)]
                                    ).astype(np.float32),
                    priority=rng.integers(lowest, 5, rows).astype(np.int32),
                    holds={}, floor=lowest))

    def problem(self, node_names: list[str]):
        """A two-row problem over `node_names`, its capacity a stale view."""
        flow = Flow(name="p")
        for name in ("a", "b"):
            flow.services[name] = Service(name=name, image="img")
        flow.stages["own"] = Stage(name="own", services=["a", "b"])
        return lower_stage(
            flow, "own", nodes=[ServerResource(name=g) for g in node_names],
            capacity=self.rng.uniform(0.0, 5e4, (len(node_names), 3)))


def _same(got: np.ndarray, want: np.ndarray) -> None:
    """Bit for bit: dtype, shape and every byte (so -0.0 is not 0.0)."""
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


INVENTORY_CASES = {
    "default-pool": lambda w: dict(tenant="default"),
    "open-reservations-only": lambda w: dict(tenant="default",
                                             exclude_demand={}),
    "churn-hold-excluded": lambda w: dict(tenant="default",
                                          exclude_demand=w.hold),
    "own-commitment-excluded": lambda w: dict(
        tenant="default",
        exclude_demand=dict(w.svc._committed[OWN].demand_by_node)),
    "tenant-beside-default": lambda w: dict(tenant="acme",
                                            exclude_demand=w.hold),
    "slugs-given": lambda w: dict(
        tenant="acme", slugs=[g for g in w.slugs[::3]] + ["n1", "gone"],
        exclude_demand=w.hold),
    "preemptor-some-rows": lambda w: dict(
        tenant="default", exclude_demand=w.hold, preemptor=(OWN, 3)),
    "preemptor-every-row": lambda w: dict(
        tenant="acme", preemptor=(OWN, 9)),
    "preemptor-no-row": lambda w: dict(
        tenant="default", exclude_demand=w.hold, preemptor=(OWN, 0)),
}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("case", INVENTORY_CASES)
def test_inventory_is_the_loop(case, seed):
    world = _World(seed)
    kw = INVENTORY_CASES[case](world)
    want_nodes, want_valid, want_pre = _ref_inventory(world.svc, **kw)
    nodes, free, valid, pre = world.svc._inventory(**kw)
    assert [n.name for n in nodes] == [n.name for n in want_nodes]
    assert [n.labels for n in nodes] == [n.labels for n in want_nodes]
    _same(free, np.array([n.capacity.as_tuple() for n in want_nodes],
                         dtype=np.float64))
    _same(valid, want_valid)
    if case in ("preemptor-some-rows", "preemptor-every-row"):
        assert want_pre is not None and want_pre.any()
    if want_pre is None:
        assert pre is None
    else:
        _same(pre, want_pre)
    # and the float32 the solver sees
    _same(free.astype(np.float32),
          np.array([n.capacity.as_tuple() for n in want_nodes],
                   dtype=np.float32))


def test_a_deficit_is_not_free_capacity():
    """A server that shrank below its allocation: excluding the caller's
    own demand must come off the deficit, not be added to a clamped 0."""
    store = Store()
    store.create("servers", Server(
        slug="s", tenant="default", status="online",
        capacity=ServerCapacity(2.0, 1000.0, 0.0),
        allocated=ServerAllocated(cpu=5.0, memory=400.0)))
    svc = PlacementService(store)
    own = {"s": np.array([2.0, 100.0, 0.0])}
    _nodes, free, _valid, _pre = svc._inventory("default",
                                                exclude_demand=own)
    assert free.tolist() == [[0.0, 700.0, 0.0]]


@pytest.mark.parametrize("tenant,slugs", [("nobody", None),
                                          ("acme", ["gone"])])
def test_no_servers_raises(tenant, slugs):
    """Another tenant's server is nobody else's, and a stage's `servers`
    that name no registered one leave nothing to solve against."""
    store = Store()
    store.create("servers", Server(slug="theirs", tenant="acme",
                                   status="online"))
    with pytest.raises(ValueError, match="no servers registered"):
        PlacementService(store)._inventory(tenant, slugs)


@pytest.mark.parametrize("seed", SEEDS)
def test_alloc_vector_is_its_row_of_the_columns(seed):
    world = _World(seed)
    servers = world.store.list("servers")
    capacity, booked = _booked_columns(servers)
    assert capacity.shape == booked.shape == (len(servers), 3)
    for s, cap, row in zip(servers, capacity, booked):
        _same(_alloc_vector(s), row)
        _same(_alloc_vector(s), _ref_alloc_vector(s))
        assert cap.tolist() == [s.capacity.cpu, s.capacity.memory,
                                s.capacity.disk]
    assert _booked_columns([])[1].shape == (0, 3)


REFRESH_CASES = {
    # (node names of the retained problem, overrides, read the columns
    # first and hand them over, as admit_batch does)
    "every-node-known": lambda w: (w.slugs[::2], None, True),
    "a-node-without-record": lambda w: (
        ["ghost"] + w.slugs[5:25] + ["gone"], None, True),
    "no-node-known": lambda w: (["ghost", "gone"], None, True),
    "store-lookups": lambda w: (w.slugs[::-1] + ["ghost"], None, False),
    # n1 is registered twice: both names read the first record's numbers
    "a-name-twice": lambda w: (["n1", "n4", "n1"], None, False),
    "burst-mate-overrides": lambda w: (
        list(w.rng.permutation(w.slugs)) + ["ghost"],
        {"p/mate": (_demand(w.rng, w.slugs, 8), _demand(w.rng, w.slugs, 8)),
         OWN: (_demand(w.rng, w.slugs, 4), _demand(w.rng, w.slugs, 4)),
         "p/mate2": ({"n0": _vec(w.rng), "gone": _vec(w.rng)},
                     {"n3": _vec(w.rng)})},
        True),
}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("case", REFRESH_CASES)
def test_refresh_capacity_is_the_loop(case, seed):
    world = _World(seed)
    names, overrides, handed = REFRESH_CASES[case](world)
    pt = world.problem([str(g) for g in names])
    # the loop looks every name up in the store: the record that
    # server_by_slug returns, the first in table order
    want = _ref_refresh_capacity(world.svc, pt, OWN, overrides)
    on = world.svc._server_rows(OWN, pt) if handed else None
    got = world.svc._refresh_capacity(pt, OWN, overrides, on)
    assert got.capacity.dtype == pt.capacity.dtype == np.float32
    _same(got.capacity, want)
    if case == "no-node-known":
        assert got is pt
    else:
        assert got is not pt and got.node_names is pt.node_names
        # nothing moved since: the very object, so that a device staging
        # keyed on identity stays warm
        assert world.svc._refresh_capacity(got, OWN, overrides) is got


# --------------------------------------------------------------------------
# servers written between two calls: the columns follow the records
# --------------------------------------------------------------------------

def _a_commit(world):
    """What a commit does: one update_many of the servers it books."""
    slugs = [str(g) for g in world.rng.choice(world.slugs, 9, replace=False)]
    assert world.svc._write_allocations(
        slugs + ["gone"], np.stack([_vec(world.rng, 0.2) for _ in range(10)])
    ) == 9


def _a_return(world):
    """A release: allocations come off, clamped at zero."""
    world.svc._write_allocations(world.slugs[:7], -np.stack(
        [_vec(world.rng, 9.0) for _ in range(7)]))


def _status_flips(world):
    world.store.bulk_server_status({"n0": "offline", "n2": "online",
                                    "n3": "offline", "whole": "offline"})
    world.store.update("servers", world.store.server_by_slug("n5").id,
                       scheduling_state="cordoned")
    world.store.heartbeat("n7")


def _a_server_shrinks(world):
    world.store.update("servers", world.store.server_by_slug("n4").id,
                       capacity=ServerCapacity(0.5, 64.0, 1.0))


def _a_new_server(world):
    world.store.create("servers", Server(
        slug="fresh", tenant="default", status="online",
        capacity=ServerCapacity(64.0, 1e5, 1e6),
        allocated=ServerAllocated(cpu=1.0)))


def _standing(world, *slugs) -> Server:
    """The first of `slugs` that still has a record (a write is applied
    more than once in a test)."""
    return next(s for s in map(world.store.server_by_slug, slugs)
                if s is not None)


def _a_server_deleted(world):
    """Under a retained stage: its node keeps its name and loses its
    record."""
    world.store.delete("servers", _standing(world, "n6", "n10", "n12").id)


def _the_first_of_two_deleted(world):
    """n1 is registered twice: the name reads the second record, then
    none."""
    world.store.delete("servers", _standing(world, "n1", "n14", "n16").id)


def _a_server_renamed(world):
    rec = _standing(world, "n8", "n18", "n20")
    world.store.update("servers", rec.id, slug=rec.slug + "-renamed")


def _a_reservation_opens(world):
    """Nothing written in the store: the book alone moved."""
    world.svc._reservations["r9"] = Reservation(
        "r9", "p/z", _demand(world.rng, world.slugs, 12), {})


WRITES = {
    "a-commit": _a_commit,
    "a-return": _a_return,
    "status-flips": _status_flips,
    "a-server-shrinks": _a_server_shrinks,
    "a-new-server": _a_new_server,
    "a-server-deleted": _a_server_deleted,
    "the-first-of-two-deleted": _the_first_of_two_deleted,
    "a-server-renamed": _a_server_renamed,
    "a-reservation-opens": _a_reservation_opens,
}


def _inventory_agrees(world, **kw):
    want_nodes, want_valid, want_pre = _ref_inventory(world.svc, **kw)
    nodes, free, valid, pre = world.svc._inventory(**kw)
    assert [n.name for n in nodes] == [n.name for n in want_nodes]
    assert [n.labels for n in nodes] == [n.labels for n in want_nodes]
    _same(free, np.array([n.capacity.as_tuple() for n in want_nodes],
                         dtype=np.float64))
    _same(valid, want_valid)
    _same(pre, want_pre)


@pytest.mark.parametrize("seed", SEEDS[:2])
@pytest.mark.parametrize("write", WRITES)
def test_inventory_is_the_loop_after_servers_were_written(write, seed):
    world = _World(seed)
    kw = dict(tenant="acme", exclude_demand=world.hold, preemptor=(OWN, 3))
    _inventory_agrees(world, **kw)
    for step in (write, "a-commit", write):
        WRITES[step](world)
        _inventory_agrees(world, **kw)


def _ref_admit_refresh(svc: PlacementService, pt, key):
    """`admit_batch`'s preamble as it walked the records: each node's
    validity from its record, the bit it has where no server carries the
    name, then the capacity loop."""
    valid = np.array(
        [bool(s.schedulable)
         if (s := svc.store.server_by_slug(slug)) is not None
         else bool(pt.node_valid[j])
         for j, slug in enumerate(pt.node_names)], dtype=bool)
    return valid, _ref_refresh_capacity(svc, pt, key)


@pytest.mark.parametrize("seed", SEEDS[:2])
@pytest.mark.parametrize("write", WRITES)
def test_admit_batch_sees_the_loops_world_after_servers_were_written(
        write, seed):
    """A retained stage admitted to twice, servers written in between: the
    problem each micro-solve is handed carries the validity and the
    capacity the loops read off the records at that moment."""
    world = _World(seed)
    names = [str(g) for g in world.slugs[2:30:2]] + ["ghost", "n1"]
    pt = world.problem(names)
    pt.node_valid = np.array(world.rng.random(len(names)) < 0.5)
    for step in (None, write, write):
        if step is not None:
            WRITES[step](world)
        want_valid, want_cap = _ref_admit_refresh(world.svc, pt, OWN)
        _placement, _rid, used = world.svc.admit_batch(OWN, pt)
        _same(used.node_valid, want_valid)
        _same(used.capacity, want_cap)
        assert used.node_names is pt.node_names
        # the candidate of the next micro-solve shares what did not move
        pt = used
    # nothing written, no reservation opened since the refresh that
    # `used` came from: the same object, for the resident delta path
    world.svc._reservations.clear()
    settled = world.svc._refresh_capacity(used, OWN)
    assert world.svc._refresh_capacity(settled, OWN) is settled


@pytest.mark.parametrize("seed", SEEDS[:2])
@pytest.mark.parametrize("write", WRITES)
def test_refresh_capacity_is_the_loop_after_servers_were_written(write, seed):
    """The churn path's refresh, with a burst-mate's overrides, keeps the
    rows of a stage's nodes with the stage: they are renewed when a
    server enters, leaves or is renamed, and not otherwise."""
    world = _World(seed)
    names = [str(g) for g in world.rng.permutation(world.slugs)] + ["ghost"]
    pt = world.problem(names)
    overrides = {"p/mate": (_demand(world.rng, world.slugs, 8),
                            _demand(world.rng, world.slugs, 8))}
    for step in (None, write, "status-flips", write):
        if step is not None:
            WRITES[step](world)
        want = _ref_refresh_capacity(world.svc, pt, OWN, overrides)
        kept = world.svc._node_rows.get(OWN)
        members = world.store.server_columns().members
        got = world.svc._refresh_capacity(pt, OWN, overrides)
        _same(got.capacity, want)
        if kept is not None:
            renewed = world.svc._node_rows[OWN][2] is not kept[2]
            assert renewed == (members != kept[1])


# --------------------------------------------------------------------------
# the lowering reads the same problem from the columns
# --------------------------------------------------------------------------

def _tiered_flow() -> Flow:
    flow = Flow(name="p")
    for name in ("a", "b", "c"):
        flow.services[name] = Service(
            name=name, image="img",
            resources=ResourceSpec(cpu=0.3, memory=77.7, disk=1.1))
    flow.stages["own"] = Stage(
        name="own", services=["a", "b", "c"],
        placement=PlacementPolicy(tier="gold",
                                  preferred_labels={"region": "tokyo"}))
    return flow


@pytest.mark.parametrize("seed", SEEDS)
def test_lowering_from_columns_is_lowering_from_nodes(seed):
    """`lower_stage(nodes=Node.., capacity=array)` gives the tensors that
    ServerResources carrying the same numbers give."""
    world = _World(seed)
    want_nodes, _valid, _pre = _ref_inventory(world.svc, "acme",
                                              exclude_demand=world.hold)
    nodes, free, _valid, _pre = world.svc._inventory(
        "acme", exclude_demand=world.hold)
    assert all(type(n) is Node for n in nodes)
    flow = _tiered_flow()
    want = lower_stage(flow, "own", nodes=want_nodes)
    got = lower_stage(flow, "own", nodes=nodes, capacity=free)
    assert got.node_names == want.node_names
    for f in dataclasses.fields(want):
        a, b = getattr(got, f.name), getattr(want, f.name)
        if isinstance(b, np.ndarray):
            _same(a, b)
    assert not want.eligible.all() and want.preferred is not None


def test_capacity_of_the_wrong_shape_is_refused():
    world = _World(SEEDS[0], n=4)
    nodes, free, _valid, _pre = world.svc._inventory("default")
    with pytest.raises(ValueError):
        lower_stage(_tiered_flow(), "own", nodes=nodes, capacity=free[:-1])


def test_backfill_fills_a_node_and_leaves_the_shared_labels_alone():
    """A node shares its record's labels; the flow's declaration fills the
    node, never that object."""
    store = Store()
    for slug in ("std", "hi", "bare"):
        store.create("servers", Server(
            slug=slug, tenant="default", status="online",
            capacity=ServerCapacity(8.0, 8192.0, 1e4),
            labels=(ServerLabelsRec(region="osaka") if slug == "hi"
                    else ServerLabelsRec())))
    svc = PlacementService(store)
    flow = _tiered_flow()
    flow.servers["std"] = ServerResource(
        name="std", labels=ServerLabels(tier="standard"))
    flow.servers["hi"] = ServerResource(
        name="hi", labels=ServerLabels(tier="gold", region="tokyo",
                                       extra={"rack": "r1"}))
    placement, _rid = svc.solve_stage(flow, "own", reserve=False)
    # `std` is declared off tier; `bare` is undeclared and blank, which
    # passes every gate; the API's region wins over the declaration's
    pt = svc._last["p/own"][0]
    assert pt.node_names == ["std", "hi", "bare"]
    assert pt.eligible.tolist() == [[False, True, True]] * 3
    assert pt.preferred is None
    assert set(placement.assignment.values()) <= {"hi", "bare"}
    assert store.server_by_slug("bare").labels == ServerLabels()
    assert store.server_by_slug("std").labels == ServerLabels()
    assert store.server_by_slug("hi").labels == ServerLabels(region="osaka")


# --------------------------------------------------------------------------
# the cost's shape: a later edit must not bring the loop back
# --------------------------------------------------------------------------

class _CountingNumpy:
    """`numpy` as placement.py sees it, counting every call it makes."""

    def __init__(self):
        self.calls = 0

    def __getattr__(self, name):
        attr = getattr(np, name)
        if not callable(attr) or isinstance(attr, type):
            return attr

        def counted(*a, **kw):
            self.calls += 1
            return attr(*a, **kw)
        return counted


def _columns_rows() -> float:
    return REGISTRY.get("fleet_store_server_columns_rows_total").value()


def _counted_inventory(monkeypatch, n: int) -> tuple[int, dict[str, int]]:
    """numpy calls (placement.py's and store.py's) and objects built by
    one inventory over `n` servers whose columns stand, 50 of them
    written since the last read."""
    store = Store()
    for j in range(n):
        store.create("servers", Server(
            slug=f"n{j}", tenant="default", status="online",
            capacity=ServerCapacity(4.0, 32768.0, 40960.0),
            allocated=ServerAllocated(cpu=0.5 * (j % 3)),
            labels=(ServerLabelsRec(tier="gold") if j % 10 == 0
                    else ServerLabelsRec())))
    svc = PlacementService(store)
    svc._reservations["r"] = Reservation(
        "r", "p/a", {f"n{j}": np.ones(3) for j in range(0, n, 7)}, {})
    hold = {f"n{j}": np.ones(3) for j in range(0, n, 11)}
    flow = _tiered_flow()
    r0 = _columns_rows()
    svc._inventory("default", exclude_demand=hold)
    assert _columns_rows() - r0 == n            # the first read builds
    svc._write_allocations([f"n{j}" for j in range(50)], np.ones((50, 3)))
    built = {}
    for cls in (ResourceSpec, ServerResource, ServerLabels, Node):
        def init(self, *a, _cls=cls, _init=cls.__init__, **kw):
            built[_cls.__name__] = built.get(_cls.__name__, 0) + 1
            _init(self, *a, **kw)
        monkeypatch.setattr(cls, "__init__", init)
    counting = _CountingNumpy()
    monkeypatch.setattr(placement_mod, "np", counting)
    monkeypatch.setattr(store_mod, "np", counting)
    r0 = _columns_rows()
    nodes, free, valid, _pre = svc._inventory("default", exclude_demand=hold)
    assert len(nodes) == n and free.shape == (n, 3) and valid.all()
    assert free[:51, 0].tolist() == [3.0 - 0.5 * (j % 3) - (j % 7 == 0)
                                     + (j % 11 == 0) for j in range(50)] + [
                                         4.0 - 0.5 * (50 % 3)]
    # the walk: the 50 records written, not the table
    assert _columns_rows() - r0 == 50
    calls, r0 = counting.calls, _columns_rows()
    svc._inventory("default", exclude_demand=hold)
    pt = lower_stage(flow, "own", nodes=nodes, capacity=free)
    assert svc._refresh_capacity(pt, "p/own") is not pt
    assert _columns_rows() - r0 == 0    # nothing written: no record read
    return calls, built


def test_inventory_cost_is_a_pass_not_a_loop(monkeypatch):
    calls_small, _built = _counted_inventory(monkeypatch, 200)
    calls, built = _counted_inventory(monkeypatch, 2000)
    # as many numpy calls for 2,000 servers as for 200, and few: the
    # inventory's own, and the patch of the columns it reads
    assert calls == calls_small <= 24
    # one object a server: a node shares its record's labels (PR 39; a
    # copy for each labelled record before: 5,000 a solve in a zoned pool)
    assert built == {"Node": 2000 * 2}
