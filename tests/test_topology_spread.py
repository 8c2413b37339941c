"""The spread constraint at a tight bound (PR 39).

`placement { spread topology_key=K max_skew=M }` is PodTopologySpread with
DoNotSchedule: the counts of a stage's rows per topology domain differ by
at most M. What is kept, and by whom (docs/guide/03): the lowering makes a
domain of every value of K among the servers the stage may use and bars the
servers that lack K; the seeds deal rows to domains within the bound; a
sweep admits the moves that cross domains only as far as the band allows,
so a chain within the bound stays within it; the host's repair has a move
for what is left; the fallback ladder drops the bound only where the stage
says so, and the reply says it did.

The cluster cases compare the system with the plain reference the benchmark
uses (benchmarks/reference_k8s_spread.py: Kubernetes scheduler_perf's
TopologySpreading as data, a one-pod-at-a-time scheduler with the source's
filter, and a checker), at sizes a CPU solves in no time, through
`PlacementService` and the `placement.solve` / `placement.commit` handlers.
"""

from __future__ import annotations

import asyncio
import dataclasses
import importlib

import jax
import numpy as np
import pytest

from benchmarks import generators_k8s_spread as gen
from benchmarks import reference_k8s_spread as ref
from benchmarks.reference_k8s_spread import INIT, MEASURED
from fleetflow_tpu.core.errors import SolverError
from fleetflow_tpu.core.model import FallbackPolicy, ServerLabels
from fleetflow_tpu.core.serialize import flow_from_dict
from fleetflow_tpu.cp.models import Server, ServerCapacity, ServerLabelsRec
from fleetflow_tpu.cp.placement import PlacementService
from fleetflow_tpu.cp.store import Store
from fleetflow_tpu.lower.tensors import Node, lower_stage
from fleetflow_tpu.obs import trace as obs_trace
from fleetflow_tpu.obs.metrics import REGISTRY
from fleetflow_tpu.sched.fallback import relax_problem
from fleetflow_tpu.solver import api as solver_api
from fleetflow_tpu.solver import solve
from fleetflow_tpu.solver.repair import repair, verify

# the package re-exports the function `anneal` under the module's name
anneal_mod = importlib.import_module("fleetflow_tpu.solver.anneal")

KEY = {INIT: f"{gen.FLOW}/{INIT}", MEASURED: f"{gen.FLOW}/{MEASURED}"}
RACKS = {f"rack-{i:02d}": n for i, n in enumerate(
    (30, 24, 20, 16, 14, 12, 10, 8, 6, 5, 3, 2))}


def _counter(name: str, **labels) -> float:
    return REGISTRY.get(name).value(**labels)


class _Cluster:
    """A model registered in a store, zone labels on the server records,
    with a PlacementService on it (the device annealer on the CPU)."""

    def __init__(self, model: dict, *, use_tpu: bool = True,
                 fallback: list[str] | None = None):
        self.model = model
        self.fallback = fallback
        self.store = Store()
        for slug, node in model["nodes"].items():
            self.store.create("servers", Server(
                slug=slug, status="online", tenant="default",
                capacity=ServerCapacity(**gen.server_capacity(node)),
                labels=ServerLabelsRec(**gen.server_labels(node))))
        self.svc = PlacementService(self.store, use_tpu=use_tpu)

    def flow(self, namespace: str, model: dict | None = None):
        flow = flow_from_dict(
            gen.solve_request(model or self.model, namespace)["flow"])
        if self.fallback is not None and namespace == MEASURED:
            flow.stages[namespace].placement.fallback_policy = \
                FallbackPolicy(relax_order=list(self.fallback))
        return flow

    def solve(self, namespace: str, model: dict | None = None, **kw):
        return self.svc.solve_stage(self.flow(namespace, model), namespace,
                                    **kw)

    def place(self, namespace: str) -> dict:
        placement, rid = self.solve(namespace)
        assert placement.feasible, placement.violations
        assert self.svc.commit(rid)
        return dict(placement.assignment)

    def record(self, namespace: str):
        return self.store.find_one(
            "placements", lambda p: p.stage_key == KEY[namespace])


def _reference(model: dict) -> dict:
    """What the reference makes of the model: its placements and check."""
    mine = ref.schedule(model, {})
    return {"placed": mine, "check": ref.check(model, mine)}


# --------------------------------------------------------------------------
# (a), (b): the source's shape, small, against the reference
# --------------------------------------------------------------------------

SHAPES = {
    "round-robin": None,
    "60-30-10": {"moon-1": 90, "moon-2": 45, "moon-3": 15},
    "12-racks": RACKS,
}


@pytest.mark.parametrize("zones", list(SHAPES), ids=list(SHAPES))
def test_the_spreading_pods_end_where_the_reference_says(zones):
    """150 nodes, 150 init pods, 60 spreading pods: every pod placed, the
    checker finds nothing over both namespaces, the zones' sorted counts
    are the reference's, served by the annealer with nothing relaxed, no
    host repair, and the record read back is the reply."""
    model = ref.cluster(5, 150, 150, 60)
    if SHAPES[zones]:
        model = ref.with_zones(model, SHAPES[zones])
    theirs = _reference(model)
    assert theirs["check"]["total"] == 0
    assert all(v is not None for v in theirs["placed"][MEASURED].values())

    c = _Cluster(model)
    init = c.place(INIT)
    solves0 = _counter("fleet_solver_spread_solves_total")
    repaired0 = _counter("fleet_solver_spread_repair_moves_total")
    relaxed0 = _counter("fleet_sched_relaxed_total", what="spread")
    device0 = _counter("fleet_solver_spread_excess_total", at="device")
    placement, rid = c.solve(MEASURED)
    assert placement.feasible and placement.source == "cpu-anneal"
    assert c.svc.commit(rid)
    found = ref.check(model, {INIT: init, MEASURED: placement.assignment})
    assert found["total"] == 0, found
    assert sorted(found["zones"].values()) \
        == sorted(theirs["check"]["zones"].values())
    assert dict(c.record(MEASURED).assignment) == placement.assignment
    assert _counter("fleet_solver_spread_solves_total") == solves0 + 1
    assert _counter("fleet_solver_spread_repair_moves_total") == repaired0
    assert _counter("fleet_sched_relaxed_total", what="spread") == relaxed0
    assert _counter("fleet_solver_spread_excess_total",
                    at="device") == device0


def test_the_constraint_rides_the_wire_and_the_commit_is_read_back():
    """`placement.solve` of the spread stage with `reserve: true`, then
    `placement.commit`, over a client connection: the reply is checked by
    the reference together with sched-0 read back from the store."""
    from test_cp import connect, start_cp

    model = ref.cluster(7, 60, 60, 31)
    theirs = _reference(model)

    async def go():
        handle = await start_cp(use_tpu_solver=True)
        store = handle.state.store
        for slug, node in model["nodes"].items():
            rec = store.register_server(slug, tenant="default",
                                        hostname=slug)
            store.update("servers", rec.id, status="online",
                         capacity=ServerCapacity(
                             **gen.server_capacity(node)),
                         labels=ServerLabelsRec(**gen.server_labels(node)))
        conn, _ = await connect(handle)
        for namespace in (INIT, MEASURED):
            reply = await conn.request(
                "placement", "solve", gen.solve_request(model, namespace))
            assert reply["feasible"] and reply["source"] == "cpu-anneal"
            done = await conn.request("placement", "commit",
                                      {"reservation": reply["reservation"]})
            assert done["ok"]
        read = {ns: dict(store.find_one(
            "placements", lambda p, k=KEY[ns]: p.stage_key == k).assignment)
            for ns in (INIT, MEASURED)}
        assert read[MEASURED] == reply["assignment"]
        found = ref.check(model, read)
        assert found["total"] == 0, found
        assert sorted(found["zones"].values()) \
            == sorted(theirs["check"]["zones"].values()) == [10, 10, 11]
        await conn.close()
        await handle.stop()

    asyncio.run(asyncio.wait_for(go(), 120))


# --------------------------------------------------------------------------
# (c): not by luck
# --------------------------------------------------------------------------

# A cold solve of a spread stage runs ONE sweep: the batched seed deals the
# rows to the domains within the bound and inside capacity, so chain 0 is
# feasible before the first sweep, a sweep cannot take it out of the bound
# (anneal._admit_spread), and the adaptive exit needs one block of one
# sweep to see it. 2 leaves room for a seed whose best-effort tail left a
# unit for the sweeps. The parent (aaf06c3) on these 24 cases: feasible in
# all 24, after 8 to 112 sweeps (median 45), under this bound in none; at
# 2,000 x 5,000 it fails 3 of 5 seeds with one chain (ISSUE 39's table).
SWEEPS_BOUND = 2


def _spread_problem(seed: int, rows: int = 400, nodes: int = 1000):
    """`rows` spreading pods over `nodes` nodes in three zones (equal for
    even seeds, 60 / 30 / 10 % for odd), capacity less `nodes` init pods
    thrown at random, lowered by `lower_stage` as the CP lowers it."""
    model = ref.cluster(seed, nodes, 0, rows)
    if seed % 2:
        model = ref.with_zones(model, {
            "moon-1": nodes * 6 // 10, "moon-2": nodes * 3 // 10,
            "moon-3": nodes - nodes * 6 // 10 - nodes * 3 // 10})
    rng = np.random.default_rng(seed)
    free = np.tile(np.array([[ref.NODE["cpu"], ref.NODE["memory"],
                              40960.0]]), (nodes, 1))
    np.subtract.at(free, rng.integers(0, nodes, nodes),
                   np.array([ref.POD["cpu"], ref.POD["memory"], 0.0]))
    servers = [Node(slug, ServerLabels(**gen.server_labels(node)))
               for slug, node in model["nodes"].items()]
    return lower_stage(gen.flow(model, MEASURED), MEASURED, nodes=servers,
                       capacity=free)


@pytest.mark.parametrize("chains", [1, 2])
@pytest.mark.parametrize("seed", range(12))
def test_a_spread_stage_is_solved_in_the_same_few_sweeps(seed, chains):
    pt = _spread_problem(seed)
    assert pt.max_skew == 1 and int(pt.node_topology.max()) == 2
    res = solve(pt, chains=chains, seed=seed)
    assert res.feasible, res.stats
    assert res.pre_repair_violations == 0 and res.moves_repaired == 0
    assert res.steps <= SWEEPS_BOUND, res.steps
    per = np.bincount(pt.node_topology[res.assignment])
    assert sorted(per.tolist()) == [133, 133, 134]


@pytest.mark.parametrize("seed_impl", ["scan", "native"])
def test_the_scan_seed_deals_and_the_sweeps_level_a_blind_seed(seed_impl):
    """The scan seed applies the source's own filter row by row; the host
    FFD reads no topology (60 % of the nodes in one zone draw 60 % of the
    rows), and the sweeps carry the excess back, downhill move by move,
    where `max - min` alone is flat."""
    pt = _spread_problem(3)
    res = solve(pt, chains=1, seed=3, seed_impl=seed_impl)
    assert res.feasible and res.pre_repair_violations == 0, res.stats
    assert res.steps <= (SWEEPS_BOUND if seed_impl == "scan" else 24)


# --------------------------------------------------------------------------
# (d): a zone that cannot take its share
# --------------------------------------------------------------------------

def _starved(fallback=None) -> tuple[_Cluster, dict]:
    """30 nodes in three zones; moon-3's ten nodes hold one pod each (cpu
    0.1 of 0.1): 45 spreading pods want 15 a zone and moon-3 takes 10."""
    model = ref.cluster(11, 30, 0, 45)
    for node in model["nodes"].values():
        if node["zone"] == "moon-3":
            node["cpu"] = ref.POD["cpu"]
    return _Cluster(model, fallback=fallback), model


def test_a_zone_that_cannot_take_its_share_is_infeasible_not_relaxed():
    c, model = _starved()
    theirs = _reference(model)
    left = sum(v is None for v in theirs["placed"][MEASURED].values())
    assert left == 45 - (10 + 11 + 11)      # the source leaves 13 pending
    relaxed0 = _counter("fleet_sched_relaxed_total", what="spread")
    final0 = _counter("fleet_solver_spread_excess_total", at="final")
    placement, rid = c.solve(MEASURED)
    assert not placement.feasible and rid is None
    assert placement.violations >= 1
    assert placement.source == "cpu-anneal"
    assert _counter("fleet_sched_relaxed_total", what="spread") == relaxed0
    # what is left over is counted where it is: skew or capacity
    pt, _ = c.svc._last[KEY[MEASURED]]
    stats = verify(pt, np.asarray(placement.raw))
    assert stats["skew"] + stats["capacity"] == stats["total"] > 0
    assert (_counter("fleet_solver_spread_excess_total", at="final")
            - final0) == stats["skew"]
    assert c.record(MEASURED) is None
    assert all(s.allocated.cpu == 0 for s in c.store.list("servers"))


def test_a_declared_fallback_drops_the_bound_and_the_reply_says_so():
    c, model = _starved(fallback=["spread"])
    relaxed0 = _counter("fleet_sched_relaxed_total", what="spread")
    placement, rid = c.solve(MEASURED)
    assert placement.feasible and rid is not None
    assert placement.source == "cpu-anneal+relaxed:spread"
    assert _counter("fleet_sched_relaxed_total", what="spread") \
        == relaxed0 + 1
    # the reference calls the answer what it is: 45 placed, skew over
    found = ref.check(model, {MEASURED: placement.assignment})
    assert found["skew"] > 0 and found["capacity"] == 0


def test_the_host_scheduler_keeps_the_bound_or_says_it_could_not():
    """A CP without the device solver places by `sched/host.py`'s greedy.
    It used to read no topology and count no skew, so a spread stage came
    back `feasible` with the bound ignored; it applies the source's filter
    now, and what it cannot level it counts."""
    model = ref.with_zones(ref.cluster(3, 60, 0, 31),
                           {"moon-1": 36, "moon-2": 18, "moon-3": 6})
    c = _Cluster(model, use_tpu=False)
    placement, _rid = c.solve(MEASURED)
    assert placement.feasible and placement.source == "host-greedy"
    found = ref.check(model, {MEASURED: placement.assignment})
    assert found["total"] == 0, found
    assert sorted(found["zones"].values()) == [10, 10, 11]
    c, model = _starved()
    c.svc.use_tpu = False
    placement, rid = c.solve(MEASURED)
    assert not placement.feasible and rid is None
    assert placement.violations > 0


# --------------------------------------------------------------------------
# a stage torn down and forgotten is solved from the seed the next time
# --------------------------------------------------------------------------

@pytest.mark.parametrize("forget", [False, True], ids=["kept", "forgotten"])
def test_a_torn_down_stage_is_forgotten_only_where_the_caller_says(forget):
    """`release_stage` returns the commitment either way. With `forget`
    (what the CP's `down` of a whole stage passes, and the benchmark's
    spread cell between ops) the retained problem, the snapshot's entry and
    the solver slot go too, so the next solve under the key is a first
    solve: the dealt seed again, not a warm start from rows that are gone.
    Without it the next solve of that shape still warm-starts."""
    c = _Cluster(ref.cluster(5, 30, 0, 20))
    c.place(MEASURED)
    key = KEY[MEASURED]
    assert c.svc.release_stage(key, forget=forget)
    assert c.record(MEASURED) is None
    kept = not forget
    assert (c.svc.retained(key) is not None) == kept
    assert (key in c.svc.snapshot()) == kept
    assert (key in [s["stage"] for s in c.svc.solver_slots()["slots"]]) \
        == kept
    seen = []
    real = c.svc._sched_tpu.place
    c.svc._sched_tpu.place = lambda pt, **kw: (
        seen.append(kw["warm_start"]), real(pt, **kw))[1]
    placement, _rid = c.solve(MEASURED, model=ref.measured_batch(c.model, 1))
    assert placement.feasible and seen == [kept]
    # and the churn loop has nothing of a forgotten stage to re-solve
    gone = next(iter(placement.assignment.values()))
    c.svc.release_stage(key, forget=forget)
    assert (key in [k for k, _p in c.svc.node_event(gone, online=False)]) \
        == kept


# --------------------------------------------------------------------------
# (e): a node without the key
# --------------------------------------------------------------------------

def test_a_node_without_the_key_takes_no_pod_and_is_no_zone():
    """12 of 42 nodes carry no zone label. They used to be a domain each,
    which pinned the emptiest domain at 0; now they are barred and
    uncounted, as the source reads a node without the topology key."""
    model = ref.with_zones(ref.cluster(2, 42, 0, 20), {None: 12})
    c = _Cluster(model)
    placement, rid = c.solve(MEASURED)
    assert placement.feasible, placement.violations
    pt, _ = c.svc._last[KEY[MEASURED]]
    assert int(pt.topology_keyless.sum()) == 12
    assert int(pt.node_topology.max()) == 2
    assert not pt.eligible[:, pt.topology_keyless].any()
    found = ref.check(model, {MEASURED: placement.assignment})
    assert found["total"] == 0 and found["unlabelled"] == 0, found
    assert sorted(found["zones"].values()) == [6, 7, 7]
    # the bar outlives an eligibility rung while the constraint stands
    assert not relax_problem(
        dataclasses.replace(pt, eligible=pt.eligible & False), "labels"
    ).eligible[:, pt.topology_keyless].any()


def test_a_zone_whose_nodes_are_all_down_is_no_zone():
    model = ref.cluster(4, 30, 0, 20)
    c = _Cluster(model)
    for s in c.store.list("servers"):
        if model["nodes"][s.slug]["zone"] == "moon-2":
            c.store.update("servers", s.id, status="offline")
    placement, _rid = c.solve(MEASURED)
    assert placement.feasible, placement.violations
    down = [n for n, node in model["nodes"].items()
            if node["zone"] == "moon-2"]
    found = ref.check(model, {MEASURED: placement.assignment}, offline=down)
    assert found["total"] == 0, found
    assert found["zones"] == {"moon-1": 10, "moon-2": 0, "moon-3": 10}


def test_a_stage_whose_key_no_server_carries_is_refused_at_lowering():
    model = ref.with_zones(ref.cluster(2, 9, 0, 4), {None: 9})
    with pytest.raises(SolverError, match="carries the key"):
        _Cluster(model).solve(MEASURED)


def test_topology_phase_and_the_debug_line(caplog):
    c = _Cluster(ref.cluster(2, 30, 0, 12))
    t0 = obs_trace.time.perf_counter()
    with caplog.at_level("INFO", logger="fleetflow.solver"):
        c.solve(MEASURED)
    spans = obs_trace.spans_between(t0, obs_trace.time.perf_counter())
    lower = [s for s in spans if s[0] == "cp.solve_stage.lower"]
    topo = [s for s in spans if s[0] == "cp.solve_stage.lower.topology"]
    assert len(lower) == len(topo) == 1
    assert lower[0][1] <= topo[0][1] and topo[0][2] <= lower[0][2]
    assert any("spread_domains=3" in r.getMessage()
               for r in caplog.records)
    # a stage that spreads over nothing opens no such phase
    t0 = obs_trace.time.perf_counter()
    c.solve(INIT, model=ref.cluster(2, 30, 5, 0), reserve=False)
    assert "cp.solve_stage.lower.topology" not in {
        s[0] for s in obs_trace.spans_between(
            t0, obs_trace.time.perf_counter())}


# --------------------------------------------------------------------------
# (f): the host's repair has a move for skew
# --------------------------------------------------------------------------

def test_repair_levels_an_assignment_two_over():
    pt = _spread_problem(0, rows=90, nodes=60)
    res = solve(pt, chains=1, seed=0)
    assert res.feasible
    skewed = res.assignment.copy()
    zone = pt.node_topology
    into = np.flatnonzero(zone == 0)
    movers = np.flatnonzero(zone[skewed] == 1)[:2]
    skewed[movers] = into[:2]
    before = verify(pt, skewed)
    assert before["skew"] == 3 and before["total"] == 3  # 32 / 28 / 30
    out = repair(pt, skewed)
    assert out.feasible and out.stats["total"] == 0
    assert out.moves == out.skew_moves == 2
    per = np.bincount(zone[out.assignment])
    assert per.max() - per.min() <= 1


# --------------------------------------------------------------------------
# (g): a stage without a spread constraint runs the program it ran
# --------------------------------------------------------------------------

# equations of `make_jaxpr(_refine)` on the fixed problem below, counted on
# the parent (aaf06c3) under jax 0.4.x as installed here: cold, warm. The
# warm count was 1493 there; since PR 40 `prerepair_state` reads its row of
# the packed plane as a slice of words unpacked (`eligible_row`), five
# equations shorter than the gather of a lookup a node it replaces
PARENT_EQUATIONS = {False: 1140, True: 1488}
PARENT_JAX = jax.__version__


def _count_equations(jaxpr) -> int:
    n = 0
    for eq in jaxpr.eqns:
        n += 1
        for v in eq.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(sub, "jaxpr"):
                    n += _count_equations(sub.jaxpr)
                elif hasattr(sub, "eqns"):
                    n += _count_equations(sub)
    return n


def _trace_refine(pt, warm: bool):
    import jax.numpy as jnp
    from fleetflow_tpu.solver.problem import prepare_problem

    prob = prepare_problem(pt)

    def refine(p, s, k):
        return solver_api._refine.__wrapped__(
            p, s, k, 1.0, 1e-3, 0.5, chains=2, steps=8, warm=warm,
            anneal_block=1, proposals_per_step=16,
            fused_prerepair=warm, prerepair_moves=16 if warm else 0,
            skip_feasible_polish=False, trace_blocks=4)

    return jax.make_jaxpr(refine)(prob, jnp.zeros((pt.S,), jnp.int32),
                                  jax.random.PRNGKey(0))


@pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
def test_a_stage_that_spreads_over_nothing_traces_none_of_the_new_code(
        warm, monkeypatch):
    from fleetflow_tpu.lower import synthetic_problem
    from fleetflow_tpu.solver import greedy as greedy_mod

    calls = {"window": 0, "admit": 0, "deal": 0}

    def counted(name, fn):
        def wrapper(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw)
        return wrapper

    monkeypatch.setattr(anneal_mod, "spread_window",
                        counted("window", anneal_mod.spread_window))
    monkeypatch.setattr(anneal_mod, "_admit_spread",
                        counted("admit", anneal_mod._admit_spread))
    monkeypatch.setattr(greedy_mod, "deal_to_domains",
                        counted("deal", greedy_mod.deal_to_domains))
    pt = synthetic_problem(48, 12, seed=3)
    assert pt.max_skew == 0
    jaxpr = _trace_refine(pt, warm)
    assert calls == {"window": 0, "admit": 0, "deal": 0}
    if jax.__version__ == PARENT_JAX:
        assert _count_equations(jaxpr.jaxpr) == PARENT_EQUATIONS[warm]
    # and a stage that does spread traces it
    spread = dataclasses.replace(
        pt, node_topology=(np.arange(12) % 3).astype(np.int32), max_skew=1)
    more = _trace_refine(spread, warm)
    assert calls["window"] >= 1 and calls["admit"] >= 1
    assert _count_equations(more.jaxpr) > _count_equations(jaxpr.jaxpr)


# --------------------------------------------------------------------------
# (h): the mesh and the sub-solve
# --------------------------------------------------------------------------

def test_the_sub_solve_keeps_the_bound_under_churn(monkeypatch):
    """A churn re-solve's localized sub-solve runs the same sweep
    (`anneal._batched_step`) over the frozen rows' counts (`topo0`): a
    killed node's rows are re-placed and the zones stay within the
    bound."""
    from fleetflow_tpu.solver.resident import ProblemDelta, ResidentProblem

    monkeypatch.setenv("FLEET_SUBSOLVE_MIN", "8")
    pt = _spread_problem(6, rows=96, nodes=48)
    kw = dict(steps=32, anneal_block=1, warm_block=1, chains=1)
    rp = ResidentProblem(pt)
    cold = solve(pt, resident=rp, **kw)
    assert cold.feasible
    victim = int(np.bincount(cold.assignment, minlength=pt.N).argmax())
    valid = pt.node_valid.copy()
    valid[victim] = False
    pt2 = dataclasses.replace(pt, node_valid=valid)
    rp.apply_delta(pt2, ProblemDelta(node_valid=valid))
    warm = solve(pt2, resident=rp, resident_warm=True, **kw)
    assert warm.feasible and warm.stats["skew"] == 0, warm.stats
    assert warm.subsolve is not None
    assert warm.subsolve["outcome"] == "localized"
    per = np.bincount(pt.node_topology[warm.assignment])
    assert per.max() - per.min() <= 1 and not (warm.assignment
                                               == victim).any()


def _sharded_from(pt, init, steps):
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from fleetflow_tpu.solver.problem import prepare_problem
    from fleetflow_tpu.solver.sharded import SVC_AXIS, anneal_sharded

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip(f"needs 8 devices, have {len(devs)}")
    mesh = Mesh(np.array(devs[:8]), (SVC_AXIS,))
    return np.asarray(anneal_sharded(
        prepare_problem(pt), jnp.asarray(init, jnp.int32),
        jax.random.PRNGKey(steps), steps=steps, mesh=mesh, block=4))


def _blind_seed(pt) -> np.ndarray:
    """Rows dealt round-robin over the nodes: a zone draws rows as it has
    nodes, 60 / 30 / 10 % here, 43 rows over the bound."""
    return (np.arange(pt.S) % pt.N).astype(np.int32)


def test_the_mesh_returns_a_seed_within_the_bound_within_it():
    """The mesh's sweep shares `_move_delta_core`, passes no band (it
    prices `max - min` as before this PR) and admits moves per service and
    per target node only. What keeps a seed at the bound within it there
    is best-ever tracking, not the sweep: the walk may cross the bound,
    the answer is the best state seen."""
    pt = _spread_problem(9, rows=96, nodes=48)
    seed = solve(pt, chains=1, seed=9).assignment
    assert verify(pt, seed)["skew"] == 0
    for steps in (3, 9, 33):
        assert verify(pt, _sharded_from(pt, seed, steps))["total"] == 0


def test_the_mesh_levels_a_blind_seed_given_sweeps():
    pt = _spread_problem(9, rows=96, nodes=48)
    assert verify(pt, _blind_seed(pt))["skew"] == 43
    assert verify(pt, _sharded_from(pt, _blind_seed(pt), 128))["total"] == 0


def test_one_chip_levels_a_blind_seed_in_a_quarter_of_that():
    pt = _spread_problem(9, rows=96, nodes=48)
    res = solve(pt, chains=1, seed=9, steps=32,
                init_assignment=_blind_seed(pt), do_repair=False)
    assert res.feasible and res.steps <= 32, (res.stats, res.steps)


@pytest.mark.xfail(strict=True, reason="anneal_sharded prices "
                   "`max - min`, flat for every move that touches neither "
                   "the fullest nor the emptiest domain, aims no proposal "
                   "at a domain with room and has no per-domain admission: "
                   "32 sweeps leave a row over where one chip is level "
                   "(PERF.md §7, Not held yet; ROADMAP Queue 3 item 1)")
def test_the_mesh_levels_a_blind_seed_as_fast_as_one_chip():
    pt = _spread_problem(9, rows=96, nodes=48)
    assert verify(pt, _sharded_from(pt, _blind_seed(pt), 32))["total"] == 0
