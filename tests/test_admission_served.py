"""Streaming admission on the served path (PR 41): Kubernetes
scheduler_perf's SchedulingBasic through the CP's queue.

A live CP — `cp.server.start`, a real `ProtocolClient` connection — is
given the deployment `k8s-sp-basic-5k` at a size the CPU solves in no time:
the nodes registered online, the init pods attached as a flow by the first
`deploy.submit`, then waves of pending pods in one `deploy.submit` each,
waited for (`wait`). Every scenario is compared with the benchmark's plain
reference (benchmarks/reference_k8s_basic.py, which imports nothing of the
program) on the same cluster: what the reference leaves pending the
program may park, nothing else; what the caller was told is what the
store's record holds; a pod that ran before the op is where it was; a
departed pod is in no view.

No test sleeps on the drain loop's 0.5 s timer: the loop is woken by the
submit and runs while there is work, and `fleet_admission_wakes_total`
says so.

The sizes are small, so the sub-solve's first tier is made small with them
(`FLEET_SUBSOLVE_MIN`, 256 by default: no problem under 256 rows would be
localized): a micro-solve then takes the path it takes at the source's
size, where only the arrivals are active. The one test that leaves the
default in place shows what the full path does to a small stage.
"""

from __future__ import annotations

import asyncio
import time

import pytest

from benchmarks import generators_k8s_basic as gen
from benchmarks import reference_k8s_basic as ref
from fleetflow_tpu.cp.admission import subsolve_outcomes
from fleetflow_tpu.cp.models import ServerCapacity
from fleetflow_tpu.cp.protocol import ProtocolClient, RpcError
from fleetflow_tpu.cp.server import ServerConfig, start
from fleetflow_tpu.obs import trace as obs_trace
from fleetflow_tpu.obs.metrics import REGISTRY

NODES, INIT, WAVE, BATCH = 150, 30, 40, 8
WAIT_S = 120.0


def _run(coro, timeout=300):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def _counter(name: str, **labels) -> float:
    metric = REGISTRY.get(name)
    assert metric is not None, f"the program has no counter {name}"
    return metric.value(**labels)


def _wakes() -> dict:
    return {by: _counter("fleet_admission_wakes_total", by=by)
            for by in ("submit", "backlog", "timer")}


@pytest.fixture
def localized(monkeypatch):
    """The sub-solve's first tier scaled down with the problem."""
    monkeypatch.setenv("FLEET_SUBSOLVE_MIN", "8")


class _Cp:
    """A live CP with the model's nodes registered online and its init
    pods attached over the wire, and one client connection."""

    @classmethod
    async def start(cls, model: dict, batch: int = BATCH, **config):
        self = cls()
        self.model = model
        self.handle = await start(ServerConfig(
            use_tpu_solver=True, admission_batch=batch, **config))
        self.adm = self.handle.state.admission
        store = self.handle.state.store
        for slug, node in model["nodes"].items():
            rec = store.register_server(slug, tenant="default", hostname=slug)
            store.update("servers", rec.id, status="online",
                         capacity=ServerCapacity(
                             **gen.server_capacity(node)))
        self.conn, self._task = await ProtocolClient.connect(
            self.handle.host, self.handle.port, identity="test-client")
        reply = await self.conn.request(
            "deploy", "submit", gen.attach_request(model), timeout=WAIT_S)
        assert reply["stage"] == gen.KEY and reply["accepted"] == []
        return self

    async def stop(self) -> None:
        await self.conn.close()
        self._task.cancel()
        await self.handle.stop()

    def committed(self) -> dict:
        """The placements record of the stage, read back from the store."""
        rec = self.handle.state.store.find_one(
            "placements", lambda p: p.stage_key == gen.KEY)
        return dict(rec.assignment)

    async def submit(self, **payload) -> dict:
        return await self.conn.request(
            "deploy", "submit",
            {"tenant": gen.TENANT, "stage": gen.KEY, **payload},
            timeout=WAIT_S)

    async def admit(self, model: dict, wait: float = WAIT_S) -> dict:
        """One wave: one `deploy.submit`, its reply held for the
        verdicts."""
        return await self.conn.request(
            "deploy", "submit", gen.submit_request(model["wave"], wait),
            timeout=WAIT_S)

    async def withdraw(self, model: dict) -> dict:
        return await self.submit(
            departures=[p["name"] for p in model["wave"]], wait=WAIT_S)


async def _until(cond) -> None:
    """Serve the event loop until `cond()` (the caller's `_run` bounds
    the wait)."""
    while not cond():
        await asyncio.sleep(0)


def _told(reply: dict) -> dict:
    """{pod: server or None} of the arrivals' verdicts."""
    return {v["name"]: v.get("server") for v in reply["verdicts"]
            if v.get("kind") == "arrival"}


def _states(reply: dict) -> dict:
    out: dict[str, int] = {}
    for v in reply["verdicts"]:
        out[v["state"]] = out.get(v["state"], 0) + 1
    return out


def test_the_sources_shape_small(localized):
    """(i) 150 nodes, 30 init pods, 40 arrivals at batch_max 8: every
    verdict `placed`, the reference's check 0 against the record read
    back, and the reference places all of them too."""
    async def go():
        model = ref.cluster(11, NODES, INIT, WAVE)
        mine = ref.schedule(model, {})
        assert None not in mine.values() and len(mine) == INIT + WAVE
        cp = await _Cp.start(model)
        try:
            before = cp.committed()
            assert ref.check(dict(model, wave=[]), {}, before, {})["total"] \
                == 0
            solves = cp.adm.stats["solves"]
            reply = await cp.admit(model)
            assert _states(reply) == {"placed": WAVE}
            assert reply["pending"] == 0
            assert len(reply["accepted"]) == WAVE
            # five micro-solves of eight, each committed
            assert cp.adm.stats["solves"] - solves == WAVE // BATCH
            after = cp.committed()
            found = ref.check(model, before, after, _told(reply))
            assert found["total"] == 0, found
            assert set(_told(reply).values()) <= set(model["nodes"])
            status = await cp.conn.request("deploy", "admit_status", {})
            assert status["stats"]["moved_rows"] == 0
            assert status["parked"] == 0 and status["stats"]["sheds"] == 0
        finally:
            await cp.stop()
    _run(go())


def test_rows_recirculate_and_a_departed_pod_is_in_no_view(localized):
    """(ii) the wave withdrawn and a second one admitted: the second
    reuses the first's rows, nothing compacts, no pod of the init set
    moves, no pod of the first wave is left in the record."""
    async def go():
        model = ref.cluster(12, NODES, INIT, WAVE)
        cp = await _Cp.start(model)
        try:
            init = cp.committed()
            first = await cp.admit(model)
            assert _states(first) == {"placed": WAVE}
            gone = await cp.withdraw(model)
            assert _states(gone) == {"departed": WAVE}
            assert cp.committed() == init
            rows = cp.adm._streams[gen.KEY].pt.S
            second_model = ref.wave(model, 1)
            before = cp.committed()
            second = await cp.admit(second_model)
            assert _states(second) == {"placed": WAVE}
            found = ref.check(second_model, before, cp.committed(),
                              _told(second))
            assert found["total"] == 0, found
            assert cp.adm._streams[gen.KEY].pt.S == rows
            assert cp.adm.stats["compactions"] == 0
            assert cp.adm.stats["moved_rows"] == 0
            # the first wave's verdicts named servers; none is in view now
            assert not set(_told(first)) & set(cp.committed())
        finally:
            await cp.stop()
    _run(go())


def test_the_loop_keeps_no_backlog_waiting(localized):
    """(iii) a backlog five micro-batches deep drains with the timer
    waking the loop never: the submit wakes it once, the backlog keeps it
    going. Asserted on the loop's own count of why it took a pass, not on
    wall time. (The parent's loop slept `drain_interval_s` after every
    pass and kept no such count.)"""
    async def go():
        model = ref.cluster(13, NODES, INIT, WAVE)
        # a timer that could not fire inside the test even once
        cp = await _Cp.start(model)
        cp.adm.cfg.drain_interval_s = 3600.0
        try:
            # the loop is asleep on the new interval once it has seen it
            await cp.submit(arrivals=gen.arrivals(
                [dict(ref.POD, name="warm-0")]), wait=WAIT_S)
            wakes, batches = _wakes(), cp.adm.stats["batches"]
            reply = await cp.admit(model)
            assert _states(reply) == {"placed": WAVE}
            took = {by: n - wakes[by] for by, n in _wakes().items()}
            assert cp.adm.stats["batches"] - batches == WAVE // BATCH
            assert took == {"submit": 1, "backlog": WAVE // BATCH - 1,
                            "timer": 0}
        finally:
            await cp.stop()
    _run(go())


def test_a_pass_that_can_do_nothing_is_left_to_the_timer(localized):
    """(iii) the other way round: departures that cannot be applied —
    three of five nodes cordoned under the running pods, so not even the
    stage less one pod fits — go back to the head of their queue, and the
    loop does not take that pass again back to back: it leaves it to the
    timer. A later submit wakes it, and with the nodes back both
    departures go through; nothing was lost."""
    async def go():
        model = ref.cluster(20, 5, 40, BATCH, node=dict(ref.NODE, cpu=1.0))
        cp = await _Cp.start(model)
        cp.adm.cfg.drain_interval_s = 3600.0
        store = cp.handle.state.store
        try:
            await cp.submit(arrivals=gen.arrivals(
                [dict(ref.POD, name="warm-0")]), wait=WAIT_S)
            servers = store.list("servers")[:3]
            for rec in servers:
                store.update("servers", rec.id, scheduling_state="cordoned")
            wakes, solves = _wakes(), cp.adm.stats["solves"]
            first, second = (p["name"] for p in model["init"][:2])
            await cp.submit(departures=[first])
            await _until(lambda: cp.adm.stats["solves"] > solves)
            for _ in range(3):          # each waits for the pass's lock
                status = await cp.conn.request("deploy", "admit_status", {})
            took = {by: n - wakes[by] for by, n in _wakes().items()}
            assert took == {"submit": 1, "backlog": 0, "timer": 0}
            assert status["queue_depth"] == 1 and cp.adm.has_work()
            assert first in cp.committed()
            for rec in servers:
                store.update("servers", rec.id,
                             scheduling_state="schedulable")
            gone = await cp.submit(departures=[second], wait=WAIT_S)
            assert _states(gone) == {"departed": 1}
            assert not {first, second} & set(cp.committed())
            took = {by: n - wakes[by] for by, n in _wakes().items()}
            assert took == {"submit": 2, "backlog": 0, "timer": 0}
            assert not cp.adm.has_work()
        finally:
            await cp.stop()
    _run(go())


def test_a_cluster_too_small_parks_what_the_reference_leaves_pending(
        localized):
    """(iv) a cluster too small by k pods: the reference leaves k pending
    and the program parks k (`reason: capacity`) — k a whole micro-batch:
    a micro-batch that does not fit parks whole (guide 14) — the waiting
    caller is told `parked` for those and `placed` for the rest, nothing
    is lost, and a departure that frees room lets the parked ones
    through."""
    async def go():
        node = dict(ref.NODE, cpu=1.0)              # ten pods a node
        free, k = 2 * BATCH, BATCH
        model = ref.cluster(14, 5, 50 - free, free + k, node=node)
        mine = ref.schedule(model, {})
        assert sum(v is None for v in mine.values()) == k
        cp = await _Cp.start(model)
        try:
            before = cp.committed()
            reply = await cp.admit(model)
            assert _states(reply) == {"placed": free, "parked": k}
            parked = [v for v in reply["verdicts"] if v["state"] == "parked"]
            assert {v["reason"] for v in parked} == {"capacity"}
            assert all("server" not in v for v in parked)
            found = ref.check(model, before, cp.committed(), _told(reply),
                              pending={v["name"] for v in parked})
            assert found["total"] == 0, found
            status = await cp.conn.request("deploy", "admit_status", {})
            assert status["parked"] == k and status["queue_depth"] == 0
            # k init pods leave: the parked ones go through — their
            # caller was told `parked`; where they land is the record's
            leaving = [p["name"] for p in model["init"][:k]]
            gone = await cp.submit(departures=leaving, wait=WAIT_S)
            assert _states(gone) == {"departed": k}
            await _until(lambda: cp.adm.stats["admitted"] == free + k)
            after = cp.committed()
            left = dict(model, init=model["init"][k:])
            told = {**_told(reply),
                    **{v["name"]: after.get(v["name"]) for v in parked}}
            assert cp.adm.stats["unparked"] == k
            found = ref.check(left, {n: s for n, s in before.items()
                                     if n not in leaving}, after, told)
            assert found["total"] == 0, found
            assert cp.adm.stats["sheds"] == 0
        finally:
            await cp.stop()
    _run(go())


def test_a_wait_that_times_out_answers_what_is_terminal_so_far(localized):
    """(v) a wait of no time at all answers with what is terminal by
    then and says how many requests are still queued; none of those is
    lost: the queue drains and the record holds every pod."""
    async def go():
        model = ref.cluster(15, NODES, INIT, WAVE)
        cp = await _Cp.start(model)
        try:
            reply = await cp.admit(model, wait=1e-6)
            states = _states(reply)
            assert set(states) <= {"queued", "placed"}
            assert reply["pending"] == states.get("queued", 0)
            assert sum(states.values()) == WAVE
            await _until(lambda: cp.adm.stats["admitted"] == WAVE)
            after = cp.committed()
            # what it was told holds; the rest it reads from the record
            told = {name: server or after.get(name)
                    for name, server in _told(reply).items()}
            assert ref.check(model, {}, after, told)["total"] == 0
        finally:
            await cp.stop()
    _run(go())


def test_a_wait_on_a_shed_request_says_shed(localized):
    """(v) an arrival that out-ages the watermark before a pass takes it
    is shed, and its waiting caller is told so. The controller's clock is
    the injected one; nothing sleeps."""
    async def go():
        model = ref.cluster(16, NODES, INIT, BATCH)
        cp = await _Cp.start(model, admission_shed_age_s=5.0)
        try:
            now = [1000.0]
            cp.adm.clock = lambda: now[0]
            cp.adm.stop()                   # passes by hand from here on
            waiting = asyncio.ensure_future(cp.admit(model))
            await _until(cp.adm.has_work)
            now[0] += 6.0
            await asyncio.get_running_loop().run_in_executor(
                None, cp.adm.step)
            reply = await waiting
            assert _states(reply) == {"shed": BATCH}
            assert reply["pending"] == 0
            assert not set(_told(reply)) & set(cp.committed())
        finally:
            await cp.stop()
    _run(go())


def test_the_event_loop_is_served_in_the_middle_of_a_backlog(localized):
    """(vi) a `deploy.admit_status` and a second submit sent while a
    backlog drains are answered between passes, not after the last."""
    async def go():
        model = ref.cluster(17, NODES, INIT, 10 * BATCH)
        cp = await _Cp.start(model)
        try:
            batches = cp.adm.stats["batches"]
            wave = asyncio.ensure_future(cp.admit(model))
            # as soon as the first pass has been taken
            await _until(lambda: cp.adm.stats["batches"] > batches)
            status = await cp.conn.request("deploy", "admit_status", {})
            second = await cp.submit(arrivals=gen.arrivals(
                [dict(ref.POD, name="late-0")]))
            done = (await wave, cp.adm.stats["batches"] - batches)
            assert status["queue_depth"] > 0
            assert status["stats"]["batches"] - batches < 10
            assert second["queued"] > 1         # it joined a live queue
            assert _states(done[0]) == {"placed": 10 * BATCH}
            await _until(lambda: "late-0" in cp.committed())
        finally:
            await cp.stop()
    _run(go())


# (vii) incumbents: sizes at which a micro-solve is localized with the
# sub-solve's first tier as it ships (256): 320 running pods, arrivals in
# micro-batches of 32, the third of which takes the stage past its padded
# tier (320 -> 352 -> 384 rows inside 384, 416 rows past it)
GROW_INIT, GROW_WAVE, GROW_BATCH = 320, 96, 32


def test_a_stage_that_outgrows_its_tier_keeps_its_incumbents():
    """(vii) rows appended past the padded tier: the stage is staged anew
    and the solve that follows is still localized to the arrivals — the
    new staging inherits the old one's placement — so no running pod
    moves and every verdict still holds at the end of the wave. (The
    parent re-solved the whole stage from the seed there: 63 of the 64
    pods the wave's first two micro-solves had placed moved.)"""
    async def go():
        model = ref.cluster(18, NODES, GROW_INIT, GROW_WAVE)
        cp = await _Cp.start(model, batch=GROW_BATCH)
        try:
            before = cp.committed()
            outcomes, moved = subsolve_outcomes(), \
                _counter("fleet_admission_moved_rows_total")
            reply = await cp.admit(model)
            assert _states(reply) == {"placed": GROW_WAVE}
            assert cp.adm._streams[gen.KEY].pt.S == GROW_INIT + GROW_WAVE
            found = ref.check(model, before, cp.committed(), _told(reply))
            assert found["total"] == 0, found
            assert _counter("fleet_admission_moved_rows_total") == moved
            took = {k: v - outcomes[k]
                    for k, v in subsolve_outcomes().items()}
            assert took == {"localized": 3, "fallback_closure": 0,
                            "fallback_small": 0, "fallback_infeasible": 0}
        finally:
            await cp.stop()
    _run(go())


def test_the_full_warm_path_moves_no_incumbent_of_a_small_stage():
    """A stage too small for the sub-solve's first tier (under 256 rows,
    FLEET_SUBSOLVE_MIN as it ships): 150 nodes, 30 init pods, 40 arrivals
    at batch_max 8. The full warm path parks arrivals on the first valid
    server with the same stickiness bonus as an incumbent, and once that
    server overflows moves whichever it likes (12 rows in the fifth
    micro-solve, 1 init pod and 11 pods of this wave, before arrivals were
    told apart). A closure of fresh arrivals alone is localized whatever
    the stage's size (solver/subsolve.py), so the full path is not taken
    and no incumbent moves."""
    async def go():
        model = ref.cluster(7, NODES, INIT, WAVE)
        cp = await _Cp.start(model)
        try:
            before = cp.committed()
            reply = await cp.admit(model)
            assert _states(reply) == {"placed": WAVE}
            found = ref.check(model, before, cp.committed(), _told(reply))
            moved = cp.adm.stats["moved_rows"]
            # the count is kept either way: it is what the cell checks
            assert moved == found["moved"] + found["untold"]
            assert moved == 0, f"{moved} rows moved: {found}"
        finally:
            await cp.stop()
    _run(go())


def test_the_span_tree_reaches_through_admission(localized):
    """A waited submit in the span tree: `cp.admission.submit` and
    `cp.admission.wait.verdict` are children of the request's
    `cp.handler`; every drain pass is a root `cp.admission.step` whose
    `.solve` child is the parent of `sched.place` and of
    `cp.admit_batch.refresh`; a pass that found work waiting wrote
    `cp.admission.wait.drain`; and `fleet_admission_solve_phase_ms` is fed
    from the same phases."""
    async def go():
        model = ref.cluster(19, NODES, INIT, 2 * BATCH)
        cp = await _Cp.start(model)
        try:
            hist = REGISTRY.get("fleet_admission_solve_phase_ms")
            before = {ph: hist.count(phase=ph)
                      for ph in ("drain", "fold", "solve", "commit")}
            t0 = time.perf_counter()
            reply = await cp.admit(model)
            t1 = time.perf_counter()
            assert _states(reply) == {"placed": 2 * BATCH}
            tree = obs_trace.tree_between(t0, t1)
            by_id = {r[4]: r for r in tree}

            def named(name):
                return [r for r in tree if r[0] == name]

            def parent(r):
                return by_id[r[5]][0] if r[5] in by_id else None

            handler, = [r for r in named("cp.handler")]
            for name in ("cp.admission.submit", "cp.admission.wait.verdict"):
                span, = named(name)
                assert parent(span) == "cp.handler", name
            steps = named("cp.admission.step")
            assert len(steps) == 2 and all(r[5] == 0 for r in steps)
            for name in ("drain", "fold", "solve", "commit"):
                kids = named(f"cp.admission.step.{name}")
                assert len(kids) == 2
                assert {parent(r) for r in kids} == {"cp.admission.step"}
                took = hist.count(phase=name) - before[name]
                assert took == 2, name
            assert {parent(r) for r in named("sched.place")} == \
                {"cp.admission.step.solve"}
            assert {parent(r) for r in named("cp.admit_batch.refresh")} == \
                {"cp.admission.step.solve"}
            assert len(named("cp.admission.wait.drain")) == 2
            # the passes ran while the caller waited, inside its handler
            assert handler[1] <= steps[0][1] and steps[-1][2] <= handler[2]
            assert cp.adm.last_phase_ms.keys() == {"drain", "fold", "solve",
                                                   "commit"}
        finally:
            await cp.stop()
    _run(go())


def test_a_cp_without_admission_refuses_a_wait_as_it_refuses_a_submit():
    async def go():
        handle = await start(ServerConfig(use_tpu_solver=True,
                                          admission=False))
        conn, task = await ProtocolClient.connect(
            handle.host, handle.port, identity="test-client")
        try:
            with pytest.raises(RpcError, match="admission is disabled"):
                await conn.request("deploy", "submit",
                                   {"arrivals": [], "wait": 1})
            status = await conn.request("deploy", "admit_status", {})
            assert status == {"enabled": False}
        finally:
            await conn.close()
            task.cancel()
            await handle.stop()
    _run(go())
