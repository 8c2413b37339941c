"""The span tree (obs/trace.py): a phase knows its parent and its request.

What is pinned here:

  * the ring's record is (name, t0, t1, thread id, id, parent id, trace
    id); nested phases link to their parents and share one trace id;
    `spans_between` still yields 4-tuples, `tree_between` the records, and
    both refuse a window the ring dropped spans from
  * a callable handed to a pool through `obs.trace.bound` runs under the
    caller's phase and trace, with `cp.wait.executor` written; without it
    its phases are roots
  * a request frame carries `trace` and `span`; a served `placement.solve`
    and `placement.commit` are one tree each, rooted in `protocol.request`,
    whole across the wire and the executor hop, with the three waits in it;
    a frame without `trace` is served
  * a held `PlacementService._lock` reads as `cp.wait.placement_lock`, not
    as self time of `cp.solve_stage`
  * every collection moves `fleet_gc_collections_total` and
    `fleet_gc_pause_ms_total`; a full one is also one `runtime.gc` record
  * the flight recorder files a phase under its trace, with `id` and
    `parent_id`, and keeps the keys it had
"""

from __future__ import annotations

import asyncio
import contextlib
import contextvars
import gc
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from fleetflow_tpu import obs
from fleetflow_tpu.cp import protocol
from fleetflow_tpu.obs import trace as obs_trace
from fleetflow_tpu.obs.metrics import REGISTRY

NAME, T0, T1, TID, ID, PARENT, TRACE = range(7)
WAITS = ("protocol.wait.dispatch", "cp.wait.executor",
         "cp.wait.placement_lock")


def _run(coro, timeout=240):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def _since(t0, prefix=""):
    return [r for r in obs_trace.tree_between(t0, time.perf_counter())
            if r[NAME].startswith(prefix)]


def _self_time(rec, records):
    """Duration less the union of the direct children's intervals, clipped
    to the record: the definition the benchmark's reader implements."""
    spans = sorted((max(r[T0], rec[T0]), min(r[T1], rec[T1]))
                   for r in records if r[PARENT] == rec[ID])
    covered, end = 0.0, rec[T0]
    for s, e in spans:
        if e > max(s, end):
            covered += e - max(s, end)
            end = e
    return rec[T1] - rec[T0] - covered


# --------------------------------------------------------------------------
# the record, the ids, the trace
# --------------------------------------------------------------------------

class TestRecord:
    @pytest.mark.parametrize("depth", [2, 4])
    def test_nested_phases_link_to_their_parents(self, depth):
        t0 = time.perf_counter()
        with obs.use_trace() as trace, contextlib.ExitStack() as stack:
            opened = [stack.enter_context(obs.phase(f"t.tree{depth}.l{i}"))
                      for i in range(depth)]
        recs = {r[NAME]: r for r in _since(t0, f"t.tree{depth}.")}
        assert len(recs) == depth
        for i, ph in enumerate(opened):
            rec = recs[f"t.tree{depth}.l{i}"]
            assert rec[ID] == ph.id and rec[TRACE] == trace
            assert rec[PARENT] == (opened[i - 1].id if i else 0)
            assert ph.parent == rec[PARENT]
        ids = [ph.id for ph in opened]
        assert ids == sorted(ids) and len(set(ids)) == depth
        assert obs_trace._phase_id.get() == 0

    def test_siblings_share_the_parent_and_ids_are_process_wide(self):
        t0 = time.perf_counter()
        with obs.phase("t.sib.parent") as parent:
            with obs.phase("t.sib.a") as a:
                pass
            assert obs_trace._phase_id.get() == parent.id
            with obs.phase("t.sib.b") as b:
                pass
        assert a.parent == b.parent == parent.id and a.id < b.id
        other = {}
        worker = threading.Thread(
            target=lambda: other.update(ph=obs.phase("t.sib.thread").__enter__()))
        worker.start()
        worker.join(10)
        assert not worker.is_alive()
        assert other["ph"].id > b.id and other["ph"].parent == 0
        assert {r[TRACE] for r in _since(t0, "t.sib.")} == {""}

    def test_spans_between_keeps_its_four_fields(self):
        t0 = time.perf_counter()
        with obs.use_trace():
            with obs.phase("t.four"):
                pass
        t1 = time.perf_counter()
        four = [s for s in obs_trace.spans_between(t0, t1)
                if s[0] == "t.four"]
        seven = [r for r in obs_trace.tree_between(t0, t1)
                 if r[NAME] == "t.four"]
        assert len(four) == len(seven) == 1
        name, s0, s1, tid = four[0]
        assert (name, s0, s1, tid) == seven[0][:4]
        assert len(seven[0]) == 7 and tid == threading.get_ident()
        assert obs_trace.RING._spans.maxlen == obs_trace.RING_CAPACITY

    @pytest.mark.parametrize("reader", ["between", "tree_between"])
    def test_a_dropped_window_is_refused_by_both_readers(self, reader):
        ring = obs_trace.SpanRing(capacity=4)
        for i in range(6):
            ring.append("t.drop", float(i), i + 0.5, 1, i + 1, 0, "x")
        with pytest.raises(obs_trace.SpansDropped):
            getattr(ring, reader)(0.0, 1e9)
        kept = getattr(ring, reader)(2.0, 1e9)
        assert len(kept) == 4
        assert len(kept[0]) == (4 if reader == "between" else 7)

    def test_a_four_field_append_is_a_root_of_no_trace(self):
        ring = obs_trace.SpanRing(capacity=4)
        ring.append("t.old", 1.0, 2.0, 7)
        assert ring.tree_between(0.0, 3.0) == [("t.old", 1.0, 2.0, 7, 0, 0, "")]
        assert ring.between(0.0, 3.0) == [("t.old", 1.0, 2.0, 7)]

    def test_a_phase_closed_in_another_context_does_not_raise(self):
        ph = obs.phase("t.elsewhere")
        contextvars.copy_context().run(ph.__enter__)
        assert ph.__exit__(None, None, None) is False
        assert obs_trace._phase_id.get() == 0

    @pytest.mark.parametrize("span, adopted", [
        ("own", True), ("feedbeef:7", False), ("no-colon", False),
        (":12", False), (None, False), (12, False)])
    def test_use_trace_adopts_a_phase_of_this_process_only(self, span,
                                                           adopted):
        with obs.phase("t.adopt.caller") as caller:
            mine = obs_trace.wire_span()
        assert mine == f"{obs_trace.PROCESS_TOKEN}:{caller.id}"
        with obs.use_trace("abcd", mine if span == "own" else span) as tid:
            with obs.phase("t.adopt.served") as served:
                pass
        assert tid == "abcd"
        assert served.parent == (caller.id if adopted else 0)
        assert obs_trace._phase_id.get() == 0
        assert obs.current_trace_id() == ""


# --------------------------------------------------------------------------
# the executor hop
# --------------------------------------------------------------------------

class TestExecutor:
    @pytest.mark.parametrize("helper", [True, False])
    def test_a_pool_thread_has_the_callers_phase_only_through_bound(
            self, helper):
        name = f"t.pool.{'bound' if helper else 'bare'}"

        def work(x, y=0):
            with obs.phase(name + ".inner") as ph:
                return ph, x + y, threading.get_ident()

        async def go():
            loop = asyncio.get_running_loop()
            with obs.use_trace() as trace, obs.phase(name) as caller:
                call = (obs_trace.bound(work, 2, y=3) if helper
                        else (lambda: work(2, y=3)))
                inner, total, tid = await loop.run_in_executor(None, call)
            return trace, caller, inner, total, tid

        t0 = time.perf_counter()
        trace, caller, inner, total, tid = _run(go(), 30)
        assert total == 5 and tid != threading.get_ident()
        recs = _since(t0)
        rec = next(r for r in recs if r[NAME] == name + ".inner")
        waits = [r for r in recs if r[NAME] == "cp.wait.executor"
                 and r[TID] == tid]
        if helper:
            assert inner.parent == caller.id == rec[PARENT]
            assert rec[TRACE] == trace and rec[TID] == tid
            assert len(waits) == 1
            (wait,) = waits
            assert wait[PARENT] == caller.id and wait[TRACE] == trace
            assert 0.0 <= wait[T1] - wait[T0] < 5.0
            assert wait[T1] <= rec[T0]
        else:
            assert inner.parent == 0 == rec[PARENT] and rec[TRACE] == ""
            assert not waits

    def test_bound_passes_the_result_and_the_exception(self):
        def boom():
            raise KeyError("nope")

        with ThreadPoolExecutor(1) as pool:
            assert pool.submit(obs_trace.bound(max, 3, 9)).result(10) == 9
            with pytest.raises(KeyError):
                pool.submit(obs_trace.bound(boom)).result(10)

    def test_the_pool_threads_context_does_not_leak_back(self):
        def work():
            ph = obs.phase("t.pool.leak")
            ph.__enter__()          # never closed: the copy dies with it
            return ph.id

        with obs.phase("t.pool.caller") as caller:
            with ThreadPoolExecutor(1) as pool:
                leaked = pool.submit(obs_trace.bound(work)).result(10)
            assert obs_trace._phase_id.get() == caller.id != leaked


# --------------------------------------------------------------------------
# the wire: a served request is one tree
# --------------------------------------------------------------------------

FLEET_KDL = """
project "p"
{servers}
service "a0" {{ image "x"; resources {{ cpu 1; memory 64; disk 1 }} }}
service "a1" {{ image "x"; resources {{ cpu 1; memory 64; disk 1 }} }}
stage "live" {{
    service "a0"
    service "a1"
    servers {names}
}}
"""
SLUGS = ["n0", "n1", "n2"]


def _flow():
    from fleetflow_tpu.core.parser import parse_kdl_string
    return parse_kdl_string(FLEET_KDL.format(
        servers="\n".join(
            f'server "{s}" {{ capacity {{ cpu 4; memory 8192; disk 99999 }} }}'
            for s in SLUGS),
        names=" ".join(f'"{s}"' for s in SLUGS)))


async def _served_cp():
    from fleetflow_tpu.cp.models import ServerCapacity
    from fleetflow_tpu.cp.server import ServerConfig, start
    handle = await start(ServerConfig(use_tpu_solver=True))
    for slug in SLUGS:
        rec = handle.state.store.register_server(slug, tenant="default",
                                                 hostname=slug)
        handle.state.store.update(
            "servers", rec.id, status="online",
            capacity=ServerCapacity(cpu=4, memory=8192, disk=99999))
    return handle


@pytest.fixture(scope="module")
def served():
    """One `placement.solve` + `placement.commit` over a real connection to
    an in-process `cp.server.start`, after a first pair that compiles:
    (records of the window, the solve's reply, the commit's reply)."""
    from fleetflow_tpu.core.serialize import flow_to_dict
    from fleetflow_tpu.cp.protocol import ProtocolClient

    async def go():
        handle = await _served_cp()
        conn, task = await ProtocolClient.connect(
            handle.host, handle.port, identity="tree-test")
        try:
            request = {"flow": flow_to_dict(_flow()), "stage": "live",
                       "reserve": True}
            for _ in range(2):
                t0 = time.perf_counter()
                solved = await conn.request("placement", "solve", request,
                                            timeout=200)
                done = await conn.request(
                    "placement", "commit",
                    {"reservation": solved["reservation"]}, timeout=60)
            return _since(t0), solved, done
        finally:
            await conn.close()
            task.cancel()
            await handle.stop()

    return _run(go(), 400)


def _tree_of(records, root):
    """The records reachable from `root` by parent links."""
    members, grew = {root[ID]}, True
    while grew:
        grew = False
        for r in records:
            if r[PARENT] in members and r[ID] not in members:
                members.add(r[ID])
                grew = True
    return [r for r in records if r[ID] in members]


class TestServedRequest:
    def test_the_replies_are_what_they_were(self, served):
        _recs, solved, done = served
        assert solved["feasible"] and solved["reservation"]
        assert set(solved["assignment"]) == {"a0", "a1"}
        assert done == {"ok": True, "evicted": 0}

    def test_one_tree_per_request_rooted_in_protocol_request(self, served):
        recs, _solved, _done = served
        roots = [r for r in recs if r[NAME] == "protocol.request"]
        assert len(roots) == 2 and all(r[PARENT] == 0 for r in roots)
        assert len({r[TRACE] for r in roots}) == 2 and all(
            r[TRACE] for r in roots)
        solve, commit = (_tree_of(recs, r) for r in roots)
        assert not {r[ID] for r in solve} & {r[ID] for r in commit}
        for tree, root in ((solve, roots[0]), (commit, roots[1])):
            assert {r[TRACE] for r in tree} == {root[TRACE]}
        # everything that carries a request's trace id hangs in its tree
        for root, tree in ((roots[0], solve), (roots[1], commit)):
            carrying = [r for r in recs if r[TRACE] == root[TRACE]]
            assert {r[ID] for r in carrying} == {r[ID] for r in tree}

    @pytest.mark.parametrize("name", [
        "protocol.serve", "cp.handler", "cp.solve_stage",
        "cp.solve_stage.inventory", "cp.solve_stage.lower",
        "cp.solve_stage.solve", "cp.solve_stage.reserve", "sched.place",
        "sched.stage", "sched.solve", "solver.anneal", "solver.fetch",
        "sched.finalize", "protocol.encode", "protocol.decode", *WAITS])
    def test_the_solves_tree_holds(self, served, name):
        recs, _solved, _done = served
        root = next(r for r in recs if r[NAME] == "protocol.request")
        found = [r for r in _tree_of(recs, root) if r[NAME] == name]
        assert found, f"no {name} in the solve's tree"
        for r in found:
            assert r[T1] >= r[T0]           # a wait is never negative

    def test_nothing_under_solve_stage_is_a_root(self, served):
        recs, _solved, _done = served
        loop_thread = threading.get_ident()
        stage = next(r for r in recs if r[NAME] == "cp.solve_stage")
        assert stage[TID] != loop_thread    # it ran in the pool
        inside = [r for r in recs if r[TID] == stage[TID]
                  and stage[T0] <= r[T0] and r[T1] <= stage[T1]]
        assert len(inside) > 10
        assert all(r[PARENT] for r in inside)
        assert {r[TRACE] for r in inside} == {stage[TRACE]}
        by_id = {r[ID]: r for r in recs}
        assert by_id[stage[PARENT]][NAME] == "cp.handler"
        assert by_id[stage[PARENT]][TID] == loop_thread

    def test_the_wire_links_the_servers_task_to_the_clients(self, served):
        recs, _solved, _done = served
        by_id = {r[ID]: r for r in recs}
        for serve in (r for r in recs if r[NAME] == "protocol.serve"):
            caller = by_id[serve[PARENT]]
            assert caller[NAME] == "protocol.request"
            assert caller[T0] <= serve[T0] and serve[T1] <= caller[T1]
        for wait in (r for r in recs if r[NAME] == "protocol.wait.dispatch"):
            assert by_id[wait[PARENT]][NAME] == "protocol.request"

    def test_a_frames_decode_is_filed_under_its_request(self, served):
        """The read loop decodes a frame before it knows whose it is; the
        frame says: a request's own trace and span, a reply's caller's."""
        recs, _solved, _done = served
        by_id = {r[ID]: r for r in recs}
        decodes = [r for r in recs if r[NAME] == "protocol.decode"]
        assert len(decodes) == 4        # two requests, two replies
        for d in decodes:
            caller = by_id[d[PARENT]]
            assert caller[NAME] == "protocol.request"
            assert d[TRACE] == caller[TRACE]
            assert caller[T0] <= d[T0] and d[T1] <= caller[T1]
        assert sorted(d[PARENT] for d in decodes) == sorted(
            2 * [r[ID] for r in recs if r[NAME] == "protocol.request"])

    def test_the_commits_tree_holds_the_commit_and_its_lock_wait(self,
                                                                 served):
        recs, _solved, _done = served
        root = [r for r in recs if r[NAME] == "protocol.request"][1]
        tree = _tree_of(recs, root)
        names = {r[NAME] for r in tree}
        assert {"protocol.serve", "cp.handler", "cp.commit",
                "cp.commit.apply_allocation", "cp.commit.persist",
                "cp.wait.placement_lock", "protocol.wait.dispatch"} <= names
        by_id = {r[ID]: r for r in tree}
        lock = next(r for r in tree if r[NAME] == "cp.wait.placement_lock")
        assert by_id[lock[PARENT]][NAME] == "cp.commit"

    def test_self_times_and_children_make_up_the_handler(self, served):
        recs, _solved, _done = served
        handler = next(r for r in recs if r[NAME] == "cp.handler")
        kids = [r for r in recs if r[PARENT] == handler[ID]]
        assert {r[NAME] for r in kids} == {"cp.wait.executor",
                                           "cp.solve_stage"}
        own = _self_time(handler, recs)
        assert 0.0 <= own <= handler[T1] - handler[T0]
        assert own + sum(r[T1] - r[T0] for r in kids) == pytest.approx(
            handler[T1] - handler[T0], abs=1e-6)


class TestFrames:
    def test_a_request_frame_carries_trace_and_span(self, monkeypatch):
        sent = []
        real = protocol.encode_frame
        monkeypatch.setattr(protocol, "encode_frame",
                            lambda msg: sent.append(msg) or real(msg))

        async def go():
            handle = await _served_cp()
            conn, task = await protocol.ProtocolClient.connect(
                handle.host, handle.port, identity="frame-test")
            try:
                with obs.use_trace("feedfacefeedface"):
                    await conn.request("placement", "reservations", {})
                await conn.request("placement", "reservations", {})
            finally:
                await conn.close()
                task.cancel()
                await handle.stop()

        _run(go(), 60)
        frames = [m for m in sent if m.get("type") == "request"]
        assert len(frames) == 2
        assert frames[0]["trace"] == "feedfacefeedface"
        assert len(frames[1]["trace"]) == 16 != frames[0]["trace"]
        for f in frames:
            token, _, pid = f["span"].partition(":")
            assert token == obs_trace.PROCESS_TOKEN and int(pid) > 0
            extra = len(real(f)) - len(real(
                {k: v for k, v in f.items() if k not in ("trace", "span")}))
            assert 0 < extra < 100

    @pytest.mark.parametrize("keys", [{}, {"trace": 7, "span": ["x"]},
                                      {"trace": "0123456789abcdef"}])
    def test_a_frame_without_a_trace_is_served(self, keys):
        """An old client, an agent: no `trace`, no `span` (or rubbish in
        them) — the request is answered, under a trace of its own or the
        one it named."""
        async def go():
            handle = await _served_cp()
            reader, writer = await asyncio.open_connection(handle.host,
                                                           handle.port)
            try:
                writer.write(protocol.encode_frame(
                    {"type": "hello", "identity": "old-client",
                     "token": None, "channels": []}))
                await writer.drain()
                welcome = await protocol.read_frame(reader)
                assert welcome["type"] == "welcome"
                t0 = time.perf_counter()
                writer.write(protocol.encode_frame(
                    {"type": "request", "id": 41, "channel": "placement",
                     "method": "reservations", "payload": {}, **keys}))
                await writer.drain()
                reply = await asyncio.wait_for(protocol.read_frame(reader),
                                               30)
                return reply, _since(t0)
            finally:
                writer.close()
                await handle.stop()

        reply, recs = _run(go(), 60)
        assert reply["type"] == "response" and reply["id"] == 41
        assert not reply.get("error")
        assert set(reply["payload"]) == {"in_flight", "committed"}
        serve = next(r for r in recs if r[NAME] == "protocol.serve")
        assert serve[PARENT] == 0 and serve[TRACE]
        if isinstance(keys.get("trace"), str):
            assert serve[TRACE] == keys["trace"]
        handler = next(r for r in recs if r[NAME] == "cp.handler")
        assert handler[PARENT] == serve[ID]
        assert handler[TRACE] == serve[TRACE]


# --------------------------------------------------------------------------
# the lock
# --------------------------------------------------------------------------

def _host_cp():
    from fleetflow_tpu.cp.models import Server, ServerCapacity
    from fleetflow_tpu.cp.placement import PlacementService
    from fleetflow_tpu.cp.store import Store
    store = Store()
    for slug in SLUGS:
        store.create("servers", Server(
            slug=slug, status="online", tenant="default",
            capacity=ServerCapacity(cpu=4, memory=8192, disk=99999)))
    return PlacementService(store, use_tpu=False), _flow()


class TestLockWait:
    HELD_S = 0.08

    def test_a_held_lock_is_a_wait_and_not_the_phases_self_time(self):
        svc, flow = _host_cp()
        svc.solve_stage(flow, "live", reserve=False)    # imports, caches
        started = threading.Event()
        out = {}

        def solve():
            started.set()
            out["placement"], out["rid"] = svc.solve_stage(flow, "live")

        t0 = time.perf_counter()
        svc._lock.acquire()
        worker = threading.Thread(target=solve)
        try:
            worker.start()
            assert started.wait(10)
            time.sleep(self.HELD_S)
        finally:
            svc._lock.release()
        worker.join(30)
        assert not worker.is_alive() and out["placement"].feasible
        recs = [r for r in _since(t0) if r[TID] == worker.ident]
        stage = next(r for r in recs if r[NAME] == "cp.solve_stage")
        wait = next(r for r in recs if r[NAME] == "cp.wait.placement_lock")
        assert wait[PARENT] == stage[ID]
        waited = wait[T1] - wait[T0]
        # the worker asked a moment after it said it had started
        assert waited >= self.HELD_S * 0.75
        assert stage[T1] - stage[T0] >= waited
        assert _self_time(stage, recs) <= (stage[T1] - stage[T0]) - waited \
            + 1e-6

    @pytest.mark.parametrize("method", ["commit", "commit_retained",
                                        "node_events", "reinstate",
                                        "release_stage"])
    def test_every_locked_step_records_its_wait(self, method):
        svc, flow = _host_cp()
        placement, rid = svc.solve_stage(flow, "live")
        assert placement.feasible
        if method != "commit":
            assert svc.commit(rid)
        t0 = time.perf_counter()
        with obs.phase("t.lock.caller") as caller:
            {"commit": lambda: svc.commit(rid),
             "commit_retained": lambda: svc.commit_retained("p/live"),
             "node_events": lambda: svc.node_events([("n0", False)]),
             "reinstate": lambda: svc.reinstate("p/live"),
             "release_stage": lambda: svc.release_stage("p/live")}[method]()
        recs = _since(t0)
        by_id = {r[ID]: r for r in recs}
        waits = [r for r in recs if r[NAME] == "cp.wait.placement_lock"]
        assert len(waits) == 1
        owner = {"commit": "cp.commit", "commit_retained":
                 "cp.commit_retained", "node_events": "cp.node_events",
                 "reinstate": "cp.reinstate",
                 "release_stage": "t.lock.caller"}[method]
        assert by_id[waits[0][PARENT]][NAME] == owner
        assert caller.id in {r[PARENT] for r in recs}
        assert not svc._lock.locked()

    def test_the_lock_is_released_when_the_step_raises(self):
        svc, flow = _host_cp()
        with pytest.raises(RuntimeError):
            with obs.phase("t.lock.raises"), svc._locked():
                assert svc._lock.locked()
                raise RuntimeError("inside")
        assert not svc._lock.locked()


# --------------------------------------------------------------------------
# the collector
# --------------------------------------------------------------------------

class TestCollector:
    def test_a_forced_collection_is_counted_and_is_one_record(self):
        obs_trace.watch_collector()
        obs_trace.watch_collector()         # again is a no-op
        assert gc.callbacks.count(obs_trace._COLLECTOR_WATCH) == 1
        count = REGISTRY.get("fleet_gc_collections_total")
        pause = REGISTRY.get("fleet_gc_pause_ms_total")
        before = (count.value(generation="2"), pause.value(generation="2"))
        t0 = time.perf_counter()
        with obs.use_trace() as trace, obs.phase("t.gc.interrupted") as ph:
            gc.collect()
        assert count.value(generation="2") >= before[0] + 1
        assert pause.value(generation="2") > before[1]
        mine = [r for r in _since(t0, "runtime.gc") if r[PARENT] == ph.id]
        assert len(mine) == 1
        (rec,) = mine
        assert rec[TRACE] == trace and rec[TID] == threading.get_ident()
        assert 0.0 < rec[T1] - rec[T0] < 30.0
        hist = REGISTRY.get("fleet_phase_ms")
        assert hist.count(phase="runtime.gc") >= 1

    @pytest.mark.parametrize("generation", [0, 1])
    def test_a_young_collection_is_counted_not_spanned(self, generation):
        obs_trace.watch_collector()
        count = REGISTRY.get("fleet_gc_collections_total")
        pause = REGISTRY.get("fleet_gc_pause_ms_total")
        label = str(generation)
        c0, p0 = count.value(generation=label), pause.value(generation=label)
        t0 = time.perf_counter()
        with obs.phase("t.gc.young") as ph:
            gc.collect(generation)
        assert count.value(generation=label) >= c0 + 1
        assert pause.value(generation=label) > p0
        assert not [r for r in _since(t0, "runtime.gc")
                    if r[PARENT] == ph.id]

    def test_a_collection_inside_a_familys_lock_does_not_deadlock(self):
        """The callback counts into the registry from whatever the thread
        was doing — which may be holding that very family's lock."""
        obs_trace.watch_collector()
        done = threading.Event()

        def collect_under_the_lock():
            family = REGISTRY.get("fleet_phase_ms")
            with family._lock:
                gc.collect()
            done.set()

        worker = threading.Thread(target=collect_under_the_lock,
                                  daemon=True)
        worker.start()
        assert done.wait(20), "the collector's callback deadlocked"

    def test_the_cp_installs_the_watch(self):
        async def go():
            handle = await _served_cp()
            await handle.stop()

        if obs_trace._COLLECTOR_WATCH in gc.callbacks:
            gc.callbacks.remove(obs_trace._COLLECTOR_WATCH)
        _run(go(), 60)
        assert gc.callbacks.count(obs_trace._COLLECTOR_WATCH) == 1


# --------------------------------------------------------------------------
# the flight recorder
# --------------------------------------------------------------------------

class TestFlightRecorder:
    def test_a_phase_under_a_trace_is_filed_with_its_ids(self, tmp_path,
                                                         monkeypatch):
        path = tmp_path / "flight.jsonl"
        monkeypatch.setenv("FLEET_TRACE_FILE", str(path))
        with obs.use_trace() as trace:
            with obs.phase("t.fr.outer", rows=3) as outer:
                with obs.phase("t.fr.inner") as inner:
                    pass
                obs_trace.record_interval("t.fr.wait",
                                          time.perf_counter() - 0.001)
        events = obs_trace.read_trace_file(str(path))
        assert [e["name"] for e in events] == ["t.fr.inner", "t.fr.outer"]
        by_name = {e["name"]: e for e in events}
        assert by_name["t.fr.inner"]["id"] == inner.id
        assert by_name["t.fr.inner"]["parent_id"] == outer.id
        assert by_name["t.fr.outer"]["parent_id"] == 0
        for e in events:
            assert e["trace"] == trace and e["span"] == ""
            assert e["kind"] == "end" and "parent" not in e
            assert e["logger"] == obs_trace.PHASE_LOGGER
        assert by_name["t.fr.outer"]["fields"] == {"rows": 3}

    def test_a_spans_phase_keeps_its_keys_and_gains_the_ids(self, tmp_path,
                                                            monkeypatch):
        path = tmp_path / "flight.jsonl"
        monkeypatch.setenv("FLEET_TRACE_FILE", str(path))
        log = obs.get_logger("test.tree")
        with obs.span(log, "t.fr.span", stage="s") as sp:
            sp["placed"] = 2
            with obs.phase("t.fr.child") as child:
                pass
        begin, inner, end = obs_trace.read_trace_file(str(path))
        assert begin["kind"] == "begin" and "id" not in begin
        assert set(end) == {"ts", "kind", "name", "logger", "trace", "span",
                            "duration_ms", "fields", "id", "parent_id"}
        assert end["span"] == begin["span"] == inner["parent"]
        assert end["fields"] == {"stage": "s", "placed": 2}
        assert inner["parent_id"] == end["id"] == child.parent
        assert inner["id"] == child.id
