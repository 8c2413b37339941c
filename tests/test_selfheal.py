"""Self-healing tests: lease state machine, structured command errors,
agent idempotency dedupe, reconverger backoff/parking/persistence, and
the acceptance e2e (CP + two real agents, kill one, heal unassisted).

Layers:
  - table-driven lease machine on a fake clock: grace expiry,
    suspect->revive, disconnect fast-path, flap-damping hysteresis;
  - AgentRegistry structured errors: retryable (AgentUnreachable) vs
    fatal (AgentCommandFailed) without string-matching;
  - agent-side idempotency window: a replayed command answers from the
    cache instead of re-executing;
  - reconverger units against fake placement/registry: exponential
    backoff with seeded jitter, retries-exhausted parking, parked-work
    persistence across a store restart (CP crash resume);
  - solver-failure degradation: churn re-solve falls back to the greedy
    host path instead of stalling convergence;
  - e2e (the ISSUE acceptance): deploy to two live agents, kill one
    WITHOUT any operator RPC — the service is redeployed on the survivor
    within the lease+backoff budget, the redelivered command carries an
    idempotency key the agent dedupes on replay, and detection + redeploy
    share one trace_id in the flight recorder.
"""

from __future__ import annotations

import asyncio
import random
import time

import pytest

from fleetflow_tpu.agent import Agent, AgentConfig
from fleetflow_tpu.core.errors import (AgentCommandFailed, AgentUnreachable,
                                       ControlPlaneError)
from fleetflow_tpu.core.model import Flow, ResourceSpec, Service, Stage
from fleetflow_tpu.cp import ServerConfig, Store, start
from fleetflow_tpu.cp.agent_registry import AgentRegistry
from fleetflow_tpu.cp.failure_detector import (ALIVE, DEAD, SUSPECT,
                                               FailureDetector, LeaseConfig)
from fleetflow_tpu.cp.models import Deployment, DeploymentStatus
from fleetflow_tpu.cp.placement import PlacementService
from fleetflow_tpu.cp.protocol import ProtocolClient
from fleetflow_tpu.cp.reconverge import ReconvergeConfig, Reconverger
from fleetflow_tpu.cp.server import AppState
from fleetflow_tpu.cp.store import Store as CpStore
from fleetflow_tpu.obs.metrics import REGISTRY
from fleetflow_tpu.runtime import DeployRequest, MockBackend
from fleetflow_tpu.runtime.converter import container_name
from fleetflow_tpu.sched.base import Placement


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 60))


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def now(self) -> float:
        return self.t


def _detector(clock, **overrides) -> FailureDetector:
    cfg = dict(lease_s=10.0, suspect_grace_s=5.0, flap_window_s=100.0,
               flap_threshold=3, damp_hold_s=30.0)
    cfg.update(overrides)
    return FailureDetector(LeaseConfig(**cfg), clock=clock.now)


def _heal_flow(name: str = "healdemo") -> Flow:
    flow = Flow(name=name)
    flow.services["web"] = Service(
        name="web", image="app", version="1",
        resources=ResourceSpec(cpu=0.5, memory=128.0))
    flow.stages["main"] = Stage(name="main", services=["web"],
                                servers=["node-1", "node-2"])
    return flow


# --------------------------------------------------------------------------
# lease state machine (table-driven on the fake clock)
# --------------------------------------------------------------------------

class TestLeaseStateMachine:
    # each case: ops in time order; "hb"/"disc" observe, "sweep" asserts
    # the exact verdict list [(slug, online), ...] returned at that time
    CASES = [
        ("alive_within_lease", [
            ("hb", "a", 0.0),
            ("sweep", 9.9, []),
        ]),
        ("lease_expiry_is_silent_suspect", [
            ("hb", "a", 0.0),
            ("sweep", 10.1, []),          # -> SUSPECT, no verdict
        ]),
        ("grace_expiry_is_dead_verdict", [
            ("hb", "a", 0.0),
            ("sweep", 11.0, []),          # suspect_since = 11
            ("sweep", 15.9, []),          # 4.9s suspect < 5s grace
            ("sweep", 16.1, [("a", False)]),
        ]),
        ("suspect_revive_is_silent", [
            ("hb", "a", 0.0),
            ("sweep", 12.0, []),          # SUSPECT
            ("hb", "a", 13.0),            # back ALIVE, never a verdict
            ("sweep", 20.0, []),
        ]),
        ("dead_revive_is_online_verdict", [
            ("hb", "a", 0.0),
            ("sweep", 11.0, []),
            ("sweep", 17.0, [("a", False)]),
            ("hb", "a", 20.0),
            ("sweep", 20.5, [("a", True)]),
        ]),
        ("disconnect_fast_paths_to_suspect", [
            ("hb", "a", 0.0),
            ("disc", "a", 1.0),           # suspect_since = 1, lease moot
            ("sweep", 5.9, []),
            ("sweep", 6.1, [("a", False)]),
        ]),
        ("two_agents_sorted_verdicts", [
            ("hb", "b", 0.0),
            ("hb", "a", 0.0),
            ("disc", "b", 1.0),
            ("disc", "a", 1.0),
            ("sweep", 7.0, [("a", False), ("b", False)]),
        ]),
    ]

    @pytest.mark.parametrize("name,ops", CASES, ids=[c[0] for c in CASES])
    def test_timeline(self, name, ops):
        clock = FakeClock()
        det = _detector(clock)
        for op in ops:
            kind, *rest = op
            if kind == "hb":
                slug, t = rest
                clock.t = t
                det.observe_heartbeat(slug)
            elif kind == "disc":
                slug, t = rest
                clock.t = t
                det.observe_disconnect(slug)
            elif kind == "sweep":
                t, expected = rest
                clock.t = t
                got = [(e.slug, e.online) for e in det.sweep()]
                assert got == expected, (name, t, got)

    def test_states_visible_in_status(self):
        clock = FakeClock()
        det = _detector(clock)
        det.observe_heartbeat("a")
        assert det.state_of("a") == ALIVE
        clock.t = 11.0
        det.sweep()
        assert det.state_of("a") == SUSPECT
        clock.t = 17.0
        det.sweep()
        assert det.state_of("a") == DEAD
        st = det.status()
        assert st["agents"]["a"]["state"] == DEAD
        assert st["config"]["lease_s"] == 10.0

    def test_flap_damping_holds_dead_verdicts(self):
        """Two die/revive cycles emit verdicts freely; the third death of
        a now-flapping agent is HELD until it has been continuously
        suspect for damp_hold_s (hysteresis: no re-solve storm)."""
        clock = FakeClock()
        det = _detector(clock)  # threshold 3, window 100, hold 30

        def kill_and_wait(t_disc, t_sweep):
            clock.t = t_disc
            det.observe_disconnect("a")
            clock.t = t_sweep
            return [(e.slug, e.online) for e in det.sweep()]

        det.observe_heartbeat("a")
        # cycle 1: verdict fires at grace expiry (1 verdict in window)
        assert kill_and_wait(1.0, 7.0) == [("a", False)]
        clock.t = 8.0
        det.observe_heartbeat("a")                 # revive -> 2 verdicts
        assert [(e.slug, e.online) for e in det.sweep()] == [("a", True)]
        # cycle 2: 3rd verdict still fires (threshold counts BEFORE it)
        assert kill_and_wait(9.0, 15.0) == [("a", False)]
        clock.t = 16.0
        det.observe_heartbeat("a")
        det.sweep()                                # drain revive verdict
        # cycle 3: agent is flapping (4 verdicts in window >= 3) —
        # grace expiry alone no longer fires
        assert kill_and_wait(17.0, 23.0) == []
        clock.t = 30.0
        assert det.sweep() == []                   # still held (< hold)
        clock.t = 47.5                             # suspect_for 30.5 > 30
        got = [(e.slug, e.online) for e in det.sweep()]
        assert got == [("a", False)]
        # the deferral was counted
        assert REGISTRY.get("fleet_lease_flap_damped_total").value() >= 1

    def test_forget_drops_tracking(self):
        clock = FakeClock()
        det = _detector(clock)
        det.observe_heartbeat("a")
        det.forget("a")
        clock.t = 100.0
        assert det.sweep() == []
        assert det.state_of("a") is None

    def test_requeue_redelivers_verdicts(self):
        """Verdicts the reconverger failed to process (solver crash) go
        back into the queue and surface on the next sweep."""
        clock = FakeClock()
        det = _detector(clock)
        det.observe_heartbeat("a")
        clock.t = 11.0
        det.sweep()
        clock.t = 17.0
        events = det.sweep()
        assert [(e.slug, e.online) for e in events] == [("a", False)]
        det.requeue(events)
        assert [(e.slug, e.online) for e in det.sweep()] == [("a", False)]


# --------------------------------------------------------------------------
# structured send_command errors (satellite: retryable vs fatal)
# --------------------------------------------------------------------------

class _NeverConn:
    _closed = False
    identity = "x"

    async def send_event(self, channel, method, payload):
        pass   # swallow: the future never resolves


class TestStructuredErrors:
    def test_not_connected_is_retryable(self):
        async def go():
            reg = AgentRegistry()
            with pytest.raises(AgentUnreachable) as ei:
                await reg.send_command("ghost", "ping", {})
            assert ei.value.retryable
            assert ei.value.reason == "not-connected"
        run(go())

    def test_timeout_is_retryable(self):
        async def go():
            reg = AgentRegistry()
            reg.register("n1", _NeverConn())
            with pytest.raises(AgentUnreachable) as ei:
                await reg.send_command("n1", "ping", {}, timeout=0.05)
            assert ei.value.retryable
            assert ei.value.reason == "timeout"
        run(go())

    def test_agent_reported_error_is_fatal(self):
        async def go():
            reg = AgentRegistry()

            class Conn(_NeverConn):
                async def send_event(self, channel, method, payload):
                    reg.resolve_result(payload["request_id"],
                                       {"error": "deploy exploded"})

            reg.register("n1", Conn())
            with pytest.raises(AgentCommandFailed) as ei:
                await reg.send_command("n1", "deploy.execute", {})
            assert not ei.value.retryable
            assert "deploy exploded" in str(ei.value)
        run(go())

    def test_disconnect_mid_command_is_retryable(self):
        async def go():
            reg = AgentRegistry()
            conn = _NeverConn()
            reg.register("n1", conn)

            async def killer():
                await asyncio.sleep(0.02)
                reg.unregister("n1", conn)

            k = asyncio.ensure_future(killer())
            with pytest.raises(AgentUnreachable) as ei:
                await reg.send_command("n1", "ping", {}, timeout=5)
            await k
            assert ei.value.retryable
            assert ei.value.reason == "disconnected"
        run(go())

    def test_delivery_hook_refusal_is_retryable_and_keeps_message(self):
        async def go():
            reg = AgentRegistry()
            reg.register("n1", _NeverConn())

            def hook(slug, command):
                raise ControlPlaneError(f"refused {slug}/{command}")
            reg.delivery_hook = hook
            with pytest.raises(AgentUnreachable, match="refused n1/ping"):
                await reg.send_command("n1", "ping", {})
        run(go())


# --------------------------------------------------------------------------
# agent-side idempotency dedupe window
# --------------------------------------------------------------------------

class _CaptureConn:
    def __init__(self):
        self.replies = []

    async def send_event(self, channel, method, payload, *, in_reply=False):
        assert in_reply      # a command's result answers its frame
        self.replies.append((method, payload))


class TestAgentIdempotency:
    def _agent(self, **cfg) -> Agent:
        return Agent(AgentConfig(slug="n1", **cfg),
                     backend=MockBackend(auto_pull=True),
                     sleep=lambda d: None)

    def test_replay_answers_from_cache(self):
        async def go():
            agent = self._agent()
            conn = _CaptureConn()
            env = {"request_id": "r1",
                   "payload": {"idempotency_key": "k1"}}
            await agent._on_command(conn, "ping", env)
            await agent._on_command(conn, "ping",
                                    {"request_id": "r2",
                                     "payload": {"idempotency_key": "k1"}})
            (m1, p1), (m2, p2) = conn.replies
            assert p1["result"] == p2["result"]
            assert "deduped" not in p1
            assert p2["deduped"] is True
        run(go())

    def test_distinct_keys_execute_independently(self):
        async def go():
            agent = self._agent()
            conn = _CaptureConn()
            for i, key in enumerate(("k1", "k2")):
                await agent._on_command(conn, "ping", {
                    "request_id": f"r{i}",
                    "payload": {"idempotency_key": key}})
            assert all("deduped" not in p for _, p in conn.replies)
        run(go())

    def test_window_expiry_reexecutes(self):
        async def go():
            agent = self._agent(idempotency_window_s=0.0)
            conn = _CaptureConn()
            env = {"request_id": "r1",
                   "payload": {"idempotency_key": "k1"}}
            await agent._on_command(conn, "ping", env)
            await asyncio.sleep(0.01)
            await agent._on_command(conn, "ping",
                                    {"request_id": "r2",
                                     "payload": {"idempotency_key": "k1"}})
            assert all("deduped" not in p for _, p in conn.replies)
        run(go())

    def test_failures_are_not_cached(self):
        async def go():
            agent = self._agent()
            conn = _CaptureConn()
            env = {"request_id": "r1",
                   "payload": {"idempotency_key": "k1"}}
            await agent._on_command(conn, "bogus-method", env)   # fails
            assert "error" in conn.replies[0][1]
            await agent._on_command(conn, "ping",
                                    {"request_id": "r2",
                                     "payload": {"idempotency_key": "k1"}})
            # the failed attempt did not poison the key: re-executed
            assert "deduped" not in conn.replies[1][1]
            assert conn.replies[1][1]["result"]["pong"] is True
        run(go())

    def test_inflight_replay_awaits_instead_of_double_executing(self):
        """A redelivery arriving while the ORIGINAL command is still
        executing (CP timeout + retry on a slow deploy) must ride the
        in-flight execution, not start a concurrent duplicate."""
        async def go():
            agent = self._agent()
            conn = _CaptureConn()
            calls = []
            gate = asyncio.Event()

            async def slow_execute(method, payload):
                calls.append(method)
                await gate.wait()
                return {"pong": True}
            agent.execute_command = slow_execute

            t1 = asyncio.ensure_future(agent._on_command(conn, "ping", {
                "request_id": "r1", "payload": {"idempotency_key": "k1"}}))
            await asyncio.sleep(0.01)    # r1 is now in flight
            t2 = asyncio.ensure_future(agent._on_command(conn, "ping", {
                "request_id": "r2", "payload": {"idempotency_key": "k1"}}))
            await asyncio.sleep(0.01)
            gate.set()
            await asyncio.gather(t1, t2)
            assert calls == ["ping"]     # executed exactly once
            by_rid = {p["request_id"]: p for _, p in conn.replies}
            assert "deduped" not in by_rid["r1"]
            assert by_rid["r2"]["deduped"] is True
            assert agent._idem_inflight == {}
        run(go())

    def test_cache_is_bounded(self):
        async def go():
            agent = self._agent()
            conn = _CaptureConn()
            for i in range(300):
                await agent._on_command(conn, "ping", {
                    "request_id": f"r{i}",
                    "payload": {"idempotency_key": f"k{i}"}})
            assert len(agent._idem) <= 256
        run(go())


# --------------------------------------------------------------------------
# reconverger units (fake placement/registry, controllable clock)
# --------------------------------------------------------------------------

class _FakePlacement:
    def __init__(self, placement=None):
        self.placement = placement
        self.committed = []

    def retained(self, key):
        return (None, self.placement) if self.placement else None

    def node_events(self, events):
        return []

    def commit_retained(self, key):
        self.committed.append(key)
        return True


def _state(store=None, placement=None) -> AppState:
    return AppState(store=store or Store(), auth=None,
                    agent_registry=AgentRegistry(), log_router=None,
                    placement=placement or _FakePlacement())


def _seed_template(db, flow: Flow) -> None:
    from fleetflow_tpu.core.serialize import flow_to_dict
    db.create("deployments", Deployment(
        tenant="default", project="p", stage="s",
        status=DeploymentStatus.SUCCEEDED.value,
        request={"flow": flow_to_dict(flow), "stage_name": "main"}))


class TestReconverger:
    def _rc(self, state, clock, **cfg):
        conf = dict(backoff_base_s=1.0, backoff_max_s=8.0, max_attempts=3)
        conf.update(cfg)
        det = FailureDetector(LeaseConfig(), clock=clock.now)
        return Reconverger(state, det, config=ReconvergeConfig(**conf),
                           clock=clock.now, rng=random.Random(0))

    def test_backoff_grows_then_parks(self):
        """Redelivery against a stage whose assigned node is absent:
        exponential backoff with jitter, then retries-exhausted parking
        (retried on the next node-online verdict, not on a timer)."""
        clock = FakeClock()
        flow = _heal_flow()
        db = Store()
        _seed_template(db, flow)
        placement = _FakePlacement(Placement(
            assignment={"web": "node-1"}, levels=[["web"]], feasible=True))
        state = _state(db, placement)
        rc = self._rc(state, clock)
        rc._enqueue("healdemo/main", "tr1")

        async def go():
            delays = []
            for _ in range(3):
                await rc.step()
                w = rc._work.get("healdemo/main")
                if w is None or w.parked:
                    break
                delays.append(w.next_try_at - clock.t)
                clock.t = w.next_try_at + 0.001
            return delays

        delays = run(go())
        # two retries before the 3rd attempt parks; jittered exponential
        assert len(delays) == 2
        assert 0.75 <= delays[0] <= 1.25
        assert 1.5 <= delays[1] <= 2.5
        assert rc.parked_stage_keys() == ["healdemo/main"]
        w = rc._work["healdemo/main"]
        assert w.reason == "retries-exhausted"
        # parked work is persisted
        assert db.find_one("parked_work",
                           lambda r: r.stage_key == "healdemo/main") is not None

    def test_infeasible_resolve_parks_immediately(self):
        clock = FakeClock()

        class Moving(_FakePlacement):
            def node_events(self, events):
                return [("healdemo/main", Placement(
                    assignment={}, levels=[], feasible=False,
                    violations=3))]

        state = _state(Store(), Moving())
        rc = self._rc(state, clock)
        rc.detector.observe_heartbeat("node-1")
        clock.t = 1000.0   # lease + grace long gone

        async def go():
            await rc.step()          # suspect
            clock.t += 1000.0
            return await rc.step()   # dead verdict -> infeasible -> park

        summary = run(go())
        assert summary["dead"] == ["node-1"]
        assert rc.parked_stage_keys() == ["healdemo/main"]

    def test_parked_work_survives_cp_restart(self, tmp_path):
        clock = FakeClock()
        path = str(tmp_path / "cp.json")
        db = CpStore(path)
        state = _state(db)
        rc = self._rc(state, clock)
        from fleetflow_tpu.cp.reconverge import _Work
        rc._park(_Work(stage_key="p/s", idempotency_key="k",
                       trace_id="t"), "infeasible", "no capacity")
        db.flush()

        db2 = CpStore(path)
        rc2 = self._rc(_state(db2), clock)
        assert rc2.resume() == 1
        assert rc2.parked_stage_keys() == ["p/s"]
        assert rc2.stats["resumed"] == 1

    def test_successful_redelivery_commits_and_records(self):
        """Full happy path against a fake connected agent: the retained
        assignment is redelivered with an idempotency key, the placement
        committed, and a deployment record written (so `fleet down`'s
        node scan stays truthful)."""
        clock = FakeClock()
        flow = _heal_flow()
        db = Store()
        _seed_template(db, flow)
        placement = _FakePlacement(Placement(
            assignment={"web": "node-1"}, levels=[["web"]], feasible=True))
        state = _state(db, placement)
        rc = self._rc(state, clock)
        seen = []

        class Conn:
            _closed = False
            identity = "node-1"

            async def send_event(self, channel, method, payload):
                seen.append((method, payload))
                state.agent_registry.resolve_result(
                    payload["request_id"], {"result": {"deployed": ["web"]}})

        state.agent_registry.register("node-1", Conn())
        rc._enqueue("healdemo/main", "tr1")
        summary = run(rc.step())
        assert summary["redelivered"] == ["healdemo/main"]
        assert placement.committed == ["healdemo/main"]
        assert rc._work == {}
        method, payload = seen[0]
        assert method == "deploy.execute"
        assert payload["payload"]["idempotency_key"].startswith(
            "heal-healdemo/main-")
        assert payload["payload"]["assignment"] == {"web": "node-1"}
        heal_deps = [d for d in db.list("deployments")
                     if d.log.startswith("self-heal")]
        assert len(heal_deps) == 1
        assert heal_deps[0].placement == {"web": "node-1"}
        assert heal_deps[0].status == DeploymentStatus.SUCCEEDED.value

    def test_node_online_unparks(self):
        clock = FakeClock()
        flow = _heal_flow()
        db = Store()
        _seed_template(db, flow)
        state = _state(db, _FakePlacement(Placement(
            assignment={"web": "node-1"}, levels=[["web"]], feasible=True)))
        rc = self._rc(state, clock)
        from fleetflow_tpu.cp.reconverge import _Work
        rc._park(_Work(stage_key="healdemo/main", idempotency_key="k",
                       trace_id="t"), "infeasible")
        # a dead node heartbeats again -> online verdict -> unpark
        rc.detector.observe_heartbeat("node-9")
        clock.t = 1000.0
        run(rc.step())
        clock.t = 2000.0
        run(rc.step())      # dead verdict for node-9
        clock.t = 2001.0
        rc.detector.observe_heartbeat("node-9")
        summary = run(rc.step())
        assert summary["online"] == ["node-9"]
        assert rc.parked_stage_keys() == []
        assert "healdemo/main" in rc.pending_stage_keys()
        # the unparked work minted a FRESH idempotency key: the parked
        # placeholder's (possibly empty/stale) key must never ride a
        # redelivery, or a timeout retry loses dedupe protection
        w = rc._work["healdemo/main"]
        assert w.idempotency_key.startswith("heal-healdemo/main-")
        assert w.idempotency_key != "k"

    def test_keys_are_unique_across_cp_restarts(self):
        """The generation counter restarts with the CP; the per-process
        nonce keeps a restarted CP's keys out of dedupe windows still
        holding the previous incarnation's results."""
        clock = FakeClock()
        a = self._rc(_state(), clock)
        b = self._rc(_state(), clock)
        assert a._next_key("p/s") != b._next_key("p/s")
        # and within one process, every assignment gets a fresh key
        assert a._next_key("p/s") != a._next_key("p/s")

    def test_verdicts_requeued_when_resolve_crashes(self):
        clock = FakeClock()

        class Exploding(_FakePlacement):
            def __init__(self):
                super().__init__()
                self.calls = 0

            def node_events(self, events):
                self.calls += 1
                if self.calls == 1:
                    raise RuntimeError("solver down")
                return []

        placement = Exploding()
        state = _state(Store(), placement)
        rc = self._rc(state, clock)
        rc.detector.observe_heartbeat("node-1")
        clock.t = 1000.0
        run(rc.step())
        clock.t = 2000.0
        run(rc.step())      # dead verdict -> node_events raises
        assert placement.calls == 1
        summary = run(rc.step())   # verdict requeued, retried
        assert placement.calls == 2
        assert summary["dead"] == ["node-1"]


# --------------------------------------------------------------------------
# solver-failure degradation in the churn path
# --------------------------------------------------------------------------

class TestChurnSolverFallback:
    def test_node_events_falls_back_to_host_greedy(self):
        db = Store()
        for slug in ("n1", "n2"):
            s = db.register_server(slug, hostname=slug)
            db.update("servers", s.id, capacity=type(s.capacity)(
                cpu=8.0, memory=8192.0, disk=40960.0), status="online")
        ps = PlacementService(db)
        flow = Flow(name="p")
        flow.services["web"] = Service(name="web", image="i", version="1",
                                       resources=ResourceSpec(cpu=0.5,
                                                              memory=64.0))
        flow.stages["main"] = Stage(name="main", services=["web"],
                                    servers=["n1", "n2"])
        pl, rid = ps.solve_stage(flow, "main")
        assert pl.feasible
        ps.commit(rid)
        before = REGISTRY.get(
            "fleet_placement_churn_fallbacks_total").value()
        # break the primary scheduler: the churn path must degrade to the
        # greedy host scheduler, not raise
        victim = pl.assignment["web"]
        ps.use_tpu = True

        class Boom:
            def reschedule(self, pt):
                raise RuntimeError("XLA exploded")

            def place(self, pt, **kw):
                raise RuntimeError("XLA exploded")

        ps._sched_tpu = Boom()
        moved = ps.node_event(victim, online=False)
        assert moved, "the stage had services on the dead node"
        key, new = moved[0]
        assert new.feasible
        assert new.assignment["web"] != victim
        assert REGISTRY.get(
            "fleet_placement_churn_fallbacks_total").value() == before + 1


# --------------------------------------------------------------------------
# e2e acceptance: CP + two real agents, kill one, heal unassisted
# --------------------------------------------------------------------------

class TestSelfHealE2E:
    def test_kill_one_agent_heals_on_survivor(self, tmp_path, monkeypatch):
        trace_file = tmp_path / "flight.jsonl"
        monkeypatch.setenv("FLEET_TRACE_FILE", str(trace_file))
        flow = _heal_flow()

        async def go():
            handle = await start(ServerConfig(
                self_heal=True, lease_s=0.4, suspect_grace_s=0.15,
                heal_interval_s=0.05, heal_backoff_base_s=0.05,
                heal_backoff_max_s=0.2),
                backend_factory=lambda: MockBackend(auto_pull=True))
            backends, agents, tasks = {}, {}, {}
            for slug in ("node-1", "node-2"):
                backends[slug] = MockBackend(auto_pull=True)
                cfg = AgentConfig(
                    cp_host=handle.host, cp_port=handle.port, slug=slug,
                    heartbeat_interval_s=0.05, monitor_interval_s=30.0,
                    capacity={"cpu": 4, "memory": 8192, "disk": 100000})
                agents[slug] = Agent(cfg, backend=backends[slug],
                                     sleep=lambda d: None)
                tasks[slug] = asyncio.ensure_future(agents[slug].run())
            while not all(handle.state.agent_registry.is_connected(s)
                          for s in agents):
                await asyncio.sleep(0.02)

            # spy on redelivery to pin the idempotency-key contract —
            # fan-outs ride the batched shard path (send_batch), single
            # commands the per-call path, so both are tapped
            sent = []
            orig_send = handle.state.agent_registry.send_command
            orig_batch = handle.state.agent_registry.send_batch

            async def spy(slug, command, payload=None, timeout=60.0):
                sent.append((slug, command, dict(payload or {})))
                return await orig_send(slug, command, payload,
                                       timeout=timeout)

            async def spy_batch(items, timeout=60.0):
                for slug, command, payload in items:
                    sent.append((slug, command, dict(payload or {})))
                return await orig_batch(items, timeout=timeout)
            handle.state.agent_registry.send_command = spy
            handle.state.agent_registry.send_batch = spy_batch

            cli, _ = await ProtocolClient.connect(handle.host, handle.port,
                                                  identity="cli")
            req = DeployRequest(flow=flow, stage_name="main")
            out = await cli.request("deploy", "execute",
                                    {"request": req.to_dict()}, timeout=30)
            assert out["deployment"]["status"] == "succeeded"
            placed = out["deployment"]["placement"]
            victim = placed["web"]
            survivor = ("node-2" if victim == "node-1" else "node-1")
            cname = container_name("healdemo", "main", "web")
            assert backends[victim].inspect(cname).running

            # ---- kill the victim agent: NO operator RPC follows --------
            agents[victim].stop()

            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                info = backends[survivor].inspect(cname)
                if info is not None and info.running:
                    break
                await asyncio.sleep(0.05)
            else:
                pytest.fail(
                    f"service never healed onto {survivor}: "
                    f"{handle.state.reconverger.status()}")

            # redelivery carried an idempotency key
            heals = [(s, p) for s, c, p in sent
                     if c == "deploy.execute" and p.get("idempotency_key")]
            assert heals, sent
            assert all(s == survivor for s, _ in heals)
            heal_key = heals[0][1]["idempotency_key"]
            assert heal_key.startswith("heal-healdemo/main-")

            # heal landed in deployment history with its placement (the
            # container runs on the survivor before the CP has the ack and
            # writes the record: wait for the record, not for a fixed time)
            while time.monotonic() < deadline:
                heal_deps = [d for d in
                             handle.state.store.list("deployments")
                             if d.log.startswith("self-heal")]
                if heal_deps:
                    break
                await asyncio.sleep(0.02)
            assert heal_deps and heal_deps[-1].placement == {
                "web": survivor}

            # idempotent replay: re-send the exact redelivery — the agent
            # answers from its dedupe window instead of re-deploying
            replays_before = REGISTRY.get(
                "fleet_agent_idempotent_replays_total").value()
            replay_payload = dict(heals[0][1])
            r1 = await orig_send(survivor, "deploy.execute", replay_payload,
                                 timeout=30)
            assert REGISTRY.get(
                "fleet_agent_idempotent_replays_total").value() \
                == replays_before + 1
            assert r1.get("deployed") == ["healdemo-main-web"]

            # heal status surface reports a converged fleet
            status = await cli.request("health", "heal.status")
            assert status["enabled"] is True
            assert status["work"] == []
            assert status["stats"]["redeliveries_ok"] >= 1

            await cli.close()
            for slug, agent in agents.items():
                agent.stop()
            for t in tasks.values():
                try:
                    await asyncio.wait_for(t, 5)
                except asyncio.TimeoutError:
                    t.cancel()
            await handle.stop()

        run(go())

        # ---- flight recorder: detection and redeploy share ONE trace ---
        from fleetflow_tpu.obs.trace import read_trace_file
        events = read_trace_file(str(trace_file))
        reconverge = [e for e in events
                      if e["logger"] == "fleetflow.cp.reconverge"
                      and e["name"] == "reconverge" and e["kind"] == "begin"]
        assert reconverge, "no reconverge span recorded"
        trace = reconverge[0]["trace"]
        redeliver = [e for e in events
                     if e["name"] == "heal.redeliver"
                     and e["trace"] == trace]
        assert redeliver, "redelivery span missing from the heal trace"
        agent_side = [e for e in events
                      if e["logger"] == "fleetflow.agent"
                      and e["name"] == "agent.deploy"
                      and e["trace"] == trace]
        assert agent_side, ("agent-side deploy span did not join the "
                            "heal trace")
