"""Replicated control plane tests (docs/guide/13-cp-replication.md).

Layers:
  - Store crash windows (property, seeded): journal replay idempotency
    across a crash between snapshot rename and journal truncate, and the
    replication stream producing BYTE-IDENTICAL table state on a standby;
  - replication units: sequence gaps force snapshot catch-up, stale
    epochs are fenced at the store, ring-window subscribe vs snapshot;
  - election: the most-caught-up standby (gossiped ack table) promotes,
    a lagging one stands down;
  - fencing at the agent: stale-epoch commands and zombie-CP welcomes
    are refused;
  - e2e (the ISSUE acceptance): real primary + standby + two agents;
    killing the primary MID-REDELIVERY completes the redelivery exactly
    once through the promoted standby (dedupe-proven), and a write from
    the old primary's epoch is fenced.
"""

from __future__ import annotations

import asyncio
import json
import random

import pytest

from fleetflow_tpu.agent import Agent, AgentConfig
from fleetflow_tpu.core.errors import ControlPlaneError
from fleetflow_tpu.core.model import Flow, ResourceSpec, Service, Stage
from fleetflow_tpu.cp import ServerConfig, start
from fleetflow_tpu.cp.models import Tenant
from fleetflow_tpu.cp.protocol import ProtocolClient, RpcError
from fleetflow_tpu.cp.replication import (ReplicationConfig, Replicator,
                                          StandbyReplica, StandbyRunner)
from fleetflow_tpu.cp.store import (ReplicationFenced, ReplicationGap, Store)
from fleetflow_tpu.obs.metrics import REGISTRY
from fleetflow_tpu.runtime import DeployRequest, MockBackend
from fleetflow_tpu.runtime.converter import container_name


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 90))


def _tables_doc(store: Store) -> str:
    doc = store.snapshot_doc()
    doc.pop("_meta", None)
    return json.dumps(doc, sort_keys=True)


def _random_ops(store: Store, rng: random.Random, n: int) -> None:
    """A seeded workload across several tables, including the batched
    path (replace_observed) and deletes — the shapes the journal and the
    replication stream must both carry."""
    from fleetflow_tpu.cp.models import ObservedContainer
    for i in range(n):
        op = rng.randrange(6)
        if op == 0:
            store.create("tenants", Tenant(name=f"t{rng.randrange(20)}-{i}"))
        elif op == 1:
            store.register_server(f"node-{rng.randrange(8)}",
                                  hostname=f"h{i}")
        elif op == 2:
            rows = store.list("servers")
            if rows:
                s = rng.choice(rows)
                store.update("servers", s.id,
                             status=rng.choice(("online", "offline")))
        elif op == 3:
            rows = store.list("tenants")
            if rows:
                store.delete("tenants", rng.choice(rows).id)
        elif op == 4:
            store.upsert_alert(f"node-{rng.randrange(8)}", "c", "unhealthy",
                               f"m{i}")
        else:
            store.replace_observed(f"node-{rng.randrange(4)}", [
                ObservedContainer(name=f"c{j}", image="img",
                                  state="running")
                for j in range(rng.randrange(3))])


class TestStoreCrashWindows:
    @pytest.mark.parametrize("seed", range(4))
    def test_replay_idempotent_across_compaction_crash(self, tmp_path,
                                                       seed):
        """Crash BETWEEN snapshot rename and journal truncate: on
        reopen, the surviving journal replays over a snapshot that
        already contains it — state must be identical to the pre-crash
        store (puts overwrite with identical rows; deletes of absent
        rows no-op)."""
        rng = random.Random(seed)
        path = tmp_path / f"cp{seed}.json"
        store = Store(str(path), journal_max_bytes=1 << 30,
                      journal_max_entries=1 << 30)
        _random_ops(store, rng, 60)
        journal = path.with_name(path.name + ".journal")
        pre_crash = journal.read_bytes()
        before = _tables_doc(store)
        store.flush()               # snapshot written, journal truncated
        assert not journal.exists()
        # the crash: snapshot landed but the truncate never did
        journal.write_bytes(pre_crash)
        reopened = Store(str(path))
        assert _tables_doc(reopened) == before

    @pytest.mark.parametrize("seed", range(4))
    def test_stream_replay_is_byte_identical(self, seed):
        """Every shipped entry applied in order on a standby produces
        byte-identical table state — including seq and epoch metadata,
        so a promoted standby continues the same journal history."""
        rng = random.Random(100 + seed)
        primary, standby = Store(), Store()
        replica = StandbyReplica(standby)
        primary.replication_sink = replica.apply_lines
        _random_ops(primary, rng, 80)
        assert json.dumps(primary.snapshot_doc(), sort_keys=True) == \
            json.dumps(standby.snapshot_doc(), sort_keys=True)
        assert standby.seq == primary.seq
        assert standby.epoch == primary.epoch

    def test_torn_tail_is_dropped_and_seq_resumes(self, tmp_path):
        path = tmp_path / "cp.json"
        store = Store(str(path))
        store.create("tenants", Tenant(name="a"))
        store.create("tenants", Tenant(name="b"))
        seq = store.seq
        journal = path.with_name(path.name + ".journal")
        with open(journal, "a") as f:
            f.write('{"op": "put", "t": "tenants", "r": {tor')  # torn
        reopened = Store(str(path))
        assert len(reopened.list("tenants")) == 2
        assert reopened.seq == seq   # numbering resumes past the tail


class TestStandbyReplica:
    def test_gap_detection_forces_resync(self):
        primary, standby = Store(), Store()
        replica = StandbyReplica(standby)
        shipped = []
        primary.replication_sink = lambda e: shipped.extend(e)
        for i in range(6):
            primary.create("tenants", Tenant(name=f"t{i}"))
        replica.apply_lines(shipped[:2])
        with pytest.raises(ReplicationGap):
            replica.apply_lines(shipped[4:])     # skipped 2 entries
        # snapshot catch-up repairs it
        replica.install(primary.snapshot_doc())
        assert replica.last_seq == primary.seq
        assert _tables_doc(standby) == _tables_doc(primary)

    def test_stale_epoch_is_fenced_at_the_store(self):
        primary, standby = Store(), Store()
        replica = StandbyReplica(standby)
        shipped = []
        primary.replication_sink = lambda e: shipped.extend(e)
        primary.create("tenants", Tenant(name="a"))
        replica.apply_lines(shipped)
        before = REGISTRY.get(
            "fleet_replication_fencing_rejections_total").value(side="store")
        replica.promote()            # epoch 2: the old primary is fenced
        primary.create("tenants", Tenant(name="zombie"))
        with pytest.raises(ReplicationFenced):
            replica.apply_lines(shipped[1:])
        assert standby.tenant_by_name("zombie") is None
        assert REGISTRY.get(
            "fleet_replication_fencing_rejections_total"
        ).value(side="store") == before + 1

    def test_already_applied_entries_skip_idempotently(self):
        """A batch queued before a snapshot resync may replay entries
        the snapshot already contains: they skip by sequence instead of
        raising a gap (which would force another full resync per stale
        batch)."""
        primary, standby = Store(), Store()
        replica = StandbyReplica(standby)
        shipped = []
        primary.replication_sink = lambda e: shipped.extend(e)
        for i in range(4):
            primary.create("tenants", Tenant(name=f"t{i}"))
        replica.install(primary.snapshot_doc())   # standby at seq 4
        # a stale in-flight batch overlapping the snapshot: 3,4 skip, 5+
        # would apply (none here) — no gap, no state change
        primary.create("tenants", Tenant(name="t4"))      # seq 5
        assert replica.apply_lines(shipped[2:4]) == 0     # seqs 3,4
        assert replica.apply_lines(shipped[2:]) == 1      # 3,4 skip; 5 lands
        assert _tables_doc(standby) == _tables_doc(primary)

    def test_epoch_bump_replicates_to_own_standbys(self):
        """A promoted primary's epoch entry rides its own journal stream
        — its standbys inherit the fencing epoch."""
        primary = Store()
        gen2 = Store()
        replica2 = StandbyReplica(gen2)
        replica2.install(primary.snapshot_doc())
        primary.replication_sink = replica2.apply_lines
        primary.bump_epoch()
        primary.create("tenants", Tenant(name="after"))
        assert gen2.epoch == 2
        assert gen2.tenant_by_name("after") is not None


class TestReplicatorRing:
    def test_subscribe_inside_ring_vs_snapshot_needed(self):
        async def go():
            store = Store()
            repl = Replicator(store, config=ReplicationConfig(
                ring_entries=8), loop=asyncio.get_running_loop())
            for i in range(30):
                store.create("tenants", Tenant(name=f"t{i}"))

            class Conn:
                identity = "sb"

                async def send_event(self, *a, **k):
                    pass

            # far behind the 8-entry ring: snapshot required
            out = repl.attach(Conn(), "sb", 0)
            assert out["snapshot_needed"] is True
            meta, chunks = repl.snapshot_chunks()
            doc = json.loads("".join(chunks))
            standby = Store()
            replica = StandbyReplica(standby)
            replica.install(doc)
            assert replica.last_seq == store.seq
            # now inside the window: streaming resumes without snapshot
            out = repl.attach(Conn(), "sb", replica.last_seq)
            assert out.get("subscribed") is True
        run(go())

    def test_ack_updates_lag(self):
        async def go():
            store = Store()
            repl = Replicator(store, loop=asyncio.get_running_loop())

            class Conn:
                identity = "sb"

                async def send_event(self, *a, **k):
                    pass

            conn = Conn()
            repl.attach(conn, "sb", 0)
            for i in range(5):
                store.create("tenants", Tenant(name=f"t{i}"))
            await asyncio.sleep(0.05)     # sender drains the queue
            st = repl.status()
            assert st["standbys"][0]["sent_seq"] == store.seq
            repl.ack(conn, store.seq)
            st = repl.status()
            assert st["standbys"][0]["lag"] == 0
        run(go())


class TestElection:
    def _runner(self, identity: str, seq: int) -> StandbyRunner:
        store = Store()
        store._seq = seq
        return StandbyRunner(StandbyReplica(store), "127.0.0.1", 1,
                             identity=identity)

    def test_most_caught_up_wins(self):
        r = self._runner("sb-a", 10)
        r._ack_table = {"sb-a": 10, "sb-b": 7}
        assert r._most_caught_up() is True

    def test_lagging_standby_stands_down(self):
        r = self._runner("sb-b", 7)
        r._ack_table = {"sb-a": 10, "sb-b": 7}
        assert r._most_caught_up() is False

    def test_seq_tie_breaks_on_identity(self):
        a = self._runner("sb-a", 9)
        a._ack_table = {"sb-a": 9, "sb-b": 9}
        assert a._most_caught_up() is True     # lowest name wins the tie
        b = self._runner("sb-b", 9)
        b._ack_table = {"sb-a": 9, "sb-b": 9}
        assert b._most_caught_up() is False

    def test_empty_table_means_sole_candidate(self):
        r = self._runner("sb-a", 3)
        assert r._most_caught_up() is True


class _CaptureConn:
    def __init__(self):
        self.replies = []

    async def send_event(self, channel, method, payload, *, in_reply=False):
        assert in_reply      # a command's result answers its frame
        self.replies.append((method, payload))


class TestAgentFencing:
    def test_stale_epoch_command_is_refused(self):
        async def go():
            agent = Agent(AgentConfig(slug="n1"),
                          backend=MockBackend(auto_pull=True),
                          sleep=lambda d: None)
            conn = _CaptureConn()
            await agent._on_command(conn, "ping",
                                    {"request_id": "r1", "epoch": 3,
                                     "payload": {}})
            assert conn.replies[0][1]["result"]["pong"] is True
            before = REGISTRY.get(
                "fleet_replication_fencing_rejections_total"
            ).value(side="agent")
            await agent._on_command(conn, "ping",
                                    {"request_id": "r2", "epoch": 2,
                                     "payload": {}})
            assert "fenced" in conn.replies[1][1]["error"]
            assert REGISTRY.get(
                "fleet_replication_fencing_rejections_total"
            ).value(side="agent") == before + 1
            # equal/newer epochs keep working
            await agent._on_command(conn, "ping",
                                    {"request_id": "r3", "epoch": 3,
                                     "payload": {}})
            assert conn.replies[2][1]["result"]["pong"] is True
        run(go())

    def test_zombie_cp_welcome_is_refused(self):
        """An agent that has seen epoch N refuses to register with a CP
        advertising epoch < N (the welcome-frame fence), and rotates to
        the next endpoint instead."""
        async def go():
            handle = await start(ServerConfig(self_heal=False))
            agent = Agent(AgentConfig(cp_host=handle.host,
                                      cp_port=handle.port, slug="n1"),
                          backend=MockBackend(auto_pull=True),
                          sleep=lambda d: None)
            agent._max_epoch = 5     # saw a newer controller generation
            with pytest.raises(RuntimeError, match="zombie"):
                await agent.run_session()
            assert not handle.state.agent_registry.is_connected("n1")
            await handle.stop()
        run(go())


class TestDaemonConfigStanza:
    def test_replication_stanza_parses(self, tmp_path):
        from fleetflow_tpu.daemon.config import load_daemon_config
        cfg_path = tmp_path / "fleetflowd.kdl"
        cfg_path.write_text(
            'replication standby-of="cp-a.internal:4510" lease=12 '
            'grace=6 ping=3 token="sekret"\n')
        cfg = load_daemon_config(str(cfg_path))
        assert cfg.standby_of == "cp-a.internal:4510"
        assert cfg.standby_lease_s == 12.0
        assert cfg.standby_grace_s == 6.0
        assert cfg.standby_ping_interval_s == 3.0
        assert cfg.standby_token == "sekret"

    def test_no_stanza_means_primary(self, tmp_path):
        from fleetflow_tpu.daemon.config import load_daemon_config
        cfg_path = tmp_path / "fleetflowd.kdl"
        cfg_path.write_text('listen "127.0.0.1" 4510\n')
        assert load_daemon_config(str(cfg_path)).standby_of is None


class TestStandbyServer:
    def test_standby_refuses_writes_and_agents_until_promoted(self):
        async def go():
            primary = await start(ServerConfig(self_heal=False))
            standby = await start(ServerConfig(
                name="cp-b", self_heal=False,
                standby_of=f"{primary.host}:{primary.port}",
                standby_ping_interval_s=0.05, standby_lease_s=0.4,
                standby_grace_s=0.15))
            cli, _ = await ProtocolClient.connect(
                standby.host, standby.port, identity="cli")
            assert cli.welcome["role"] == "standby"
            with pytest.raises(RpcError, match="not primary"):
                await cli.request("tenant", "create", {"name": "x"})
            with pytest.raises(RpcError, match="not primary"):
                await cli.request("agent", "register", {"slug": "n1"})
            # reads are served from the replicated state
            out = await cli.request("health", "overview")
            assert out["servers"] == 0
            await cli.close()
            await standby.stop()
            await primary.stop()
        run(go())

    def test_standby_web_surface_refuses_writes(self):
        """The REST face mirrors the channel rule: a standby serves GETs
        from the replicated state but 503s every mutation — a write
        applied to a replica would be ghost state after promotion."""
        async def go():
            import json as _json
            import urllib.error
            import urllib.request
            from fleetflow_tpu.daemon.web import WebServer
            primary = await start(ServerConfig(self_heal=False))
            standby = await start(ServerConfig(
                name="cp-b", self_heal=False,
                standby_of=f"{primary.host}:{primary.port}",
                standby_ping_interval_s=0.05, standby_lease_s=0.4,
                standby_grace_s=0.15))
            web = WebServer(standby.state)
            host, port = await web.start()

            def fetch(method, path, body=None):
                data = (_json.dumps(body).encode()
                        if body is not None else None)
                req = urllib.request.Request(
                    f"http://{host}:{port}{path}", data=data,
                    method=method)
                req.add_header("Content-Type", "application/json")
                try:
                    with urllib.request.urlopen(req, timeout=5) as resp:
                        return resp.status
                except urllib.error.HTTPError as e:
                    return e.code

            loop = asyncio.get_running_loop()
            st = await loop.run_in_executor(
                None, lambda: fetch("GET", "/api/overview"))
            assert st == 200
            st = await loop.run_in_executor(
                None, lambda: fetch("POST", "/api/tenants",
                                    {"name": "ghost"}))
            assert st == 503
            assert standby.state.store.tenant_by_name("ghost") is None
            await web.stop()
            await standby.stop()
            await primary.stop()
        run(go())

    def test_replication_survives_primary_compaction(self):
        """Journal compaction on the primary (snapshot + truncate) must
        not disturb the shipped stream or the standby's state."""
        async def go():
            import tempfile
            with tempfile.TemporaryDirectory() as td:
                primary = await start(ServerConfig(
                    self_heal=False, db_path=f"{td}/cp.json"))
                standby = await start(ServerConfig(
                    name="cp-b", self_heal=False,
                    standby_of=f"{primary.host}:{primary.port}",
                    standby_ping_interval_s=0.05, standby_lease_s=0.4,
                    standby_grace_s=0.15))
                db = primary.state.store
                for i in range(10):
                    db.create("tenants", Tenant(name=f"t{i}"))
                db.flush()
                for i in range(10, 15):
                    db.create("tenants", Tenant(name=f"t{i}"))
                for _ in range(100):
                    await asyncio.sleep(0.02)
                    if standby.state.store.seq == db.seq:
                        break
                assert len(standby.state.store.list("tenants")) == 15
                await standby.stop()
                await primary.stop()
        run(go())


# --------------------------------------------------------------------------
# e2e acceptance: kill the primary mid-redelivery; the promoted standby
# completes it exactly once; the old epoch is fenced
# --------------------------------------------------------------------------

def _heal_flow() -> Flow:
    flow = Flow(name="repldemo")
    flow.services["web"] = Service(
        name="web", image="app", version="1",
        resources=ResourceSpec(cpu=0.5, memory=128.0))
    flow.stages["main"] = Stage(name="main", services=["web"],
                                servers=["node-1", "node-2"])
    return flow


class TestCpFailoverE2E:
    def test_primary_killed_mid_redelivery_heals_via_standby(self):
        flow = _heal_flow()

        async def go():
            fast = dict(self_heal=True, lease_s=0.4, suspect_grace_s=0.15,
                        heal_interval_s=0.05, heal_backoff_base_s=0.2,
                        heal_backoff_max_s=0.4, heal_max_attempts=50,
                        standby_ping_interval_s=0.05, standby_lease_s=0.4,
                        standby_grace_s=0.15)
            primary = await start(
                ServerConfig(**fast),
                backend_factory=lambda: MockBackend(auto_pull=True))
            standby = await start(
                ServerConfig(name="cp-b",
                             standby_of=f"{primary.host}:{primary.port}",
                             **fast),
                backend_factory=lambda: MockBackend(auto_pull=True))

            backends, agents, tasks, executed = {}, {}, {}, []
            for slug in ("node-1", "node-2"):
                backends[slug] = MockBackend(auto_pull=True)
                cfg = AgentConfig(
                    cp_endpoints=[(primary.host, primary.port),
                                  (standby.host, standby.port)],
                    slug=slug, heartbeat_interval_s=0.05,
                    monitor_interval_s=30.0, reconnect_backoff_s=0.05,
                    capacity={"cpu": 4, "memory": 8192, "disk": 100000})
                agent = Agent(cfg, backend=backends[slug],
                              sleep=lambda d: None)
                orig_exec = agent.execute_command

                async def spy_exec(method, payload, _slug=slug,
                                   _orig=orig_exec):
                    if (method == "deploy.execute"
                            and payload.get("idempotency_key")):
                        executed.append(
                            (_slug, dict(payload)))
                    return await _orig(method, payload)
                agent.execute_command = spy_exec
                agents[slug] = agent
                tasks[slug] = asyncio.ensure_future(agent.run())
            while not all(primary.state.agent_registry.is_connected(s)
                          for s in agents):
                await asyncio.sleep(0.02)

            cli, _ = await ProtocolClient.connect(
                primary.host, primary.port, identity="cli")
            assert cli.welcome["epoch"] == 1
            req = DeployRequest(flow=flow, stage_name="main")
            out = await cli.request("deploy", "execute",
                                    {"request": req.to_dict()}, timeout=30)
            assert out["deployment"]["status"] == "succeeded"
            victim = out["deployment"]["placement"]["web"]
            survivor = "node-2" if victim == "node-1" else "node-1"
            cname = container_name("repldemo", "main", "web")
            assert backends[victim].inspect(cname).running

            # arm the mid-redelivery window: the primary's next heal
            # redeliveries all fail at the delivery hook, so the work
            # stays in flight (journaled + replicated) when we kill it
            def refuse(slug, command):
                if command == "deploy.execute":
                    raise ControlPlaneError("wire cut (chaos)")
            primary.state.agent_registry.delivery_hook = refuse

            # ---- kill the victim agent; NO operator RPC follows -------
            agents[victim].stop()
            deadline = asyncio.get_running_loop().time() + 20
            rc = primary.state.reconverger
            while asyncio.get_running_loop().time() < deadline:
                work = rc.status()["work"]
                if any(w["attempt"] >= 1 for w in work):
                    break            # redelivery in flight, retrying
                await asyncio.sleep(0.02)
            else:
                pytest.fail(f"no in-flight redelivery: {rc.status()}")

            # ---- kill the primary MID-REDELIVERY ----------------------
            await cli.close()
            await primary.stop()

            deadline = asyncio.get_running_loop().time() + 20
            while asyncio.get_running_loop().time() < deadline:
                if standby.state.replication_role == "primary":
                    break
                await asyncio.sleep(0.02)
            else:
                pytest.fail("standby never promoted")
            assert standby.state.store.epoch == 2

            # the promoted standby finishes the heal: web runs on the
            # survivor, driven by the resumed (replicated) work
            deadline = asyncio.get_running_loop().time() + 20
            while asyncio.get_running_loop().time() < deadline:
                info = backends[survivor].inspect(cname)
                if info is not None and info.running:
                    break
                await asyncio.sleep(0.05)
            else:
                pytest.fail(
                    f"service never healed onto {survivor}: "
                    f"{standby.state.reconverger.status()}")

            # exactly once: the survivor executed ONE keyed redelivery
            survivor_execs = [p for s, p in executed if s == survivor]
            assert len(survivor_execs) == 1, survivor_execs
            heal_payload = survivor_execs[0]

            # dedupe-proven: replay the exact redelivery through the new
            # primary — the agent answers from its window, executing
            # nothing
            replays = REGISTRY.get("fleet_agent_idempotent_replays_total")
            before = replays.value()
            await standby.state.agent_registry.send_command(
                survivor, "deploy.execute", heal_payload, timeout=30)
            assert replays.value() == before + 1
            assert len([p for s, p in executed if s == survivor]) == 1

            # fenced write: the old primary's epoch bounces off the new
            # primary's replication door (+ the store-side counter)
            fenced = REGISTRY.get(
                "fleet_replication_fencing_rejections_total")
            before_cp = fenced.value(side="cp")
            zombie, _ = await ProtocolClient.connect(
                standby.host, standby.port, identity="old-primary")
            with pytest.raises(RpcError, match="fenced"):
                await zombie.request("replication", "append", {
                    "epoch": 1, "entries": [[standby.state.store.seq + 1,
                                             '{"op": "del", "t": '
                                             '"tenants", "id": "x", '
                                             '"q": 1, "e": 1}']]})
            assert fenced.value(side="cp") == before_cp + 1
            await zombie.close()

            # the new primary reports a converged fleet
            cli2, _ = await ProtocolClient.connect(
                standby.host, standby.port, identity="cli2")
            assert cli2.welcome["role"] == "primary"
            assert cli2.welcome["epoch"] == 2
            status = await cli2.request("health", "heal.status")
            assert status["replication"]["role"] == "primary"
            assert status["work"] == []
            await cli2.close()

            for agent in agents.values():
                agent.stop()
            for t in tasks.values():
                try:
                    await asyncio.wait_for(t, 5)
                except asyncio.TimeoutError:
                    t.cancel()
            await standby.stop()

        run(go())
