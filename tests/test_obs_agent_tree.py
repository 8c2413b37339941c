"""The span tree crosses to the agents (cp/protocol.py, agent/agent.py).

What is pinned here:

  * an event frame carries `trace` and `span` inside a trace and neither
    outside one: a heartbeat outside a trace is the bare frame, byte for
    byte; handling an event reads no environment
  * an event is handled under the trace and the sender's phase its frame
    carried, after `protocol.wait.event` (never `protocol.wait.dispatch`);
    a frame without the keys is handled as before
  * one `deploy.execute` through an in-process CP to two agents on
    `MockBackend` is one tree: `agents.send_batch` > `agent.command` >
    `agent.command.parse`, `agent.wait.executor`, `agent.deploy`; the
    `command_result`'s decode and wait are children of `agents.send_batch`;
    the live log events carry the deploy's trace
  * a command whose `span` is another process's: the agent's phases are
    roots under the sender's trace id
  * every `command_result` — executed, fenced, replayed from the
    idempotency window — is filed under the phase that sent its command
  * a replication `append` carries the trace of the write it ships, and
    the stream's task none of the subscribe request's
"""

from __future__ import annotations

import asyncio
import json
import time
import types

import pytest

from fleetflow_tpu import obs
from fleetflow_tpu.agent import Agent, AgentConfig
from fleetflow_tpu.cp import protocol
from fleetflow_tpu.obs import trace as obs_trace
from fleetflow_tpu.runtime import MockBackend

NAME, T0, T1, TID, ID, PARENT, TRACE = range(7)
SLUGS = ["n0", "n1"]
FOREIGN = "0badf00d:5"      # a span minted by another process


def _run(coro, timeout=120):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def _since(t0):
    return obs_trace.tree_between(t0, time.perf_counter())


def _frames(monkeypatch):
    """Every message encoded in this process, both ends of every
    connection, in order."""
    sent = []
    real = protocol.encode_frame
    monkeypatch.setattr(protocol, "encode_frame",
                        lambda msg: sent.append(msg) or real(msg))
    return sent


def _agent(host, port, slug):
    return Agent(AgentConfig(cp_host=host, cp_port=port, slug=slug,
                             heartbeat_interval_s=3600,
                             monitor_interval_s=3600,
                             capacity={"cpu": 4, "memory": 8192,
                                       "disk": 99999}),
                 backend=MockBackend(auto_pull=True), sleep=lambda _s: None)


async def _connected(agents, is_connected):
    sessions = [asyncio.ensure_future(a.run_session()) for a in agents]
    for _ in range(400):
        if all(is_connected(a.config.slug) for a in agents):
            return sessions
        await asyncio.sleep(0.01)
    raise AssertionError("agents did not connect")


async def _stopped(agents, sessions):
    for a in agents:
        a.stop()
    await asyncio.wait_for(asyncio.gather(*sessions, return_exceptions=True),
                           10)


# --------------------------------------------------------------------------
# the frame
# --------------------------------------------------------------------------

class _Writer:
    """A StreamWriter that keeps what it is given."""

    def __init__(self):
        self.data = b""

    def write(self, data):
        self.data += data

    async def drain(self):
        pass


def _sent_event(payload, trace=None, *, in_reply=False):
    writer = _Writer()
    conn = protocol.Connection(reader=None, writer=writer)

    async def go():
        if trace is None:
            await conn.send_event("agent", "heartbeat", payload,
                                  in_reply=in_reply)
        else:
            with obs.use_trace(trace), obs.phase("t.sender") as ph:
                await conn.send_event("agent", "heartbeat", payload,
                                      in_reply=in_reply)
            return ph

    return _run(go(), 10), writer.data


class TestEventFrame:
    PAYLOAD = {"version": "0.1.0", "metrics": {"v": 1, "m": [["x", 2.5]]}}

    def test_a_heartbeat_outside_a_trace_is_the_bare_frame(self):
        _, data = _sent_event(self.PAYLOAD)
        body = json.dumps({"type": "event", "channel": "agent",
                           "method": "heartbeat", "payload": self.PAYLOAD},
                          separators=(",", ":")).encode()
        assert data == len(body).to_bytes(4, "big") + body

    def test_inside_a_trace_the_frame_carries_trace_and_span(self):
        ph, data = _sent_event(self.PAYLOAD, "feedfacefeedface")
        msg = json.loads(data[4:])
        assert msg["trace"] == "feedfacefeedface"
        assert msg["span"] == f"{obs_trace.PROCESS_TOKEN}:{ph.id}"
        _, bare = _sent_event(self.PAYLOAD)
        assert 0 < len(data) - len(bare) < 100

    def test_a_reply_outside_any_event_carries_nothing(self):
        """`in_reply` answers the event being handled: with none, the
        frame is bare even inside a trace of the sender's own."""
        _, data = _sent_event(self.PAYLOAD, "feedfacefeedface",
                              in_reply=True)
        msg = json.loads(data[4:])
        assert "trace" not in msg and "span" not in msg


class _Reader:
    """A StreamReader over given frames, then a disconnect."""

    def __init__(self, *msgs):
        self.data = b"".join(protocol.encode_frame(m) for m in msgs)

    async def readexactly(self, n):
        if len(self.data) < n:
            raise asyncio.IncompleteReadError(self.data, n)
        out, self.data = self.data[:n], self.data[n:]
        return out


def _handled(*msgs):
    """Run a connection's read loop over `msgs` (events of channel
    `agent`) and return what the handler saw: method, the trace and phase
    open in its body, and what a reply to it would carry."""
    seen = []

    async def handler(conn, method, payload):
        seen.append((method, obs_trace.current_trace_id(),
                     obs_trace._phase_id.get(),
                     protocol._event_origin.get()))

    async def go():
        conn = protocol.Connection(reader=_Reader(*msgs), writer=_Writer(),
                                   event_handlers={"agent": handler})
        await conn.run()
        await asyncio.gather(*list(conn._tasks))

    t0 = time.perf_counter()
    _run(go(), 10)
    return seen, _since(t0)


def _event(method, **keys):
    return {"type": "event", "channel": "agent", "method": method,
            "payload": {}, **keys}


class TestEventHandling:
    def test_an_event_is_handled_under_its_frames_trace_and_phase(self):
        with obs.phase("t.sender") as sender:
            span = obs_trace.wire_span()
        seen, recs = _handled(_event("a", trace="0123456789abcdef",
                                     span=span))
        assert seen == [("a", "0123456789abcdef", sender.id,
                         ("0123456789abcdef", span))]
        [decode] = [r for r in recs if r[NAME] == "protocol.decode"]
        [wait] = [r for r in recs if r[NAME] == "protocol.wait.event"]
        for r in (decode, wait):
            assert r[PARENT] == sender.id and r[TRACE] == "0123456789abcdef"
        assert wait[T0] >= decode[T1]
        assert not [r for r in recs if r[NAME] == "protocol.wait.dispatch"]

    def test_another_processs_span_leaves_the_trace_and_no_parent(self):
        seen, recs = _handled(_event("a", trace="0123456789abcdef",
                                     span=FOREIGN))
        assert seen == [("a", "0123456789abcdef", 0,
                         ("0123456789abcdef", FOREIGN))]
        [wait] = [r for r in recs if r[NAME] == "protocol.wait.event"]
        assert wait[PARENT] == 0 and wait[TRACE] == "0123456789abcdef"

    @pytest.mark.parametrize("keys", [{}, {"trace": 7, "span": ["x"]},
                                      {"trace": "", "span": FOREIGN}])
    def test_a_frame_without_the_keys_is_handled_as_before(self, keys):
        """An older peer's event: handled, in no trace, and a reply to it
        carries nothing; its wait is written all the same."""
        seen, recs = _handled(_event("a", **keys))
        assert seen == [("a", "", 0, (None, None))]
        [wait] = [r for r in recs if r[NAME] == "protocol.wait.event"]
        assert wait[TRACE] == "" and wait[PARENT] == 0

    def test_handling_an_event_reads_no_environment(self, monkeypatch):
        reads = []

        class Environ(dict):
            def get(self, *a):
                reads.append(a)
                return None

        monkeypatch.setattr(obs_trace, "os",
                            types.SimpleNamespace(environ=Environ()))
        seen, _ = _handled(_event("a", trace="0123456789abcdef",
                                  span=FOREIGN), _event("b"))
        assert [s[0] for s in seen] == ["a", "b"] and reads == []

    def test_bound_as_writes_its_own_wait_only(self):
        async def go():
            with obs.use_trace("0123456789abcdef"), obs.phase("t.hop") as ph:
                await asyncio.get_running_loop().run_in_executor(
                    None, obs_trace.bound_as("t.wait.pool", lambda: None))
            return ph

        t0 = time.perf_counter()
        ph = _run(go(), 10)
        recs = _since(t0)
        [wait] = [r for r in recs if r[NAME] == "t.wait.pool"]
        assert wait[PARENT] == ph.id and wait[TRACE] == "0123456789abcdef"
        assert not [r for r in recs if r[NAME] == "cp.wait.executor"
                    and r[PARENT] == ph.id]


# --------------------------------------------------------------------------
# a deploy through the CP to two agents: one tree
# --------------------------------------------------------------------------

FLEET_KDL = """
project "p"
{servers}
service "a0" {{ image "x"; resources {{ cpu 1; memory 64; disk 1 }} }}
service "a1" {{ image "x"; resources {{ cpu 1; memory 64; disk 1 }} }}
service "a2" {{ image "x"; resources {{ cpu 1; memory 64; disk 1 }} }}
stage "live" {{
    service "a0"
    service "a1"
    service "a2"
    servers {names}
}}
"""


def _flow():
    from fleetflow_tpu.core.parser import parse_kdl_string
    return parse_kdl_string(FLEET_KDL.format(
        servers="\n".join(
            f'server "{s}" {{ capacity {{ cpu 4; memory 8192; disk 99999 }} }}'
            for s in SLUGS),
        names=" ".join(f'"{s}"' for s in SLUGS)))


@pytest.fixture(scope="module")
def deployed():
    """Two `deploy.execute`s of one stage over a real connection to an
    in-process `cp.server.start`, to two agents on `MockBackend`: (records
    of the second one's window, its reply, every message encoded in it)."""
    from fleetflow_tpu.cp.server import ServerConfig, start
    from fleetflow_tpu.runtime.engine import DeployRequest

    sent = []
    real = protocol.encode_frame

    async def go():
        handle = await start(ServerConfig())
        agents = [_agent(handle.host, handle.port, s) for s in SLUGS]
        sessions = await _connected(
            agents, handle.state.agent_registry.is_connected)
        conn, task = await protocol.ProtocolClient.connect(
            handle.host, handle.port, identity="deployer")
        payload = {"request": DeployRequest(flow=_flow(),
                                            stage_name="live").to_dict()}
        try:
            await conn.request("deploy", "execute", payload, timeout=60)
            protocol.encode_frame = lambda m: sent.append(m) or real(m)
            t0 = time.perf_counter()
            reply = await conn.request("deploy", "execute", payload,
                                       timeout=60)
            # the live log events may land after the reply
            await asyncio.sleep(0.2)
            return _since(t0), reply
        finally:
            protocol.encode_frame = real
            await conn.close()
            task.cancel()
            await _stopped(agents, sessions)
            await handle.stop()

    recs, reply = _run(go(), 120)
    return recs, reply, sent


def _by_name(recs, name):
    return [r for r in recs if r[NAME] == name]


class TestDeployTree:
    def test_the_deploy_succeeded_on_both_agents(self, deployed):
        _, reply, _ = deployed
        assert reply["deployment"]["status"] == "succeeded"
        assert len(_by_name(deployed[0], "agent.command")) == len(SLUGS)

    def test_agent_command_is_a_child_of_send_batch(self, deployed):
        recs = deployed[0]
        [batch] = _by_name(recs, "agents.send_batch")
        for cmd in _by_name(recs, "agent.command"):
            assert cmd[PARENT] == batch[ID]
            assert cmd[TRACE] == batch[TRACE] != ""

    @pytest.mark.parametrize("name", ["agent.command.parse",
                                      "agent.wait.executor", "agent.deploy"])
    def test_the_agents_steps_are_children_of_agent_command(self, deployed,
                                                            name):
        recs = deployed[0]
        commands = {r[ID]: r for r in _by_name(recs, "agent.command")}
        mine = _by_name(recs, name)
        assert len(mine) == len(SLUGS)
        for r in mine:
            parent = commands[r[PARENT]]
            assert r[TRACE] == parent[TRACE]
            assert parent[T0] <= r[T0] and r[T1] <= parent[T1]

    def test_the_results_decode_and_wait_are_children_of_send_batch(
            self, deployed):
        recs, _, sent = deployed
        [batch] = _by_name(recs, "agents.send_batch")
        results = [m for m in sent if m.get("method") == "command_result"]
        assert len(results) == len(SLUGS)
        span = f"{obs_trace.PROCESS_TOKEN}:{batch[ID]}"
        assert all(m["trace"] == batch[TRACE] and m["span"] == span
                   for m in results)
        kids = [r for r in recs if r[PARENT] == batch[ID]]
        # the commands' frames decoded on the agents and the results'
        # decoded on the CP, each after its wait on the loop
        assert len([r for r in kids if r[NAME] == "protocol.decode"]) \
            == 2 * len(SLUGS)
        assert len([r for r in kids if r[NAME] == "protocol.wait.event"]) \
            == 2 * len(SLUGS)

    def test_one_trace_from_the_client_to_the_engine(self, deployed):
        recs = deployed[0]
        [batch] = _by_name(recs, "agents.send_batch")
        [serve] = _by_name(recs, "protocol.serve")
        engine = [r for r in _by_name(recs, "deploy.execute")
                  if r[TID] != serve[TID]]
        assert engine
        for r in (serve, *engine, *_by_name(recs, "agent.command")):
            assert r[TRACE] == batch[TRACE]

    def test_the_live_log_events_carry_the_command_trace(self, deployed):
        recs, _, sent = deployed
        [batch] = _by_name(recs, "agents.send_batch")
        logs = [m for m in sent if m.get("method") == "log"]
        assert logs
        assert all(m["trace"] == batch[TRACE] for m in logs)
        agent_side = {r[ID] for r in recs if r[TRACE] == batch[TRACE]
                      and r[TID] != batch[TID]}
        for m in logs:
            token, _, pid = m["span"].partition(":")
            assert token == obs_trace.PROCESS_TOKEN
            assert int(pid) in agent_side

    def test_events_write_no_dispatch_wait(self, deployed):
        """The one request of the window is the deploy: one
        `protocol.wait.dispatch`, under the client's `protocol.request`."""
        recs = deployed[0]
        [dispatch] = _by_name(recs, "protocol.wait.dispatch")
        [request] = _by_name(recs, "protocol.request")
        assert dispatch[PARENT] == request[ID]
        assert len(_by_name(recs, "protocol.wait.event")) >= 2 * len(SLUGS)


# --------------------------------------------------------------------------
# one agent, driven frame by frame by a CP stand-in
# --------------------------------------------------------------------------

class _Cp:
    """A bare `ProtocolServer` that registers agents and keeps their
    events: enough of a CP to send commands as frames of its choosing."""

    def __init__(self):
        self.server = protocol.ProtocolServer(name="stand-in")
        self.conns: dict[str, protocol.Connection] = {}
        self.results: asyncio.Queue = asyncio.Queue()

        async def handle(conn, method, p):
            self.conns[p["slug"]] = conn
            return {"registered": True}

        async def events(conn, method, p):
            if method == "command_result":
                await self.results.put(p)

        self.server.register_channel("agent", handle, events)

    async def __aenter__(self):
        host, port = await self.server.start()
        self.agent = _agent(host, port, "n0")
        self.sessions = await _connected([self.agent],
                                         lambda s: s in self.conns)
        return self

    async def __aexit__(self, *exc):
        await _stopped([self.agent], self.sessions)
        await self.server.stop()

    async def command(self, method, payload, **frame):
        """Send one command as an event frame with `frame`'s keys; its
        result."""
        rid = f"req_{time.perf_counter_ns()}"
        await self.conns["n0"]._send(
            {"type": "event", "channel": "agent", "method": method,
             "payload": {"request_id": rid, "payload": payload},
             **frame})
        result = await asyncio.wait_for(self.results.get(), 30)
        assert result["request_id"] == rid
        return result


def _deploy_payload(trace=None, key=None):
    from fleetflow_tpu.runtime.engine import DeployRequest
    request = DeployRequest(flow=_flow(), stage_name="live", node="n0",
                            trace_id=trace).to_dict()
    out = {"request": request, "assignment": {"a0": "n0", "a1": "n0",
                                              "a2": "n0"}}
    if key:
        out["idempotency_key"] = key
    return out


class TestAcrossProcesses:
    def test_another_processs_command_makes_roots_under_its_trace(
            self, monkeypatch):
        sent = _frames(monkeypatch)

        async def go():
            async with _Cp() as cp:
                t0 = time.perf_counter()
                result = await cp.command(
                    "deploy.execute", _deploy_payload("0123456789abcdef"),
                    trace="0123456789abcdef", span=FOREIGN)
                return result, _since(t0)

        result, recs = _run(go())
        assert "result" in result and not result.get("error")
        [cmd] = _by_name(recs, "agent.command")
        assert cmd[PARENT] == 0 and cmd[TRACE] == "0123456789abcdef"
        for name in ("agent.command.parse", "agent.wait.executor",
                     "agent.deploy"):
            [r] = _by_name(recs, name)
            assert r[PARENT] == cmd[ID] and r[TRACE] == "0123456789abcdef"
        [reply] = [m for m in sent if m.get("method") == "command_result"]
        assert (reply["trace"], reply["span"]) == ("0123456789abcdef",
                                                   FOREIGN)

    def test_a_command_without_the_keys_is_answered_as_before(
            self, monkeypatch):
        sent = _frames(monkeypatch)

        async def go():
            async with _Cp() as cp:
                t0 = time.perf_counter()
                return await cp.command("ping", {}), _since(t0)

        result, recs = _run(go())
        assert result["result"] == {"pong": True, "slug": "n0"}
        [reply] = [m for m in sent if m.get("method") == "command_result"]
        assert "trace" not in reply and "span" not in reply
        [cmd] = _by_name(recs, "agent.command")
        assert cmd[TRACE] == "" and cmd[PARENT] == 0


class TestRepliesAreFiledUnderTheSender:
    @staticmethod
    def _sent_under_a_phase(cp, method, payload, trace):
        """Send a command with the real `send_event`, from inside phase
        `t.sender`: (the phase, the result)."""
        async def go():
            rid = f"req_{time.perf_counter_ns()}"
            with obs.use_trace(trace), obs.phase("t.sender") as ph:
                await cp.conns["n0"].send_event(
                    "agent", method, {"request_id": rid, "payload": payload,
                                      "epoch": 1})
            result = await asyncio.wait_for(cp.results.get(), 30)
            assert result["request_id"] == rid
            return ph, result
        return go()

    def _check_filed(self, sent, recs, ph, trace):
        [reply] = [m for m in sent if m.get("method") == "command_result"]
        assert reply["trace"] == trace
        assert reply["span"] == f"{obs_trace.PROCESS_TOKEN}:{ph.id}"
        decodes = [r for r in recs if r[NAME] == "protocol.decode"
                   and r[PARENT] == ph.id]
        waits = [r for r in recs if r[NAME] == "protocol.wait.event"
                 and r[PARENT] == ph.id]
        # the command's on the agent, the result's on the CP
        assert len(decodes) == len(waits) == 2
        assert all(r[TRACE] == trace for r in decodes + waits)

    def test_a_fenced_reply(self, monkeypatch):
        async def go():
            async with _Cp() as cp:
                cp.agent._max_epoch = 5
                t0 = time.perf_counter()
                del sent[:]
                ph, result = await self._sent_under_a_phase(
                    cp, "ping", {}, "fe11cedfe11cedaa")
                return ph, result, _since(t0)

        sent = _frames(monkeypatch)
        ph, result, recs = _run(go())
        assert result["error"].startswith("fenced")
        self._check_filed(sent, recs, ph, "fe11cedfe11cedaa")

    def test_an_idempotent_replay(self, monkeypatch):
        async def go():
            async with _Cp() as cp:
                first = await cp.command("deploy.execute",
                                         _deploy_payload(key="k-1"))
                assert not first.get("deduped")
                t0 = time.perf_counter()
                del sent[:]
                ph, result = await self._sent_under_a_phase(
                    cp, "deploy.execute", _deploy_payload(key="k-1"),
                    "5eed5eed5eed5eed")
                return ph, result, _since(t0)

        sent = _frames(monkeypatch)
        ph, result, recs = _run(go())
        assert result["deduped"] is True
        self._check_filed(sent, recs, ph, "5eed5eed5eed5eed")


# --------------------------------------------------------------------------
# the replication stream: an append carries the write's trace
# --------------------------------------------------------------------------

class TestReplicationStream:
    def test_an_append_carries_the_writes_trace_and_no_other(self):
        """The stream's task outlives the subscribe request that started
        it and takes none of its trace; each append carries the trace
        and the phase of the write that emitted its entries."""
        from fleetflow_tpu.cp.models import Tenant
        from fleetflow_tpu.cp.replication import Replicator
        from fleetflow_tpu.cp.store import Store

        sent = []

        class Conn:
            identity = "sb"

            async def send_event(self, channel, method, payload, **kw):
                sent.append((method, obs_trace.current_trace_id(),
                             obs_trace.wire_span()))

        async def go():
            store = Store()
            repl = Replicator(store, loop=asyncio.get_running_loop())
            with obs.use_trace("5ub5cr1be5ub5cr1"), obs.phase("t.subscribe"):
                repl.attach(Conn(), "sb", 0)
            with obs.use_trace("c0mm17c0mm17c0mm"), obs.phase("t.commit") \
                    as ph:
                store.create("tenants", Tenant(name="traced"))
            await asyncio.sleep(0.05)
            store.create("tenants", Tenant(name="untraced"))
            await asyncio.sleep(0.05)
            return ph

        ph = _run(go(), 10)
        assert [s[0] for s in sent] == ["append", "append"]
        assert sent[0][1:] == ("c0mm17c0mm17c0mm",
                               f"{obs_trace.PROCESS_TOKEN}:{ph.id}")
        assert sent[1][1] == ""
