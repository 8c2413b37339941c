"""A churn reply that carries what moved (PR 35).

`placement.node_events` and `placement.node_event` answer in one of
`cp.handlers.NODE_EVENTS_REPLY_FORMS`, by the request's "reply": each
re-solved stage's whole assignment (the default, as before), or
`{"stage", "feasible", "rows", "moved": {row: server}}` — exactly the rows
whose server differs from the placement the burst started from.

The system is compared with the benchmark's plain reference
(benchmarks/reference_churn.py, which imports nothing of the program) on
seeded sequences of kills and revives over a live CP — a real server, a
real client connection — at a size the CPU solves in no time: on the one
device, and through the mesh-sharded annealer the pod-scale cell runs
(`FLEET_SHARDED=1` on the suite's 8 virtual devices: 2 tempering lanes x 4
service shards).
"""

from __future__ import annotations

import asyncio
import json
import time
from collections import Counter

import jax
import jax.monitoring
import pytest

from benchmarks import checker, generators
from benchmarks import reference_churn as ref
from fleetflow_tpu.cp import handlers, protocol
from fleetflow_tpu.cp.models import ServerCapacity
from fleetflow_tpu.cp.protocol import ProtocolClient, RpcError
from fleetflow_tpu.cp.server import ServerConfig, start
from fleetflow_tpu.obs import trace as obs_trace
from fleetflow_tpu.obs.metrics import REGISTRY

SERVICES, NODES, STAGE = 240, 24, "app0"
MAX_DEAD = 2
STEPS = 5          # a kill, a kill, then three of kill + revive
SEEDS = [11, 3_000_000_019]


def _run(coro, timeout=240):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def _counter(name: str, **labels) -> float:
    return REGISTRY.get(name).value(**labels)


class _Compiles:
    """Backend compiles while it is open (jax.monitoring)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __enter__(self):
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event: str, _secs: float, **_kw) -> None:
        self.events += event == self.EVENT

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


class _Fleet:
    """A live CP with the generated stage solved and committed on it, one
    client connection, and the client's own copy of the assignment."""

    @classmethod
    async def start(cls, seed: int, services=SERVICES, nodes=NODES):
        self = cls()
        flow, model = generators.live_stage(services, nodes, seed, STAGE)
        self.model = checker.Model(**model)
        self.key = f"{flow.name}/{STAGE}"
        self.handle = await start(ServerConfig(use_tpu_solver=True))
        self.svc = self.handle.state.placement
        store = self.handle.state.store
        for slug, cap in model["servers"].items():
            rec = store.register_server(slug, tenant="default", hostname=slug)
            store.update("servers", rec.id, status="online",
                         capacity=ServerCapacity(**cap))
        placement, rid = self.svc.solve_stage(flow, STAGE)
        assert placement.feasible and self.svc.commit(rid)
        self.assignment = dict(placement.assignment)
        self.dead: list[str] = []
        self.conn, self._task = await ProtocolClient.connect(
            self.handle.host, self.handle.port, identity="test-client")
        return self

    async def stop(self) -> None:
        await self.conn.close()
        self._task.cancel()
        await self.handle.stop()

    def burst(self) -> list[dict]:
        """nc's traffic: kill the busiest live server; once MAX_DEAD are
        down, revive the one dead longest."""
        loads = Counter(n for n in self.assignment.values()
                        if n not in self.dead)
        victim = max(sorted(loads), key=loads.__getitem__)
        events = [{"slug": victim, "online": False}]
        if len(self.dead) >= MAX_DEAD:
            events.append({"slug": self.dead.pop(0), "online": True})
        self.dead.append(victim)
        return events

    async def ask(self, events, **extra) -> dict:
        return await self.conn.request(
            "placement", "node_events", {"events": events, **extra},
            timeout=120)

    def committed(self) -> dict:
        rec = self.handle.state.store.find_one(
            "placements", lambda p: p.stage_key == self.key)
        return dict(rec.assignment)

    async def moved_step(self) -> dict:
        """One burst asked with "moved", held to the reference; returns
        the stage's entry of the reply."""
        events = self.burst()
        before = dict(self.assignment)
        reply = await self.ask(events, reply="moved")
        (entry,) = reply["rescheduled"]
        assert set(entry) == {"stage", "feasible", "rows", "moved"}
        assert entry["stage"] == self.key and entry["feasible"]
        found = ref.check_moved(before, entry["moved"], self.dead,
                                entry["rows"])
        assert found["total"] == 0, found
        self.assignment = ref.apply_moved(before, entry["moved"])
        _pt, retained = self.svc.retained(self.key)
        assert self.assignment == retained.assignment
        assert entry["moved"] == ref.expected_moved(before,
                                                    retained.assignment)
        assert self.svc.commit_retained(self.key)
        assert self.assignment == self.committed()
        faults = checker.check(self.model, self.assignment,
                               offline=self.dead)
        assert faults["total"] == 0, faults
        return entry


# -- (a) the moved reply against the reference, one device ------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_moved_reply_is_the_difference(seed):
    async def go():
        fleet = await _Fleet.start(seed)
        try:
            carried0 = _counter("fleet_placement_reply_rows_total",
                                form="moved")
            t0, carried = time.perf_counter(), 0
            for _ in range(STEPS):
                entry = await fleet.moved_step()
                assert 0 < len(entry["moved"]) < entry["rows"] / 2
                assert entry["rows"] == len(fleet.model.rows)
                carried += len(entry["moved"])
            assert _counter("fleet_placement_reply_rows_total",
                            form="moved") == carried0 + carried
            diffs = [s for s in obs_trace.spans_between(
                t0, time.perf_counter())
                if s[0] == "cp.node_events.diff"]
            assert len(diffs) == STEPS
        finally:
            await fleet.stop()
    _run(go())


def test_node_event_takes_the_form_too():
    async def go():
        fleet = await _Fleet.start(5)
        try:
            victim = fleet.burst()[0]["slug"]
            reply = await fleet.conn.request(
                "placement", "node_event",
                {"slug": victim, "online": False, "reply": "moved"})
            (entry,) = reply["rescheduled"]
            assert ref.check_moved(fleet.assignment, entry["moved"],
                                   [victim], entry["rows"])["total"] == 0
            assert set(entry["moved"]) == {
                r for r, n in fleet.assignment.items() if n == victim}
            with pytest.raises(RpcError, match="unknown reply form"):
                await fleet.ask([{"slug": victim, "online": True}],
                                reply="rows")
        finally:
            await fleet.stop()
    _run(go())


# -- (b) the default reply is what it was ------------------------------------

def test_default_reply_is_unchanged():
    """One frame of nc's shape: no "reply" in the request, and the
    response is `{"rescheduled": [{"stage", "assignment", "feasible"}]}`
    with the whole assignment, keys in that order, byte for byte what the
    handler built before it had forms."""
    async def go():
        fleet = await _Fleet.start(7)
        try:
            sent: list[bytes] = []
            encode = protocol.encode_frame

            def spy(msg):
                frame = encode(msg)
                if msg.get("type") == "response":
                    sent.append(frame)
                return frame

            protocol.encode_frame = spy
            try:
                carried0 = _counter("fleet_placement_reply_rows_total",
                                    form="assignment")
                reply = await fleet.ask(fleet.burst())
            finally:
                protocol.encode_frame = encode
            _pt, retained = fleet.svc.retained(fleet.key)
            golden = {"type": "response", "id": 1, "payload": {
                "rescheduled": [{"stage": fleet.key,
                                 "assignment": retained.assignment,
                                 "feasible": True}]}}
            body = json.dumps(golden, separators=(",", ":")).encode()
            assert sent == [len(body).to_bytes(4, "big") + body]
            assert reply == golden["payload"]
            assert _counter("fleet_placement_reply_rows_total",
                            form="assignment") == carried0 + len(
                                fleet.model.rows)
        finally:
            await fleet.stop()
    _run(go())


# -- (c) mutants of a correct reply, each caught -----------------------------

BEFORE = {"a": "n0", "b": "n0", "c": "n1", "d": "n2", "e": "n2"}
CORRECT = {"a": "n1", "b": "n2"}          # n0 died

MUTANTS = {
    "displaced_row_dropped": ({"a": "n1"}, 5, "left_on_offline"),
    "row_left_on_a_dead_server": ({"a": "n1", "b": "n0"}, 5,
                                  "left_on_offline"),
    "row_moved_onto_a_dead_server": ({"a": "n1", "b": "n2", "c": "n0"}, 5,
                                     "left_on_offline"),
    "needless_entry": ({"a": "n1", "b": "n2", "d": "n2"}, 5, "needless"),
    "unknown_row": ({"a": "n1", "b": "n2", "zz": "n1"}, 5, "unknown_row"),
    "wrong_row_count": (CORRECT, 6, "row_count"),
}


def test_reference_accepts_the_correct_reply():
    assert ref.check_moved(BEFORE, CORRECT, ["n0"], 5)["total"] == 0
    after = ref.apply_moved(BEFORE, CORRECT)
    assert after == {"a": "n1", "b": "n2", "c": "n1", "d": "n2", "e": "n2"}
    assert ref.expected_moved(BEFORE, after) == CORRECT
    assert ref.apply_moved(BEFORE, {}) == BEFORE and BEFORE["a"] == "n0"


@pytest.mark.parametrize("mutant", sorted(MUTANTS))
def test_reference_catches_a_mutant(mutant):
    moved, rows, kind = MUTANTS[mutant]
    found = ref.check_moved(BEFORE, moved, ["n0"], rows)
    assert found[kind] >= 1 and found["total"] >= 1, found


def test_reference_imports_nothing_of_the_program():
    with open(ref.__file__, encoding="utf-8") as f:
        source = f.read()
    assert "fleetflow_tpu" not in source.split('"""', 2)[2]
    assert "import numpy" not in source and "import jax" not in source


# -- (d) the mesh under the CP -----------------------------------------------

def test_mesh_under_the_cp(monkeypatch):
    """The same sequence through the mesh-sharded annealer, as the
    pod-scale cell drives it: every op a resident delta on the mesh, no
    fallback, no host transfer under the armed guard, nothing compiled
    after the second op, 0 faults."""
    if len(jax.devices()) < 8:
        pytest.skip("needs the suite's 8 virtual devices")
    monkeypatch.setenv("FLEET_SHARDED", "1")
    monkeypatch.setenv("FLEET_TRANSFER_GUARD", "disallow")

    async def go():
        fleet = await _Fleet.start(13, services=160, nodes=16)
        try:
            for step in range(STEPS):
                delta0 = _counter("fleet_solver_sharded_solves_total",
                                  outcome="delta")
                fallbacks0 = _counter("fleet_placement_churn_fallbacks_total")
                transfers0 = _counter("fleet_solver_host_transfers_total")
                reuse0 = _counter("fleet_solver_resident_reuse_total",
                                  outcome="delta")
                with _Compiles() as compiles:
                    await fleet.moved_step()
                assert _counter("fleet_solver_sharded_solves_total",
                                outcome="delta") == delta0 + 1
                assert _counter("fleet_solver_resident_reuse_total",
                                outcome="delta") == reuse0 + 1
                assert _counter(
                    "fleet_placement_churn_fallbacks_total") == fallbacks0
                assert _counter(
                    "fleet_solver_host_transfers_total") == transfers0
                if step >= 2:
                    assert compiles.events == 0, (step, compiles.events)
        finally:
            await fleet.stop()
    _run(go())


# -- (e) a reply too large for a frame ---------------------------------------

@pytest.mark.parametrize("form, other", [("assignment", "moved"),
                                         ("moved", "assignment")])
def test_over_long_reply_is_an_error_naming_the_other_form(
        monkeypatch, form, other):
    """Neither a dropped connection nor the request's timeout: the client
    hears at once, and is told the other form."""
    assert handlers.NODE_EVENTS_REPLY_FORMS == ("assignment", "moved")

    async def go():
        # 30 rows a server: a kill moves more than 600 bytes of them
        fleet = await _Fleet.start(17, nodes=8)
        try:
            events = fleet.burst()
            monkeypatch.setattr(protocol, "MAX_FRAME", 600)
            with pytest.raises(RpcError) as err:
                await asyncio.wait_for(fleet.ask(events, reply=form), 20)
            text = str(err.value)
            assert "frame too large" in text
            assert f'"reply": "{other}"' in text
            assert "placement.node_events" in text
            # the connection is alive and the burst was applied
            monkeypatch.setattr(protocol, "MAX_FRAME", 1 << 20)
            reply = await fleet.conn.request("placement", "reservations")
            assert isinstance(reply, dict)
            _pt, retained = fleet.svc.retained(fleet.key)
            assert not set(fleet.dead) & set(retained.assignment.values())
        finally:
            await fleet.stop()
    _run(go())


# -- (f) the mesh's parts can be read from a trace ---------------------------

def test_named_scopes_are_in_the_lowered_anneal():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    from fleetflow_tpu.lower.tensors import synthetic_problem
    from fleetflow_tpu.solver import sharded

    mesh = sharded.tempering_mesh(2, 2, devices=jax.devices()[:4])
    rp = sharded.ShardedResident(synthetic_problem(40, 8, seed=3), mesh=mesh)
    text = sharded.anneal_sharded.lower(
        rp.prob, jax.numpy.zeros((rp.prob.S,), jax.numpy.int32),
        jax.random.PRNGKey(0), steps=8, mesh=mesh, block=4,
        return_stats=True).as_text(debug_info=True)
    for part in sharded.SCOPES:
        assert sharded.SCOPE + part in text, part
