"""Streaming admission tests (cp/admission.py + PlacementService.admit_batch).

Four layers:
  - backpressure: depth/age watermarks shed (structured, retryable) or
    park; nothing is ever silently dropped (the census stays terminal)
  - fairness: deficit round robin — a flooding tenant drains at its
    weight's share while light tenants drain completely
  - the REPLAY property: N seeded random arrival/departure streams
    replayed through micro-solves end bit-identical in committed
    placements to one equivalent batch solve (batching boundaries must
    never leak into placement decisions)
  - the resident delta path: steady-state micro-solves reuse the
    device-resident staging — zero cold restages, zero host transfers,
    proven under jax.transfer_guard("disallow")
"""

from __future__ import annotations

import asyncio
import time

import pytest

from fleetflow_tpu.chaos.faults import FaultSchedule
from fleetflow_tpu.chaos.runner import _Runner
from fleetflow_tpu.cp.admission import (AdmissionConfig,
                                        AdmissionController,
                                        AdmissionRejected)


def _world(services=20, nodes=4, stages=1):
    runner = _Runner(FaultSchedule("admission", 1, [], horizon=0.0),
                     services, nodes, stages, 0)

    async def go():
        runner._bootstrap()
        for st in sorted(runner.world.flow.stages):
            assert await runner._deploy(st)
    asyncio.run(go())
    return runner.world


def _ctrl(world, store=None, **cfg) -> AdmissionController:
    defaults = dict(batch_max=8, quantum=4.0, max_queue=64,
                    shed_age_s=0.0)
    defaults.update(cfg)
    return AdmissionController(world.state.placement,
                               clock=world.clock.now, store=store,
                               config=AdmissionConfig(**defaults))


def _drain(world, ctrl, max_steps=200) -> list[dict]:
    outs = []
    for _ in range(max_steps):
        if not ctrl.has_work():
            break
        world.clock.advance(1.0)
        outs.append(ctrl.step())
    assert not ctrl.has_work(), "drain did not converge"
    return outs


class TestSubmitValidation:
    def test_constrained_arrivals_are_rejected(self):
        w = _world()
        ctrl = _ctrl(w)
        ctrl.attach(w.flow, "app0")
        from fleetflow_tpu.core.model import Port, Service
        for bad, match in [
            (Service(name="x", ports=[Port(host=80, container=80)]),
             "ports"),
            (Service(name="x", depends_on=["svc0000"]), "depends_on"),
            (Service(name="x", replicas=2), "replicas"),
            (Service(name="x", anti_affinity=["x"]), "anti_affinity"),
        ]:
            with pytest.raises(ValueError, match=match):
                ctrl.submit("t0", arrivals=[bad])

    def test_duplicate_and_unknown_names_are_rejected(self):
        w = _world()
        ctrl = _ctrl(w)
        ctrl.attach(w.flow, "app0")
        ctrl.submit("t0", arrivals=[{"name": "a1"}])
        with pytest.raises(ValueError, match="already live or queued"):
            ctrl.submit("t0", arrivals=[{"name": "a1"}])
        with pytest.raises(ValueError, match="no such live"):
            ctrl.submit("t0", departures=["nope"])
        _drain(w, ctrl)
        with pytest.raises(ValueError, match="already live"):
            ctrl.submit("t0", arrivals=[{"name": "a1"}])

    def test_constrained_base_departure_routed_to_deploy_down(self):
        w = _world()
        ctrl = _ctrl(w)
        ctrl.attach(w.flow, "app0")
        # every 20th chaos service carries hard replica anti-affinity
        with pytest.raises(ValueError, match="deploy.down"):
            ctrl.submit("t0", departures=["svc0010"])

    def test_duplicate_departures_rejected(self):
        """A doubled departure would tombstone one row twice (double
        free-list entry -> one row handed to two arrivals): rejected in
        one call AND across calls while the first is still pending."""
        w = _world()
        ctrl = _ctrl(w)
        key = ctrl.attach(w.flow, "app0")
        ctrl.submit("t0", arrivals=[{"name": "a0"}, {"name": "a1"}])
        _drain(w, ctrl)
        with pytest.raises(ValueError, match="already pending"):
            ctrl.submit("t0", departures=["a0", "a0"])
        ctrl.submit("t0", departures=["a0"])
        with pytest.raises(ValueError, match="already pending"):
            ctrl.submit("t1", departures=["a0"])
        _drain(w, ctrl)
        assert ctrl.live_names(key) == ["a1"]
        # the freed row is handed out exactly once
        st = ctrl.status()["streams"][key]
        assert (st["tombstones"], st["free_rows"]) == (1, 1)

    @pytest.mark.parametrize("before, arrivals, departures, want", [
        # an arrival duplicated within one wave
        ("", ["a1", "a1"], [], "duplicate arrival 'a1'"),
        # a departure of an arrival in the same submit is queued
        ("", ["a1"], ["a1"], [("arrival", "a1"), ("departure", "a1")]),
        # a departure doubled within one submit
        ("live", [], ["a0", "a0"], "departure 'a0' is already pending"),
        # a departure of a parked arrival is accepted
        ("parked", [], ["whale"], [("departure", "whale")]),
        # one unknown departure refuses the whole submit
        ("live", ["b0"], ["a0", "nope"], "departure 'nope': no such live"),
        # already queued AND duplicated: the first refusal wins
        ("queued", ["a1", "a1"], [], "arrival 'a1' already live or queued"),
    ], ids=["dup-arrival", "depart-queued-in-submit", "doubled-departure",
            "depart-parked", "unknown-departure", "queued-beats-duplicate"])
    def test_wave_name_checks(self, before, arrivals, departures, want):
        """Each refusal that a wave's own names decide, with its message
        and precedence; an accepted submit lists arrivals then
        departures in order, a refused one enqueues nothing."""
        w = _world()
        ctrl = _ctrl(w)
        ctrl.attach(w.flow, "app0")
        if before == "live":
            ctrl.submit("t0", arrivals=[{"name": "a0"}])
            _drain(w, ctrl)
        elif before == "queued":
            ctrl.submit("t0", arrivals=[{"name": "a1"}])
        elif before == "parked":
            ctrl.submit("t0", arrivals=[{"name": "whale", "cpu": 1e6,
                                         "memory": 1e9}])
            _drain(w, ctrl)
            assert ctrl.pressure()["parked"] == 1
        n_requests = len(ctrl.requests)
        depth = ctrl.pressure()["queue_depth"]
        call = dict(arrivals=[{"name": n} for n in arrivals],
                    departures=departures)
        if isinstance(want, str):
            with pytest.raises(ValueError, match=want):
                ctrl.submit("t0", **call)
            assert len(ctrl.requests) == n_requests
            assert ctrl.pressure()["queue_depth"] == depth
        else:
            out = ctrl.submit("t0", **call)
            assert [(ctrl.requests[i].kind, ctrl.requests[i].name)
                    for i in out["accepted"]] == want

    def test_wave_validation_is_linear(self):
        """20,000 arrivals and their 20,000 departures in one submit:
        every name is checked against a set, so it takes well under a
        second on a CPU, where a check that scans the wave per name
        takes tens of seconds."""
        w = _world()
        ctrl = _ctrl(w, max_queue=50_000)
        ctrl.attach(w.flow, "app0")
        names = [f"p{i:05d}" for i in range(20_000)]
        t0 = time.perf_counter()
        out = ctrl.submit("t0", arrivals=[{"name": n} for n in names],
                          departures=names)
        took = time.perf_counter() - t0
        assert len(out["accepted"]) == 40_000
        assert ctrl.pressure()["queue_depth"] == 40_000
        assert took < 5.0, f"submit of 40,000 requests took {took:.2f} s"


class TestBackpressure:
    def test_depth_watermark_sheds_with_retryable_error(self):
        w = _world()
        ctrl = _ctrl(w, max_queue=4)
        ctrl.attach(w.flow, "app0")
        ctrl.submit("t0", arrivals=[{"name": f"a{i}"} for i in range(4)])
        with pytest.raises(AdmissionRejected) as ei:
            ctrl.submit("t0", arrivals=[{"name": "a9"}])
        assert ei.value.retryable
        assert ei.value.reason == "queue-depth"
        assert ei.value.retry_after_s > 0
        assert "retry_after_s" in str(ei.value)
        # the queue is BOUNDED: the shed submit left depth untouched
        assert ctrl.pressure()["queue_depth"] == 4
        _drain(w, ctrl)
        # nothing silently dropped: every accepted request is terminal
        from fleetflow_tpu.cp.admission import AdmissionRequest
        assert all(r.state in AdmissionRequest.TERMINAL
                   for r in ctrl.requests.values())

    def test_park_on_full_defers_and_retries(self):
        w = _world()
        ctrl = _ctrl(w, max_queue=2, on_full="park")
        key = ctrl.attach(w.flow, "app0")
        ctrl.submit("t0", arrivals=[{"name": "a0"}, {"name": "a1"}])
        out = ctrl.submit("t0", arrivals=[{"name": "a2"}])
        assert out.get("parked") == 1
        assert ctrl.stats["parked"] == 1
        _drain(w, ctrl)
        assert ctrl.live_names(key) == ["a0", "a1"]
        # a departure frees capacity -> the capacity epoch bumps -> the
        # parked arrival re-queues and lands
        ctrl.submit("t0", departures=["a0"])
        _drain(w, ctrl)
        assert ctrl.stats["unparked"] == 1
        assert ctrl.live_names(key) == ["a1", "a2"]

    def test_age_watermark_sheds_stale_arrivals(self):
        w = _world()
        ctrl = _ctrl(w, shed_age_s=5.0, batch_max=1)
        ctrl.attach(w.flow, "app0")
        ctrl.submit("t0", arrivals=[{"name": f"a{i}"} for i in range(3)])
        w.clock.advance(10.0)           # everything out-ages the mark
        out = ctrl.step()
        assert out["batch"] == 0
        assert ctrl.stats["sheds"] == 3
        assert all(r.state == "shed" for r in ctrl.requests.values())

    def test_pure_departures_bypass_the_depth_bound(self):
        """Departures only ever FREE capacity: a full queue must accept
        them, or transient backpressure becomes a standing stall."""
        w = _world()
        ctrl = _ctrl(w, max_queue=3)
        key = ctrl.attach(w.flow, "app0")
        ctrl.submit("t0", arrivals=[{"name": f"a{i}"} for i in range(3)])
        _drain(w, ctrl)
        ctrl.submit("t0", arrivals=[{"name": f"b{i}"} for i in range(3)])
        with pytest.raises(AdmissionRejected):
            ctrl.submit("t0", arrivals=[{"name": "b9"}])
        out = ctrl.submit("t0", departures=["a0", "a1"])   # still accepted
        assert len(out["accepted"]) == 2
        _drain(w, ctrl)
        assert sorted(ctrl.live_names(key)) == ["a2", "b0", "b1", "b2"]

    def test_infeasible_arrivals_park_not_lost(self):
        w = _world(services=6, nodes=2)
        ctrl = _ctrl(w)
        key = ctrl.attach(w.flow, "app0")
        # an arrival no node can hold: parked, counted, retryable
        ctrl.submit("t0", arrivals=[{"name": "whale", "cpu": 1e6,
                                     "memory": 1e9}])
        w.clock.advance(1.0)
        out = ctrl.step()
        assert out["parked"] == ["whale"]
        assert ctrl.stats["parked"] == 1
        assert ctrl.pressure()["parked"] == 1
        assert "whale" not in ctrl.live_names(key)
        req = next(r for r in ctrl.requests.values() if r.name == "whale")
        assert req.state == "parked"
        # a later departure of it cancels the parked arrival cleanly
        ctrl.submit("t0", departures=["whale"])
        _drain(w, ctrl)
        assert req.state == "cancelled"


class TestFairness:
    def test_drr_flood_cannot_starve_light_tenants(self):
        w = _world()
        ctrl = _ctrl(w, batch_max=8, quantum=4.0, max_queue=512)
        ctrl.attach(w.flow, "app0")
        ctrl.submit("flood", arrivals=[{"name": f"f{i}"}
                                       for i in range(40)])
        ctrl.submit("calm", arrivals=[{"name": "c0"}, {"name": "c1"}])
        w.clock.advance(1.0)
        out = ctrl.step()
        # the light tenant drains COMPLETELY in the first batch even
        # though the flood was submitted first
        assert {"c0", "c1"} <= set(out["placed"])
        assert len([n for n in out["placed"] if n.startswith("f")]) <= 6
        _drain(w, ctrl)
        waits = ctrl.wait_samples
        assert max(waits["calm"]) <= min(max(waits["flood"]), 10.0)

    def test_weights_scale_the_share(self):
        w = _world()
        ctrl = _ctrl(w, batch_max=9, quantum=3.0, max_queue=512,
                     tenant_weights={"heavy": 2.0, "light": 1.0})
        ctrl.attach(w.flow, "app0")
        ctrl.submit("heavy", arrivals=[{"name": f"h{i}"}
                                       for i in range(20)])
        ctrl.submit("light", arrivals=[{"name": f"l{i}"}
                                       for i in range(20)])
        w.clock.advance(1.0)
        out = ctrl.step()
        h = len([n for n in out["placed"] if n.startswith("h")])
        li = len([n for n in out["placed"] if n.startswith("l")])
        assert h == 2 * li, (h, li)     # quantum*weight: 6 vs 3


class TestReplayProperty:
    """N seeded random arrival/departure streams replayed through
    micro-solves end BIT-IDENTICAL in committed placements to one
    equivalent batch solve. This is the determinism contract that makes
    micro-batching safe: chunking boundaries (and tombstone row reuse)
    must never leak into placement decisions."""

    def _gen_stream(self, seed: int, n: int):
        import random
        rng = random.Random(seed)
        events = []          # ("arrival", spec) | ("departure", name)
        live = []
        for i in range(n):
            if live and rng.random() < 0.35:
                name = live.pop(rng.randrange(len(live)))
                events.append(("departure", name))
            else:
                # distinct demand per arrival: placement order must be
                # content-determined, not row-index-determined
                spec = {"name": f"s{seed}-{i:03d}", "cpu": 0.01,
                        "memory": 16.0 + i * 0.125}
                events.append(("arrival", spec))
                live.append(spec["name"])
        return events

    def _replay(self, seed: int, batch_max: int) -> tuple[dict, list]:
        w = _world(services=16, nodes=4)
        ctrl = _ctrl(w, batch_max=batch_max, max_queue=10_000)
        key = ctrl.attach(w.flow, "app0")
        for kind, payload in self._gen_stream(seed, 40):
            if kind == "arrival":
                ctrl.submit("t0", arrivals=[payload])
            else:
                ctrl.submit("t0", departures=[payload])
        _drain(w, ctrl)
        committed = w.state.placement._committed[key]
        return dict(committed.assignment), ctrl.live_names(key)

    @pytest.mark.parametrize("seed", range(6))
    def test_micro_solves_equal_one_batch_solve(self, seed):
        micro_asg, micro_live = self._replay(seed, batch_max=4)
        batch_asg, batch_live = self._replay(seed, batch_max=10_000)
        assert micro_live == batch_live
        assert micro_asg == batch_asg


class TestResidentDeltaPath:
    def test_steady_state_zero_cold_zero_host_transfers(self):
        """After warm-up, every admission micro-solve (arrivals appended
        into phantom rows, departures tombstoned, rows reused) rides the
        donated on-device delta merge — no cold restaging, no host
        transfer of problem tensors — proven under
        jax.transfer_guard('disallow')."""
        import os

        from fleetflow_tpu.cp.placement import PlacementService
        from fleetflow_tpu.obs.metrics import REGISTRY
        w = _world(services=24, nodes=6)
        pl = PlacementService(w.state.store, use_tpu=True)
        ctrl = AdmissionController(
            pl, clock=w.clock.now,
            config=AdmissionConfig(batch_max=16))
        key = ctrl.attach(w.flow, "app0")
        reuse = REGISTRY.get("fleet_solver_resident_reuse_total")
        xfer = REGISTRY.get("fleet_solver_host_transfers_total")
        # warm-up: arrival append, departure tombstone, row reuse
        ctrl.submit("t0", arrivals=[{"name": f"w{i}"} for i in range(3)])
        w.clock.advance(1.0); ctrl.step()
        ctrl.submit("t0", departures=["w0"])
        w.clock.advance(1.0); ctrl.step()
        ctrl.submit("t0", arrivals=[{"name": "w3"}])
        w.clock.advance(1.0); ctrl.step()
        cold0, xfer0 = reuse.value(outcome="cold"), xfer.value()
        prev = os.environ.get("FLEET_TRANSFER_GUARD")
        os.environ["FLEET_TRANSFER_GUARD"] = "disallow"
        try:
            for i in range(3):
                ctrl.submit("t0", arrivals=[{"name": f"s{i}"}],
                            departures=[f"w{i + 1}"])
                w.clock.advance(1.0)
                out = ctrl.step()
                assert out["violations"] == 0
                assert out["placed"] == [f"s{i}"]
        finally:
            if prev is None:
                os.environ.pop("FLEET_TRANSFER_GUARD", None)
            else:
                os.environ["FLEET_TRANSFER_GUARD"] = prev
        assert reuse.value(outcome="cold") == cold0
        assert xfer.value() == xfer0
        assert sorted(ctrl.live_names(key)) == ["s0", "s1", "s2"]

    def test_churn_resolve_carries_tombstones_through_resync(self):
        """placement.node_events re-solves a streaming stage by reusing
        its rows: the controller's resync must CARRY the tombstone book
        over, or departed services reappear in the committed view and
        their rows leak forever."""
        w = _world(services=20, nodes=4)
        ctrl = _ctrl(w)
        key = ctrl.attach(w.flow, "app0")
        ctrl.submit("t0", arrivals=[{"name": f"a{i}"} for i in range(4)])
        _drain(w, ctrl)
        ctrl.submit("t0", departures=["a0", "a1"])
        _drain(w, ctrl)
        # node churn: kill + revive a node some service sits on — the
        # placement service replaces the retained pt object
        victim = sorted(set(
            w.state.placement.snapshot()[key]["assignment"].values()))[0]
        w.state.placement.node_events([(victim, False)])
        w.state.placement.node_events([(victim, True)])
        rows_before = ctrl.status()["streams"][key]["rows"]
        ctrl.submit("t0", arrivals=[{"name": "fresh"}])
        _drain(w, ctrl)
        st = ctrl.status()["streams"][key]
        snap = w.state.placement.snapshot()[key]
        # departed services stay masked, and the fresh arrival REUSED a
        # carried free row instead of growing the problem
        assert "a0" not in snap["assignment"]
        assert "a1" not in snap["assignment"]
        assert "fresh" in snap["assignment"]
        assert st["rows"] == rows_before
        assert st["tombstones"] == 1 and st["free_rows"] == 1

    def test_compaction_on_tier_crossing(self):
        """Growth that would cross the padded shape tier while tombstones
        exist compacts first (one counted restage) instead of dragging
        dead rows into a bigger executable forever."""
        w = _world(services=20, nodes=4)
        ctrl = _ctrl(w, batch_max=128, max_queue=512)
        key = ctrl.attach(w.flow, "app0")
        # fill toward the 64-row tier (chaos flow lowers ~21 rows)
        ctrl.submit("t0", arrivals=[{"name": f"a{i}"} for i in range(40)])
        _drain(w, ctrl)
        ctrl.submit("t0", departures=[f"a{i}" for i in range(10)])
        _drain(w, ctrl)
        assert ctrl.status()["streams"][key]["tombstones"] == 10
        before = ctrl.stats["compactions"]
        ctrl.submit("t0", arrivals=[{"name": f"b{i}"} for i in range(15)])
        _drain(w, ctrl)
        assert ctrl.stats["compactions"] == before + 1
        assert ctrl.status()["streams"][key]["tombstones"] == 0
        assert set(ctrl.live_names(key)) == (
            {f"a{i}" for i in range(10, 40)} | {f"b{i}" for i in range(15)})


class TestStatusSurface:
    def test_status_shape(self):
        w = _world()
        ctrl = _ctrl(w)
        key = ctrl.attach(w.flow, "app0")
        ctrl.submit("t0", arrivals=[{"name": "a0"}])
        st = ctrl.status()
        assert st["enabled"] and st["queue_depth"] == 1
        assert key in st["streams"]
        assert st["tenants"]["t0"]["queued"] == 1
        assert st["config"]["batch_max"] == 8
        _drain(w, ctrl)
        st = ctrl.status()
        assert st["queue_depth"] == 0
        assert st["tenants"]["t0"]["wait_p50_s"] is not None
        assert st["pressure"]["sustained"] is False
        # the micro-solve tail is a first-class status number (ISSUE 14):
        # a drained batch leaves p50/p99 samples behind
        assert st["solve_ms_p50"] is not None
        assert st["solve_ms_p99"] is not None
        assert st["solve_ms_p99"] >= st["solve_ms_p50"] > 0


class TestTenantQuota:
    """Hard per-tenant caps (PR 16): overflow PARKS with reason="quota"
    (accepted, journaled, never shed), quota parks stay out of the
    pressure/SLO surfaces, and each departure requeues the oldest park
    exactly up to the cap."""

    def _capped(self, w, store=None, cap=2):
        return _ctrl(w, store=store, tenant_caps={"acme": cap})

    def test_overflow_parks_not_sheds(self):
        w = _world()
        ctrl = self._capped(w)
        ctrl.attach(w.flow, "app0")
        res = ctrl.submit("acme", arrivals=[{"name": f"q{i}", "cpu": 0.05,
                                             "memory": 8.0}
                                            for i in range(4)])
        assert res.get("quota_parked") == 2
        assert not res.get("shed")
        st = ctrl.status()
        assert st["parked_quota"] == 2
        assert st["tenants"]["acme"]["cap"] == 2
        assert st["tenants"]["acme"]["usage"] == 4   # live+queued+parked
        _drain(w, ctrl)
        st = ctrl.status()
        assert st["tenants"]["acme"]["live"] == 2    # never over the cap
        assert st["parked_quota"] == 2               # overflow still safe

    def test_quota_parks_excluded_from_pressure(self):
        """Capacity cannot be provisioned around a policy cap: with only
        quota parks outstanding the autoscaler signal must read drained."""
        w = _world()
        ctrl = self._capped(w)
        ctrl.attach(w.flow, "app0")
        ctrl.submit("acme", arrivals=[{"name": f"q{i}", "cpu": 0.05,
                                       "memory": 8.0} for i in range(4)])
        _drain(w, ctrl)
        p = ctrl.pressure()
        assert p["parked_quota"] == 2
        assert p["drained"] is True

    def test_departures_requeue_parks_up_to_cap(self):
        w = _world()
        ctrl = self._capped(w)
        key = ctrl.attach(w.flow, "app0")
        ctrl.submit("acme", arrivals=[{"name": f"q{i}", "cpu": 0.05,
                                       "memory": 8.0} for i in range(4)])
        _drain(w, ctrl)
        ctrl.submit("acme", departures=["q0", "q1"])
        _drain(w, ctrl)
        st = ctrl.status()
        assert st["tenants"]["acme"]["live"] == 2
        assert st["parked_quota"] == 0
        assert sorted(ctrl.live_names(key))[-2:] == ["q2", "q3"]

    def test_quota_parks_exempt_from_age_shed(self):
        """A quota park's age is the wait the controller itself imposed
        when it ACCEPTED the arrival — the age-shed watermark must not
        turn that acceptance into a retroactive shed on requeue."""
        w = _world()
        ctrl = _ctrl(w, shed_age_s=2.0, tenant_caps={"acme": 1})
        key = ctrl.attach(w.flow, "app0")
        ctrl.submit("acme", arrivals=[{"name": "q0", "cpu": 0.05,
                                       "memory": 8.0},
                                      {"name": "q1", "cpu": 0.05,
                                       "memory": 8.0}])
        _drain(w, ctrl)
        w.clock.advance(30.0)              # far past the shed watermark
        ctrl.submit("acme", departures=["q0"])
        _drain(w, ctrl)
        st = ctrl.status()
        assert ctrl.stats["sheds"] == 0
        assert st["parked_quota"] == 0
        assert "q1" in ctrl.live_names(key)


class TestParkedJournal:
    """Parked arrivals are journaled into the store's admission_parked
    table (PR 16): rows persist on park, clear on requeue/terminal, and
    a rebuilt controller on the same store — the failover path —
    restores the parked set before serving."""

    def _capped(self, w, store, cap=2):
        return _ctrl(w, store=store, tenant_caps={"acme": cap})

    def test_journal_rows_track_park_lifecycle(self):
        from fleetflow_tpu.cp.store import Store
        w = _world()
        store = Store.connect_memory()
        ctrl = self._capped(w, store)
        ctrl.attach(w.flow, "app0")
        ctrl.submit("acme", arrivals=[{"name": f"q{i}", "cpu": 0.05,
                                       "memory": 8.0} for i in range(4)])
        assert len(store.list("admission_parked")) == 2
        _drain(w, ctrl)
        ctrl.submit("acme", departures=["q0", "q1"])
        _drain(w, ctrl)
        # requeued-and-placed parks must delete their journal rows
        assert len(store.list("admission_parked")) == 0

    def test_rebuilt_controller_restores_parked_set(self):
        from fleetflow_tpu.cp.store import Store
        w = _world()
        store = Store.connect_memory()
        ctrl = self._capped(w, store)
        key = ctrl.attach(w.flow, "app0")
        ctrl.submit("acme", arrivals=[{"name": f"q{i}", "cpu": 0.05,
                                       "memory": 8.0} for i in range(4)])
        _drain(w, ctrl)
        assert ctrl.status()["parked_quota"] == 2

        # the failover: a NEW controller over the same store (standby
        # promotion rebuilds admission from the replicated journal)
        ctrl2 = self._capped(w, store)
        ctrl2.attach(w.flow, "app0")
        st2 = ctrl2.status()
        assert st2["stats"]["restored"] == 2
        assert st2["parked_quota"] == 2

        # id/seq counters advanced past the restored rows: new submits
        # must not collide with restored request ids
        r3 = ctrl2.submit("beta", arrivals=[{"name": "b1", "cpu": 0.05,
                                             "memory": 8.0}])
        assert len(r3["accepted"]) == 1

        # departures on the RESTORED controller open headroom: the
        # restored parks place — the journaled work survived the kill
        ctrl2.submit("acme", departures=["q0", "q1"])
        _drain(w, ctrl2)
        st2 = ctrl2.status()
        assert st2["parked_quota"] == 0
        assert len(store.list("admission_parked")) == 0
        live = ctrl2.live_names(key)
        assert "q2" in live and "q3" in live
