"""Fleet flight recorder tests: metrics registry, Prometheus exposition,
the daemon's GET /metrics surface, and end-to-end trace correlation.

Three layers:
  - registry semantics (fresh MetricsRegistry instances, no global state):
    get-or-create identity, counter monotonicity, label children,
    histogram buckets, exposition format, JSON snapshot;
  - the live surfaces: GET /metrics over the in-process daemon web server
    (golden-pinned names/types/HELP — the acceptance criterion), token
    auth, the health.metrics channel, the log router's slow-consumer drop
    counter (ISSUE 3 satellite);
  - trace correlation: one CP-routed deploy against a REAL agent produces
    flight-recorder span events sharing one trace_id on the CP side and
    the agent side (the acceptance criterion's second half).
"""

import asyncio
import importlib.util
import json
import math
import pathlib
import urllib.error
import urllib.request

import pytest

# imported for their metric registrations: the golden test pins the FULL
# exposition surface, which includes the solver and agent-monitor families
import fleetflow_tpu.agent.monitor    # noqa: F401
import fleetflow_tpu.chaos.simulate   # noqa: F401  (plan-simulate families)
import fleetflow_tpu.chaos.worldgen   # noqa: F401  (world families)
import fleetflow_tpu.solver.api       # noqa: F401
import fleetflow_tpu.solver.sharded   # noqa: F401  (pod-scale families)
from fleetflow_tpu.agent import Agent, AgentConfig
from fleetflow_tpu.core.loader import load_project_from_root_with_stage
from fleetflow_tpu.cp import ServerConfig, start
from fleetflow_tpu.cp.log_router import LogEntry, LogRouter
from fleetflow_tpu.cp.protocol import ProtocolClient
from fleetflow_tpu.daemon.web import WebServer
from fleetflow_tpu.obs.metrics import REGISTRY, MetricsRegistry
from fleetflow_tpu.obs.trace import read_trace_file
from fleetflow_tpu.runtime import DeployRequest, MockBackend

GOLDEN = pathlib.Path(__file__).parent / "goldens" / "metrics_exposition.txt"

# one source of truth for "what is a valid exposition": the CI gate script
# (scripts/check_metrics_endpoint.py) owns the grammar + golden logic and
# the test suite imports it, so the two can never disagree
_spec = importlib.util.spec_from_file_location(
    "check_metrics_endpoint",
    pathlib.Path(__file__).parent.parent / "scripts"
    / "check_metrics_endpoint.py")
check_metrics_endpoint = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_metrics_endpoint)


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 30))


def mock_backend_factory():
    return MockBackend(auto_pull=True)


async def http_get_text(host, port, path, token=None):
    def fetch():
        req = urllib.request.Request(f"http://{host}:{port}{path}")
        if token:
            req.add_header("Authorization", f"Bearer {token}")
        try:
            with urllib.request.urlopen(req, timeout=5) as resp:
                return (resp.status, resp.read().decode(),
                        resp.headers.get("Content-Type", ""))
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode(), e.headers.get("Content-Type", "")
    return await asyncio.get_running_loop().run_in_executor(None, fetch)


# --------------------------------------------------------------------------
# registry semantics
# --------------------------------------------------------------------------

class TestCounter:
    def test_inc_and_value(self):
        r = MetricsRegistry()
        c = r.counter("x_total", "things")
        c.inc()
        c.inc(2.5)
        assert c.value() == 3.5

    def test_never_decreases(self):
        r = MetricsRegistry()
        c = r.counter("x_total")
        with pytest.raises(ValueError, match="cannot decrease"):
            c.inc(-1)

    def test_labels_make_independent_children(self):
        r = MetricsRegistry()
        c = r.counter("ops_total", labels=("table", "op"))
        c.inc(table="servers", op="put")
        c.inc(3, table="servers", op="del")
        assert c.value(table="servers", op="put") == 1
        assert c.value(table="servers", op="del") == 3
        assert c.value(table="alerts", op="put") == 0

    def test_wrong_labels_raise(self):
        r = MetricsRegistry()
        c = r.counter("ops_total", labels=("table",))
        with pytest.raises(ValueError, match="takes labels"):
            c.inc(nope="x")
        with pytest.raises(ValueError, match="takes labels"):
            c.inc()   # missing the declared label


class TestGauge:
    def test_set_inc_dec(self):
        r = MetricsRegistry()
        g = r.gauge("temp")
        g.set(10)
        g.inc(5)
        g.dec(2)
        assert g.value() == 13

    def test_gauges_can_go_negative(self):
        r = MetricsRegistry()
        g = r.gauge("delta")
        g.dec(4)
        assert g.value() == -4


class TestHistogram:
    def test_observe_buckets_sum_count(self):
        r = MetricsRegistry()
        h = r.histogram("lat_seconds", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        assert h.count() == 3
        assert h.sum() == pytest.approx(5.55)
        text = h.render()
        # cumulative: 1 <= 0.1, 2 <= 1.0, 3 <= +Inf
        assert 'lat_seconds_bucket{le="0.1"} 1' in text
        assert 'lat_seconds_bucket{le="1"} 2' in text
        assert 'lat_seconds_bucket{le="+Inf"} 3' in text
        assert "lat_seconds_count 3" in text

    def test_labeled_histogram(self):
        r = MetricsRegistry()
        h = r.histogram("lat_seconds", labels=("channel",), buckets=(1.0,))
        h.observe(0.5, channel="deploy")
        assert h.count(channel="deploy") == 1
        assert h.count(channel="health") == 0
        assert 'lat_seconds_bucket{channel="deploy",le="1"} 1' in h.render()


class TestRegistry:
    def test_get_or_create_returns_same_instance(self):
        r = MetricsRegistry()
        assert r.counter("a_total") is r.counter("a_total")

    def test_type_mismatch_raises(self):
        r = MetricsRegistry()
        r.counter("a_total")
        with pytest.raises(ValueError, match="already registered"):
            r.gauge("a_total")

    def test_labelset_mismatch_raises(self):
        r = MetricsRegistry()
        r.counter("a_total", labels=("x",))
        with pytest.raises(ValueError, match="already registered"):
            r.counter("a_total", labels=("y",))

    def test_render_has_help_type_and_trailing_newline(self):
        r = MetricsRegistry()
        r.counter("a_total", "does things")
        g = r.gauge("b", "level")
        g.set(2)
        text = r.render()
        assert "# HELP a_total does things" in text
        assert "# TYPE a_total counter" in text
        assert "\nb 2\n" in text or text.endswith("b 2\n")
        # unlabeled metrics expose a zero sample from definition time
        assert "\na_total 0\n" in text

    def test_label_values_escaped(self):
        r = MetricsRegistry()
        c = r.counter("a_total", labels=("msg",))
        c.inc(msg='say "hi"\nnow')
        assert 'msg="say \\"hi\\"\\nnow"' in r.render()

    def test_snapshot_is_json_able(self):
        r = MetricsRegistry()
        r.counter("a_total", "help!", labels=("k",)).inc(k="v")
        h = r.histogram("h_seconds")
        h.observe(0.2)
        snap = json.loads(json.dumps(r.snapshot()))
        assert snap["a_total"]["type"] == "counter"
        assert snap["a_total"]["values"] == [
            {"labels": {"k": "v"}, "value": 1.0}]
        assert snap["h_seconds"]["values"][0]["count"] == 1

    def test_counter_values_flat_map(self):
        r = MetricsRegistry()
        r.counter("a_total", labels=("k",)).inc(2, k="v")
        r.gauge("g").set(9)   # gauges excluded
        vals = r.counter_values()
        assert vals == {'a_total{k="v"}': 2.0}


# --------------------------------------------------------------------------
# live surfaces
# --------------------------------------------------------------------------

class TestMetricsEndpoint:
    def test_scrape_is_valid_and_golden_pinned(self):
        """Acceptance: GET /metrics returns valid Prometheus exposition
        containing solver, deploy, store, log-router, and agent-registry
        metrics, with the name/type/HELP surface pinned by the golden
        (same validator + golden logic as the CI gate script)."""
        async def go():
            handle = await start(ServerConfig(),
                                 backend_factory=mock_backend_factory)
            web = WebServer(handle.state)
            host, port = await web.start("127.0.0.1", 0)
            st, text, ctype = await http_get_text(host, port, "/metrics")
            await web.stop()
            await handle.stop()
            return st, text, ctype

        st, text, ctype = run(go())
        assert st == 200
        assert ctype.startswith("text/plain")
        assert check_metrics_endpoint.validate_format(text) == []
        got = sorted(ln for ln in text.splitlines() if ln.startswith("# "))
        want = [ln for ln in GOLDEN.read_text().splitlines() if ln]
        assert got == want, (
            "exposition surface drifted from the golden — regenerate with "
            "`python scripts/check_metrics_endpoint.py --update` and update "
            "docs/guide/10-observability.md")

    def test_metrics_requires_token_when_auth_enabled(self):
        async def go():
            handle = await start(ServerConfig(auth_kind="token",
                                              auth_secret="s3cret"),
                                 backend_factory=mock_backend_factory)
            web = WebServer(handle.state)
            host, port = await web.start("127.0.0.1", 0)
            st_anon, _, _ = await http_get_text(host, port, "/metrics")
            ro = handle.state.auth.issue("dash@example.com", ["read:health"])
            st_ro, body, _ = await http_get_text(host, port, "/metrics",
                                                 token=ro)
            wrong = handle.state.auth.issue("dns@example.com", ["read:dns"])
            st_wrong, _, _ = await http_get_text(host, port, "/metrics",
                                                 token=wrong)
            await web.stop()
            await handle.stop()
            return st_anon, st_ro, body, st_wrong

        st_anon, st_ro, body, st_wrong = run(go())
        assert st_anon == 401
        assert st_ro == 200 and "fleet_store_ops_total" in body
        assert st_wrong == 403

    def test_health_metrics_channel_and_overview_field(self):
        async def go():
            handle = await start(ServerConfig(),
                                 backend_factory=mock_backend_factory)
            conn, _ = await ProtocolClient.connect(handle.host, handle.port,
                                                   identity="cli")
            snap = (await conn.request("health", "metrics"))["metrics"]
            over = await conn.request("health", "overview")
            await conn.close()
            await handle.stop()
            return snap, over

        snap, over = run(go())
        assert snap["fleet_store_ops_total"]["type"] == "counter"
        # the overview points at the registry rather than embedding it
        assert over["metrics"]["families"] == len(snap)

    def test_request_latency_histogram_counts_channel_calls(self):
        async def go():
            handle = await start(ServerConfig(),
                                 backend_factory=mock_backend_factory)
            conn, _ = await ProtocolClient.connect(handle.host, handle.port,
                                                   identity="cli")
            before = REGISTRY.get(
                "fleet_cp_request_duration_seconds").count(channel="health")
            await conn.request("health", "ping")
            await conn.request("health", "ping")
            after = REGISTRY.get(
                "fleet_cp_request_duration_seconds").count(channel="health")
            await conn.close()
            await handle.stop()
            return before, after

        before, after = run(go())
        assert after == before + 2


class TestLogRouterDrops:
    def test_full_queue_counts_drops_without_blocking(self):
        """ISSUE 3 satellite: slow-consumer drops are counted per
        subscriber and in the aggregate counter, and the publisher never
        blocks on a full bounded queue."""
        async def go():
            router = LogRouter(queue_size=5)
            sid, q = router.subscribe()
            dropped_before = REGISTRY.get(
                "fleet_log_lines_dropped_total").value()
            for i in range(12):   # 12 lines into a 5-deep queue
                delivered = router.publish(
                    LogEntry(topic="logs/n/c", line=f"l{i}"))
                assert delivered == 1   # still delivered: oldest evicted
            sub = router.subscriber(sid)
            assert sub.dropped == 7
            assert (REGISTRY.get("fleet_log_lines_dropped_total").value()
                    == dropped_before + 7)
            assert q.qsize() == 5
            # the survivors are the NEWEST lines (drop-oldest policy)
            assert (await q.get()).line == "l7"
            # a second, fast subscriber is unaffected; the slow one has
            # room again after the get, so no further drop
            sid2, _q2 = router.subscribe()
            router.publish(LogEntry(topic="logs/n/c", line="x"))
            assert router.subscriber(sid2).dropped == 0
            assert router.subscriber(sid).dropped == 7
        run(go())

    def test_unsubscribed_id_has_no_subscriber_record(self):
        router = LogRouter()
        sid, _ = router.subscribe()
        router.unsubscribe(sid)
        assert router.subscriber(sid) is None


# --------------------------------------------------------------------------
# end-to-end trace correlation (acceptance criterion, second half)
# --------------------------------------------------------------------------

class TestTraceCorrelation:
    def test_single_deploy_shares_one_trace_id_cp_and_agent(
            self, project, tmp_path, monkeypatch):
        """One `fleet deploy` against a live CP with a REAL agent: the
        flight recorder must hold CP-side and agent-side span events that
        share one trace_id (carried over the wire in
        DeployRequest.trace_id)."""
        trace_file = tmp_path / "flight.jsonl"
        monkeypatch.setenv("FLEET_TRACE_FILE", str(trace_file))
        root, _ = project
        flow = load_project_from_root_with_stage(str(root), "local")
        flow.stages["local"].servers = ["node-1"]

        async def go():
            handle = await start(ServerConfig(),
                                 backend_factory=mock_backend_factory)
            backend = MockBackend(auto_pull=True)
            cfg = AgentConfig(cp_host=handle.host, cp_port=handle.port,
                              slug="node-1", heartbeat_interval_s=0.05,
                              monitor_interval_s=0.05,
                              capacity={"cpu": 8, "memory": 16384,
                                        "disk": 100000})
            agent = Agent(cfg, backend=backend, sleep=lambda d: None)
            task = asyncio.ensure_future(agent.run())
            while not handle.state.agent_registry.is_connected("node-1"):
                await asyncio.sleep(0.02)
            cli, _ = await ProtocolClient.connect(handle.host, handle.port,
                                                  identity="cli")
            req = DeployRequest(flow=flow, stage_name="local")
            out = await cli.request("deploy", "execute",
                                    {"request": req.to_dict()}, timeout=20)
            stored = handle.state.store.list("deployments")[0].request
            await cli.close()
            agent.stop()
            await asyncio.wait_for(task, 5)
            await handle.stop()
            return out, stored

        out, stored = run(go())
        assert out["deployment"]["status"] == "succeeded"
        # the persisted replay template must NOT capture the trace id: a
        # redeploy replaying it would inherit this operation's trace and
        # `fleet events --trace` would interleave two distinct deploys
        assert "trace_id" not in stored

        events = read_trace_file(str(trace_file))
        cp_spans = [e for e in events if e["logger"] == "fleetflow.cp.deploy"
                    and e["name"] == "deploy.execute"]
        agent_spans = [e for e in events if e["logger"] == "fleetflow.agent"
                       and e["name"] == "agent.deploy"]
        engine_spans = [e for e in events
                        if e["logger"] == "fleetflow.engine"]
        assert cp_spans and agent_spans and engine_spans
        traces = {e["trace"] for e in cp_spans + agent_spans + engine_spans}
        assert len(traces) == 1, f"trace ids diverged: {traces}"
        # the CP span completed (end, not fail), with begin/end paired
        kinds = {e["kind"] for e in cp_spans}
        assert kinds == {"begin", "end"}
        # agent-side engine span is parented under the agent.deploy span
        begin_agent = next(e for e in agent_spans if e["kind"] == "begin")
        begin_engine = next(e for e in engine_spans
                            if e["kind"] == "begin"
                            and e["name"] == "deploy.execute")
        assert begin_engine["parent"] == begin_agent["span"]

    def test_deploy_events_carry_the_trace_id(self, project):
        """Every DeployEvent of a local engine run carries the request's
        trace_id (minted when the caller didn't provide one)."""
        from fleetflow_tpu.runtime import DeployEngine
        root, _ = project
        flow = load_project_from_root_with_stage(str(root), "local")
        engine = DeployEngine(MockBackend(auto_pull=True),
                              sleep=lambda d: None)
        seen = []
        req = DeployRequest(flow=flow, stage_name="local")
        res = engine.execute(req, on_event=seen.append)
        assert res.ok
        assert req.trace_id   # minted by the engine
        assert seen and all(e.trace_id == req.trace_id for e in seen)

    def test_trace_id_survives_request_serialization(self, project):
        root, _ = project
        flow = load_project_from_root_with_stage(str(root), "local")
        req = DeployRequest(flow=flow, stage_name="local", trace_id="abc123")
        back = DeployRequest.from_dict(json.loads(json.dumps(req.to_dict())))
        assert back.trace_id == "abc123"
        # absent stays absent (wire compat with pre-trace payloads)
        req2 = DeployRequest(flow=flow, stage_name="local")
        assert "trace_id" not in req2.to_dict()


# --------------------------------------------------------------------------
# solver acceptance stats (surfaced from anneal_adaptive_states)
# --------------------------------------------------------------------------

class TestSolverMetrics:
    def test_solve_reports_acceptance_and_updates_registry(self):
        from fleetflow_tpu.lower import synthetic_problem
        from fleetflow_tpu.solver import solve
        sweeps_before = REGISTRY.get("fleet_solver_sweeps_total").value()
        solves_before = REGISTRY.get(
            "fleet_solver_solve_duration_seconds").count()
        pt = synthetic_problem(16, 4, seed=0)
        res = solve(pt, chains=2, steps=8)
        assert res.feasible
        assert res.accepted_moves >= 0
        assert 0.0 <= res.acceptance_rate <= 1.0
        assert (REGISTRY.get("fleet_solver_sweeps_total").value()
                == sweeps_before + res.steps)
        assert (REGISTRY.get("fleet_solver_solve_duration_seconds").count()
                == solves_before + 1)
        assert math.isfinite(
            REGISTRY.get("fleet_solver_violations").value())

    def test_mesh_path_reports_acceptance_not_counted(self, monkeypatch):
        """The sharded anneal does not count accepted moves: its result
        says so with -1, and the acceptance counter does not move."""
        from fleetflow_tpu.lower import synthetic_problem
        from fleetflow_tpu.solver import solve
        monkeypatch.setenv("FLEET_SHARDED", "1")
        accepted_before = REGISTRY.get(
            "fleet_solver_proposals_accepted_total").value()
        pt = synthetic_problem(32, 4, seed=1)
        res = solve(pt, steps=8)
        assert res.tempering is not None          # the mesh path ran
        assert res.accepted_moves == -1
        assert res.acceptance_rate == -1.0
        assert (REGISTRY.get("fleet_solver_proposals_accepted_total").value()
                == accepted_before)
