#!/usr/bin/env python
"""CI gate: boot the daemon web server in-process, scrape GET /metrics,
and validate the exposition against the golden surface.

Two layers of checking:

1. the text parses as Prometheus exposition format (every non-comment line
   is `name[{labels}] value`, every family has HELP+TYPE);
2. the set of `# HELP` / `# TYPE` lines equals tests/goldens/
   metrics_exposition.txt exactly — metric names, types, and help text are
   an API surface for every dashboard scraping the daemon, so adding,
   renaming, or retyping one must show up in review as a golden diff.

Run with --update after intentionally changing the metric catalog (and
update docs/guide/10-observability.md to match).
"""

from __future__ import annotations

import asyncio
import pathlib
import re
import sys
import urllib.request

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

GOLDEN = ROOT / "tests" / "goldens" / "metrics_exposition.txt"

# one line per subsystem the tentpole instrumented: the endpoint must never
# silently lose a whole subsystem even if the golden is regenerated blindly
REQUIRED = (
    "fleet_solver_solves_total",        # solver
    "fleet_placements_total",           # scheduler
    "fleet_deploys_total",              # deploy engine
    "fleet_store_ops_total",            # CP store
    "fleet_log_lines_dropped_total",    # CP log router
    "fleet_agents_connected",           # CP agent registry
    "fleet_cp_request_duration_seconds",  # CP handlers
    "fleet_agent_anomalies_total",      # agent monitor
    "fleet_lease_transitions_total",    # CP failure detector
    "fleet_reconverge_redeliveries_total",  # CP reconverger
    "fleet_agent_send_failures_total",  # agent session loops
    "fleet_solver_resident_reuse_total",    # device-resident warm path
    "fleet_solver_sharded_solves_total",    # pod-scale sharded path
    "fleet_admission_queue_depth",          # streaming admission
    "fleet_autoscaler_pressure",            # admission -> autoscaler loop
    "fleet_cloud_provider_degraded_total",  # misconfigured-provider alarm
    "fleet_obs_samples_total",              # TSDB collector
    "fleet_slo_stream_quantile",            # SLO quantile export
    "fleet_solver_dispatches_in_flight",    # device profiling hooks
    "fleet_cp_shard_agents",                # CP shard table (ISSUE 19)
)

_SAMPLE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_]+="[^"]*"(,[a-zA-Z_]+="[^"]*")*\})? '
    r'(-?\d+(\.\d+)?([eE][+-]?\d+)?|\+Inf|-Inf|NaN)$')


def scrape() -> str:
    # import the full instrumented surface so the exposition is complete
    # regardless of which subsystems the web server pulls in transitively
    import fleetflow_tpu.agent.agent      # noqa: F401
    import fleetflow_tpu.agent.monitor    # noqa: F401
    import fleetflow_tpu.chaos.simulate   # noqa: F401  (plan-simulate families)
    import fleetflow_tpu.chaos.worldgen   # noqa: F401  (world families)
    import fleetflow_tpu.cloud.provider   # noqa: F401  (degraded alarm)
    import fleetflow_tpu.cp.autoscaler    # noqa: F401  (pressure gauge)
    import fleetflow_tpu.solver.api       # noqa: F401
    import fleetflow_tpu.solver.sharded   # noqa: F401  (pod-scale families)
    from fleetflow_tpu.cp.server import ServerConfig, start
    from fleetflow_tpu.daemon.web import WebServer

    async def go() -> str:
        handle = await start(ServerConfig())
        web = WebServer(handle.state)
        host, port = await web.start("127.0.0.1", 0)

        def fetch() -> str:
            with urllib.request.urlopen(
                    f"http://{host}:{port}/metrics", timeout=10) as r:
                assert r.status == 200, r.status
                ctype = r.headers.get("Content-Type", "")
                assert ctype.startswith("text/plain"), ctype
                return r.read().decode()

        try:
            return await asyncio.get_running_loop().run_in_executor(
                None, fetch)
        finally:
            await web.stop()
            await handle.stop()

    return asyncio.run(go())


def validate_format(text: str) -> list[str]:
    errors = []
    helped, typed = set(), set()
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            helped.add(line.split(" ", 3)[2])
        elif line.startswith("# TYPE "):
            typed.add(line.split(" ", 3)[2])
        elif not _SAMPLE.match(line):
            errors.append(f"unparseable sample line: {line!r}")
    for fam in sorted(typed - helped):
        errors.append(f"family {fam} has TYPE but no HELP")
    base = {n.rsplit("_bucket", 1)[0].rsplit("_sum", 1)[0]
            for n in typed}
    for name in REQUIRED:
        if name not in base:
            errors.append(f"required metric family missing: {name}")
    return errors


def main() -> int:
    text = scrape()
    errors = validate_format(text)
    got = sorted(ln for ln in text.splitlines() if ln.startswith("# "))
    if "--update" in sys.argv:
        GOLDEN.write_text("\n".join(got) + "\n")
        print(f"wrote {GOLDEN} ({len(got) // 2} families)")
        return 0
    want = [ln for ln in GOLDEN.read_text().splitlines() if ln]
    for ln in want:
        if ln not in got:
            errors.append(f"golden line missing from exposition: {ln!r}")
    for ln in got:
        if ln not in want:
            errors.append(f"exposition line not in golden "
                          f"(run --update + doc the metric): {ln!r}")
    if errors:
        print("metrics exposition check FAILED:", file=sys.stderr)
        for e in errors:
            print(f"  {e}", file=sys.stderr)
        return 1
    print(f"metrics exposition OK ({len(got) // 2} families, "
          f"{len(text.splitlines())} lines)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
