#!/usr/bin/env python3
"""Generate the README performance table from the newest BENCH_r*.json.

VERDICT r3 item 10: the README must quote a recorded artifact, not
development-session recollections. The block between the bench:begin/end
markers is machine-written from the newest artifact — driver artifacts
outrank a same-round `*_dev.json` (a full `python bench.py` run the
builder commits after changing the bench, so the table never quotes a
superseded record while waiting for the next driver run; the rendered
block says which kind it used). tests/test_readme_bench.py fails on any
drift (run `python scripts/update_readme_bench.py` to refresh).
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
BEGIN = "<!-- bench:begin (generated: python scripts/update_readme_bench.py) -->"
END = "<!-- bench:end -->"


def newest_artifact() -> tuple[str, dict]:
    def key(p: Path) -> tuple[int, int]:
        m = re.search(r"r(\d+)", p.stem)
        # same round: a driver artifact outranks a dev-machine one
        return (int(m.group(1)) if m else -1,
                0 if p.stem.endswith("_dev") else 1)

    # numeric sort: lexicographic would pin r99 over r100
    arts = sorted(REPO.glob("BENCH_r*.json"), key=key)
    if not arts:
        raise SystemExit("no BENCH_r*.json artifacts found")
    # newest USABLE artifact: a driver record whose bench line failed to
    # parse carries `"parsed": null` — walk back to the next artifact
    # with a real section instead of crashing on the null
    for path in reversed(arts):
        doc = json.loads(path.read_text())
        # driver artifacts wrap the bench line under "parsed"
        parsed = doc.get("parsed", doc)
        if isinstance(parsed, dict) and "solve_ms" in parsed:
            return path.name, parsed
    raise SystemExit(
        "no BENCH_r*.json artifact holds a usable bench section "
        f"(checked {len(arts)}: newest {arts[-1].name} has parsed=null?)")


def render(name: str, d: dict) -> str:
    backend = d.get("backend", "?")
    rows = [
        ("Cold solve, 10,000 services × 1,000 nodes "
         "(multi-tenant, ports/volumes/anti-affinity)",
         f"**{d['solve_ms']:.0f} ms** on `{backend}`, "
         f"{d['violations']} violations, "
         f"{d.get('moves_repaired', 0)} host-repaired"),
        (("Warm reschedule, rolling node-churn loop "
          "(device-resident deltas, transfer-guard pinned)",
          f"p50 **{d['reschedule_ms']:.0f} ms** / "
          f"p99 {d['reschedule_p99_ms']:.0f} ms over "
          f"{d['reschedule_bursts']} bursts "
          f"({d['reschedule_compiles']} recompiles, "
          f"{d.get('reschedule_speedup_vs_legacy', '?')}× vs legacy "
          f"staging), "
          f"{d['reschedule_violations']} violations")
         if "reschedule_p99_ms" in d else
         ("Warm reschedule after killing the busiest node",
          (f"{d['reschedule_ms']:.0f} ms median of "
           f"{len(d['reschedule_runs'])} runs "
           f"(min {d['reschedule_ms_min']:.0f}, "
           f"{d['reschedule_compiles']} recompiles), "
           if "reschedule_runs" in d else
           f"{d['reschedule_ms']:.0f} ms, ")
          + f"{d['reschedule_violations']} violations")),
    ]
    burst = d.get("burst")
    if burst:
        ev = burst.get("events", {})
        rows.append((
            f"Churn burst ({ev.get('killed', '?')} nodes die, "
            f"{ev.get('revived', '?')} revives, "
            f"{ev.get('arrived_services', '?')} services arrive) — one "
            "coalesced warm re-solve",
            f"{burst['reschedule_ms']:.0f} ms, "
            f"{burst['violations']} violations"))
    sharded = d.get("sharded")
    if sharded and sharded.get("ok"):
        rows.append((
            f"Service-axis SPMD solve, {sharded['shape'][0]:,} × "
            f"{sharded['shape'][1]:,} over {sharded['devices']} devices "
            f"(`{sharded['backend']}`)",
            f"{sharded['sharded_solve_ms']:.0f} ms, "
            f"{sharded['violations']} violations"
            + (f", {sharded['per_device_sharded_mib']:.1f} MiB sharded "
               f"tensors/device (bit-packed eligibility)"
               if "per_device_sharded_mib" in sharded else "")))
        sres = sharded.get("resident")
        if sres:
            rows.append((
                f"Sharded warm re-solve, mesh-resident deltas "
                f"({sres['mesh'][0]}×{sres['mesh'][1]} mesh, "
                "transfer-guard pinned)",
                f"p50 **{sres['p50_ms']:.0f} ms** / "
                f"p99 {sres['p99_ms']:.0f} ms over {sres['bursts']} bursts "
                f"({sres['compiles_total']} recompiles), "
                f"{sres['violations_max']} violations"))
        curve = sharded.get("quality_vs_devices")
        if curve and curve.get("points"):
            pts = curve["points"]
            detail = ", ".join(
                f"{p['replicas']}×lanes soft {p['soft_median']:.3f}"
                for p in pts)
            rows.append((
                f"Quality vs devices (parallel tempering, "
                f"{curve['steps']} sweeps, ladder {curve['ladder']})",
                detail + (" — tempering wins"
                          if curve.get("tempering_wins") else "")))
    adm = d.get("admission")
    if adm and adm.get("ok"):
        rows.append((
            f"Streaming admission: {adm['virtual_s']:.0f} s of open-loop "
            f"Poisson+diurnal churn at {adm['rows']:,} rows × "
            f"{adm['shape'][1]:,} nodes (micro-solves on the resident "
            "delta path, transfer-guard pinned)",
            f"**{adm['placements_per_s']:.0f} placements/s** sustained, "
            f"solve p50 {adm['solve_ms_p50']:.0f} ms / "
            f"p99 {adm['solve_ms_p99']:.0f} ms, "
            f"{adm['compiles']} recompiles, "
            f"{adm['host_transfers']} host transfers, "
            f"{adm['violations_max']} violations"))
    pipe = d.get("pipeline")
    if pipe:
        rows.append((
            f"Whole pipeline: {pipe['fleets']}-fleet registry as KDL text "
            f"({pipe['kdl_bytes'] / 1e6:.1f} MB) → "
            + ("native" if pipe.get("native_parse") else "Python")
            + " parse → aggregate/lower → stage → solve",
            f"{pipe['end_to_end_ms']:.0f} ms "
            f"(parse {pipe['parse_ms']:.0f} / lower {pipe['lower_ms']:.0f} "
            f"/ stage {pipe['stage_ms']:.0f} / solve "
            f"{pipe['solve_ms']:.0f}), {pipe['violations']} violations"))
        fe = pipe.get("frontend")
        if fe and fe.get("warm"):
            w = fe["warm"]
            pc = fe.get("parse_cache", {})
            rows.append((
                "Warm front end, caches hot (content-addressed parse "
                "cache + per-stage FlowCache + whole-instance lowering "
                "reuse + staging-arena restage)",
                f"**{w['total_ms']:.0f} ms** "
                f"(parse {w['parse_ms']:.1f} / lower {w['lower_ms']:.1f} "
                f"/ stage {w['stage_ms']:.1f}), parse cache "
                f"{pc.get('hits', 0)} hits / {pc.get('misses', 0)} misses"))
        cc = pipe.get("compile_cache")
        if cc:
            rows.append((
                "Persistent caches in the default leg (XLA compile "
                "cache + `FLEET_PARSE_CACHE`)",
                f"compile cache {'on' if cc.get('enabled') else 'OFF'}, "
                f"{cc.get('entries', 0)} entries"))
        cwf = (pipe.get("cold_warm") or {}).get("frontend")
        if cwf:
            rows.append((
                "Cold → warm process restart (fresh shared XLA + parse "
                "cache dirs)",
                f"parse {cwf['cold_parse_ms']:.0f} → "
                f"{cwf['warm_parse_ms']:.0f} ms "
                f"({cwf['parse_ratio']}×), warm-process front end "
                f"{cwf['warm_front_end_ms']:.0f} ms"))
    rows.append((
        "Reference's own path (sequential per-service Docker round-trips, "
        "engine.rs:157-167)",
        f"~{10000 / 50:.0f} s at this scale (50 placements/s)"))

    kind = "dev-machine" if name.endswith("_dev.json") else "driver"
    lines = [BEGIN,
             f"Newest {kind} artifact: `{name}` "
             f"(`vs_baseline: {d.get('vs_baseline', '?')}×`).",
             "",
             "| Scenario | Record |",
             "|---|---|"]
    lines += [f"| {a} | {b} |" for a, b in rows]
    lines.append(END)
    return "\n".join(lines)


def main() -> int:
    check = "--check" in sys.argv
    name, d = newest_artifact()
    block = render(name, d)
    readme = (REPO / "README.md").read_text()
    pattern = re.compile(re.escape(BEGIN) + ".*?" + re.escape(END), re.S)
    if not pattern.search(readme):
        raise SystemExit("README.md is missing the bench:begin/end markers")
    updated = pattern.sub(lambda _: block, readme)
    if check:
        if updated != readme:
            print("README bench table is stale; run "
                  "python scripts/update_readme_bench.py", file=sys.stderr)
            return 1
        return 0
    (REPO / "README.md").write_text(updated)
    print(f"README bench table refreshed from {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
