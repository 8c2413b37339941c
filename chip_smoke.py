#!/usr/bin/env python3
"""Chip smoke: the placement path, once, on the accelerator.

    python chip_smoke.py [--seed N]           # needs a TPU; exits 1 without
    python chip_smoke.py --cpu-dry-run        # sandbox: small sizes, CPU

ONE process, which holds the chip for its whole life, drives the main path
through the objects the product itself builds, at the BASELINE.json sizes:

  device  jax.devices() in-process; versions, cache dir, native libraries
  cold    config 4: 8-fleet KDL registry -> parse -> aggregate -> place
          (10,000 services x 1,000 nodes)
  churn   config 5: PlacementService over a 1,000-server store and one
          ~9,200-service stage: solve_stage, commit, node_events bursts
  admit   AdmissionController on that stage: arrivals + departures drained
          to empty through admit_batch
  pod     >= 2 devices only: a stage that routes to the mesh by its own size
          (100,000 x 1,000), then two warm reschedules — through the
          scheduler alone. The measured one is the benchmark's cell
          `pod100kx1k.node-churn-moved`: the same size through the CP,
          every op timed and checked (PERF.md §4-5)

FLEET_TRANSFER_GUARD=disallow is set for the whole run. Every phase is
checked by the host oracle (solver/repair.verify) and by the counters that
show the DEVICE did the work (no host repair, no greedy fallback, no host
transfer, no compile in warm iterations). Any failed check or exception
exits non-zero; nothing is caught and continued. Everything is generated
from --seed; nothing is read from outside the checkout.

Each phase prints one JSON line; the LAST stdout line is
{"ok": true, "device": {"platform", "kind", "count"}}. The wall/compile
seconds printed here are smoke timings, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import hashlib
import itertools
import json
import os
import sys
import time

# admit: (arrivals, departures) per wave; the stream then drains out
FULL = dict(cold=(10_000, 1_000), churn=(9_200, 1_000), bursts=8,
            admit=((8, 0), (40, 4), (128, 20), (128, 64)),
            pod=(100_000, 1_000))
# dry run: every phase's control flow at the smallest size whose churn
# closure still fits a sub-solve mini tier (256 rows < S)
DRY = dict(cold=(800, 80), churn=(900, 100), bursts=8,
           admit=((8, 0), (24, 4), (48, 12)), pod=(2_000, 64))


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str, detail=None) -> None:
    if not ok:
        raise SmokeFailure(f"{what}: {detail!r}" if detail is not None
                           else what)


def digest(raw) -> str:
    import numpy as np
    return hashlib.sha256(
        np.ascontiguousarray(raw, dtype=np.int32).tobytes()).hexdigest()[:16]


class Watch:
    """Process-wide compile and counter observation. Compile events come
    from jax.monitoring (every backend compile-or-cache-load, the tiny
    eager ones included), the rest from the product's metrics registry."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == self.COMPILE:
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1

    def read(self) -> dict:
        from fleetflow_tpu.obs.metrics import REGISTRY

        def val(name, **labels):
            m = REGISTRY.get(name)
            return float(m.value(**labels)) if m is not None else 0.0

        return {
            "compile_events": self.compiles,
            "compile_s": self.compile_s,
            "cache_hits": self.hits,
            "cache_misses": self.misses,
            "sweeps": val("fleet_solver_sweeps_total"),
            "solver_compiles": val("fleet_solver_compile_events_total"),
            "reuse_delta": val("fleet_solver_resident_reuse_total",
                               outcome="delta"),
            "reuse_cold": val("fleet_solver_resident_reuse_total",
                              outcome="cold"),
            "host_transfers": val("fleet_solver_host_transfers_total"),
            "churn_fallbacks": val("fleet_placement_churn_fallbacks_total"),
            "subsolve_localized": val("fleet_solver_subsolve_total",
                                      outcome="localized"),
            "subsolve_fallback_infeasible": val(
                "fleet_solver_subsolve_total",
                outcome="fallback_infeasible"),
            "sharded_cold": val("fleet_solver_sharded_solves_total",
                                outcome="cold"),
            "sharded_delta": val("fleet_solver_sharded_solves_total",
                                 outcome="delta"),
        }


def delta(after: dict, before: dict) -> dict:
    return {k: round(after[k] - before[k], 3) for k in after}


def oracle(pt, raw, what: str, dead=()) -> None:
    """The host ground truth: zero hard violations, nothing on a dead node."""
    import numpy as np
    from fleetflow_tpu.solver.repair import verify
    stats = verify(pt, np.asarray(raw))
    check(stats["total"] == 0, f"{what}: host verify found violations", stats)
    if dead:
        names = set(dead)
        on_dead = [pt.node_names[int(j)] for j in np.unique(np.asarray(raw))
                   if pt.node_names[int(j)] in names]
        check(not on_dead, f"{what}: services left on dead nodes", on_dead)


def device_did_the_work(what: str) -> dict:
    """The most recent solve's gauges: what the DEVICE returned had no
    violation, so the host repair backstop never ran. moves_repaired has no
    gauge of its own — repair runs only on a device result with violations
    (api._solve / sharded.solve_sharded), so pre_repair == 0 implies 0."""
    from fleetflow_tpu.obs.metrics import REGISTRY
    last = {"violations":
            int(REGISTRY.get("fleet_solver_violations").value()),
            "pre_repair_violations":
            int(REGISTRY.get("fleet_solver_pre_repair_violations").value())}
    check(last["violations"] == 0, f"{what}: solver reports violations", last)
    check(last["pre_repair_violations"] == 0,
          f"{what}: the host repair backstop did the work", last)
    return dict(last, moves_repaired=0)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def phase_device(dry: bool) -> dict:
    from fleetflow_tpu.platform import (compile_cache_info, force_cpu,
                                        init_platform)
    if dry:
        force_cpu(8)
    elif os.environ.get("JAX_PLATFORMS", "").strip().lower() == "tpu":
        # the pod phase stages the (S, N) planes on the host backend before
        # sharding them (ShardedResident._staging_device); keep the TPU
        # first (= default) and allow the CPU backend beside it
        os.environ["JAX_PLATFORMS"] = "tpu,cpu"
    device = init_platform()
    if not dry and device["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{device['platform']!r}, JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS', '')!r}); nothing was run. "
              f"--cpu-dry-run is the sandbox rehearsal.", file=sys.stderr)
        raise SystemExit(1)

    import jax
    import jaxlib
    from fleetflow_tpu.native import lib as native_lib
    from fleetflow_tpu.native.kdl import kdl_native_available
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    emit("device", platform=device["platform"], device_kind=device["kind"],
         count=device["count"], jax=jax.__version__,
         jaxlib=jaxlib.__version__, libtpu=libtpu,
         compile_cache=compile_cache_info(),
         native_placer=native_lib.load() is not None,
         native_kdl=bool(kdl_native_available()),
         transfer_guard=os.environ["FLEET_TRANSFER_GUARD"],
         dry_run=dry)
    return device


def gen_registry(S: int, N: int, seed: int, fleets: int = 8):
    """A multi-tenant registry: `fleets` tenant
    fleets of S/fleets services (ports / volumes / anti-affinity on) over
    one N-node server pool, as KDL text."""
    from fleetflow_tpu.core.parser import parse_kdl_string
    from fleetflow_tpu.lower.fleetgen import (generate_fleet_kdl,
                                              generate_servers_kdl)
    from fleetflow_tpu.registry.model import FleetEntry, Registry

    per = S // fleets
    # disjoint port_base per fleet: conflict identity is (ip, port, proto)
    texts = {f"t{i}": generate_fleet_kdl(
        f"t{i}", per, seed=seed + 100 + i, n_nodes_hint=N,
        port_base=10000 + i * per) for i in range(fleets)}
    pool = parse_kdl_string(generate_servers_kdl(N, seed=seed + 7))
    reg = Registry(fleets={n: FleetEntry(name=n, path=n) for n in texts},
                   servers=pool.servers)
    return texts, reg, (lambda path, stage: parse_kdl_string(texts[path]))


def phase_cold(watch: Watch, sizes: dict, seed: int) -> None:
    from fleetflow_tpu.registry.aggregate import aggregate_fleets
    from fleetflow_tpu.sched.tpu import TpuSolverScheduler

    S, N = sizes["cold"]
    t0 = time.perf_counter()
    texts, reg, loader = gen_registry(S, N, seed)
    t1 = time.perf_counter()
    pt, _ = aggregate_fleets(reg, stages={n: ["prod"] for n in texts},
                             loader=loader)
    t2 = time.perf_counter()
    check(pt.S >= S and pt.N == N,     # replicas add rows
          "cold: aggregated shape", (pt.S, pt.N))
    sched = TpuSolverScheduler(seed=seed)
    c0 = watch.read()
    pl = sched.place(pt, stage="registry/prod")
    c1 = watch.read()
    t3 = time.perf_counter()
    check(pl.feasible and pl.violations == 0, "cold: placement infeasible",
          pl.violations)
    oracle(pt, pl.raw, "cold")
    last = device_did_the_work("cold")
    # second cold placement of the same fleet: every executable is compiled
    again = sched.place(pt, stage="registry/prod")
    c2 = watch.read()
    t4 = time.perf_counter()
    oracle(pt, again.raw, "cold (repeat)")
    device_did_the_work("cold (repeat)")
    first, repeat = delta(c1, c0), delta(c2, c1)
    check(repeat["compile_events"] == 0,
          "cold: compile events after the first iteration", repeat)
    emit("cold", shape=[pt.S, pt.N], source=pl.source,
         kdl_bytes=sum(len(t) for t in texts.values()),
         wall_s={"generate": round(t1 - t0, 3),
                 "parse_aggregate_lower": round(t2 - t1, 3),
                 "place_first": round(t3 - t2, 3),
                 "place_repeat": round(t4 - t3, 3)},
         compile_s=first["compile_s"], compile_events=first["compile_events"],
         repeat_compile_events=repeat["compile_events"],
         sweeps=[first["sweeps"], repeat["sweeps"]],
         solve_ms=[round(pl.solve_ms, 1), round(again.solve_ms, 1)],
         soft=round(pl.soft, 4), **last,
         digest=digest(pl.raw), digest_repeat=digest(again.raw))


def build_cp(S: int, N: int, seed: int):
    """A PlacementService exactly as cp/server.py builds it
    (`PlacementService(store, use_tpu=True)`), over a store with N
    registered online servers and one S-service stage."""
    from fleetflow_tpu.chaos.runner import make_flow, node_slug
    from fleetflow_tpu.cp.models import ServerCapacity
    from fleetflow_tpu.cp.placement import PlacementService
    from fleetflow_tpu.cp.store import Store

    store = Store(None)
    slugs = [node_slug(i) for i in range(N)]
    flow = make_flow(S, 1, slugs, seed=seed)
    # ~2x headroom over the stage's demand plus the admit phase's arrivals
    cpu = max(2.0 * (0.15 * S + 100.0) / N, 1.0)
    for slug in slugs:
        rec = store.register_server(slug, tenant="default", hostname=slug)
        store.update("servers", rec.id, status="online",
                     capacity=ServerCapacity(cpu=cpu, memory=cpu * 2048.0,
                                             disk=10240.0))
    return store, flow, PlacementService(store, use_tpu=True)


@contextlib.contextmanager
def _env(name: str, value: str):
    """Set one environment variable for a with-block."""
    prev = os.environ.get(name)
    os.environ[name] = value
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = prev


def phase_churn(watch: Watch, sizes: dict, seed: int):
    import numpy as np

    S, N = sizes["churn"]
    t0 = time.perf_counter()
    store, flow, placement = build_cp(S, N, seed)
    key = f"{flow.name}/app0"
    c0 = watch.read()
    t1 = time.perf_counter()
    pl, rid = placement.solve_stage(flow, "app0")
    t2 = time.perf_counter()
    check(pl.feasible and rid is not None, "churn: baseline infeasible",
          pl.violations)
    pt0, _ = placement.retained(key)
    oracle(pt0, pl.raw, "churn baseline")
    base_last = device_did_the_work("churn baseline")
    check(placement.commit(rid), "churn: baseline commit refused")
    c1 = watch.read()

    dead: list[str] = []

    def burst(tag: str) -> dict:
        """The placement channel's node_events call: kill the busiest live
        node, revive the victim of two bursts ago."""
        pt, cur = placement.retained(key)
        loads = np.bincount(np.asarray(cur.raw), minlength=pt.N).astype(float)
        loads[~np.asarray(pt.node_valid)] = -1.0
        victim = pt.node_names[int(loads.argmax())]
        events = [(victim, False)]
        if len(dead) >= 2:
            events.append((dead.pop(0), True))
        dead.append(victim)
        before = watch.read()
        t = time.perf_counter()
        moved = placement.node_events(events)
        ms = (time.perf_counter() - t) * 1e3
        d = delta(watch.read(), before)
        check([k for k, _ in moved] == [key], f"{tag}: stage not re-solved",
              [k for k, _ in moved])
        new = moved[0][1]
        check(new.feasible, f"{tag}: re-solve infeasible", new.violations)
        pt_new, _ = placement.retained(key)
        oracle(pt_new, new.raw, tag, dead=dead)
        last = device_did_the_work(tag)
        check(d["churn_fallbacks"] == 0,
              f"{tag}: CP degraded to the host greedy path", d)
        check(placement.commit_retained(key), f"{tag}: commit refused")
        return {"ms": round(ms, 1), "events": len(events),
                "moved": int((np.asarray(new.raw)
                              != np.asarray(cur.raw)).sum()),
                "sweeps": d["sweeps"], "compile_events": d["compile_events"],
                "compile_s": d["compile_s"], **last}

    # first iterations: compile what the steady state runs — the full warm
    # fused executable (the fallback a gate-rejected sub-solve re-runs,
    # reached here by switching the active-set path off for one burst),
    # then the localized mini tier for a kill and for a kill+revive
    with _env("FLEET_SUBSOLVE", "0"):
        warmup = [burst("churn warm-up 1 (full warm path)")]
    warmup += [burst(f"churn warm-up {i}") for i in (2, 3)]
    c2 = watch.read()
    runs = [burst(f"churn burst {i + 1}") for i in range(sizes["bursts"])]
    c3 = watch.read()
    steady = delta(c3, c2)
    check(steady["compile_events"] == 0,
          "churn: compile events in warm iterations", steady)
    check(steady["host_transfers"] == 0,
          "churn: problem tensors crossed the host boundary", steady)
    check(steady["reuse_delta"] == len(runs) and steady["reuse_cold"] == 0,
          "churn: warm solves did not all ride the resident delta path",
          steady)
    check(steady["subsolve_localized"] >= 1,
          "churn: no sub-solve was localized", steady)
    ms = sorted(r["ms"] for r in runs)
    emit("churn", shape=[pt0.S, pt0.N], source=pl.source,
         wall_s={"build_store_flow": round(t1 - t0, 3),
                 "solve_stage": round(t2 - t1, 3)},
         baseline=dict(delta(c1, c0), **base_last),
         warmup=warmup, bursts=runs,
         burst_ms={"min": ms[0], "median": ms[len(ms) // 2], "max": ms[-1]},
         steady=steady, dead=list(dead),
         digest=digest(placement.retained(key)[1].raw))
    return store, flow, placement, dead


def phase_admit(watch: Watch, sizes: dict, store, flow, placement,
                dead: list) -> None:
    from fleetflow_tpu.cp.admission import (AdmissionConfig,
                                            AdmissionController,
                                            AdmissionRequest)
    from fleetflow_tpu.cp.server import ServerConfig

    cfg = ServerConfig()
    # as cp/server.py _build_admission builds it (the drain loop is stepped
    # here instead of spawned: no event loop in a smoke)
    ctrl = AdmissionController(
        placement,
        config=AdmissionConfig(max_queue=cfg.admission_queue,
                               batch_max=cfg.admission_batch,
                               shed_age_s=cfg.admission_shed_age_s),
        store=store)
    key = ctrl.attach(flow, "app0")
    seq = itertools.count(1)
    live: list[str] = []

    def specs(k: int) -> list[dict]:
        return [{"name": f"gen-{next(seq):06d}", "cpu": 0.1, "memory": 64.0}
                for _ in range(k)]

    def drain(tag: str) -> dict:
        before = watch.read()
        t = time.perf_counter()
        placed = departed = solves = 0
        while ctrl.has_work():
            out = ctrl.step()
            check(out["violations"] == 0, f"{tag}: micro-solve violations",
                  out["violations"])
            check(not out["parked"], f"{tag}: arrivals parked",
                  out["parked"])
            live.extend(out["placed"])
            for n in out["departed"]:
                live.remove(n)
            placed += len(out["placed"])
            departed += len(out["departed"])
            if out["batch"]:
                solves += 1
                device_did_the_work(tag)
        ms = (time.perf_counter() - t) * 1e3    # before the host oracle
        d = delta(watch.read(), before)
        check(d["churn_fallbacks"] == 0,
              f"{tag}: CP degraded to the host greedy path", d)
        pt, cur = placement.retained(key)
        oracle(pt, cur.raw, tag, dead=dead)
        return {"ms": round(ms, 1), "placed": placed, "departed": departed,
                "micro_solves": solves, "sweeps": d["sweeps"],
                "compile_events": d["compile_events"],
                "compile_s": d["compile_s"]}

    def cycle(tag: str) -> list[dict]:
        """The waves in, then every streamed service out again: the stage
        ends where it began, so a second cycle replays the same shapes."""
        runs = []
        for i, (k, gone) in enumerate(sizes["admit"]):
            leaving = list(live[:gone])
            ctrl.submit("gen", arrivals=specs(k), departures=leaving)
            runs.append(drain(f"{tag} wave {i + 1} (+{k} -{len(leaving)})"))
        while live:
            ctrl.submit("gen", departures=list(live[:cfg.admission_batch]))
            runs.append(drain(f"{tag} drain-out"))
        return runs

    # first iteration: compiles each merge scatter tier and sub-solve mini
    # tier the cycle touches, after one batch on the full warm fused
    # executable (the fallback a gate-rejected sub-solve re-runs)
    with _env("FLEET_SUBSOLVE", "0"):
        ctrl.submit("gen", arrivals=specs(8))
        first = [drain("admit first (full warm path)")]
    first += cycle("admit first")
    c0 = watch.read()
    runs = cycle("admit")
    steady = delta(watch.read(), c0)

    census: dict[str, int] = {}
    for r in ctrl.requests.values():
        census[r.state] = census.get(r.state, 0) + 1
    check(all(s in AdmissionRequest.TERMINAL for s in census),
          "admit: census entries not terminal", census)
    check(set(census) <= {"placed", "departed"},
          "admit: requests shed, parked or cancelled", census)
    check(not ctrl.has_work() and not ctrl.live_names(key),
          "admit: stream not drained to empty", ctrl.live_names(key))
    check(steady["compile_events"] == 0,
          "admit: compile events in warm iterations", steady)
    check(steady["host_transfers"] == 0,
          "admit: problem tensors crossed the host boundary", steady)
    check(steady["reuse_cold"] == 0, "admit: cold restage at steady state",
          steady)
    check(steady["reuse_delta"] == sum(r["micro_solves"] for r in runs),
          "admit: micro-solves did not all ride the resident delta path",
          steady)
    check(steady["subsolve_localized"] >= 1,
          "admit: no sub-solve was localized", steady)
    pt, cur = placement.retained(key)
    emit("admit", rows=pt.S, census=census,
         first_iteration=first, waves=runs, steady=steady,
         digest=digest(cur.raw))


def phase_pod(watch: Watch, sizes: dict, seed: int, device: dict,
              dry: bool) -> None:
    import jax
    import numpy as np
    from fleetflow_tpu.lower import synthetic_problem
    from fleetflow_tpu.sched.tpu import TpuSolverScheduler
    from fleetflow_tpu.solver.resident import ProblemDelta
    from fleetflow_tpu.solver.sharded import sharded_route

    if device["count"] < 2:
        emit("pod", ran=False, reason=f"not run: {device['count']} device")
        return
    S, N = sizes["pod"]
    t0 = time.perf_counter()
    pt = synthetic_problem(S, N, seed=seed, n_tenants=8, port_fraction=0.2,
                           volume_fraction=0.1)
    t1 = time.perf_counter()
    # the real run routes by the stage's OWN size; only the small dry run
    # has to force the route
    with (_env("FLEET_SHARDED", "1") if dry else contextlib.nullcontext()):
        mesh = sharded_route(pt)
        check(mesh is not None, "pod: stage did not route to the mesh",
              (S, N, device["count"]))
        lanes = mesh.shape["replica"]
        check(mesh.devices.size == device["count"] // lanes * lanes,
              "pod: mesh does not span the visible devices",
              (dict(mesh.shape), device["count"]))
        sched = TpuSolverScheduler(seed=seed)
        c0 = watch.read()
        pl = sched.place(pt, stage="pod")
        c1 = watch.read()
        t2 = time.perf_counter()
        check(pl.feasible, "pod: cold placement infeasible", pl.violations)
        oracle(pt, pl.raw, "pod cold")
        cold_last = device_did_the_work("pod cold")

        # per-device residency, as JAX reports it: every live array's
        # addressable shards, summed per device
        per_dev = {str(d): 0 for d in mesh.devices.flat}
        for arr in jax.live_arrays():
            for sh in arr.addressable_shards:
                if str(sh.device) in per_dev:
                    per_dev[str(sh.device)] += int(sh.data.nbytes)
        check(all(b > 0 for b in per_dev.values()),
              "pod: a mesh device holds no shard", per_dev)

        cur, raw, dead, warm = pt, np.asarray(pl.raw), [], []
        for i in range(2):
            loads = np.bincount(raw, minlength=pt.N).astype(float)
            loads[~cur.node_valid] = -1.0
            victim = int(loads.argmax())
            valid = cur.node_valid.copy()
            valid[victim] = False
            dead.append(pt.node_names[victim])
            cur = dataclasses.replace(cur, node_valid=valid)
            before = watch.read()
            t = time.perf_counter()
            new = sched.reschedule(cur, delta=ProblemDelta(node_valid=valid),
                                   stage="pod")
            ms = (time.perf_counter() - t) * 1e3
            d = delta(watch.read(), before)
            check(new.feasible, f"pod warm {i + 1}: infeasible",
                  new.violations)
            oracle(cur, new.raw, f"pod warm {i + 1}", dead=dead)
            last = device_did_the_work(f"pod warm {i + 1}")
            warm.append({"ms": round(ms, 1), "sweeps": d["sweeps"],
                         "moved": int((np.asarray(new.raw) != raw).sum()),
                         "compile_events": d["compile_events"],
                         "compile_s": d["compile_s"],
                         "sharded_delta": d["sharded_delta"],
                         "host_transfers": d["host_transfers"], **last})
            raw = np.asarray(new.raw)
    total = delta(watch.read(), c0)
    check(total["sharded_cold"] == 1 and total["sharded_delta"] == 2,
          "pod: fleet_solver_sharded_solves_total did not move as expected",
          total)
    check(total["host_transfers"] == 0,
          "pod: problem tensors crossed the host boundary", total)
    check(warm[1]["compile_events"] == 0,
          "pod: compile events after the first warm iteration", warm[1])
    emit("pod", ran=True, shape=[pt.S, pt.N], route="forced (dry run)"
         if dry else "by size", mesh=dict(mesh.shape), source=pl.source,
         per_device_bytes=per_dev,
         wall_s={"generate": round(t1 - t0, 3),
                 "place_cold": round(t2 - t1, 3)},
         cold=dict(delta(c1, c0), **cold_last), warm=warm,
         digest=digest(raw))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="sandbox rehearsal: small sizes on 8 virtual CPU "
                         "devices; prints platform cpu, proves nothing "
                         "about the chip")
    args = ap.parse_args(argv)
    dry = args.cpu_dry_run
    sizes = DRY if dry else FULL
    os.environ["FLEET_TRANSFER_GUARD"] = "disallow"

    t0 = time.perf_counter()
    device = phase_device(dry)
    watch = Watch()
    phase_cold(watch, sizes, args.seed)
    store, flow, placement, dead = phase_churn(watch, sizes, args.seed)
    phase_admit(watch, sizes, store, flow, placement, dead)
    phase_pod(watch, sizes, args.seed, device, dry)

    from fleetflow_tpu.platform import compile_cache_info
    total = watch.read()
    emit("summary", wall_s=round(time.perf_counter() - t0, 1),
         compile_s=round(total["compile_s"], 2),
         compile_events=total["compile_events"],
         cache_hits=total["cache_hits"], cache_misses=total["cache_misses"],
         compile_cache=compile_cache_info())
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
