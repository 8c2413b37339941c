"""Compile-contract registry: the solver's hot-path executables, with the
donation/sharding declarations each one must keep.

The perf contracts (PRs 4-9, 14) live or die on five jitted programs:

  resident.merge    the donated single-chip delta-merge kernel
                    (solver/resident._merge_fn) — churn folds into the
                    resident buffers in place, no second (S, N) copy
  sharded.merge     the mesh-sharded variant (sharded._merge_fn_sharded)
                    with explicit sharding constraints pinning every
                    output to its input layout
  refine.warm       the fused solve pipeline (api._refine) in its warm
                    resident configuration — the steady-state dispatch
  subsolve.localized  the churn-localized gather -> mini-anneal ->
                    scatter -> exact-gate dispatch (subsolve._subsolve_fn);
                    pinned donation-FREE — the original assignment must
                    outlive a gate-rejected attempt
  sharded.anneal    the SPMD anneal + tempering dispatch
                    (sharded.anneal_sharded) on a tempered mesh

Each :class:`KernelContract` names the executable, anchors its jit
declaration in source (module + lexical qualname, consumed by
analysis/jitspec AST extraction — the recompile-axis ground truth), and
builds *lowerable cases at representative bucket tiers* using the same
staging code the production path runs (ResidentProblem.merge_inputs,
ShardedResident, the api._solve warm-config derivations). The auditor
(fleetflow_tpu/analysis/auditor.py) lowers each case and checks donation
aliasing, host-callback absence, and output shardings against
tests/goldens/compile_contract.json.

Keeping the registry inside solver/ is deliberate: whoever changes a
kernel's jit declaration is looking at this module's neighbors, and the
contract entry is the documentation of record for what the declaration
promises.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Optional

import numpy as np

__all__ = ["KernelCase", "KernelContract", "hot_path_kernels",
           "problem_static_fields", "AUDIT_TIERS"]

# representative (S, N) instances: one inside the first bucket tier (64)
# and one in the next (80) — enough to prove tier drift stays inside the
# declared static set without paying fleet-scale compile time in CI
AUDIT_TIERS: tuple[tuple[int, int], ...] = ((60, 12), (73, 12))


@dataclass
class KernelCase:
    """One lowerable instance of a kernel at a concrete bucket tier."""
    tier: str                       # "<padded_S>x<N>" label
    fn: Any                         # the jitted callable
    args: tuple                     # positional args (device-staged)
    kwargs: dict                    # static kwargs, exactly as dispatched
    arg_names: tuple                # names for the positional args
    # declared output shardings: flat leaf-path -> normalized spec string
    # ("P('svc')", "P()" ...); None = single-device kernel, not checked
    out_shardings: Optional[dict] = None


@dataclass
class KernelContract:
    name: str                       # registry key, e.g. "resident.merge"
    module: str                     # dotted module holding the jit decl
    qualname: str                   # lexical path for jitspec extraction
    cases: Callable[[], list[KernelCase]]
    # donated leaf names (arg.field) that MUST alias an output in the
    # lowered artifact — the buffers whose in-place reuse IS the perf
    # story; a dropped alias here is a silent memory/latency regression
    must_alias: tuple = ()
    needs_devices: int = 1
    # packed-plane policing (solver/problem.py): the staged problem must
    # carry a bit-packed uint32 eligibility plane and NO preference plane
    # — an f32/bool (S, N) plane reappearing in a hot-path executable is
    # an intrinsic audit violation, not just a golden diff
    packed_planes: bool = True


def problem_static_fields() -> list[str]:
    """DeviceProblem fields that are static jit metadata — every one is a
    recompile axis for ALL kernels taking a problem, exactly like a
    static_argnames entry. Enumerated from the dataclass so a new static
    field shows up as a contract diff, not a latent compile cliff."""
    from .problem import DeviceProblem
    return sorted(f.name for f in dataclasses.fields(DeviceProblem)
                  if f.metadata.get("static"))


def _synthetic(S: int, N: int):
    from ..lower import synthetic_problem
    return synthetic_problem(S, N, seed=0, port_fraction=0.3,
                             volume_fraction=0.2)


def _rich_delta(pt, n_rows: int = 3):
    """A delta exercising every merge input: validity + capacity drift
    plus demand/eligibility/conflict-id row scatters (every has_* flag
    True — the richest static variant, the one whose lowering touches
    every donated plane)."""
    from .resident import ProblemDelta
    rows = np.arange(min(n_rows, pt.S), dtype=np.int32)
    return ProblemDelta(
        node_valid=np.asarray(pt.node_valid, dtype=bool).copy(),
        capacity=np.asarray(pt.capacity, dtype=np.float32).copy(),
        demand_rows=(rows, np.asarray(pt.demand, np.float32)[rows]),
        eligible_rows=(rows, np.asarray(pt.eligible, bool)[rows]),
        conflict_rows=rows)


_MERGE_ARG_NAMES = ("prob", "assignment", "node_valid", "capacity",
                    "dem_idx", "dem_val", "elig_idx", "elig_rows",
                    "conf_idx", "conf_val", "preemptible", "n_real")

# the donated (S, .) buffers whose in-place reuse the merge kernels exist
# for; small node-state leaves may or may not alias (XLA's choice) and
# prob.n_real is replaced by the n_real argument, so none of those gate.
# prob.preferred is ABSENT from the packed layout (solver/problem.py): the
# hot-path stagings carry no preference plane, so there is nothing to
# alias — and the packed-plane policing below guarantees one can never
# silently reappear.
_MERGE_MUST_ALIAS = ("prob.demand", "prob.eligible", "prob.conflict_ids",
                     "prob.coloc_ids", "assignment")


def _merge_case(rp, pt, tier: str,
                out_shardings: Optional[dict]) -> KernelCase:
    uploads, n_real, statics = rp.merge_inputs(pt, _rich_delta(pt))
    if rp.assignment is None:
        rp.adopt_host(np.zeros(pt.S, np.int32), pt.node_valid, warm=False)
    return KernelCase(
        tier=tier, fn=rp._merge(),
        args=(rp.prob, rp.assignment, *uploads, n_real),
        kwargs=statics,
        arg_names=_MERGE_ARG_NAMES,
        out_shardings=out_shardings)


def _resident_merge_cases() -> list[KernelCase]:
    from .resident import ResidentProblem
    out = []
    for S, N in AUDIT_TIERS:
        pt = _synthetic(S, N)
        rp = ResidentProblem(pt)
        out.append(_merge_case(rp, pt, f"{rp.prob.S}x{N}", None))
    return out


def _sharded_mesh(replicas: int = 1, svc_shards: int = 4):
    from .sharded import tempering_mesh
    return tempering_mesh(replicas, svc_shards)


def _sharded_merge_decl_shardings() -> dict:
    """The layout contract of the sharded merge: every (S, .) plane and
    the assignment stay service-sharded, node state replicated."""
    svc = "P('svc')"
    rep = "P()"
    return {
        "prob.demand": svc, "prob.eligible": svc,
        "prob.conflict_ids": svc, "prob.coloc_ids": svc,
        "prob.capacity": rep, "prob.node_valid": rep,
        "prob.node_topology": rep, "prob.n_real": rep,
        "assignment": svc,
    }


def _sharded_merge_cases() -> list[KernelCase]:
    from .sharded import ShardedResident
    mesh = _sharded_mesh(1, 4)
    out = []
    for S, N in AUDIT_TIERS:
        pt = _synthetic(S, N)
        rp = ShardedResident(pt, mesh=mesh)
        out.append(_merge_case(rp, pt, f"{rp.prob.S}x{N}",
                               _sharded_merge_decl_shardings()))
    return out


_REFINE_ARG_NAMES = ("prob", "seed_assignment", "key", "t0", "t1",
                     "migration_weight")


def _refine_cases() -> list[KernelCase]:
    """api._refine in the warm resident configuration — the steady-state
    dispatch of the churn path, statics derived exactly as api._solve
    derives them (drift there IS the recompile event the contract
    exists to catch)."""
    import jax

    from .api import _refine
    from .resident import ResidentProblem

    out = []
    for S, N in AUDIT_TIERS:
        pt = _synthetic(S, N)
        rp = ResidentProblem(pt)
        rp.adopt_host(np.zeros(pt.S, np.int32), pt.node_valid, warm=False)
        prob = rp.prob
        from .anneal import backend_proposals_per_step, solve_trace_blocks
        proposals = backend_proposals_per_step(prob.S)
        t0_d, t1_d, mw_d = rp.warm_scalars(0.1, 1e-3, 0.5)
        key = jax.random.PRNGKey(0)
        out.append(KernelCase(
            tier=f"{prob.S}x{N}", fn=_refine,
            args=(prob, rp.assignment, key, t0_d, t1_d, mw_d),
            kwargs=dict(chains=1, steps=16, warm=True, anneal_block=1,
                        proposals_per_step=proposals, sharding=None,
                        fused_prerepair=True,
                        prerepair_moves=max(16, min(prob.S, 256)),
                        skip_feasible_polish=True,
                        # the flight-deck buffer length IS a static of
                        # the warm executable (ISSUE 15): auditing with
                        # it pins that telemetry stays compiled-in —
                        # zero extra dispatches, no donation drift
                        trace_blocks=solve_trace_blocks()),
            arg_names=_REFINE_ARG_NAMES,
            out_shardings=None))
    return out


_SUBSOLVE_ARG_NAMES = ("prob", "assignment", "rows", "sub_conflict",
                       "sub_coloc", "load0", "used0", "coloc0", "topo0",
                       "n_sub", "key", "t0", "t1", "migration_weight")


def _subsolve_cases() -> list[KernelCase]:
    """The churn-localized sub-solve (solver/subsolve.py) in its warm
    production configuration: a staged resident problem, a killed-node
    delta, the planner's own closure/frozen-base staging, and the statics
    derived exactly as api._solve derives them."""
    import dataclasses as _dc

    import jax

    from .resident import ProblemDelta, ResidentProblem
    from .subsolve import (ActiveIndex, SubsolveConfig, _subsolve_fn,
                           plan_active, stage_subsolve)

    # permissive gates: the audit instances sit far below the production
    # mini-tier ladder, and the contract pins kernel structure, not the
    # production closure heuristics
    cfg = SubsolveConfig(enabled=True, frac=1.0, min_tier=8)
    out = []
    for S, N in AUDIT_TIERS:
        pt = _synthetic(S, N)
        rp = ResidentProblem(pt)
        rp.adopt_host(np.arange(pt.S, dtype=np.int32) % N, pt.node_valid,
                      warm=False)
        valid = np.asarray(pt.node_valid, dtype=bool).copy()
        valid[0] = False                     # kill one node: evictions
        cur = _dc.replace(pt, node_valid=valid)
        rp.apply_delta(cur, ProblemDelta(node_valid=valid))
        index = ActiveIndex(rp.pt)
        pending = (rp._pending_rows if rp._pending_rows is not None
                   else np.empty(0, dtype=np.int64))
        plan, outcome = plan_active(index, rp.pt, rp._mirror, rp.prob.S,
                                    rp.prob.T, pending, cfg,
                                    G_full=rp.prob.G, Gc_full=rp.prob.Gc)
        assert plan is not None, f"audit sub-plan fell back: {outcome}"
        staged = stage_subsolve(rp, plan)
        from .anneal import backend_proposals_per_step, solve_trace_blocks
        t0_d, t1_d, mw_d = rp.warm_scalars(0.1, 1e-3, 0.5)
        key = jax.random.PRNGKey(0)
        out.append(KernelCase(
            tier=f"{rp.prob.S}x{N}:t{plan.tier}", fn=_subsolve_fn(),
            args=(rp.prob, rp.assignment, *staged, key, t0_d, t1_d, mw_d),
            kwargs=dict(chains=1, steps=16, block=1,
                        proposals_per_step=backend_proposals_per_step(
                            plan.tier),
                        prerepair_moves=max(16, min(plan.tier, 256)),
                        Gc_sub=plan.Gc_sub,
                        trace_blocks=solve_trace_blocks()),
            arg_names=_SUBSOLVE_ARG_NAMES,
            out_shardings=None))
    return out


_ANNEAL_SHARDED_ARG_NAMES = ("prob", "init_assignment", "key")


def _anneal_sharded_cases() -> list[KernelCase]:
    """sharded.anneal_sharded on a tempered 2x4 mesh with return_stats
    (the solve_sharded production shape): assignment stays svc-sharded,
    every stat scalar replicated."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .sharded import ShardedResident, anneal_sharded

    from .anneal import solve_trace_blocks

    mesh = _sharded_mesh(2, 4)
    stats_fields = ("assignment", "sweeps", "capacity", "conflicts",
                    "eligibility", "skew", "soft", "swap_attempts",
                    "swap_accepts", "telemetry")
    decl = {f: ("P('svc')" if f == "assignment" else "P()")
            for f in stats_fields}
    out = []
    for S, N in AUDIT_TIERS:
        pt = _synthetic(S, N)
        rp = ShardedResident(pt, mesh=mesh)
        rp.adopt_host(np.zeros(pt.S, np.int32), pt.node_valid, warm=False)
        t0_d, t1_d, lad_d = rp.warm_scalars(0.1, 1e-3, 1.3)
        key = jax.device_put(jax.random.PRNGKey(0),
                             NamedSharding(mesh, P()))
        out.append(KernelCase(
            tier=f"{rp.prob.S}x{N}", fn=anneal_sharded,
            args=(rp.prob, rp.assignment, key),
            kwargs=dict(steps=16, t0=t0_d, t1=t1_d,
                        proposals_per_step=None, mesh=mesh, block=8,
                        ladder=lad_d, exchange_every=1, return_stats=True,
                        trace_blocks=solve_trace_blocks()),
            arg_names=_ANNEAL_SHARDED_ARG_NAMES,
            out_shardings=decl))
    return out


def hot_path_kernels() -> list[KernelContract]:
    """The registry the auditor iterates. Order is the order findings
    print in; keep the single-chip pair first (they audit without a
    mesh)."""
    return [
        KernelContract(
            name="resident.merge",
            module="fleetflow_tpu.solver.resident",
            qualname="_merge_fn.merge",
            cases=_resident_merge_cases,
            must_alias=_MERGE_MUST_ALIAS),
        KernelContract(
            name="refine.warm",
            module="fleetflow_tpu.solver.api",
            qualname="_refine",
            cases=_refine_cases),
        KernelContract(
            name="subsolve.localized",
            module="fleetflow_tpu.solver.subsolve",
            qualname="_subsolve_fn.subsolve",
            # deliberately NO donation (must_alias empty): the original
            # assignment must outlive the dispatch — a gate-rejected
            # sub-solve re-seeds the full path from it. The contract
            # pins the ABSENCE: a donated_params entry appearing here is
            # a reviewed golden diff.
            cases=_subsolve_cases),
        KernelContract(
            name="sharded.merge",
            module="fleetflow_tpu.solver.sharded",
            qualname="_merge_fn_sharded.merge",
            cases=_sharded_merge_cases,
            must_alias=_MERGE_MUST_ALIAS,
            needs_devices=4),
        KernelContract(
            name="sharded.anneal",
            module="fleetflow_tpu.solver.sharded",
            qualname="anneal_sharded",
            cases=_anneal_sharded_cases,
            needs_devices=8),
    ]
