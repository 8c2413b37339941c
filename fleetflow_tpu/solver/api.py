"""Top-level solve pipeline.

    ProblemTensors ──prepare──▶ DeviceProblem (staged once)
        ──greedy seed (lax.scan FFD)──▶ assignment
        ──perturbed chain fan-out──▶ (C, S)
        ──anneal (vmapped chains, mesh-shardable)──▶ (C, S)
        ──exact rank + pick best──▶ assignment
        ──host repair backstop──▶ SolveResult (zero violations or infeasible)

`mesh=` shards the chain axis over a jax.sharding.Mesh so chains run
data-parallel across devices (the "pmapped independent annealing chains" of
the north star); with mesh=None everything runs on one device.
"""

from __future__ import annotations

import contextlib
import dataclasses
from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .anneal import (TRACE_COLS, anneal_adaptive_states,
                     chain_states_from_assignment, prerepair_state_counted,
                     solve_trace_blocks)
from .buckets import (bucket_config, pad_assignment, pad_problem_tiers,
                      record_bucket, soft_score_host, stage_problem_tiers,
                      _env_flag)
from .greedy import greedy_place, greedy_place_batched, placement_order
from .kernels import _skew_excess, soft_score, violation_stats
from .problem import DeviceProblem, prepare_problem
from .repair import RepairResult, repair, verify
from .resident import ResidentProblem, transfer_guard_ctx
from ..lower.tensors import ProblemTensors
from ..obs import get_logger, kv, phase, profile_trace
from ..obs.metrics import REGISTRY, SOLVE_SECONDS_BUCKETS

log = get_logger("solver")

# metric catalog: docs/guide/10-observability.md
_M_SOLVES = REGISTRY.counter(
    "fleet_solver_solves_total", "Placement solves by backend and start mode",
    labels=("backend", "warm"))
_M_SOLVE_S = REGISTRY.histogram(
    "fleet_solver_solve_duration_seconds", "End-to-end solve() wall time",
    buckets=SOLVE_SECONDS_BUCKETS)
_M_SWEEPS = REGISTRY.counter(
    "fleet_solver_sweeps_total", "Annealing sweeps run across all solves")
_M_ACCEPTED = REGISTRY.counter(
    "fleet_solver_proposals_accepted_total",
    "Metropolis proposals accepted (adaptive anneal)")
_M_COMPILES = REGISTRY.counter(
    "fleet_solver_compile_events_total",
    "XLA compilations of the fused refine pipeline")
_M_VIOL = REGISTRY.gauge(
    "fleet_solver_violations",
    "Hard violations of the most recent solve (post-repair)")
_M_PRE_VIOL = REGISTRY.gauge(
    "fleet_solver_pre_repair_violations",
    "Device-solver violations of the most recent solve before host repair")
_M_BUCKET = REGISTRY.counter(
    "fleet_solver_bucket_solves_total",
    "Bucketed solves by executable reuse (hit = padded shape already "
    "compiled for in this process)", labels=("hit",))
_M_PAD_WASTE = REGISTRY.gauge(
    "fleet_solver_bucket_pad_waste_ratio",
    "Phantom fraction of the most recent bucketed solve's service rows")
_M_SPREAD = REGISTRY.counter(
    "fleet_solver_spread_solves_total",
    "Solves of a stage that carries a spread constraint (max_skew > 0)")
_M_SPREAD_EXCESS = REGISTRY.counter(
    "fleet_solver_spread_excess_total",
    "Excess of (max - min) rows per topology domain over max_skew, summed "
    "over spread solves: after the seed, in the device's winner before "
    "the host touches it, and in what is returned", labels=("at",))
_M_SPREAD_REPAIR = REGISTRY.counter(
    "fleet_solver_spread_repair_moves_total",
    "Rows the host's repair moved in solves of spread stages (above 0 "
    "the host finished the annealer's work)")
_M_INFLIGHT = REGISTRY.gauge(
    "fleet_solver_dispatches_in_flight",
    "Solver anneal dispatches currently executing (full fused + "
    "localized sub-solve) — deep-sampled by the obs collector")

DEFAULT_STEPS = 128   # batched sweeps (anneal.default_proposals_per_step wide)

__all__ = ["solve", "SolveResult", "make_chain_inits"]

CHAIN_AXIS = "chains"


@contextlib.contextmanager
def _dispatch_scope(label: str):
    """Every hot anneal dispatch runs inside this scope: the in-flight
    gauge the obs collector deep-samples, and the `solver.dispatch.<label>`
    phase, which is how the dispatch shows in a profiler trace
    (`fleet/solver.dispatch.refine`) beside the device's own events."""
    _M_INFLIGHT.inc()
    try:
        with phase("solver.dispatch." + label):
            yield
    finally:
        _M_INFLIGHT.dec()


@dataclass
class SolveResult:
    assignment: np.ndarray          # (S,) node index per service
    stats: dict                     # exact violation stats (host-verified)
    soft: float                     # soft score of the final assignment
    feasible: bool
    moves_repaired: int = 0
    # violations of the device solver's own best assignment, before the host
    # repair backstop touched it — the honesty metric (VERDICT round 1: "we
    # cannot tell whether the device solver or the host numpy repair backstop
    # is doing the real work"). 0 means the TPU solve was already feasible.
    pre_repair_violations: int = 0
    timings_ms: dict = field(default_factory=dict)
    chains: int = 0
    steps: int = 0
    # the proposal width the anneal actually ran (after backend defaults),
    # so artifacts report the config that produced the number
    proposals_per_step: int = 0
    # Metropolis moves applied across all chains (-1 = not counted: the
    # mesh-sharded path). With sweeps/chains/proposals_per_step this
    # yields the acceptance rate the anneal ran at.
    accepted_moves: int = -1
    # shape bucketing applied to this solve (solver/buckets.py), or None
    # for an exact-shape solve: {"orig_S", "padded_S", "pad_waste", "hit"}
    bucket: Optional[dict] = None
    # churn pre-repair ran as a fused on-device prologue inside the anneal
    # dispatch (anneal.prerepair_state): true of every warm solve, false of
    # a cold one
    fused_prerepair: bool = False
    # pod-scale sharded solves (solver/sharded.solve_sharded) report their
    # parallel-tempering config + replica-exchange outcome here:
    # {replicas, ladder, exchange_every, swap_attempts, swap_accepts}
    tempering: Optional[dict] = None
    # churn-localized sub-solve (solver/subsolve.py): {rows, tier,
    # affected, outcome, ms} when a localized dispatch ran (outcome
    # "localized" = committed by the exact gate, "fallback_infeasible" =
    # the full fused path re-ran), None when the solve was full-problem
    subsolve: Optional[dict] = None
    # in-dispatch flight-deck telemetry (docs/guide/10, "solver flight
    # deck"): {"schema": TRACE_COLS, "blocks": [[...], ...] one row per
    # sweep-block, "init": {violations, soft} of the prologue/seed,
    # "prerepair_moves": fused-prologue relocations, "exit_sweep",
    # "path": "full" | "subsolve"}. None when the dispatch ran with
    # FLEET_SOLVE_TRACE_BLOCKS=0.
    telemetry: Optional[dict] = None

    @property
    def acceptance_rate(self) -> float:
        """Accepted / proposed, or -1.0 when acceptance was not tracked."""
        proposed = self.steps * self.chains * self.proposals_per_step
        if self.accepted_moves < 0 or proposed <= 0:
            return -1.0
        return self.accepted_moves / proposed

    @property
    def violations(self) -> int:
        return int(self.stats["total"])


def make_chain_inits(prob: DeviceProblem, seed_assignment: jax.Array,
                     chains: int, key: jax.Array,
                     perturb_frac: float = 0.08) -> jax.Array:
    """(C, S) chain initializations: chain 0 is the pure greedy seed, the
    rest perturb a random `perturb_frac` of services onto random nodes for
    basin diversity."""
    def one(k):
        k1, k2 = jax.random.split(k)
        mask = jax.random.uniform(k1, (prob.S,)) < perturb_frac
        rand = jax.random.randint(k2, (prob.S,), 0, prob.N, dtype=jnp.int32)
        return jnp.where(mask, rand, seed_assignment)

    keys = jax.random.split(key, chains)
    inits = jax.vmap(one)(keys)
    return inits.at[0].set(seed_assignment)


@partial(jax.jit, static_argnames=("chains", "steps", "warm",
                                   "anneal_block", "proposals_per_step",
                                   "sharding", "fused_prerepair",
                                   "prerepair_moves",
                                   "skip_feasible_polish", "trace_blocks"))
def _refine(prob: DeviceProblem, seed_assignment: jax.Array, key: jax.Array,
            t0: float, t1: float, migration_weight: float, *,
            chains: int, steps: int, warm: bool, anneal_block: int = 8,
            proposals_per_step: Optional[int] = None,
            sharding=None, fused_prerepair: bool = False,
            prerepair_moves: int = 0, skip_feasible_polish: bool = False,
            trace_blocks: int = 0):
    """The fused device pipeline after the seed: chain fan-out, annealing,
    per-chain exact cost, best-chain selection, exact violation stats and the
    soft score of the winner — ONE dispatch, five scalars + the winning
    assignment come back. Every eager op between the seed and the host-side
    repair decision is a separate dispatch plus a host round-trip, so all of
    it lives in a single XLA program.

    `warm` folds the migration-stickiness bonus in on-device: the previous
    placement earns `migration_weight` soft units per service for staying
    put, except on dead/ineligible nodes (churn-forced moves stay free).
    `sharding` (static, hashable NamedSharding) lays the chain axis over a
    mesh so chains anneal data-parallel across devices.

    `fused_prerepair` runs the churn pre-repair as an on-device prologue
    (anneal.prerepair_state, bounded by `prerepair_moves`) before the chain
    fan-out: services stranded on dead/ineligible nodes are relocated
    inside THIS dispatch, so a warm reschedule pays no host repair pass
    and no seed re-upload. The stickiness bonus is computed from the
    pre-repair seed (staying put is rewarded at the PREVIOUS placement;
    forced moves stay free either way)."""
    # named scopes are metadata: the profiler shows the device's ops under
    # the name solver/contracts.py registers, the program is the same
    scope = "refine.warm" if fused_prerepair else "refine.cold"
    if warm:
        # stickiness rides the proposal delta + soft ranking on the fly
        # (problem.sticky_prev/sticky_w) instead of materializing a
        # bonused (S, N) preferred plane — three full-plane passes,
        # ~37 ms of the warm dispatch at 10k x 1k, for the same
        # semantics: staying on the previous still-eligible node earns
        # migration_weight; churn-forced moves stay free
        prob_a = dataclasses.replace(
            prob, sticky_prev=seed_assignment,
            sticky_w=jnp.asarray(migration_weight, jnp.float32))
    else:
        prob_a = prob
    init_states = None
    prerepair_applied = jnp.int32(0)
    if fused_prerepair:
        with jax.named_scope(scope + "/prerepair"):
            st0 = chain_states_from_assignment(prob_a, seed_assignment)
            st0, prerepair_applied = prerepair_state_counted(
                prob_a, st0, prerepair_moves)
            seed_assignment = st0.assignment
            if sharding is None:
                # warm chains are not perturbed: every chain starts from the
                # repaired state, so broadcast the prologue's carried state
                # instead of a per-chain scatter rebuild inside the anneal
                init_states = jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(x[None], (chains,) + x.shape),
                    st0)
    with jax.named_scope(scope + "/anneal"):
        k_init, k_anneal = jax.random.split(key)
        # warm starts are NOT perturbed: scattering 8% of a known-good placement
        # is anti-sticky by construction, and with adaptive early exit a
        # perturbed chain can win before restoring its perturbed services.
        # Chains still diverge through their proposal RNG streams.
        inits = make_chain_inits(prob_a, seed_assignment, chains, k_init,
                                 perturb_frac=0.0 if warm else 0.08)
        if sharding is not None:
            inits = jax.lax.with_sharding_constraint(inits, sharding)
        # the anneal tracks each chain's best-ever state with its
        # (violations, soft) as SEPARATE scalars; chain ranking is
        # feasibility-first — a folded W_HARD*v+soft argmin would both
        # prefer an infeasible chain whose warm-bonused soft undercuts
        # W_HARD (aggregate bonus gap is unbounded in the fleet size) AND
        # round the soft tie-break away in float32 at large v
        (best_assign_c, best_viol_c, best_soft_c, sweeps_run, accepted_c,
         telem) = anneal_adaptive_states(
                prob_a, inits, k_anneal, max_steps=steps, block=anneal_block,
                t0=t0, t1=t1,
                proposals_per_step=proposals_per_step,
                init_states=init_states,
                exit_on_feasible_init=skip_feasible_polish,
                trace_blocks=trace_blocks)
        accepted = accepted_c.sum()
        # exact lexicographic (violations, soft): among minimal-violation
        # chains (0 when any chain saw feasibility), best soft wins
        min_viol = best_viol_c.min()
        best = jnp.argmin(jnp.where(best_viol_c == min_viol,
                                    best_soft_c, jnp.inf))
        winner = best_assign_c[best]
    # The WINNER's stats are recomputed with the exact from-scratch kernels
    # (one scatter rebuild, ~5 ms): the carried float32 load accumulates
    # .add(+d)/.add(-d) round-off over thousands of proposals, and the
    # feasibility gate that decides whether the host repair backstop runs
    # must not trust drifted state. Chain RANKING above stays carried-state
    # (cheap, and an argmin among near-equals tolerates drift).
    #
    # EXCEPTION (ROADMAP item 2 shave): on the resident warm path
    # (skip_feasible_polish), a 0-sweep exit means ZERO proposals were
    # applied — the carried best state IS the prologue's scratch-built
    # state, so its violation count is exact, not drifted. When it says
    # feasible, every stat component is exactly 0 and the winner's soft
    # was scratch-built by the same prologue: trust them and skip the
    # final rebuild (~12 ms of the remaining warm CPU floor at 10k x 1k).
    with jax.named_scope(scope + "/polish"):
        if skip_feasible_polish:
            best_viol = best_viol_c[best]
            trust = (sweeps_run == 0) & (best_viol == 0)
            zero = jnp.float32(0)
            stats, soft = jax.lax.cond(
                trust,
                lambda: ({"capacity": zero, "conflicts": zero,
                          "eligibility": zero, "skew": zero, "total": zero},
                         best_soft_c[best]),
                lambda: (violation_stats(prob, winner),
                         soft_score(prob, winner)))
        else:
            stats = violation_stats(prob, winner)
            soft = soft_score(prob, winner)
    telem = dict(telem, prerepair_moves=prerepair_applied)
    if prob.max_skew > 0:
        # what the seed (a warm start's, after the prologue) left the
        # sweeps to do; rides the one fetch
        telem["seed_skew"] = _skew_excess(prob, seed_assignment)
    return winner, stats, soft, sweeps_run, accepted, telem


def solve(pt: ProblemTensors, **kw) -> SolveResult:
    """Solve a placement instance end to end (see _solve for parameters).
    When FLEET_PROFILE_DIR is set the whole solve is captured as a
    jax.profiler trace (obs.profile_trace).

    Pod-scale routing: instances of sharded.SHARDED_MIN_CELLS cells and
    more (or any instance under FLEET_SHARDED=1) with >= 2 devices
    visible solve through the mesh-sharded resident path
    (solver/sharded.solve_sharded — service-axis sharding + parallel
    tempering) instead of the single-chip pipeline; explicit staging
    kwargs (prob/resident/mesh) always pin the call to this path."""
    # idempotent: callers that never pass through platform.init_platform
    # (library embedding, tests) still get the persistent compile cache.
    # The self-check runs HERE: the probe compiles against the backend
    from ..platform import maybe_enable_compile_cache, verify_compile_cache
    if maybe_enable_compile_cache() is not None:
        verify_compile_cache()
    with profile_trace("solve"):
        from .sharded import maybe_solve_sharded
        res = maybe_solve_sharded(pt, **kw)
        if res is not None:
            return res
        return _solve(pt, **kw)


def _solve(pt: ProblemTensors, *,
           chains: Optional[int] = None, steps: int = DEFAULT_STEPS,
           seed: int = 0, do_repair: bool = True,
           mesh: Optional[Mesh] = None,
           prob: Optional[DeviceProblem] = None,
           init_assignment: Optional[np.ndarray] = None,
           t0: float = 1.0, t1: float = 1e-3,
           migration_weight: float = 0.5,
           seed_impl: Optional[str] = None,
           seed_batch: int = 256,
           seed_rounds: int = 2,
           anneal_block: int = 1,
           warm_block: int = 1,
           proposals_per_step: Optional[int] = None,
           bucket: Optional[bool] = None,
           resident: Optional[ResidentProblem] = None,
           resident_warm: bool = False,
           overlap_host_work=None) -> SolveResult:
    """Solve a placement instance end to end.

    `init_assignment` warm-starts from a previous solve (streaming reschedule
    path: BASELINE config 5 — keep the old placement, anneal the delta).
    `migration_weight` makes warm starts sticky: each service pays that much
    soft score for leaving its previous node, so a reschedule moves only what
    churn forces (the analog of not restarting healthy containers on an
    unrelated node failure). `prob` reuses an already-staged DeviceProblem
    across re-solves.

    `seed_impl` picks the greedy seed: "scan" (one lax.scan step per service
    — exact FFD, best when the device is fast but dispatch is cheap),
    "batched" (ceil(S/256)-deep batch placement — the accelerator shape:
    sequential depth is what a TPU pays for, per-step width is nearly
    free), "native" (host C++ FFD via native/placer.cpp — the violation-
    free floor, ~82 ms at 10k x 1k; VERDICT r2 item 5), "partitioned"
    (service slices x disjoint node subsets, one full-capacity native FFD
    each — ~22 ms at 10k x 1k at equal soft, greedy.partitioned_seed), or
    None to choose by backend: the CPU fallback prefers "partitioned" at
    fleet scale (S*N >= 1e6), "native" below it, "scan" when the library
    is absent; accelerators use "batched".

    `warm_block` is the adaptive-exit check granularity for warm starts:
    a churn reschedule starts one node-event away from feasible and the
    targeted proposal half re-places the dead node's services within a
    sweep or two, so checking every `warm_block` sweeps (instead of the
    cold path's `anneal_block`) exits earlier. Since best-ever tracking
    (r5) decoupled block size from quality, both defaults are small —
    the block is purely a latency/check-granularity knob and the exit
    keys on seen-feasibility, so a fine block exits at the earliest
    feasible boundary.

    `chains=None` resolves by backend: 1 on CPU (vmapped chains serialize
    on host, and the feasible-by-construction seed means extra chains buy
    nothing; measured r4) and 2 on accelerators (measured r5 on TPU:
    2 chains 102.6 ms vs 4 chains 123.9 ms at equal soft, 10k x 1k).

    `bucket` pads the problem to a shape tier (solver/buckets.py) so
    fleets whose sizes drift within one tier reuse the compiled
    executable instead of paying the XLA compile cliff. None defers to
    the environment (FLEET_BUCKET=1 opts direct solves in; the scheduler
    path passes True and FLEET_BUCKET=0 force-disables). Spread
    constraints (max_skew > 0) bucket too: padded problems carry a traced
    `n_real` and the kernels keep phantom rows out of topology/skew
    accounting. Violations/soft are always reported against the REAL rows
    (numpy-exact), and the returned assignment never contains phantoms.

    `resident` + `resident_warm=True` is the DELTA-STAGED warm path
    (solver/resident.py): the padded problem and the previous assignment
    are already on device (CP churn arrived as on-device deltas), the
    seed never crosses the host boundary, pre-repair runs fused inside
    the anneal dispatch, and the whole dispatch can run under
    `jax.transfer_guard("disallow")` (FLEET_TRANSFER_GUARD=disallow) to
    prove no problem tensor moved. `overlap_host_work` (zero-arg
    callable) runs between the async solve dispatch and the result
    fetch — host work (e.g. re-lowering a changed fleet) overlaps the
    in-flight anneal.
    """
    timings: dict[str, float] = {}
    if chains is None:
        chains = 1 if jax.default_backend() == "cpu" else 2
    resident_warm = bool(resident is not None and resident_warm
                         and resident.assignment is not None)

    with phase("solver.stage") as ph_stage:
        binfo = None
        staged_cold = False
        if prob is None:
            if resident is not None:
                prob = resident.prob
            else:
                # cold staging: the bucketed path stages DIRECTLY at the
                # padded tier shape through the host arenas
                # (buckets.stage_problem_tiers) — pure memcpy + upload, no
                # jnp.pad/fill ops, so a fresh process pays zero staging
                # compiles and restages of the same tier reuse the buffers
                if bucket is None:
                    bucket = _env_flag("FLEET_BUCKET", False)
                cfg0 = bucket_config()
                if bucket and cfg0.enabled:
                    prob, binfo = stage_problem_tiers(pt, cfg0)
                    staged_cold = True
                else:
                    prob = prepare_problem(pt)
        orig_prob = prob  # soft score is reported against the un-bonused problem

        # ---- shape bucketing (solver/buckets.py) -----------------------------
        # Round the churn-sensitive extents up to tiers so a fleet drifting a
        # few services reuses the compiled executable. A caller that staged a
        # pre-padded DeviceProblem (sched/tpu.py resident state) is honored
        # as-is: pad_problem_tiers is idempotent, so the staged object passes
        # through unchanged and re-solves never re-pad.
        if bucket is None:
            bucket = _env_flag("FLEET_BUCKET", False) or prob.S != pt.S
        # a resident staging carries the bucket config it was padded under;
        # honoring it keeps pad_problem_tiers idempotent even if the tier
        # ladder env knobs changed since cold staging
        cfg = resident.cfg if resident is not None else bucket_config()
        if bucket and cfg.enabled and not staged_cold:
            prob, binfo = pad_problem_tiers(prob, cfg)
        if binfo is not None:
            binfo.orig_S = pt.S   # a pre-padded staging reports the REAL rows
        bucketed = binfo is not None and prob.S != pt.S
        if resident_warm:
            # delta staging happened in ResidentProblem.apply_delta (donated
            # on-device merge); report it where stage_ms reports cold staging
            timings["delta_stage_ms"] = resident.consume_delta_ms()
    timings["stage_ms"] = ph_stage.ms

    with phase("solver.seed") as ph_seed:
        warm = init_assignment is not None or resident_warm
        # a FACTORY, not a context instance: jax.transfer_guard is a one-shot
        # generator CM, and a sub-solve the gate rejects dispatches twice
        # (mini attempt, then the full fused path) — each under its own guard
        guard_ctx = (transfer_guard_ctx if resident_warm
                     else contextlib.nullcontext)
        if resident_warm:
            # seed already resident: the previous padded winner, phantoms
            # re-parked at delta time; nothing crosses the host boundary
            seed_assignment = resident.assignment
            t0 = min(t0, 0.1)  # warm start: refine, don't re-scramble
        elif warm:
            seed_np = np.asarray(init_assignment, dtype=np.int32)
            if bucketed:
                seed_np = pad_assignment(seed_np, prob.S, pt.node_valid)
            seed_assignment = jnp.asarray(seed_np, dtype=jnp.int32)
            t0 = min(t0, 0.1)  # warm start: refine, don't re-scramble
        else:
            if seed_impl is None:
                if pt.max_skew > 0:
                    # the host FFDs never read node_topology; the batched
                    # seed deals a spread stage's rows to its domains
                    seed_impl = "batched"
                elif jax.default_backend() == "cpu":
                    # nobuild: auto-pick must never trigger a synchronous make
                    # inside the timed solve; explicit seed_impl="native" may
                    from ..native.lib import available_nobuild
                    if available_nobuild():
                        # partitioned FFD past the crossover where the O(S*N/4)
                        # work cut beats the slicing overhead — measured r5 at
                        # 10k x 1k: 82.2 -> 21.8 ms at EQUAL soft (1.3527 vs
                        # 1.3521) and 0 violations (x2: 35.2 ms @ 1.3502, x8:
                        # 12.6 ms @ 1.3547 — x4 is the quality-neutral knee)
                        seed_impl = ("partitioned" if pt.S * pt.N >= 1_000_000
                                     else "native")
                    else:
                        seed_impl = "scan"
                else:
                    seed_impl = "batched"
            if seed_impl not in ("scan", "batched", "native", "partitioned"):
                raise ValueError(f"seed_impl must be 'scan', 'batched', "
                                 f"'native', 'partitioned' or None, "
                                 f"got {seed_impl!r}")
            if seed_impl in ("native", "partitioned"):
                # Host C++ FFD (whole-instance, or service-slices x disjoint
                # node subsets): feasible in tens of ms at 10k x 1k, so the
                # anneal only buys soft score (the CPU-fallback design point).
                try:
                    if seed_impl == "partitioned":
                        from .greedy import partitioned_seed
                        host_assignment = partitioned_seed(pt, 4)
                    else:
                        from ..native.lib import native_place
                        host_assignment, _ = native_place(
                            pt.demand, pt.capacity, pt.eligible, pt.node_valid,
                            pt.dep_depth, pt.port_ids, pt.volume_ids,
                            pt.anti_ids, strategy=pt.strategy.value)
                    if bucketed:
                        host_assignment = pad_assignment(
                            host_assignment, prob.S, pt.node_valid)
                    seed_assignment = jnp.asarray(host_assignment,
                                                  dtype=jnp.int32)
                except (RuntimeError, OSError):
                    # corrupt/stale .so: degrade to the device scan seed rather
                    # than fail the solve (the .so existing was only a hint)
                    log.warning("native seed unavailable at call time; "
                                "falling back to scan")
                    seed_impl = "scan"
            if seed_impl not in ("native", "partitioned"):
                order_np = placement_order(
                    pt.demand, pt.dep_depth,
                    np.asarray(prob.conflict_ids)[: pt.S, :])
                if bucketed:
                    # phantoms place last: zero demand + eligible everywhere
                    # means the greedy scan parks them on any valid node
                    order_np = np.concatenate(
                        [np.asarray(order_np),
                         np.arange(pt.S, prob.S, dtype=np.int64)])
                order = jnp.asarray(order_np)
                if seed_impl == "scan":
                    seed_assignment = greedy_place(prob, order)
                else:
                    seed_assignment = greedy_place_batched(prob, order,
                                                           batch=seed_batch,
                                                           rounds=seed_rounds)
            # no block here: the refine dispatch queues behind the seed on-device
            # (device impls), so seed_ms is dispatch time only and the device
            # runs back-to-back; the native impl is synchronous host work.
    timings["seed_ms"] = ph_seed.ms

    if proposals_per_step is None:
        # derived from the PADDED row count: proposals_per_step is a static
        # jit argument, so deriving it from the exact S would recompile on
        # every fleet-size drift and defeat the bucketing (the clamps make
        # this a no-op at fleet scale). CPU sweep cost is ~linear in
        # proposals (no free width the way the MXU gives it): a 64-wide
        # sweep costs ~25 ms at 10k x 1k vs ~100 ms at the 256 TPU knee,
        # and with a feasible seed the sweeps only buy soft polish
        # (measured in VERDICT r2 item 5) — backend_proposals_per_step
        # holds the knee for this path AND the sub-solve's.
        from .anneal import backend_proposals_per_step
        proposals_per_step = backend_proposals_per_step(prob.S)
    # flight-deck buffer length: a STATIC of every refine/subsolve
    # executable (compiled in, like proposals_per_step), so the telemetry
    # rides the dispatch with zero extra compiles and zero host
    # transfers; FLEET_SOLVE_TRACE_BLOCKS=0 restores the pre-telemetry
    # program (the parity test's reference leg)
    trace_blocks = solve_trace_blocks()

    with phase("solver.anneal") as ph_anneal:
        sharding = (NamedSharding(mesh, P(CHAIN_AXIS, None))
                    if mesh is not None else None)
        # compile-event telemetry: the jit cache only grows when XLA compiled
        # a new variant of the fused pipeline, which is exactly the event an
        # operator watching solve latency needs to see (a recompile can turn a
        # 100 ms reschedule into seconds — VERDICT r4 weak #1)
        # a warm start's churn pre-repair is FUSED into the anneal dispatch
        # (anneal.prerepair_state): no host work, no timing of its own. Its
        # budget is a static bound the while_loop exits early from, derived
        # from the PADDED rows so it cannot break bucket reuse
        prerepair_moves = max(16, min(prob.S, 256)) if warm else 0
        # ---- churn-localized sub-solve plan (solver/subsolve.py) ------------
        # when the resident delta path knows the affected set and its
        # constraint closure is small, the anneal runs over a mini tier of
        # gathered rows instead of the full problem; the exact full-problem
        # gate below decides whether the localized result commits
        sub_plan = None
        if resident_warm and mesh is None:
            sub_plan = resident.take_active_plan()
        if binfo is not None:
            # hit = this process already ran the fused pipeline at these
            # jit-relevant extents, so the dispatch below will not recompile
            binfo.hit = record_bucket(
                (prob.S, prob.N, prob.G, prob.Gc, prob.T, prob.strategy,
                 prob.max_skew, prob.conflict_ids.shape[1],
                 prob.coloc_ids.shape[1], chains, steps,
                 bool(warm and migration_weight > 0),
                 min(warm_block, anneal_block) if warm else anneal_block,
                 proposals_per_step, prerepair_moves, resident_warm,
                 prob.n_real is not None, trace_blocks,
                 # plane layout is part of the executable identity: a packed
                 # and a dense staging (or absent vs present preference) are
                 # different treedefs/dtypes, hence different XLA programs
                 str(prob.eligible.dtype), prob.preferred is not None,
                 # a localized dispatch is its own executable, keyed by the
                 # mini tier and compact id ladders (solver/subsolve.py)
                 (sub_plan.tier, sub_plan.G_sub, sub_plan.Gc_sub)
                 if sub_plan is not None else None))
            _M_BUCKET.inc(hit="true" if binfo.hit else "false")
            _M_PAD_WASTE.set(binfo.pad_waste)
        # the PRNG key is minted BEFORE the transfer guard arms: it is not a
        # problem tensor, and the guard's job is to prove the big (S, ·)
        # planes and the seed assignment never cross the host boundary
        key = jax.random.PRNGKey(seed)
        if resident_warm:
            t0_d, t1_d, mw_d = resident.warm_scalars(t0, t1, migration_weight)
        else:
            t0_d, t1_d, mw_d = t0, t1, migration_weight
        refine_kw = dict(
            chains=chains, steps=steps,
            warm=bool(warm and migration_weight > 0),
            anneal_block=min(warm_block, anneal_block) if warm else anneal_block,
            proposals_per_step=proposals_per_step, sharding=sharding,
            fused_prerepair=warm, prerepair_moves=prerepair_moves,
            # the resident delta path skips the 1-block soft polish when the
            # fused prologue already landed feasible: stickiness rejects
            # nearly all polish moves, so the sweep bought latency only. The
            # host warm path keeps its 1-block polish.
            skip_feasible_polish=resident_warm,
            trace_blocks=trace_blocks)
        cache_before = _refine._cache_size()
        sub_info = None
        sub_cache_before = 0
        if sub_plan is not None:
            from .anneal import backend_proposals_per_step
            from .subsolve import (record_outcome, record_subsolve_ms,
                                   stage_subsolve, subsolve_cache_size,
                                   subsolve_dispatch)
            sub_cache_before = subsolve_cache_size()
            with phase("solver.subsolve") as ph_sub:
                # small per-burst uploads (closure rows, compact ids, frozen
                # base) stage BEFORE the guard arms — the merge-upload discipline
                staged = stage_subsolve(resident, sub_plan)
                sub_props = backend_proposals_per_step(sub_plan.tier)
                with guard_ctx(), _dispatch_scope("subsolve"):
                    (best_assignment, dstats, dsoft, sweeps_run, accepted,
                     dtelem) = subsolve_dispatch(
                            prob, resident.assignment, staged, sub_plan, key,
                            t0_d, t1_d, mw_d, chains=chains, steps=steps,
                            block=min(warm_block, anneal_block),
                            proposals_per_step=sub_props,
                            trace_blocks=trace_blocks,
                            overfull=resident.pt.priced)
                if overlap_host_work is not None:
                    # the gate decision below synchronizes with the in-flight
                    # sub dispatch, so the overlapped host work must run NOW —
                    # after it, the async window is gone
                    with phase("solver.overlap_host") as ph_ov:
                        overlap_host_work()
                    timings["overlap_host_ms"] = ph_ov.ms
                    overlap_host_work = None
                # the exact full-problem gate rules: feasible commits the
                # scattered result; infeasible discards it and the full fused
                # path re-runs from the ORIGINAL seed (the kernel does not
                # donate, so the previous assignment — stranded rows intact, the
                # battle-tested prerepair shape — is still alive)
                # the first point at which the host blocks on the device
                with phase("solver.fetch"):
                    sub_feasible = float(jax.device_get(dstats["total"])) == 0
            # disjoint phases: overlapped host work is reported under
            # overlap_host_ms, not double-counted into the sub-solve timing
            timings["subsolve_ms"] = (ph_sub.ms
                                      - timings.get("overlap_host_ms", 0.0))
            record_subsolve_ms(timings["subsolve_ms"])
            outcome = "localized" if sub_feasible else "fallback_infeasible"
            record_outcome(outcome)
            sub_info = {"rows": sub_plan.n_sub, "tier": sub_plan.tier,
                        "affected": sub_plan.affected, "outcome": outcome,
                        "ms": round(timings["subsolve_ms"], 2)}
            if sub_feasible:
                resident.adopt(best_assignment)
            else:
                sub_plan = None     # seed_assignment still holds the original
        if sub_plan is None:
            # the proof: under FLEET_TRANSFER_GUARD=disallow any host->device
            # transfer inside the warm dispatch raises (every input above is
            # already resident; statics hash, they don't transfer); off the
            # resident path the guard is a nullcontext
            with guard_ctx(), _dispatch_scope("refine"):
                (best_assignment, dstats, dsoft, sweeps_run, accepted,
                 dtelem) = _refine(
                    prob, seed_assignment, key, t0_d, t1_d, mw_d, **refine_kw)
            if resident is not None:
                # the padded winner stays on device as the next warm seed
                resident.adopt(best_assignment)
        compile_events = _refine._cache_size() - cache_before
        if sub_info is not None:
            from .subsolve import subsolve_cache_size
            compile_events += subsolve_cache_size() - sub_cache_before
        if overlap_host_work is not None:
            # async dispatch: the solve is in flight on device; do host work
            # (e.g. lower/ re-lowering of changed fleets) before blocking
            with phase("solver.overlap_host") as ph_ov:
                overlap_host_work()
            timings["overlap_host_ms"] = ph_ov.ms
        # ONE transfer for everything the host decision needs — the
        # flight-deck telemetry rides it (no extra fetch, no extra dispatch)
        with phase("solver.fetch"):
            (assignment, dstats, soft, sweeps_run, accepted,
             htelem) = jax.device_get(
                (best_assignment, dstats, dsoft, sweeps_run, accepted, dtelem))
        # FORCE a host copy: on the CPU backend device_get returns a VIEW of
        # the device buffer, and the resident path DONATES that buffer into
        # the next burst's merge/sub-solve dispatch — without the copy every
        # retained SolveResult.assignment (scheduler slot, bench bookkeeping)
        # is clobbered in place when XLA reuses the storage (observed as
        # garbage node indices once the localized kernel aliased it to a
        # float scratch buffer)
        assignment = np.array(assignment, copy=True)
        # the padded winner, host side: the sub-solve mirror rides this fetch
        # (the result crossed the boundary anyway — no extra transfer)
        padded_host = assignment
        if bucketed:
            # phantom placements are an implementation detail of the padded
            # executable; no caller ever sees them
            assignment = assignment[: pt.S]
        soft = float(soft)
        accepted = int(accepted)
    timings["anneal_ms"] = ph_anneal.ms

    # the numpy ground-truth path is entered only when the device solve
    # left violations and repair is needed
    with phase("solver.verify_repair") as ph_verify:
        if float(dstats["total"]) == 0:
            stats = {k: int(v) for k, v in dstats.items()}
            moves = 0
            pre_repair = 0
        else:
            stats = verify(pt, assignment)
            moves = 0
            pre_repair = int(stats["total"])
            if do_repair and stats["total"] > 0:
                rr: RepairResult = repair(pt, assignment)
                assignment, stats, moves = rr.assignment, rr.stats, rr.moves
                if resident is not None and moves:
                    # the resident seed must track what the fleet actually
                    # runs; a host repair rewrite is the rare re-upload the
                    # host-transfer counter exists for
                    resident.adopt_host(assignment, pt.node_valid,
                                        warm=resident_warm)
                # repair changed the winner: re-score its soft objective
                # (host-exact under bucketing — orig_prob may itself be a
                # pre-padded staging whose shape no longer matches)
                if not bucketed:
                    soft = float(jax.device_get(
                        soft_score(orig_prob, jnp.asarray(assignment))))
        if bucketed:
            # report the REAL rows' soft score: the device number was computed
            # on the padded problem, whose /S mean denominators count phantoms
            soft = soft_score_host(pt, assignment)
        elif (resident_warm and int(sweeps_run) == 0
              and float(stats["total"]) == 0):
            # trusted 0-sweep exit (carried stats): the dispatch returned the
            # carried RANKING score, which includes the stickiness bonus —
            # recompute the un-bonused objective host-side (exact, and this
            # on-tier-unpadded corner is rare; the bucketed branch above
            # already does the same for the common path)
            soft = soft_score_host(pt, assignment)
        if resident is not None:
            # active-set bookkeeping (solver/subsolve.py): the mirror is what
            # the next burst's closure/frozen-base is computed against, and
            # feasibility is the frozen-base precondition. A host repair
            # rewrite already refreshed the mirror through adopt_host.
            resident.note_host_assignment(
                padded=None if moves else padded_host,
                feasible=stats["total"] == 0)
    timings["verify_repair_ms"] = ph_verify.ms
    timings["total_ms"] = (ph_verify.t1 - ph_stage.t0) * 1e3
    # -- flight-deck payload (docs/guide/10, "solver flight deck") ---------
    telemetry = None
    if trace_blocks > 0:
        filled = int(htelem["filled"])
        rows = np.asarray(htelem["blocks"])[:filled]
        telemetry = {
            "schema": list(TRACE_COLS),
            "blocks": [[round(float(x), 6) for x in row] for row in rows],
            "trace_blocks": trace_blocks,
            "init": {"violations": float(htelem["init_violations"]),
                     "soft": round(float(htelem["init_soft"]), 6)},
            "prerepair_moves": int(htelem["prerepair_moves"]),
            "exit_sweep": int(sweeps_run),
            "path": ("subsolve" if sub_info is not None
                     and sub_info["outcome"] == "localized" else "full"),
        }
        if sub_info is not None:
            telemetry["subsolve"] = dict(sub_info)
        _record_solve_trace(telemetry, S=pt.S, N=prob.N,
                            warm=bool(warm), resident=bool(resident_warm),
                            violations=int(stats["total"]),
                            pre_repair=pre_repair,
                            total_ms=round(timings["total_ms"], 3))
    _M_SOLVES.inc(backend=jax.default_backend(),
                  warm="true" if warm else "false")
    _M_SOLVE_S.observe(timings["total_ms"] / 1e3)
    _M_SWEEPS.inc(int(sweeps_run))
    _M_ACCEPTED.inc(accepted)
    if compile_events > 0:
        _M_COMPILES.inc(compile_events)
    _M_VIOL.set(int(stats["total"]))
    _M_PRE_VIOL.set(pre_repair)
    if pt.max_skew > 0:
        _M_SPREAD.inc()
        seed_skew = htelem.get("seed_skew")
        if seed_skew is not None:
            _M_SPREAD_EXCESS.inc(int(seed_skew), at="seed")
        _M_SPREAD_EXCESS.inc(int(dstats["skew"]), at="device")
        _M_SPREAD_EXCESS.inc(int(stats["skew"]), at="final")
        _M_SPREAD_REPAIR.inc(moves)
    log.info("solve %s", kv(
        S=pt.S, N=prob.N, chains=chains, steps=steps,
        sweeps=int(sweeps_run),
        accepted=accepted,
        compiles=compile_events or None,
        bucket=prob.S if bucketed else None,
        bucket_hit=(binfo.hit or None) if binfo is not None else None,
        violations=int(stats["total"]), pre_repair=pre_repair,
        repaired=moves or None, warm=warm or None,
        spread_domains=prob.T if pt.max_skew > 0 else None,
        resident=resident_warm or None,
        sub=(f"{sub_info['rows']}/{sub_info['tier']}"
             f"({sub_info['outcome']})" if sub_info else None),
        **{k: f"{v:.1f}" for k, v in timings.items()}))
    return SolveResult(
        assignment=assignment, stats=stats, soft=soft,
        feasible=stats["total"] == 0, moves_repaired=moves,
        pre_repair_violations=pre_repair,
        timings_ms=timings, chains=chains, steps=int(sweeps_run),
        proposals_per_step=proposals_per_step,
        accepted_moves=accepted,
        bucket=binfo.to_dict() if binfo is not None else None,
        fused_prerepair=warm,
        subsolve=sub_info,
        telemetry=telemetry,
    )


def _record_solve_trace(payload: dict, **fields) -> None:
    """Record one solve's flight-deck telemetry as a flight-recorder span
    payload (kind="telemetry", rendered by `fleet solve trace`). No-op —
    one env lookup — when FLEET_TRACE_FILE is unset."""
    from ..obs.trace import (current_span_id, current_trace_id,
                             flight_recorder, new_span_id, new_trace_id,
                             record_span_event)
    if flight_recorder() is None:
        return
    record_span_event(
        "telemetry", "solve.trace", "fleetflow.solver",
        trace=current_trace_id() or new_trace_id(),
        span=current_span_id() or new_span_id(),
        fields={**fields, "telemetry": payload})
