"""Tenant multiplexer: batched same-tier warm solves in ONE dispatch.

Production for jax_graft means thousands of independent stages, not one
big one — and the tier ladder (solver/buckets.py) already forces
same-tier stage problems into identical padded shapes, which is exactly
the precondition for vmapping them into one batched dispatch. This
module stacks K same-tier resident-warm ``DeviceProblem`` stagings
(packed planes gain a leading lane axis, per-stage scalars become (K,)
vectors) and runs ONE vmapped fused-prerepair + adaptive anneal over
all K lanes:

    K x (dispatch + device_get + host gate)   ->   1 x (all of it)

Per-lane semantics are UNCHANGED: the vmapped pipeline is lane-wise the
same program as ``api._refine`` (jax batches the adaptive while_loop by
masking finished lanes, so each lane's proposal stream, early exit and
best-ever tracking are its own), each lane keeps its own PRNG key, its
own exact violation stats, its own acceptance gate and its own
flight-deck telemetry buffer (PR 15 schema, one buffer per lane). The
parity property test pins this: a lane's assignment is bit-identical to
a solo solve of the same stage with the same seed.

K is bucketed on a small power-of-two ladder (``mux_k``) so fleet-count
drift never recompiles: a batch of 5 pads to 8 by replicating lane 0
(padded lanes are discarded, counted on
``fleet_solver_mux_lanes_total{kind="pad"}``), and the executable
identity is (tier statics, ladder K) — tests/test_multiplex.py pins zero
recompiles across the ladder after warm-up.

Lanes that cannot batch (singleton tier groups, host-warm stagings,
sharded residents) fall through to the serial ``api._solve`` path with
identical results; the multiplexer is a latency optimization, never a
semantics fork.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .anneal import TRACE_COLS, backend_proposals_per_step, solve_trace_blocks
from .api import DEFAULT_STEPS, SolveResult, _refine, _solve
from .buckets import soft_score_host
from .problem import DeviceProblem
from .repair import RepairResult, repair, verify
from .resident import ResidentProblem, transfer_guard_ctx
from ..lower.tensors import ProblemTensors
from ..obs import get_logger, kv
from ..obs.metrics import REGISTRY

log = get_logger("solver.mux")

__all__ = ["MuxEntry", "solve_multiplexed", "mux_k", "mux_cache_size",
           "stack_problems", "MUX_LADDER_MAX"]

# metric catalog: docs/guide/10-observability.md
_M_MUX_BATCHES = REGISTRY.counter(
    "fleet_solver_mux_batches_total",
    "Batched multiplexer dispatches by ladder lane count", labels=("k",))
_M_MUX_LANES = REGISTRY.counter(
    "fleet_solver_mux_lanes_total",
    "Multiplexer lanes by kind (stage = real stage solved in a batch, "
    "pad = ladder-padding replica, serial = mux-ineligible fallback)",
    labels=("kind",))
_M_MUX_STACK_MS = REGISTRY.gauge(
    "fleet_solver_mux_stack_ms",
    "Host+device time spent stacking the most recent mux batch")

# default ceiling of the lane ladder; FLEET_MUX_MAX overrides
MUX_LADDER_MAX = 16


def _ladder_max() -> int:
    import os
    try:
        return max(1, int(os.environ.get("FLEET_MUX_MAX") or MUX_LADDER_MAX))
    except ValueError:
        return MUX_LADDER_MAX


def mux_k(k: int, *, maximum: Optional[int] = None) -> int:
    """Round a lane count up to the power-of-two ladder (2, 4, 8, ...,
    FLEET_MUX_MAX). Like buckets.subsolve_tier for the mini-anneal, the
    ladder keeps the batched executable count logarithmic in fleet-count
    drift: K is a leading-axis extent, hence a recompile axis."""
    cap = _ladder_max() if maximum is None else maximum
    if k <= 1:
        return 1
    p = 2
    while p < k and p < cap:
        p *= 2
    return min(p, cap)


@dataclass
class MuxEntry:
    """One stage's slice of a batched solve: its problem tensors, its
    resident staging (device problem + committed assignment already on
    device), and its solve scalars — exactly what the serial resident-
    warm ``solve()`` call would take."""
    pt: ProblemTensors
    resident: ResidentProblem
    seed: int = 0
    t0: float = 1.0
    t1: float = 1e-3
    migration_weight: float = 0.5
    stage: Optional[str] = None     # caller's stage key (logging only)


def stack_problems(probs: list[DeviceProblem]) -> DeviceProblem:
    """Stack same-tier device problems along a new leading lane axis.
    The static fields are pytree aux data, so tree_map itself enforces
    the tier identity: mismatched statics are a treedef error, not a
    silent mis-batch. Leaves stack on device (no host transfer)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *probs)


@partial(jax.jit, static_argnames=("chains", "steps", "warm", "adaptive",
                                   "anneal_block", "proposals_per_step",
                                   "fused_prerepair", "prerepair_moves",
                                   "skip_feasible_polish", "trace_blocks"))
def _mux_refine(prob: DeviceProblem, seed_assignment: jax.Array,
                key: jax.Array, t0: jax.Array, t1: jax.Array,
                migration_weight: jax.Array, *,
                chains: int, steps: int, warm: bool, adaptive: bool = True,
                anneal_block: int = 1,
                proposals_per_step: Optional[int] = None,
                fused_prerepair: bool = True, prerepair_moves: int = 0,
                skip_feasible_polish: bool = True, trace_blocks: int = 0):
    """The batched fused pipeline: lane-wise ``api._refine`` under vmap.
    Inputs carry a leading (K,) lane axis (problem planes, seeds, PRNG
    keys, anneal scalars); outputs are the per-lane refine tuple with
    the same leading axis — winner (K, S), exact stats (K,) per
    component, soft (K,), sweeps (K,), accepted (K,), telemetry buffers
    (K, trace_blocks, cols). The inner jit inlines under the trace, so
    this is ONE XLA program per (tier statics, K)."""

    def lane(p, s, k, a, b, c):
        return _refine(p, s, k, a, b, c, chains=chains, steps=steps,
                       warm=warm, adaptive=adaptive,
                       anneal_block=anneal_block,
                       proposals_per_step=proposals_per_step,
                       sharding=None, fused_prerepair=fused_prerepair,
                       prerepair_moves=prerepair_moves,
                       skip_feasible_polish=skip_feasible_polish,
                       trace_blocks=trace_blocks)

    return jax.vmap(lane)(prob, seed_assignment, key, t0, t1,
                          migration_weight)


def mux_cache_size() -> int:
    """Compiled-variant count of the batched executable (the tests'
    recompile watch, like api._refine._cache_size for the serial path)."""
    return _mux_refine._cache_size()


def _eligible(e: MuxEntry) -> bool:
    rp = e.resident
    return (isinstance(rp, ResidentProblem)
            and getattr(rp, "mesh", None) is None
            and rp.assignment is not None)


def _tier_key(e: MuxEntry):
    """Group key: everything that feeds the executable identity. The
    leaf (shape, dtype) tuple covers S/N/G/Gc/T/widths/plane layout; the
    treedef covers the static fields and absent-plane structure."""
    prob = e.resident.prob
    leaves, treedef = jax.tree_util.tree_flatten(prob)
    shapes = tuple((x.shape, str(x.dtype)) for x in leaves)
    return (treedef, shapes, bool(e.migration_weight > 0))


def solve_multiplexed(entries: list[MuxEntry], *,
                      chains: Optional[int] = None,
                      steps: int = DEFAULT_STEPS,
                      anneal_block: int = 1,
                      warm_block: int = 1,
                      do_repair: bool = True) -> list[SolveResult]:
    """Solve a set of resident-warm stages, batching same-tier groups
    into single vmapped dispatches. Returns one SolveResult per entry,
    in entry order. Entries that cannot batch (singleton tier groups or
    mux-ineligible stagings) run through the serial ``api._solve`` warm
    path — same results, just without the shared dispatch."""
    if chains is None:
        chains = 1 if jax.default_backend() == "cpu" else 2

    results: list[Optional[SolveResult]] = [None] * len(entries)
    groups: dict = {}
    serial: list[int] = []
    for i, e in enumerate(entries):
        if _eligible(e):
            groups.setdefault(_tier_key(e), []).append(i)
        else:
            serial.append(i)

    for key, idxs in groups.items():
        if len(idxs) < 2:
            serial.extend(idxs)
            continue
        cap = _ladder_max()
        for at in range(0, len(idxs), cap):
            chunk = idxs[at:at + cap]
            _solve_batch(entries, chunk, results, chains=chains,
                         steps=steps, anneal_block=anneal_block,
                         warm_block=warm_block, do_repair=do_repair)

    for i in serial:
        e = entries[i]
        _M_MUX_LANES.inc(kind="serial")
        results[i] = _solve(
            e.pt, chains=chains, steps=steps, seed=e.seed,
            do_repair=do_repair, t0=e.t0, t1=e.t1,
            migration_weight=e.migration_weight,
            anneal_block=anneal_block, warm_block=warm_block,
            resident=e.resident if isinstance(e.resident, ResidentProblem)
            else None,
            resident_warm=_eligible(e),
            bucket=getattr(e.resident, "bucket", None))
    return results  # type: ignore[return-value]


def _solve_batch(entries: list[MuxEntry], idxs: list[int],
                 results: list, *, chains: int, steps: int,
                 anneal_block: int, warm_block: int,
                 do_repair: bool) -> None:
    t = time.perf_counter
    t_start = t()
    lanes = [entries[i] for i in idxs]
    K = len(lanes)
    Kp = mux_k(K)

    # ---- staging: everything host-touching happens BEFORE the guard ----
    # ladder padding replicates lane 0 (its result is discarded); the
    # replica shares lane 0's device buffers, so padding costs no memory
    # beyond the stacked copy every lane pays anyway
    def lane_at(j: int) -> MuxEntry:
        return lanes[j] if j < K else lanes[0]

    probs = [lane_at(j).resident.prob for j in range(Kp)]
    stacked = stack_problems(probs)
    seeds = jnp.stack([lane_at(j).resident.assignment for j in range(Kp)])
    keys = jnp.stack([jax.random.PRNGKey(lane_at(j).seed)
                      for j in range(Kp)])
    # warm scalars stage per lane through the resident's device cache
    # (the merge-upload discipline: scalars are resident before the
    # guard arms), then stack device-side into (K,) vectors
    scal = [lane_at(j).resident.warm_scalars(
        min(lane_at(j).t0, 0.1), lane_at(j).t1,
        lane_at(j).migration_weight) for j in range(Kp)]
    t0v = jnp.stack([s[0] for s in scal])
    t1v = jnp.stack([s[1] for s in scal])
    mwv = jnp.stack([s[2] for s in scal])

    prob0 = probs[0]
    warm = bool(lanes[0].migration_weight > 0)
    proposals = backend_proposals_per_step(prob0.S)
    prerepair_moves = max(16, min(prob0.S, 256))
    trace_blocks = solve_trace_blocks()
    refine_kw = dict(
        chains=chains, steps=steps, warm=warm, adaptive=True,
        anneal_block=min(warm_block, anneal_block),
        proposals_per_step=proposals, fused_prerepair=True,
        prerepair_moves=prerepair_moves, skip_feasible_polish=True,
        trace_blocks=trace_blocks)
    _M_MUX_STACK_MS.set((t() - t_start) * 1e3)

    cache_before = _mux_refine._cache_size()
    t_anneal = t()
    # the proof: under FLEET_TRANSFER_GUARD=disallow nothing inside the
    # batched dispatch crosses the host boundary — every lane's planes,
    # seed and scalars are already resident, statics hash
    with transfer_guard_ctx():
        (winners, dstats, dsoft, dsweeps, daccepted,
         dtelem) = _mux_refine(stacked, seeds, keys, t0v, t1v, mwv,
                               **refine_kw)
    compile_events = _mux_refine._cache_size() - cache_before
    # the padded winner stays on device as each lane's next warm seed
    # (lane slicing is a device op; padded replicas are never adopted)
    for j in range(K):
        lanes[j].resident.adopt(winners[j])
    # ONE transfer for every lane's host decision — the whole point
    (h_win, h_stats, h_soft, h_sweeps, h_acc, h_telem) = jax.device_get(
        (winners, dstats, dsoft, dsweeps, daccepted, dtelem))
    anneal_ms = (t() - t_anneal) * 1e3

    _M_MUX_BATCHES.inc(k=str(Kp))
    _M_MUX_LANES.inc(K, kind="stage")
    if Kp > K:
        _M_MUX_LANES.inc(Kp - K, kind="pad")
    from .api import _M_ACCEPTED, _M_COMPILES, _M_SOLVES, _M_SWEEPS
    if compile_events > 0:
        _M_COMPILES.inc(compile_events)

    for j in range(K):
        e = lanes[j]
        rp = e.resident
        prob = rp.prob
        # FORCE a host copy: device_get can return a view of a buffer
        # the resident path later donates (see api._solve)
        assignment = np.array(h_win[j], copy=True)
        padded_host = assignment
        bucketed = prob.S != e.pt.S
        if bucketed:
            assignment = assignment[: e.pt.S]
        stats_lane = {k: float(v[j]) for k, v in h_stats.items()}
        soft = float(h_soft[j])
        sweeps = int(h_sweeps[j])
        accepted = int(h_acc[j])
        moves = 0
        pre_repair = 0
        if stats_lane["total"] == 0:
            stats = {k: int(v) for k, v in stats_lane.items()}
        else:
            # per-lane exact gate, same as the serial path: verify on
            # host ground truth, repair backstop, resident re-upload
            stats = verify(e.pt, assignment)
            pre_repair = int(stats["total"])
            if do_repair and stats["total"] > 0:
                rr: RepairResult = repair(e.pt, assignment)
                assignment, stats, moves = rr.assignment, rr.stats, rr.moves
                if moves:
                    rp.adopt_host(assignment, e.pt.node_valid, warm=True)
        if bucketed or (sweeps == 0 and stats["total"] == 0):
            # padded-mean / stickiness-bonused device score: recompute
            # the un-bonused objective against the REAL rows host-side
            soft = soft_score_host(e.pt, assignment)
        rp.note_host_assignment(
            padded=None if moves else padded_host,
            feasible=stats["total"] == 0)
        telemetry = None
        if trace_blocks > 0 and accepted >= 0:
            filled = int(h_telem["filled"][j])
            rows = np.asarray(h_telem["blocks"][j])[:filled]
            telemetry = {
                "schema": list(TRACE_COLS),
                "blocks": [[round(float(x), 6) for x in row]
                           for row in rows],
                "trace_blocks": trace_blocks,
                "init": {
                    "violations": float(h_telem["init_violations"][j]),
                    "soft": round(float(h_telem["init_soft"][j]), 6)},
                "prerepair_moves": int(h_telem["prerepair_moves"][j]),
                "exit_sweep": sweeps,
                "path": "mux",
                "mux": {"k": Kp, "lane": j},
            }
        _M_SOLVES.inc(backend=jax.default_backend(), warm="true")
        _M_SWEEPS.inc(sweeps)
        if accepted >= 0:
            _M_ACCEPTED.inc(accepted)
        results[idxs[j]] = SolveResult(
            assignment=assignment, stats=stats, soft=soft,
            feasible=stats["total"] == 0, moves_repaired=moves,
            pre_repair_violations=pre_repair,
            timings_ms={"anneal_ms": anneal_ms, "mux_k": float(Kp),
                        "mux_lane": float(j)},
            chains=chains, steps=sweeps, proposals_per_step=proposals,
            accepted_moves=accepted, fused_prerepair=True,
            telemetry=telemetry)
    log.info("mux %s", kv(
        k=Kp, stages=K, tier=f"{prob0.S}x{prob0.N}",
        compiles=compile_events or None,
        ms=f"{anneal_ms:.1f}"))
