"""Active-set warm solves: churn-localized sub-problem annealing.

The warm path's remaining tax is sweep cost whenever churn actually
needs annealing: a rolling-kill burst that moves 80 of 10k services pays
FULL-problem sweeps, and admission micro-solves sweep all ~10.7k rows to
place an 81-arrival batch. Steady-state churn is sparse — the rows that
can possibly move are the AFFECTED set (killed-node evictions, arrivals, demand and
eligibility drift) plus their constraint closure — so this module solves
exactly that set:

  ActiveIndex    host-side constraint index built once per resident
                 staging: unified conflict ids, coloc ids, dependency
                 adjacency and replica groups, each inverted id -> rows
  plan_active    the closure rule: affected rows ∪ rows sharing any
                 conflict/coloc id with an affected incumbent ∪
                 dependency neighbors ∪ replica siblings, padded onto a
                 mini tier ladder (256/512/1024/... —
                 buckets.subsolve_tier) so the localized executable
                 compiles once per tier. A fresh arrival (a row that
                 held nothing before the delta) pulls in no conflict
                 partner: they are incumbents, which the frozen
                 occupancy makes it avoid exactly
  subsolve       ONE jitted dispatch: gather the closure rows' planes
                 from the resident problem, seed the mini anneal's
                 carried state with the FROZEN remainder (load / conflict
                 occupancy / coloc occupancy / topology counts of every
                 untouched row — capacity is debited by what the frozen
                 fleet already consumes), run the fused pre-repair
                 prologue + adaptive anneal over the tiny planes (a sweep
                 over 512 rows streams ~20x fewer bytes than one over
                 10k), scatter the accepted rows back into the resident
                 assignment (donated in place), and compute EXACT
                 full-problem stats of the result as the acceptance gate

Correctness story: the frozen base makes every carried gradient exact
against the untouched fleet (frozen-frozen violations are zero because
the previous committed placement was feasible — a precondition the
planner checks), closure rows are visited in ascending row order so a
0-sweep feasible prologue exit commits the SAME relocations the full
fused prologue would, and regardless of what the mini anneal claims, the
dispatch's last act is `kernels.exact_stats_and_soft` on the full
problem: a gate-rejected sub-solve is DISCARDED and the full fused path
re-runs from the ORIGINAL seed (which is why the kernel never donates
the assignment — see the scatter note in the kernel body). Closures
whose incumbents (the rows that held something before the churn) pass
``FLEET_SUBSOLVE_FRAC`` of the real rows, or that run past the tier
ladder, fall back up front; so does a closure with an incumbent in a
problem no bigger than its tier. A closure of fresh arrivals alone is
localized whatever its share: the full path has no placement of theirs
to keep, and it may move the incumbents they land beside.

Knobs: FLEET_SUBSOLVE=0 disables; FLEET_SUBSOLVE_FRAC (default 0.25) is
the closure cap as a fraction of real rows; FLEET_SUBSOLVE_MIN (default
256) is the first mini tier, and the ladder ends at 4096 rows.
Tuning + runbook: docs/guide/11-performance.md; metric catalog:
docs/guide/10-observability.md.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import lru_cache
from typing import Optional

import numpy as np

from .buckets import subsolve_tier, width_bucket
from ..obs import get_logger, kv
from ..obs.metrics import MS_BUCKETS, REGISTRY

log = get_logger("solver.subsolve")

__all__ = ["SubsolveConfig", "subsolve_config", "ActiveIndex", "ActivePlan",
           "plan_active", "stage_subsolve", "subsolve_dispatch",
           "subsolve_cache_size", "record_outcome"]

# metric catalog: docs/guide/10-observability.md
_M_SUB = REGISTRY.counter(
    "fleet_solver_subsolve_total",
    "Active-set sub-solve attempts by outcome: localized = mini anneal "
    "accepted by the exact full-problem gate, fallback_closure = closure "
    "exceeded the size cap, fallback_small = the problem is too small for "
    "a sub-problem to win, fallback_infeasible = the sub-solve landed "
    "infeasible and the full fused path re-ran",
    labels=("outcome",))
_M_SUB_CLOSURE_ROWS = REGISTRY.counter(
    "fleet_solver_subsolve_closure_rows_total",
    "Real rows of the closures the active-set planner built, one closure "
    "an attempt: over fleet_solver_subsolve_total, the mean closure of a "
    "sub-solve attempt")
_M_SUB_ROWS = REGISTRY.gauge(
    "fleet_solver_subsolve_rows",
    "Closure size (real rows) of the most recent active-set sub-solve")
_M_SUB_TIER = REGISTRY.gauge(
    "fleet_solver_subsolve_tier",
    "Padded mini-tier of the most recent active-set sub-solve")
_M_SUB_MS = REGISTRY.histogram(
    "fleet_solver_subsolve_ms",
    "Wall milliseconds per localized sub-solve dispatch "
    "(staging + mini anneal + scatter + exact full-problem gate)",
    buckets=MS_BUCKETS)


def record_outcome(outcome: str) -> None:
    _M_SUB.inc(outcome=outcome)


# the outcome vocabulary the operator surfaces render
# (cp/admission.SUBSOLVE_OUTCOMES mirrors this list by name — the CP
# reads the counter through the registry so its status calls never
# import jax; tests pin the two lists equal)
SUB_OUTCOMES = ("localized", "fallback_closure", "fallback_small",
                "fallback_infeasible")


# largest mini tier (beyond: full path)
SUBSOLVE_MAX_TIER = 4096


@dataclass(frozen=True)
class SubsolveConfig:
    enabled: bool = True
    frac: float = 0.25       # closure cap as a fraction of real rows
    min_tier: int = 256      # first mini tier


def subsolve_config(default_enabled: bool = True) -> SubsolveConfig:
    """Process-wide active-set knobs, read from the environment per call
    (cheap; hot callers hold the result)."""
    def _f(name, d):
        try:
            return float(os.environ.get(name, "") or d)
        except ValueError:
            return d
    v = os.environ.get("FLEET_SUBSOLVE", "").strip().lower()
    enabled = (default_enabled if not v
               else v not in ("0", "false", "off", "no"))
    return SubsolveConfig(
        enabled=enabled,
        frac=min(max(_f("FLEET_SUBSOLVE_FRAC", 0.25), 0.0), 1.0),
        min_tier=max(int(_f("FLEET_SUBSOLVE_MIN", 256)), 8),
    )


def _invert_ids(ids: np.ndarray):
    """CSR inversion of a (S, K) -1-padded id table: (uniq ids, offsets,
    rows) such that rows[offsets[i]:offsets[i+1]] carry uniq[i]."""
    mask = ids >= 0
    if not mask.any():
        return (np.empty(0, np.int64), np.zeros(1, np.int64),
                np.empty(0, np.int64))
    rows = np.nonzero(mask)[0]
    vals = ids[mask]
    order = np.argsort(vals, kind="stable")
    vals, rows = vals[order], rows[order]
    uniq, starts = np.unique(vals, return_index=True)
    offsets = np.append(starts, vals.size)
    return uniq, offsets, rows


class ActiveIndex:
    """Host constraint index over a resident staging's ProblemTensors:
    everything the closure rule needs to expand an affected set, built
    once per cold staging (O(S*K) numpy — the same order as staging
    itself) and reused every burst; a delta that changes rows' conflict
    ids or appends rows `update`s it for those rows."""

    def __init__(self, pt):
        from .problem import _unify_conflict_ids
        self.pt = pt
        self.S = pt.S
        self.conflict = _unify_conflict_ids(pt)              # (S, K)
        self.coloc = np.asarray(pt.coloc_ids, dtype=np.int32)
        self._conf_inv = _invert_ids(self.conflict)
        self._coloc_inv = _invert_ids(self.coloc)
        self._dep = np.asarray(pt.dep_adj, dtype=bool)
        # replica groups: rows sharing a base service move together
        self._groups: dict[str, list[int]] = {}
        for i, base in enumerate(pt.replica_of or ()):
            self._groups.setdefault(base, []).append(i)

    def update(self, pt, rows) -> None:
        """Follow `pt`, which differs from the problem indexed by the
        conflict ids of rows `rows` and by rows appended: those rows'
        ids re-read, the id inversion redone, appended rows given their
        replica group. Dependencies are not re-read: an appended row has
        none (cp/admission.py streams no dependency)."""
        from .problem import unified_conflict_rows
        grow = pt.S - self.S
        if grow > 0:
            self.conflict = np.vstack([self.conflict, np.full(
                (grow, self.conflict.shape[1]), -1, dtype=np.int32)])
            self.coloc = np.vstack([self.coloc, np.full(
                (grow, self.coloc.shape[1]), -1, dtype=np.int32)])
            for i, base in enumerate(pt.replica_of[self.S:pt.S], self.S):
                self._groups.setdefault(base, []).append(i)
            self.S = pt.S
        self.pt = pt
        rows = np.asarray(rows, dtype=np.int64)
        if not rows.size:
            return
        ids = unified_conflict_rows(pt, rows)
        wider = ids.shape[1] - self.conflict.shape[1]
        if wider > 0:
            self.conflict = np.hstack([self.conflict, np.full(
                (self.S, wider), -1, dtype=np.int32)])
        self.conflict[rows] = -1
        self.conflict[rows, :ids.shape[1]] = ids
        self._conf_inv = _invert_ids(self.conflict)

    @staticmethod
    def _rows_sharing(inv, ids: np.ndarray) -> np.ndarray:
        uniq, offs, rows = inv
        ids = np.unique(ids[ids >= 0])
        if not ids.size or not uniq.size:
            return np.empty(0, np.int64)
        pos = np.searchsorted(uniq, ids)
        pos = pos[pos < uniq.size]
        pos = pos[np.isin(uniq[pos], ids)]
        if not pos.size:
            return np.empty(0, np.int64)
        return np.concatenate([rows[offs[p]:offs[p + 1]] for p in pos])

    def closure(self, affected: np.ndarray,
                fresh: Optional[np.ndarray] = None) -> np.ndarray:
        """One-level constraint closure of `affected` (sorted, unique):
        rows sharing any conflict or coloc id with an affected row that
        is not `fresh`, dependency neighbors (either direction), replica
        siblings. One level suffices for correctness — the frozen-base
        occupancy makes second-order interactions exact in the
        sub-problem — and keeps the closure from percolating to the
        whole fleet through id chains. A fresh row (an arrival: it held
        nothing before) pulls in no id partner: they are incumbents with
        no reason to move, and the frozen occupancy keeps it off them."""
        affected = np.unique(affected)
        inside = affected[affected < self.S]
        placed = (inside if fresh is None
                  else inside[~np.isin(inside, fresh)])
        out = [affected]
        if placed.size:
            out.append(self._rows_sharing(self._conf_inv,
                                          self.conflict[placed].ravel()))
            out.append(self._rows_sharing(self._coloc_inv,
                                          self.coloc[placed].ravel()))
        if inside.size:
            # rows appended since the index was built have no dependency
            dep = inside[inside < self._dep.shape[0]]
            if self._dep.size and dep.size:
                nbr = (self._dep[dep].any(axis=0)
                       | self._dep[:, dep].any(axis=1))
                out.append(np.nonzero(nbr)[0])
            for i in inside:
                base = (self.pt.replica_of[i]
                        if i < len(self.pt.replica_of or ()) else None)
                if base is not None and base in self._groups:
                    out.append(np.asarray(self._groups[base]))
        return np.unique(np.concatenate(out)).astype(np.int64)

    def frozen_occupancy(self, ids: np.ndarray, inv, mirror: np.ndarray,
                         in_sub: np.ndarray, N: int) -> np.ndarray:
        """(N, len(ids)) int32 occupancy of the given ORIGINAL ids by
        frozen rows (carriers outside the closure), placed at their
        mirror nodes — the conflict/coloc base counts the mini anneal's
        carried state starts from."""
        out = np.zeros((N, max(len(ids), 1)), dtype=np.int32)
        uniq, offs, rows = inv
        if not uniq.size:
            return out
        pos = np.searchsorted(uniq, ids)
        for g, p in enumerate(pos):
            if p >= uniq.size or uniq[p] != ids[g]:
                continue
            carriers = rows[offs[p]:offs[p + 1]]
            carriers = carriers[~in_sub[carriers]]
            if carriers.size:
                np.add.at(out, (mirror[carriers], g), 1)
        return out


@dataclass
class ActivePlan:
    """A staged-on-host localized sub-problem, ready for ONE device
    dispatch. All arrays are small (O(tier) rows / O(N) node state) —
    the (S, ·) planes never leave the device; their closure rows are
    gathered inside the jitted kernel."""
    rows: np.ndarray          # (tier,) i32, pad slots = padded_S (dropped)
    n_sub: int                # real closure rows
    tier: int
    G_sub: int                # compact conflict-id count (padded ladder)
    Gc_sub: int               # compact coloc-id count (0 = none)
    sub_conflict: np.ndarray  # (tier, Kc) i32 compact-remapped, -1 pad
    sub_coloc: np.ndarray     # (tier, Cc) i32 compact-remapped, -1 pad
    load0: np.ndarray         # (N, R) f32 frozen load
    used0: np.ndarray         # (N, G_sub) i32 frozen conflict occupancy
    coloc0: np.ndarray        # (N, max(Gc_sub, 1)) i32 frozen coloc occ.
    topo0: np.ndarray         # (T,) i32 frozen topology counts
    affected: int = 0         # pre-closure affected rows (telemetry)


def plan_active(index: ActiveIndex, pt, mirror: np.ndarray, padded_S: int,
                T: int, pending_rows: np.ndarray,
                cfg: Optional[SubsolveConfig] = None,
                G_full: int = 1 << 30, Gc_full: int = 1 << 30,
                fresh_rows: Optional[np.ndarray] = None,
                ) -> tuple[Optional[ActivePlan], str]:
    """Build the localized sub-problem for the churn accumulated since
    the last solve. Returns (plan, outcome): plan None means the caller
    runs the full fused path, with `outcome` saying why (counted into
    fleet_solver_subsolve_total by the caller for fallbacks; "localized"
    is counted after the gate accepts).

    `mirror` is the host copy of the resident PADDED assignment as of the
    previous solve (phantom re-parks replayed); `pending_rows` the rows
    churn deltas touched (arrivals, tombstones, demand/eligibility
    drift, rows on capacity-shrunk nodes), `fresh_rows` those of them
    that held nothing before (arrivals: `ActiveIndex.closure`; the size
    caps count the closure's other rows, the incumbents). Stranded rows
    (previous node now invalid or ineligible) are recomputed here from
    the post-delta tensors, so killed nodes need no separate
    bookkeeping."""
    cfg = cfg or subsolve_config()
    S = pt.S                         # real rows of the post-delta problem
    prev = mirror[:S]
    elig = np.asarray(pt.eligible)
    stranded = np.nonzero(~(np.asarray(pt.node_valid)[prev]
                            & elig[np.arange(S), prev]))[0]
    affected = np.unique(np.concatenate(
        [np.asarray(pending_rows, dtype=np.int64), stranded]))
    affected = affected[affected < S]
    if not affected.size:
        # nothing moved and nothing is stranded: the fused path's
        # 0-sweep exit is already optimal, and a 0-row sub-problem would
        # only add a gate pass
        return None, "fallback_small"
    rows = index.closure(affected, fresh_rows)
    rows = rows[rows < S]
    k = int(rows.size)
    _M_SUB_CLOSURE_ROWS.inc(k)
    # the caps weigh the incumbents a mini anneal would re-decide: a
    # fresh arrival has no placement to keep
    placed = (k if fresh_rows is None
              else k - int(np.isin(rows, fresh_rows).sum()))
    if placed > max(cfg.frac * S, 1):
        return None, "fallback_closure"
    tier = subsolve_tier(k, minimum=cfg.min_tier,
                         maximum=SUBSOLVE_MAX_TIER)
    if tier == 0:
        return None, "fallback_closure"
    if tier >= S and placed:
        return None, "fallback_small"

    N = pt.N
    R = np.asarray(pt.demand).shape[1]
    in_sub = np.zeros(max(index.S, S), dtype=bool)
    in_sub[rows] = True

    # compact id spaces: only ids carried by closure rows exist in the
    # sub-problem; frozen carriers of those ids enter as base occupancy
    inside = rows[rows < index.S]
    conf_rows = (index.conflict[inside] if inside.size
                 else np.empty((0, index.conflict.shape[1]), np.int32))
    coloc_rows = (index.coloc[inside] if inside.size
                  else np.empty((0, index.coloc.shape[1]), np.int32))
    conf_ids = np.unique(conf_rows[conf_rows >= 0])
    coloc_ids = np.unique(coloc_rows[coloc_rows >= 0])
    # id-space sizes are pinned to the TIER (and the staging's full
    # G/Gc), NOT the closure content: a content-derived ladder recompiled
    # the mini executable whenever burst-to-burst id counts crossed a
    # step (measured: two ~1.4 s compiles inside a 16-burst churn loop).
    # One tier == one executable; a closure denser in ids than the tier
    # can hold is a (counted) fallback, not a compile
    G_sub = max(min(tier, G_full), 16)
    Gc_sub = 0 if Gc_full == 0 else max(min(tier // 4, Gc_full), 4)
    if len(conf_ids) > G_sub or len(coloc_ids) > Gc_sub:
        return None, "fallback_closure"

    Kc = width_bucket(index.conflict.shape[1], 4)
    Cc = width_bucket(index.coloc.shape[1], 4)
    sub_conflict = np.full((tier, Kc), -1, dtype=np.int32)
    sub_coloc = np.full((tier, Cc), -1, dtype=np.int32)
    if inside.size:
        remap = np.where(conf_rows >= 0,
                         np.searchsorted(conf_ids,
                                         np.where(conf_rows >= 0,
                                                  conf_rows, 0)), -1)
        at = np.nonzero(rows < index.S)[0]
        sub_conflict[at, :conf_rows.shape[1]] = remap
        if len(coloc_ids):
            cremap = np.where(coloc_rows >= 0,
                              np.searchsorted(coloc_ids,
                                              np.where(coloc_rows >= 0,
                                                       coloc_rows, 0)), -1)
            sub_coloc[at, :coloc_rows.shape[1]] = cremap

    # frozen remainder: load / occupancy / topology of every untouched
    # real row at its mirror node — the capacity debit and the exact
    # cross-boundary conflict/coloc/skew accounting in one state seed
    frozen = np.nonzero(~in_sub[:S])[0]
    load0 = np.zeros((N, R), dtype=np.float32)
    np.add.at(load0, prev[frozen],
              np.asarray(pt.demand, dtype=np.float32)[frozen])
    used0 = np.zeros((N, G_sub), dtype=np.int32)
    used0[:, : max(len(conf_ids), 1)] = index.frozen_occupancy(
        conf_ids, index._conf_inv, prev, in_sub, N) \
        if len(conf_ids) else 0
    coloc0 = np.zeros((N, max(Gc_sub, 1)), dtype=np.int32)
    if len(coloc_ids):
        coloc0[:, : len(coloc_ids)] = index.frozen_occupancy(
            coloc_ids, index._coloc_inv, prev, in_sub, N)
    topo0 = np.bincount(np.asarray(pt.node_topology)[prev[frozen]],
                        minlength=T).astype(np.int32)

    padded_rows = np.full(tier, padded_S, dtype=np.int32)
    padded_rows[:k] = rows            # ascending: prologue order matches
    plan = ActivePlan(
        rows=padded_rows, n_sub=k, tier=tier, G_sub=G_sub, Gc_sub=Gc_sub,
        sub_conflict=sub_conflict, sub_coloc=sub_coloc, load0=load0,
        used0=used0, coloc0=coloc0, topo0=topo0, affected=int(affected.size))
    log.debug("active-set plan %s", kv(affected=plan.affected, closure=k,
                                       tier=tier, G=G_sub, Gc=Gc_sub))
    return plan, "planned"


@lru_cache(maxsize=1)
def _subsolve_fn():
    """The localized gather -> mini-anneal -> scatter -> exact-gate
    kernel, built lazily (importing the planner never pays JAX startup).
    The resident assignment is read, not donated — see the scatter note
    in the kernel body for why the input must outlive the dispatch."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from .anneal import (anneal_adaptive_states, chain_states_from_assignment,
                         prerepair_state_counted)
    from .kernels import exact_stats_and_soft
    from .problem import DeviceProblem

    def subsolve(prob, assignment, rows, sub_conflict, sub_coloc, load0,
                 used0, coloc0, topo0, n_sub, key, t0, t1,
                 migration_weight, *, chains, steps, block,
                 proposals_per_step, prerepair_moves, Gc_sub,
                 trace_blocks=0, overfull=False):
        # named scopes are metadata: the profiler shows the device's ops
        # under subsolve.localized/<step>, the program is the same
        with jax.named_scope("subsolve.localized/gather"):
            S_sub = rows.shape[0]
            rows_g = jnp.minimum(rows, prob.S - 1)   # clamp-safe gather index
            real = jnp.arange(S_sub) < n_sub
            demand_sub = jnp.where(real[:, None], prob.demand[rows_g], 0.0)
            if prob.eligible.dtype == jnp.uint32:
                elig_fill = jnp.uint32(0xFFFFFFFF)
            else:
                elig_fill = jnp.asarray(True)
            eligible_sub = jnp.where(real[:, None], prob.eligible[rows_g],
                                     elig_fill)
            pref_sub = None
            if prob.preferred is not None:
                pref_sub = jnp.where(real[:, None], prob.preferred[rows_g], 0.0)
            # phantom sub rows park on a valid node (inert: zero demand, no
            # ids, eligible everywhere — the bucket-phantom construction)
            park = jnp.argmax(prob.node_valid).astype(jnp.int32)
            seed_sub = jnp.where(real, assignment[rows_g], park).astype(jnp.int32)
        with jax.named_scope("subsolve.localized/anneal"):
            sub = DeviceProblem(
                demand=demand_sub, capacity=prob.capacity,
                conflict_ids=sub_conflict, coloc_ids=sub_coloc,
                eligible=eligible_sub, node_valid=prob.node_valid,
                node_topology=prob.node_topology,
                S=S_sub, N=prob.N, G=used0.shape[1], Gc=Gc_sub, T=prob.T,
                strategy=prob.strategy, max_skew=prob.max_skew,
                preferred=pref_sub, n_real=n_sub)
            # warm stickiness rides the sub proposal delta exactly as on the
            # full path: staying on the previous still-eligible node earns
            # migration_weight; churn-forced moves stay free
            sub_a = dataclasses.replace(
                sub, sticky_prev=seed_sub,
                sticky_w=jnp.asarray(migration_weight, jnp.float32))
            st0 = chain_states_from_assignment(
                sub_a, seed_sub, base=(load0, used0, coloc0, topo0))
            # a closure row that shares a conflict id with another on its
            # node (fresh arrivals parked together, or on a frozen
            # carrier's server) is relocated up front, like a stranded one
            st0, prerepair_applied = prerepair_state_counted(
                sub_a, st0, prerepair_moves, conflicted=True,
                overfull=overfull)
            init_states = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (chains,) + x.shape), st0)
            inits = jnp.broadcast_to(st0.assignment[None], (chains, S_sub))
            (best_assign_c, best_viol_c, best_soft_c, sweeps_run, accepted_c,
             telem) = anneal_adaptive_states(
                    sub_a, inits, key, max_steps=steps, block=block,
                    t0=t0, t1=t1, proposals_per_step=proposals_per_step,
                    init_states=init_states, exit_on_feasible_init=True,
                    trace_blocks=trace_blocks)
            accepted = accepted_c.sum()
            telem = dict(telem, prerepair_moves=prerepair_applied)
            # same lexicographic (violations, soft) rank as the full pipeline
            min_viol = best_viol_c.min()
            best = jnp.argmin(jnp.where(best_viol_c == min_viol,
                                        best_soft_c, jnp.inf))
            winner = best_assign_c[best]
        with jax.named_scope("subsolve.localized/scatter"):
            # scatter the accepted rows back into a FRESH assignment buffer;
            # pad slots carry prob.S and are dropped. The input is
            # deliberately NOT donated: (a) a gate-rejected sub-solve must
            # re-run the full fused path from the ORIGINAL seed — stranded
            # rows intact, the battle-tested prerepair path — so the old
            # buffer has to survive; and (b) an (S,) i32 copy is ~40 KB at
            # fleet scale, noise next to the planes the merge kernel's
            # donation exists for
            new_assignment = assignment.at[rows].set(winner, mode="drop")
        with jax.named_scope("subsolve.localized/gate"):
            # the acceptance gate: exact full-problem stats of the scattered
            # result — whatever the mini anneal believed, THIS decides
            stats, soft = exact_stats_and_soft(prob, new_assignment)
        return new_assignment, stats, soft, sweeps_run, accepted, telem

    return jax.jit(subsolve,
                   static_argnames=("chains", "steps", "block",
                                    "proposals_per_step",
                                    "prerepair_moves", "Gc_sub",
                                    "trace_blocks", "overfull"))


def subsolve_cache_size() -> int:
    """Compiled-variant count of the localized kernel (compile-event
    telemetry: a new mini tier or id-ladder step is a compile)."""
    try:
        return _subsolve_fn()._cache_size()
    except Exception:                               # pragma: no cover
        return 0


def stage_subsolve(resident, plan: ActivePlan):
    """Device-stage a plan's small arrays (host -> device, BEFORE the
    transfer guard arms — the same discipline as the delta merge's
    uploads). Returns the positional args following (prob, assignment)."""
    import jax.numpy as jnp

    uploads = resident._put_small(
        (plan.rows, plan.sub_conflict, plan.sub_coloc, plan.load0,
         plan.used0, plan.coloc0, plan.topo0))
    return (*uploads, jnp.asarray(plan.n_sub, jnp.int32))


SUB_MAX_STEPS = 16   # mini-anneal sweep budget: a feasible closure exits
# in 0-2 sweeps (prerepair + targeted proposals over a tiny plane); one
# that hasn't converged by 16 is closure-starved and should bail to the
# full path instead of burning a full-problem budget on a lost cause


def subsolve_dispatch(prob, assignment, staged, plan: ActivePlan, key,
                      t0, t1, migration_weight, *, chains: int, steps: int,
                      block: int, proposals_per_step: int,
                      trace_blocks: int = 0, overfull: bool = False):
    """Run the localized kernel (call under the transfer guard: every
    argument is already resident). Returns the device outputs
    (new_assignment, stats, soft, sweeps_run, accepted, telemetry).
    `overfull`: the problem is priced (lower/tensors.py `with_price`), and
    the prologue also moves a row off a node over its capacity (anneal.py
    `prerepair_state_counted`)."""
    prerepair_moves = max(16, min(plan.tier, 256))
    _M_SUB_ROWS.set(plan.n_sub)
    _M_SUB_TIER.set(plan.tier)
    return _subsolve_fn()(
        prob, assignment, *staged, key, t0, t1, migration_weight,
        chains=chains, steps=min(steps, SUB_MAX_STEPS), block=block,
        proposals_per_step=proposals_per_step,
        prerepair_moves=prerepair_moves, Gc_sub=plan.Gc_sub,
        trace_blocks=trace_blocks, overfull=overfull)


def record_subsolve_ms(ms: float) -> None:
    _M_SUB_MS.observe(ms)
