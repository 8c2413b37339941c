"""Device-resident fleet state: the padded placement problem and the last
committed assignment live ON DEVICE between solves, and CP churn arrives as
structured deltas applied by a donated, jitted merge kernel.

Before this module the warm path still rebuilt host state every burst:
`sched/tpu.py` re-staged the padded DeviceProblem whenever capacity drifted
(identity-keyed cache), `solver/api._solve` uploaded the previous assignment
from host numpy and ran the churn pre-repair in host numpy. The paper's
thesis is that the placement hot loop lives on TPU; this closes the
remaining host round-trips:

  ResidentProblem      owns the padded, bucketed DeviceProblem + the last
                       assignment as device buffers across bursts
  ProblemDelta         what churn actually is: node up/down (valid-mask
                       flip), capacity drift, demand drift, arrivals into
                       phantom rows (row scatters + an n_real bump), the
                       conflict ids of the rows an arrival or departure
                       changed, what lower-ranking rows hold (a priced
                       candidate's preference plane, recomputed on device)
  apply_delta          ONE jitted dispatch, `donate_argnums` on the problem
                       and assignment buffers (SNIPPETS.md [1]-[3] donation
                       pattern) — the old buffers are reused in place, and
                       phantom rows are re-parked on a valid node on device

The warm re-solve itself then runs with every input already resident
(problem pytree, seed assignment, temperature scalars), provable with
``FLEET_TRANSFER_GUARD=disallow``: `jax.transfer_guard("disallow")` wraps
the dispatch and any host->device transfer of problem tensors raises.
Pre-repair is fused into the anneal entry (`anneal.prerepair_state`), so
the warm path is: small delta upload -> one donated merge dispatch -> one
fused solve dispatch -> scalars back.

Delta reuse is gated by bucket identity: the candidate ProblemTensors must
sit in the same shape tier with the same strategy/skew statics AND share
(by object identity) every tensor the delta does not cover — content drift
beyond the delta falls back to cold staging (counted in
`fleet_solver_resident_reuse_total{outcome="cold"}` and, on warm attempts,
`fleet_solver_host_transfers_total`). docs/guide/11-performance.md covers
tuning and transfer-guard debugging.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from dataclasses import dataclass
from functools import lru_cache
from typing import Any, Optional

import numpy as np

from ..obs import get_logger, kv, phase
from ..obs.metrics import MS_BUCKETS, REGISTRY
from .buckets import bucket_config, bucket_size

log = get_logger("solver.resident")

__all__ = ["ProblemDelta", "ResidentProblem", "transfer_guard_ctx"]

# metric catalog: docs/guide/10-observability.md
_M_REUSE = REGISTRY.counter(
    "fleet_solver_resident_reuse_total",
    "Resident-state staging decisions: delta = on-device delta applied to "
    "the resident problem, cold = full host (re)staging",
    labels=("outcome",))
_M_DELTA_MS = REGISTRY.histogram(
    "fleet_solver_delta_stage_ms",
    "Milliseconds spent applying on-device churn deltas per warm solve "
    "(upload + donated merge dispatch)",
    buckets=MS_BUCKETS)
_M_HOST_XFER = REGISTRY.counter(
    "fleet_solver_host_transfers_total",
    "Warm-path solves that had to move problem tensors across the host "
    "boundary (cold restage on a warm attempt, or a host repair re-upload) "
    "— each is an event the transfer guard would have caught in disallow "
    "mode")


def transfer_guard_ctx():
    """The context the resident warm path dispatches under.
    FLEET_TRANSFER_GUARD= unset/off/allow -> no guard; log -> jax logs every
    host transfer; disallow -> any host->device transfer raises (the proof
    mode the resident tests run in)."""
    mode = os.environ.get("FLEET_TRANSFER_GUARD", "").strip().lower()
    if mode in ("", "0", "off", "false", "allow"):
        return contextlib.nullcontext()
    if mode not in ("log", "disallow", "log_explicit", "disallow_explicit"):
        mode = "disallow"
    import jax
    return jax.transfer_guard(mode)


@dataclass
class ProblemDelta:
    """Structured churn: what changed since the resident staging.

    `node_valid`/`capacity` are FULL small arrays ((N,) / (N, R) — a few KB
    at fleet scale); row-sparse fields scatter into the big (S, ·) tensors.
    Fields left None mean "unchanged" (node_valid/capacity then upload from
    the accompanying ProblemTensors, which is the truth either way). The
    contract for delta staging: the new ProblemTensors differs from the
    resident one ONLY by fields this delta covers — anything else (a
    relowered fleet, conflict ids of rows `conflict_rows` does not name)
    must cold-stage, and `ResidentProblem.compatible` enforces it by
    object identity."""
    node_valid: Optional[np.ndarray] = None       # (N,) new validity mask
    capacity: Optional[np.ndarray] = None         # (N, R) new capacity
    # demand drift / arrivals: (rows (k,), values (k, R))
    demand_rows: Optional[tuple[np.ndarray, np.ndarray]] = None
    # arrival eligibility: (rows (k,), masks (k, N))
    eligible_rows: Optional[tuple[np.ndarray, np.ndarray]] = None
    # new real-row count (arrivals activate phantom rows; None = unchanged)
    n_real: Optional[int] = None
    # rows (k,) whose conflict ids (ports, volumes, anti-affinity) changed:
    # an arrival that declares a key, a departure whose ids are cleared.
    # Their ids are read from the accompanying ProblemTensors, as
    # node_valid and capacity are, and scattered at the staging's width
    conflict_rows: Optional[np.ndarray] = None
    # (N, R): what lower-ranking rows hold in the candidate's capacity,
    # which is priced (lower/tensors.py `with_price`): the merge writes
    # the staged preference plane anew from it, `capacity` and the merged
    # demand (`price_plane`), so the plane never crosses the host boundary
    preemptible: Optional[np.ndarray] = None


def _row_tier(k: int) -> int:
    """Scatter-row padding tier (8, 32, 128, ...): delta sizes drift burst
    to burst and each distinct row count would otherwise be a fresh XLA
    program for the merge kernel."""
    tier = 8
    while tier < k:
        tier *= 4
    return tier


def price_plane(demand, capacity, preemptible):
    """lower/tensors.py `preemption_price` on device: (S, N) f32, the
    price of each row of `demand` ((S, R)) on each server of `capacity`
    ((N, R), what lower-ranking rows hold, `preemptible`, included). The
    same f32 arithmetic, so the plane staged from the host and the plane
    a merge writes are one plane."""
    import jax.numpy as jnp

    over = jnp.maximum(demand[:, None, :] - (capacity - preemptible)[None],
                       0.0)
    share = jnp.where(over > 0.0, over / preemptible[None], 0.0)
    return -(jnp.rint(jnp.clip(share.max(axis=2), 0.0, 1.0) * 256.0)
             / 256.0)


@lru_cache(maxsize=1)
def _merge_fn():
    """The donated delta-merge kernel, built lazily so importing
    ProblemDelta never pays JAX startup (cp/ imports this module on the
    host path)."""
    import jax
    import jax.numpy as jnp

    def merge(prob, assignment, node_valid, capacity, dem_idx, dem_val,
              elig_idx, elig_rows, conf_idx, conf_val, preemptible, n_real,
              *, has_demand, has_eligible, has_conflict, has_price):
        with jax.named_scope("resident.merge"):     # metadata only
            # scatter rows ride padded tiers; pad slots carry an out-of-range
            # index and mode="drop" discards them. The static has_* flags keep
            # the common mask/capacity-only delta from touching the big (S, ·)
            # planes at all — they alias straight through the donation.
            demand = (prob.demand.at[dem_idx].set(dem_val, mode="drop")
                      if has_demand else prob.demand)
            eligible = (prob.eligible.at[elig_idx].set(elig_rows, mode="drop")
                        if has_eligible else prob.eligible)
            conflict_ids = (
                prob.conflict_ids.at[conf_idx].set(conf_val, mode="drop")
                if has_conflict else prob.conflict_ids)
            preferred = (price_plane(demand, capacity, preemptible)
                         if has_price else prob.preferred)
            # re-park phantom rows on a valid node: the previous winner may
            # have left them on a node this delta just killed, and a phantom
            # on an invalid node is the one way it stops being inert
            first_valid = jnp.argmax(node_valid).astype(jnp.int32)
            ar = jnp.arange(prob.S)
            assignment = jnp.where(ar >= n_real, first_valid, assignment)
            prob = dataclasses.replace(
                prob, demand=demand, eligible=eligible,
                conflict_ids=conflict_ids, preferred=preferred,
                node_valid=node_valid, capacity=capacity, n_real=n_real)
            return prob, assignment

    # donation: the stale problem/assignment buffers are dead the moment
    # the merge lands, so XLA reuses them in place — no second copy of the
    # (S, N) planes ever exists (SNIPPETS.md [1]-[3])
    return jax.jit(merge, donate_argnums=(0, 1),
                   static_argnames=("has_demand", "has_eligible",
                                    "has_conflict", "has_price"))


class ResidentProblem:
    """The device-resident placement state a TpuSolverScheduler owns.

    Lifecycle: `cold_stage(pt)` pads + uploads once; each churn burst calls
    `apply_delta(pt, delta)` (donated on-device merge); `solver.api._solve`
    seeds the warm anneal from `self.assignment` (device) and calls
    `adopt()` with the padded winner. `compatible()` is the bucket-identity
    gate deciding delta reuse vs cold fallback.

    The staging primitives (`_merge`, `_put_small`, `_put_n_real`,
    `_put_assignment`, `_stage_scalars`, `_expected_padded_S`) are hooks:
    the single-chip default stages onto the default device, and
    solver/sharded.ShardedResident overrides them to keep the same state
    mesh-sharded (committed NamedShardings + a sharding-constrained
    donated merge) for the pod-scale path."""

    # the mesh this staging is committed to (None = single chip); the
    # scheduler's slot matching keys on it so a routing flip mid-life can
    # never hand a sharded staging to the single-chip path or vice versa
    mesh = None
    # the single-chip staging supports churn-localized sub-solves
    # (solver/subsolve.py); the mesh-sharded subclass runs its own SPMD
    # anneal and opts out
    supports_subsolve = True

    def __init__(self, pt, *, bucket: bool = True,
                 cfg=None):
        self.cfg = cfg or bucket_config()
        self.bucket = bool(bucket and self.cfg.enabled)
        self.pt: Any = None
        self.prob: Any = None                 # padded DeviceProblem
        self.assignment: Any = None           # (padded_S,) i32 device array
        self.n_real: int = 0
        self._valid_fp: Optional[np.ndarray] = None
        self._cap_fp: Optional[np.ndarray] = None
        self._delta_ms: float = 0.0
        self._scalars: dict[tuple, tuple] = {}
        self._staged_fp: tuple = (None, None)
        # active-set sub-solve state (solver/subsolve.py): host mirror of
        # the padded device assignment as of the last solve, the host
        # constraint index (built lazily per staging), and the row set
        # churn deltas have touched since that solve
        self._mirror: Optional[np.ndarray] = None
        self._mirror_feasible: bool = False
        self._index: Any = None
        self._pending_rows: Optional[np.ndarray] = None
        # of those rows, the ones that held nothing before the delta that
        # named them: arrivals, whose conflict partners the planner keeps
        # frozen (solver/subsolve.py `ActiveIndex.closure`)
        self._pending_fresh: Optional[np.ndarray] = None
        self._pending_churn: bool = False
        # where the volume and anti-affinity ids start in the staged
        # conflict plane (solver/problem.conflict_offsets)
        self._conf_offsets: tuple[int, int] = (0, 0)
        self.cold_stage(pt)

    # -- staging -----------------------------------------------------------

    def cold_stage(self, pt) -> None:
        """Full host staging: prepare + pad + upload. Also the fallback
        when a delta's compatibility gate fails."""
        import jax.numpy as jnp

        from .buckets import stage_problem_tiers
        from .problem import conflict_offsets, prepare_problem

        if self.bucket:
            # arena staging (compile-free), but with PRIVATE device
            # buffers: the resident merge kernels donate these planes, so
            # the shared device-constant cache must not hand the same
            # array to two stagings
            prob, _ = stage_problem_tiers(
                pt, self.cfg, device=self._staging_device(),
                reuse_device_constants=False)
        else:
            prob = prepare_problem(pt, device=self._staging_device())
        if prob.n_real is None:
            # always traced, even unpadded/on-tier: keeps one treedef for
            # every resident solve and lets the merge kernel re-park
            prob = dataclasses.replace(
                prob, n_real=jnp.asarray(pt.S, jnp.int32))
        self.pt = pt
        self.prob = prob
        self.assignment = None
        self.n_real = int(pt.S)
        self._valid_fp = np.asarray(pt.node_valid, dtype=bool).copy()
        self._cap_fp = np.asarray(pt.capacity, dtype=np.float32).copy()
        self._delta_ms = 0.0
        # a cold staging invalidates the sub-solve state: the mirror is
        # of a dead assignment and the index of dead tensors
        self._mirror = None
        self._mirror_feasible = False
        self._index = None
        self._pending_rows = None
        self._pending_fresh = None
        self._pending_churn = False
        self._conf_offsets = conflict_offsets(pt)
        _M_REUSE.inc(outcome="cold")

    def compatible(self, pt, delta: Optional[ProblemDelta] = None) -> bool:
        """Bucket-identity gate for delta reuse: same shape tier and solver
        statics, and every tensor the delta does NOT cover is the same
        OBJECT as the resident staging's (dataclasses.replace shares the
        untouched arrays, which is exactly how the CP mutates churn).
        Content drift the delta cannot express -> False -> cold staging."""
        if self.pt is None or self.prob is None:
            return False
        old = self.pt
        if pt is old:
            return True
        if pt.N != old.N:
            return False
        if pt.strategy != old.strategy or pt.max_skew != old.max_skew:
            return False
        if pt.S != old.S:
            return self._arrivals_compatible(pt, delta, old)
        if self.bucket and self._expected_padded_S(pt) != self.prob.S:
            return False
        if not (pt.port_ids is old.port_ids
                and pt.volume_ids is old.volume_ids
                and pt.anti_ids is old.anti_ids
                or self._ids_fit(pt, delta)):
            return False
        same = (pt.coloc_ids is old.coloc_ids
                and pt.node_topology is old.node_topology
                and (pt.preferred is old.preferred
                     or self._prices(pt, delta)))
        if delta is None or delta.demand_rows is None:
            same = same and pt.demand is old.demand
        if delta is None or delta.eligible_rows is None:
            same = same and pt.eligible is old.eligible
        return same

    def _arrivals_compatible(self, pt, delta: Optional[ProblemDelta],
                             old, same_tier: bool = True) -> bool:
        """Can a GROWN pt (arrivals appended since the resident staging)
        still ride the delta path? Yes when the new rows activate phantom
        rows already on device: the fleet stays inside the padded tier,
        the delta writes the arrivals' demand + eligibility and bumps
        n_real, and the appended rows bring no hard-constraint id the
        delta does not scatter (`conflict_rows`, within the staged width
        and group count: `_ids_fit`; the padded id planes read -1 in
        every other phantom row). Anything richer — a crossed tier, a
        colocation id, a preference plane other than a price the delta
        carries (`_prices`) — cold-stages. `same_tier=False` asks the same
        of the rows and leaves the tier out (`grown_by`)."""
        if delta is None or delta.n_real != pt.S or pt.S <= old.S:
            return False
        if same_tier and (not self.bucket or
                          self._expected_padded_S(pt) != self.prob.S):
            return False
        if delta.demand_rows is None or delta.eligible_rows is None:
            return False
        new = np.arange(old.S, pt.S)
        if not (np.isin(new, np.asarray(delta.demand_rows[0])).all()
                and np.isin(new, np.asarray(delta.eligible_rows[0])).all()):
            return False
        if pt.node_topology is not old.node_topology:
            return False
        if ((pt.preferred is not None or old.preferred is not None)
                and not self._prices(pt, delta, staged=same_tier)):
            return False
        scattered = delta.conflict_rows is not None
        for name in ("port_ids", "volume_ids", "anti_ids", "coloc_ids"):
            if scattered and name != "coloc_ids":
                continue        # the delta's rows; `_ids_fit` below
            a, b = getattr(pt, name), getattr(old, name)
            if (a.shape[1] != b.shape[1]
                    or not np.array_equal(a[:old.S], b)
                    or (a[old.S:] != -1).any()):
                return False
        return not (scattered and same_tier) or self._ids_fit(pt, delta)

    def _prices(self, pt, delta: Optional[ProblemDelta],
                staged: bool = True) -> bool:
        """Is `pt`'s preference plane a price the delta carries, which the
        merge writes anew (`price_plane`)? Where `staged`, this staging has
        to have a plane to write: one staged without (a stage that began to
        preempt) stages anew."""
        return (delta is not None and delta.preemptible is not None
                and pt.priced and pt.preferred is not None
                and (not staged or self.prob.preferred is not None))

    def _ids_fit(self, pt, delta: Optional[ProblemDelta]) -> bool:
        """Can the delta's `conflict_rows` be scattered into this staging?
        The id space starts its families where the staged one does, and
        each row's ids fit the staged width and fall below its (padded)
        group count — an arrival whose key the stage already has adds no
        column. Otherwise the ids cold-stage."""
        if delta is None or delta.conflict_rows is None:
            return False
        from .problem import conflict_offsets, unified_conflict_rows
        if conflict_offsets(pt) != self._conf_offsets:
            return False
        vals = unified_conflict_rows(pt, delta.conflict_rows,
                                     self.prob.conflict_ids.shape[1],
                                     self._conf_offsets)
        return vals is not None and not (vals >= self.prob.G).any()

    def grown_by(self, pt, delta: Optional[ProblemDelta]) -> bool:
        """Is `pt` this staging's problem with plain arrivals appended —
        what `_arrivals_compatible` admits to the delta path, but past
        the padded tier, so that it has to stage anew? Then the new
        staging can `inherit` this one's placement."""
        old = self.pt
        return (old is not None and self._mirror is not None
                and pt.N == old.N and pt.strategy == old.strategy
                and pt.max_skew == old.max_skew
                and self._arrivals_compatible(pt, delta, old,
                                              same_tier=False))

    def inherit(self, old: "ResidentProblem",
                delta: ProblemDelta) -> None:
        """This fresh staging of a problem that `old.grown_by` takes
        `old`'s place as if its tier had had room: `old`'s committed
        assignment for the rows it had, the appended rows parked where
        the merge kernel parks phantoms, and the delta's rows pending for
        the active-set planner. The solve that follows is the resident
        warm one — localized to the arrivals where their closure is
        small, sticky otherwise — so a stage that outgrows its tier keeps
        its incumbents where they run."""
        self.adopt_host(old._mirror[:old.pt.S], self.pt.node_valid,
                        warm=False)
        self._mirror_feasible = old._mirror_feasible
        # capacity that shrank since `old` was solved puts its rows in the
        # active set, as on the delta path
        self._note_churn(self.pt, delta, since=old._cap_fp, before=old.pt)

    def merge_inputs(self, pt, delta: Optional[ProblemDelta] = None):
        """Stage the per-burst merge-kernel inputs for `delta`: returns
        ``(uploads, n_real, statics)`` where `uploads` is the
        device-staged small tuple the merge kernel consumes after
        ``(prob, assignment)`` and `statics` its static flags
        (has_demand, has_eligible, has_conflict, has_price). Split out of
        :meth:`apply_delta` so the
        compile-contract auditor (solver/contracts.py) can lower the
        EXACT argument shapes the production dispatch uses — not a
        hand-built approximation that would drift. Mutates `self.n_real`
        when the delta bumps it (the staging is the commit point)."""
        delta = delta or ProblemDelta()
        S = self.prob.S
        R = self.prob.demand.shape[1]
        N = self.prob.N

        valid = np.asarray(
            delta.node_valid if delta.node_valid is not None
            else pt.node_valid, dtype=bool)
        cap = np.asarray(
            delta.capacity if delta.capacity is not None
            else pt.capacity, dtype=np.float32)

        def pad_rows(rows_vals, width, fill_dtype):
            idx, vals = rows_vals
            idx = np.asarray(idx, dtype=np.int32)
            vals = np.asarray(vals, dtype=fill_dtype)
            k = _row_tier(max(idx.shape[0], 1))
            pad = k - idx.shape[0]
            if pad:
                idx = np.concatenate([idx, np.full(pad, S, dtype=np.int32)])
                vals = np.concatenate(
                    [vals, np.zeros((pad, width), dtype=fill_dtype)])
            return idx, vals

        has_demand = delta.demand_rows is not None
        has_eligible = delta.eligible_rows is not None
        dem_idx, dem_val = (pad_rows(delta.demand_rows, R, np.float32)
                            if has_demand else (None, None))
        if has_eligible:
            # the delta contract stays host-friendly ((k, N) bool masks);
            # the rows are packed HERE to match the resident plane's
            # layout, so the donated merge scatters packed words — an
            # arrival costs k*ceil(N/32)*4 bytes on the wire, not k*N
            idx, masks = delta.eligible_rows
            if self.prob.eligible.dtype == np.uint32:
                from .problem import pack_bool_rows, packed_width
                masks = pack_bool_rows(
                    np.asarray(masks, dtype=bool).reshape(-1, N))
                elig_idx, elig_rows = pad_rows((idx, masks),
                                               packed_width(N), np.uint32)
            else:
                elig_idx, elig_rows = pad_rows((idx, masks), N, bool)
        else:
            elig_idx, elig_rows = None, None
        has_conflict = delta.conflict_rows is not None
        conf_idx = conf_val = None
        if has_conflict:
            from .problem import unified_conflict_rows
            K = self.prob.conflict_ids.shape[1]
            rows = np.asarray(delta.conflict_rows, dtype=np.int32)
            conf_idx, conf_val = pad_rows(
                (rows, unified_conflict_rows(pt, rows, K,
                                             self._conf_offsets)),
                K, np.int32)
        has_price = delta.preemptible is not None
        pre = (np.asarray(delta.preemptible, dtype=np.float32)
               if has_price else None)
        if delta.n_real is not None:
            self.n_real = int(delta.n_real)
        n_real = self._put_n_real()

        # explicit small uploads; the warm solve after the merge runs
        # with everything already resident
        uploads = self._put_small(
            (valid, cap, dem_idx, dem_val, elig_idx, elig_rows, conf_idx,
             conf_val, pre))
        # host fingerprints adopted by apply_delta AFTER a successful
        # merge (drifted() must keep matching the pre-merge staging when
        # the merge fails and cold_stage recovers)
        self._staged_fp = (valid, cap)
        return uploads, n_real, dict(has_demand=has_demand,
                                     has_eligible=has_eligible,
                                     has_conflict=has_conflict,
                                     has_price=has_price)

    def _note_churn(self, pt, delta: Optional[ProblemDelta],
                    since: Optional[np.ndarray] = None,
                    before=None) -> None:
        """Accumulate the row set this delta touches for the active-set
        planner (solver/subsolve.py) — called BEFORE the fingerprints
        roll over so capacity shrink is measured against the staging the
        mirror assignment was solved on (`since`, where that was another
        staging's: `inherit`). Node kills need no bookkeeping here:
        stranded rows are recomputed from the post-delta tensors at plan
        time. Of the rows, those the delta gives demand that held none in
        `before` (the problem the mirror was solved on; default this
        staging's) are fresh: arrivals into a phantom or tombstone row."""
        if not self.supports_subsolve or self._mirror is None:
            return    # nothing to localize against (no previous solve)
        rows = [np.empty(0, dtype=np.int64)]
        fresh = [np.empty(0, dtype=np.int64)]
        if delta is not None:
            if delta.demand_rows is not None:
                drows = np.asarray(delta.demand_rows[0], dtype=np.int64)
                rows.append(drows)
                old = np.asarray((self.pt if before is None
                                  else before).demand)
                held = np.zeros(drows.shape[0], dtype=bool)
                inside = drows < old.shape[0]
                held[inside] = old[drows[inside]].any(axis=1)
                asks = np.asarray(delta.demand_rows[1]).any(axis=1)
                fresh.append(drows[asks & ~held])
            if delta.eligible_rows is not None:
                rows.append(np.asarray(delta.eligible_rows[0],
                                       dtype=np.int64))
            if delta.conflict_rows is not None:
                rows.append(np.asarray(delta.conflict_rows,
                                       dtype=np.int64))
        # capacity shrink: frozen rows on a shrunk node may overflow the
        # new capacity — they must join the active set (growth is safe)
        new_cap = np.asarray(
            delta.capacity if delta is not None and
            delta.capacity is not None else pt.capacity, dtype=np.float32)
        since = self._cap_fp if since is None else since
        if since is not None and new_cap.shape == since.shape:
            shrunk = (new_cap < since - 1e-6).any(axis=1)
            if shrunk.any():
                n = min(self.n_real, self._mirror.shape[0])
                rows.append(np.nonzero(shrunk[self._mirror[:n]])[0])
        pending = np.unique(np.concatenate(rows))
        if self._pending_rows is not None:
            pending = np.union1d(self._pending_rows, pending)
        self._pending_rows = pending
        fresh = np.unique(np.concatenate(fresh))
        if self._pending_fresh is not None:
            fresh = np.union1d(self._pending_fresh, fresh)
        self._pending_fresh = fresh
        self._pending_churn = True

    def apply_delta(self, pt, delta: Optional[ProblemDelta] = None) -> float:
        """Merge churn into the resident buffers on device; returns the
        delta-staging wall ms (also accumulated for the next solve's
        `delta_stage_ms` timing). The caller has already checked
        `compatible`; node_valid/capacity always re-upload from `pt` (a few
        KB — the (S, N) problem planes are what never move)."""
        with phase("sched.stage.delta") as ph:
            self._note_churn(pt, delta)
            uploads, n_real, statics = self.merge_inputs(pt, delta)
            valid, cap = self._staged_fp
            # ONE donated merge dispatch
            try:
                self.prob, self.assignment = self._merge()(
                    self.prob, self.assignment, *uploads, n_real, **statics)
            except Exception:
                # a failed merge leaves donated buffers in an unknown state:
                # the only safe recovery is a full cold restage
                log.warning("delta merge failed; cold restaging %s",
                            kv(S=pt.S, N=pt.N))
                self.cold_stage(pt)
                raise
            self.pt = pt
            self._valid_fp = valid.copy()
            self._cap_fp = cap.copy()
            changed = None if delta is None else delta.conflict_rows
            if self._index is not None and (changed is not None
                                            or pt.S != self._index.S):
                # the planner's index follows the rows the delta changed
                self._index.update(pt, () if changed is None else changed)
            if self._mirror is not None:
                # replay the merge kernel's deterministic phantom re-park so
                # the mirror stays an exact host copy of the device assignment
                self._mirror[self.n_real:] = int(np.argmax(valid))
        self._delta_ms += ph.ms
        _M_DELTA_MS.observe(ph.ms)
        _M_REUSE.inc(outcome="delta")
        return ph.ms

    # -- staging hooks (overridden by solver/sharded.ShardedResident) ------

    def _expected_padded_S(self, pt) -> int:
        """The padded S a cold staging of `pt` would produce — the shape
        half of the bucket-identity gate."""
        return bucket_size(pt.S, minimum=self.cfg.minimum,
                           align=self.cfg.align)

    def _staging_device(self):
        """Where cold_stage materializes the prepared problem. None = the
        default device (the single-chip contract: staging IS the final
        placement). The sharded override stages on the host CPU backend so
        the whole (S, N) planes never materialize on one accelerator
        before being committed shard-by-shard to the mesh."""
        return None

    def _merge(self):
        """The donated delta-merge kernel for this staging's layout."""
        return _merge_fn()

    def _put_small(self, tree):
        """Stage the per-burst small uploads (masks, capacity, scatter
        rows) where the merge kernel expects them."""
        import jax
        return jax.device_put(tree)

    def _put_n_real(self):
        """The traced real-row count, staged for the merge kernel."""
        import jax.numpy as jnp
        return jnp.asarray(self.n_real, jnp.int32)

    def _put_assignment(self, padded: np.ndarray):
        """Upload a padded host assignment as the resident warm seed."""
        import jax
        return jax.device_put(padded)

    def _stage_scalars(self, key: tuple) -> tuple:
        import jax.numpy as jnp
        return tuple(jnp.float32(v) for v in key)

    def drifted(self, pt) -> bool:
        """Has node validity or capacity drifted since the last staging?
        (The implicit-delta check for callers that mutate ProblemTensors in
        place instead of sending a ProblemDelta.)"""
        return not (np.array_equal(self._valid_fp, pt.node_valid)
                    and np.array_equal(
                        self._cap_fp,
                        np.asarray(pt.capacity, dtype=np.float32)))

    # -- solve-side hooks (solver/api._solve) ------------------------------

    def consume_delta_ms(self) -> float:
        ms, self._delta_ms = self._delta_ms, 0.0
        return ms

    def warm_scalars(self, t0: float, t1: float, mw: float) -> tuple:
        """Device-staged anneal scalars: traced args to the fused solve
        must already be resident or the transfer guard fires. Keyed on the
        values; a scheduler re-uses one config so this stages once."""
        key = (float(t0), float(t1), float(mw))
        staged = self._scalars.get(key)
        if staged is None:
            staged = self._stage_scalars(key)
            self._scalars = {key: staged}    # one live config at a time
        return staged

    def adopt(self, padded_assignment) -> None:
        """Keep the padded winner (already on device) as the next warm
        seed — no transfer happens here."""
        self.assignment = padded_assignment

    def adopt_host(self, assignment: np.ndarray, node_valid, *,
                   warm: bool = True) -> None:
        """Host repair rewrote the winner: re-upload the repaired
        assignment. On the warm path that is a host transfer the disallow
        guard would have caught — the event the counter exists for (a cold
        solve's upload is just staging)."""
        from .buckets import pad_assignment
        padded = pad_assignment(np.asarray(assignment, dtype=np.int32),
                                self.prob.S, np.asarray(node_valid))
        self.assignment = self._put_assignment(padded)
        self._mirror = padded.copy()
        if warm:
            _M_HOST_XFER.inc()

    def record_warm_fallback(self) -> None:
        """A warm attempt had to cold-stage: problem tensors crossed the
        host boundary where the disallow guard would have fired."""
        _M_HOST_XFER.inc()

    def eviction_snapshot(self) -> Optional[tuple[np.ndarray, bool]]:
        """Host snapshot for the scheduler's slot manager (sched/tpu.py):
        the committed PADDED assignment mirror + its feasibility flag.
        Padded — not the real-row slice — so a re-admission
        ``adopt_host`` restores the exact device seed, phantom parking
        included, and the readmitted warm solve is bit-identical to a
        never-evicted one. Costs no device transfer: the mirror is
        maintained host-side by note_host_assignment/adopt_host. None
        before the first committed solve (nothing worth snapshotting)."""
        if self._mirror is None:
            return None
        return np.array(self._mirror, copy=True), bool(self._mirror_feasible)

    def device_nbytes(self) -> int:
        """Resident device footprint: per-plane byte accounting over the
        staged problem + assignment. Packed planes count at their uint32
        width (solver/problem.py packed-plane math) — this is the number
        the slot manager's byte budget enforces at runtime."""
        import jax
        leaves = jax.tree_util.tree_leaves((self.prob, self.assignment))
        return int(sum(int(x.size) * x.dtype.itemsize for x in leaves))

    # -- active-set sub-solve hooks (solver/subsolve.py) -------------------

    def note_host_assignment(self, padded=None,
                             feasible: Optional[bool] = None) -> None:
        """api._solve's end-of-solve note: the padded winner it fetched
        (the sub-solve mirror — no extra transfer, the result crossed the
        boundary anyway) and whether the committed stats were feasible
        (the frozen-base precondition: frozen-frozen violations are zero
        only when the previous placement was). Clears the pending churn —
        whatever was pending is folded into this assignment now."""
        if padded is not None:
            arr = np.asarray(padded, dtype=np.int32)
            if self.prob is not None and arr.shape[0] == self.prob.S:
                self._mirror = arr.copy()
        if feasible is not None:
            self._mirror_feasible = bool(feasible)
        self._pending_rows = None
        self._pending_fresh = None
        self._pending_churn = False

    def take_active_plan(self):
        """The churn-localized sub-problem for the warm solve about to
        dispatch, or None for the full fused path. Consumes the pending
        churn either way. Fallback outcomes are counted here;
        "localized"/"fallback_infeasible" are counted by the caller after
        the exact gate rules."""
        pending, self._pending_rows = self._pending_rows, None
        fresh, self._pending_fresh = self._pending_fresh, None
        churn, self._pending_churn = self._pending_churn, False
        if not churn:
            return None
        from .subsolve import (ActiveIndex, plan_active, record_outcome,
                               subsolve_config)
        cfg = subsolve_config()
        if not (cfg.enabled and self.supports_subsolve):
            return None
        if self._mirror is None or not self._mirror_feasible:
            return None
        if self._index is None:
            # built once a staging; a delta that changes rows' conflict
            # ids or appends rows updates it for those rows (apply_delta)
            self._index = ActiveIndex(self.pt)
        plan, outcome = plan_active(
            self._index, self.pt, self._mirror, self.prob.S, self.prob.T,
            pending if pending is not None
            else np.empty(0, dtype=np.int64), cfg,
            G_full=self.prob.G, Gc_full=self.prob.Gc, fresh_rows=fresh)
        if plan is None:
            record_outcome(outcome)
        return plan
