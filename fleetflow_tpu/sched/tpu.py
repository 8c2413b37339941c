"""TPU solver scheduler backend: wraps fleetflow_tpu.solver.solve.

Owns the DEVICE-RESIDENT fleet state (solver/resident.py): the padded
DeviceProblem and the last committed assignment live on device across
re-solves, and CP churn arrives as structured `ProblemDelta`s applied by a
donated on-device merge — warm reschedules never round-trip the host
(SURVEY.md hard part (d): keep the host<->device boundary out of the
per-reschedule path). Content drift the delta cannot express (a relowered
fleet, new conflict ids, a different shape tier) falls back to cold
staging, counted in fleet_solver_resident_reuse_total{outcome}.

Warm deltas additionally feed the ACTIVE-SET path (solver/subsolve.py):
the resident staging tracks which rows each delta touched, and when the
churn's constraint closure is small the warm anneal runs over a gathered
mini tier instead of the full problem — the O(affected) sweep cost the
burst-reschedule and admission micro-solve legs ride. The scheduler needs
no extra bookkeeping for this: `ResidentProblem.apply_delta` accumulates
the affected rows and `solver.api._solve` plans/gates the localized
dispatch, so every `reschedule()` caller gets it for free (the outcome is
visible on `fleet_solver_subsolve_total{outcome}` and the debug log
line below).

Resident slots live under a SLOT MANAGER with a device-memory byte
budget (FLEET_RESIDENT_BYTES, count-bounded too by
FLEET_RESIDENT_STAGES): admission of a new resident evicts
least-recently-used slots until the budget holds, using the packed-plane
byte math (`ResidentProblem.device_nbytes`) as the accounting unit.
Eviction keeps a HOST snapshot of the committed padded assignment
(`ResidentProblem.eviction_snapshot` — the sub-solve mirror, so the
snapshot costs zero device transfers), and re-admission warm-seeds from
it through `adopt_host` instead of cold-staging: the readmitted warm
solve runs the exact resident-warm executable, bit-identical to a
never-evicted slot (pinned by the eviction property test). Occupancy is
rendered by `fleet solve slots` from `slots_status()`.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from .base import (Placement, assignment_names, level_schedule,
                   record_placement)
from ..lower.tensors import ProblemTensors
from ..obs import get_logger, kv, phase
from ..obs.metrics import REGISTRY

log = get_logger("sched.tpu")

__all__ = ["TpuSolverScheduler"]

# metric catalog: docs/guide/10-observability.md
_M_EVICTIONS = REGISTRY.counter(
    "fleet_sched_slot_evictions_total",
    "Resident slots evicted by the device-memory slot manager")
_M_READMITS = REGISTRY.counter(
    "fleet_sched_slot_readmissions_total",
    "Evicted stages re-admitted warm from their host snapshot")
_M_LEVELS = REGISTRY.counter(
    "fleet_sched_level_schedules_total",
    "Level schedules a placement was given, by outcome: kept (the stage's "
    "slot had one built from the very same dep_depth and service_names "
    "objects) or built",
    labels=("outcome",))
_M_RES_BYTES = REGISTRY.gauge(
    "fleet_sched_resident_bytes",
    "Device bytes held by resident stage slots (packed-plane accounting)")
_M_RES_SLOTS = REGISTRY.gauge(
    "fleet_sched_resident_slots", "Resident stage slots currently held")
_M_RES_DRIFT = REGISTRY.gauge(
    "fleet_solver_resident_bytes_drift",
    "Live device bytes of resident slots minus the slot manager's "
    "admission-time accounting — nonzero drift means a slot's buffers "
    "grew or shrank after admission (refreshed by slots_status / the "
    "obs collector's cross-check)")

# default device budget for resident stage state: roomy on a real chip,
# and far above what the test-scale problems allocate, so the budget
# only bites when an operator configures it (or the fleet is real)
_DEFAULT_BUDGET = 256 << 20


@dataclass
class _StageSlot:
    """Per-stage resident state. The CP drives every stage through ONE
    scheduler, so resident reuse must be per stage: a single shared slot
    would make each stage's churn evict the other's device buffers (every
    multi-stage burst cold-stages) and could warm-seed one stage's anneal
    from another stage's assignment when their shapes coincide."""
    resident: Any                                  # solver.resident.ResidentProblem
    last_assignment: Optional[np.ndarray] = None   # host warm seed for cold fallback
    key: Optional[str] = None                      # CP stage key, when the caller has one
    nbytes: int = 0                                # device footprint at admission
    last_used: float = 0.0                         # monotonic stamp for LRU + status
    # (dep_depth, service_names, levels) of the last level schedule built:
    # reused while a problem brings the same two OBJECTS (a churn re-solve
    # replaces capacity and keeps the graph); shared by the stage's
    # placements and read-only
    schedule: Optional[tuple] = None


@dataclass
class _EvictRecord:
    """What eviction preserves: the committed padded assignment (host
    side — the sub-solve mirror rode the last solve's fetch, so the
    snapshot is free) and enough metadata to validate re-admission."""
    assignment: np.ndarray
    feasible: bool
    S: int                                         # real (unpadded) rows
    evictions: int = 1                             # times this key was evicted
    host_seed: Optional[np.ndarray] = field(default=None)


class TpuSolverScheduler:
    def __init__(self, *, chains=None, steps: int = 128, seed: int = 0,
                 mesh=None, bucket: Optional[bool] = None,
                 resident_bytes: Optional[int] = None):
        # chains=None defers to the solver's backend-aware default
        # (1 on CPU, 2 on accelerators — measured r4/r5)
        self.chains = chains
        self.steps = steps
        self.seed = seed
        self.mesh = mesh
        # bucket=None -> ON for the scheduler (this is the churn/reschedule
        # path the bucketing exists for; FLEET_BUCKET=0 force-disables)
        self.bucket = bucket
        # slot manager state: MRU-ordered per-stage resident slots, byte-
        # and count-bounded so a CP cycling through many stages cannot pin
        # unbounded device memory; evicted stages keep a host snapshot so
        # re-admission warm-seeds instead of cold-staging
        self._residents: list[_StageSlot] = []
        self._evicted: dict[str, _EvictRecord] = {}
        try:
            self._max_residents = max(
                1, int(os.environ.get("FLEET_RESIDENT_STAGES") or "8"))
        except ValueError:
            self._max_residents = 8
        if resident_bytes is None:
            try:
                resident_bytes = max(1, int(
                    os.environ.get("FLEET_RESIDENT_BYTES")
                    or str(_DEFAULT_BUDGET)))
            except ValueError:
                resident_bytes = _DEFAULT_BUDGET
        self._budget_bytes = int(resident_bytes)
        # bounded: snapshots are (padded_S,) i32 vectors, but a CP churning
        # through unbounded stage keys must not grow host memory forever
        self._max_evicted = max(4 * self._max_residents, 64)

    @staticmethod
    def _platform() -> str:
        from ..platform import init_platform
        return init_platform()["platform"]

    def _bucket_enabled(self, pt: ProblemTensors) -> bool:
        from ..solver.buckets import bucket_config
        if self.bucket is False:
            return False
        # spread constraints bucket too since phantoms carry a traced
        # n_real mask (the former max_skew bypass is closed)
        return bucket_config().enabled

    # -- slot manager ------------------------------------------------------

    def _resident_bytes(self) -> int:
        return sum(s.nbytes for s in self._residents)

    def _evict(self, slot: _StageSlot) -> None:
        """Drop a slot's device state, keeping the host snapshot of its
        committed assignment so re-admission warm-seeds. Keyless slots
        evict without a snapshot (no identity to re-admit under)."""
        snap = None
        try:
            snap = slot.resident.eviction_snapshot()
        except Exception:
            snap = None
        if slot.key is not None:
            prev = self._evicted.pop(slot.key, None)
            count = (prev.evictions + 1) if prev is not None else 1
            if snap is not None:
                self._evicted[slot.key] = _EvictRecord(
                    assignment=snap[0], feasible=snap[1],
                    S=int(slot.resident.n_real), evictions=count,
                    host_seed=slot.last_assignment)
            elif slot.last_assignment is not None:
                # nothing committed on device yet: preserve the host seed
                # so the fallback warm start survives eviction too
                self._evicted[slot.key] = _EvictRecord(
                    assignment=np.empty(0, np.int32), feasible=False,
                    S=int(slot.last_assignment.shape[0]), evictions=count,
                    host_seed=slot.last_assignment)
            if len(self._evicted) > self._max_evicted:
                # oldest-inserted falls off; dict preserves insert order
                self._evicted.pop(next(iter(self._evicted)))
        _M_EVICTIONS.inc()
        log.debug("slot-evict %s", kv(
            stage=slot.key, bytes=slot.nbytes,
            snapshot=snap is not None))

    def _admit(self, slot: _StageSlot) -> None:
        """Insert a slot at the MRU head, then evict from the LRU tail
        until the byte budget and the count bound hold. The newly
        admitted slot is NEVER evicted — a stage larger than the whole
        budget still solves (over-budget by itself), so a full budget
        cannot deadlock admission."""
        try:
            slot.nbytes = int(slot.resident.device_nbytes())
        except Exception:
            slot.nbytes = 0
        slot.last_used = time.monotonic()
        self._residents.insert(0, slot)
        while len(self._residents) > 1 and (
                len(self._residents) > self._max_residents
                or self._resident_bytes() > self._budget_bytes):
            self._evict(self._residents.pop())
        _M_RES_BYTES.set(self._resident_bytes())
        _M_RES_SLOTS.set(len(self._residents))

    def forget(self, stage: str) -> None:
        """The stage is gone (torn down), not idle: its resident slot and
        its eviction snapshot go with it, so a later solve under the same
        key stages cold and seeds afresh, and the device memory is free
        now instead of at the next eviction."""
        self._residents = [s for s in self._residents if s.key != stage]
        self._evicted.pop(stage, None)
        _M_RES_BYTES.set(self._resident_bytes())
        _M_RES_SLOTS.set(len(self._residents))

    def restore(self, stage: str, pt: ProblemTensors,
                raw: np.ndarray) -> None:
        """The caller did not adopt the last solve of `stage` (a candidate
        that came back infeasible: cp/placement.py admit_batch) and the
        stage stands as `pt` placed by `raw`: make THAT the slot's
        resident state again, so the next delta is one against what
        stands and its solve the resident warm one. Without it the slot
        would hold the dropped candidate, the next delta would not fit
        it, and the stage would be solved again from the seed — every
        running service free to move. One cold staging, no solve. A
        stage on the mesh is forgotten instead (it stages cold next
        time, as before)."""
        from ..solver.resident import ResidentProblem

        slot = next((s for s in self._residents if s.key == stage), None)
        if slot is None:
            return
        if slot.resident.mesh is not None:
            self.forget(stage)
            return
        resident = ResidentProblem(pt, bucket=self._bucket_enabled(pt))
        resident.adopt_host(raw, pt.node_valid, warm=False)
        resident.note_host_assignment(feasible=True)
        slot.resident = resident
        slot.last_assignment = np.asarray(raw)
        slot.nbytes = int(resident.device_nbytes())
        _M_RES_BYTES.set(self._resident_bytes())

    def byte_drift(self) -> int:
        """Live device bytes minus the accounted admission-time bytes,
        summed over resident slots — the cross-check the profiling hook
        (ISSUE 18) exports: the slot manager budgets on admission-time
        `device_nbytes`, so any post-admission buffer growth (a resident
        re-staged larger in place, an adopted oversized assignment) is
        invisible to eviction until it drifts this gauge off zero. A
        host-side walk of buffer shapes; no device sync."""
        drift = 0
        for s in self._residents:
            try:
                drift += int(s.resident.device_nbytes()) - int(s.nbytes)
            except Exception:
                continue
        _M_RES_DRIFT.set(drift)
        return drift

    def slots_status(self) -> dict:
        """Occupancy payload for the health channel (`fleet solve slots`):
        per-slot stage key, tier, resident bytes, last-use age and
        eviction count, plus the manager's budget totals."""
        now = time.monotonic()
        slots = []
        for s in self._residents:
            prob = getattr(s.resident, "prob", None)
            tier = (f"{prob.S}x{prob.N}" if prob is not None else "-")
            evs = self._evicted.get(s.key) if s.key is not None else None
            slots.append({
                "stage": s.key or "-", "tier": tier,
                "bytes": int(s.nbytes),
                "idle_s": round(max(0.0, now - s.last_used), 3),
                "evictions": evs.evictions if evs is not None else 0,
                "warm": s.resident.assignment is not None,
            })
        parked = [{
            "stage": k, "evictions": rec.evictions, "S": rec.S,
            "snapshot": bool(rec.assignment.size),
        } for k, rec in self._evicted.items()]
        return {
            "budget_bytes": self._budget_bytes,
            "max_slots": self._max_residents,
            "resident_bytes": self._resident_bytes(),
            "bytes_drift": self.byte_drift(),
            "slots": slots,
            "evicted": parked,
        }

    def _stage(self, pt: ProblemTensors, delta, warm: bool,
               stage_key: Optional[str] = None, mesh=None):
        """Resident staging decision: DELTA (on-device merge into the
        resident buffers) when the bucket identity holds and the drift is
        expressible, else COLD (full host staging). The old identity-keyed
        cache re-staged the whole padded problem whenever capacity drifted
        (every churn burst with commitments); the resident layer turns
        that into a few-KB upload + one donated dispatch.

        `mesh` is the pod-scale route (solver.sharded.sharded_route): the
        slot then holds a mesh-sharded ShardedResident, and slot matching
        keys on the mesh so a routing flip mid-life can never hand a
        sharded staging to the single-chip solve or vice versa.

        Returns (slot, resident_warm): resident_warm=True means the
        solve seeds from the device-resident previous assignment — either
        live in the slot, or restored from an eviction snapshot (the
        re-admission path, bit-identical to never having been evicted)."""
        from ..solver.resident import ProblemDelta, ResidentProblem

        # warm delta reuse: the slot whose resident staging matches this
        # pt (compatible() checks shape tier + statics + object identity
        # on the untouched tensors, so only this stage's own slot can hit)
        if warm:
            for i, slot in enumerate(self._residents):
                rp = slot.resident
                if rp.mesh != mesh:
                    continue
                if rp.assignment is not None and rp.compatible(pt, delta):
                    if i:
                        self._residents.insert(0, self._residents.pop(i))
                    slot.last_used = time.monotonic()
                    if stage_key is not None:
                        # a caller may start passing stage keys mid-life:
                        # stamp the slot so keyed cold reclaims find it
                        slot.key = stage_key
                    if delta is not None:
                        rp.apply_delta(pt, delta)
                    elif rp.pt is not pt or rp.drifted(pt):
                        # in-place mutation path (node_event flips
                        # pt.node_valid, capacity refresh replaces it):
                        # synthesize the delta
                        rp.apply_delta(pt, ProblemDelta())
                    return slot, True

        # cold (re)staging: reclaim this stage's old slot so its host
        # assignment can still warm-seed the fallback and the pool keeps
        # one slot per stage. An explicit stage key (the CP passes its
        # flow/stage key) is authoritative — two stages of one project can
        # carry IDENTICAL service name lists, so names alone cannot tell
        # them apart; without a key, fall back to shape + service-name
        # match (in-place churn shares the list object, a relowered stage
        # compares equal)
        slot = None
        if stage_key is not None:
            for i, cand in enumerate(self._residents):
                if cand.key == stage_key:
                    slot = self._residents.pop(i)
                    break
        if slot is None:
            # no keyed match: a keyless slot matching shape + names is
            # this stage from an earlier keyless call — adopt (and stamp)
            # it rather than leaking a second device-resident copy
            for i, cand in enumerate(self._residents):
                old = cand.resident.pt
                if (cand.key is None
                        and old is not None and old.S == pt.S
                        and old.N == pt.N
                        and (old.service_names is pt.service_names
                             or old.service_names == pt.service_names)):
                    slot = self._residents.pop(i)
                    break
        outgrown = None
        if warm and slot is not None and slot.resident.assignment is not None:
            # this stage HAD resident state but the delta contract broke:
            # problem tensors will cross the host boundary (the
            # transfer-guard event)
            slot.resident.record_warm_fallback()
            if (mesh is None and slot.resident.mesh is None
                    and slot.resident.grown_by(pt, delta)):
                # plain arrivals pushed the stage past its padded tier:
                # the new staging inherits the old one's placement below
                outgrown = slot.resident
        if mesh is not None:
            from ..solver.sharded import ShardedResident
            resident = ShardedResident(pt, mesh=mesh,
                                       bucket=self._bucket_enabled(pt))
        else:
            resident = ResidentProblem(pt, bucket=self._bucket_enabled(pt))
        if slot is None:
            slot = _StageSlot(resident=resident, key=stage_key)
        else:
            slot.resident = resident
            if stage_key is not None:
                slot.key = stage_key

        # re-admission: this stage was evicted with a committed snapshot
        # and the fleet shape still matches — restore the padded
        # assignment through adopt_host (warm=False: re-admission is
        # staging, not a guard-violating mid-solve transfer) and run the
        # resident-warm executable, exactly as if never evicted
        resident_warm = False
        rec = (self._evicted.get(stage_key)
               if warm and stage_key is not None else None)
        if rec is not None and slot.last_assignment is None:
            slot.last_assignment = rec.host_seed
        if (rec is not None and rec.assignment.size
                and rec.S == pt.S
                and rec.assignment.shape[0] == resident.prob.S):
            resident.adopt_host(rec.assignment, pt.node_valid, warm=False)
            resident.note_host_assignment(padded=rec.assignment,
                                          feasible=rec.feasible)
            resident_warm = True
            _M_READMITS.inc()
            log.debug("slot-readmit %s", kv(stage=stage_key,
                                            evictions=rec.evictions))
        elif outgrown is not None:
            resident.inherit(outgrown, delta)
            resident_warm = True
            log.debug("slot-outgrown %s", kv(stage=stage_key,
                                             rows=pt.S,
                                             tier=resident.prob.S))
        self._admit(slot)
        return slot, resident_warm

    def _solve_one(self, pt: ProblemTensors, slot, resident_warm: bool,
                   sh_mesh, init, overlap_host_work=None):
        from ..solver import solve
        rp = slot.resident
        if sh_mesh is not None:
            from ..solver.sharded import solve_sharded
            return solve_sharded(pt, resident=rp,
                                 resident_warm=resident_warm,
                                 init_assignment=init, steps=self.steps,
                                 seed=self.seed,
                                 overlap_host_work=overlap_host_work)
        # bucket flag comes from the slot's OWN staging, not a fresh
        # env read: rp.prob was padded (or not) under the config
        # captured at cold-stage time, and a mid-life FLEET_BUCKET
        # flip must not make _solve skip the phantom-row slice on an
        # already-padded staging
        return solve(pt, prob=rp.prob, chains=self.chains,
                     steps=self.steps, seed=self.seed, mesh=self.mesh,
                     init_assignment=init, bucket=rp.bucket,
                     resident=rp, resident_warm=resident_warm,
                     overlap_host_work=overlap_host_work)

    def _finalize(self, pt: ProblemTensors, res, slot, ms: float,
                  stage: Optional[str], ph) -> Placement:
        """`ph` is the open `sched.finalize` phase: its `levels` says
        whether the level schedule was "built" or "kept"."""
        slot.last_assignment = res.assignment
        slot.last_used = time.monotonic()
        sub = getattr(res, "subsolve", None)
        if sub is not None:
            # the churn rode the mini-tier path (or tried to): the line
            # an operator correlates with a reschedule latency change
            log.debug("active-set %s", kv(
                stage=stage, rows=sub["rows"], tier=sub["tier"],
                outcome=sub["outcome"], ms=sub["ms"]))
        kept = slot.schedule
        if (kept is not None and kept[0] is pt.dep_depth
                and kept[1] is pt.service_names):
            levels, outcome = kept[2], "kept"
        else:
            levels, outcome = level_schedule(pt), "built"
            slot.schedule = (pt.dep_depth, pt.service_names, levels)
        _M_LEVELS.inc(outcome=outcome)
        ph.set(levels=outcome)
        placement = Placement(
            assignment=assignment_names(pt, res.assignment),
            levels=levels,
            feasible=res.feasible,
            violations=res.violations,
            soft=res.soft,
            source=f"{self._platform()}-anneal",
            solve_ms=ms,
            raw=res.assignment,
        )
        record_placement(placement)
        return placement

    def place(self, pt: ProblemTensors, *, warm_start: bool = False,
              delta=None, overlap_host_work=None,
              stage: Optional[str] = None) -> Placement:
        """Solve `pt`. `delta` (solver.resident.ProblemDelta) is the CP's
        structured churn for a warm reschedule: applied on device when the
        resident bucket identity holds. `overlap_host_work` runs host-side
        work (e.g. re-lowering) while the solve is in flight. `stage` is
        the caller's stable stage key, used to keep one resident slot per
        stage (two stages of one project can carry identical service
        names, so the key is the only reliable identity)."""
        # first device use on the CP path: whatever platform JAX
        # initialises in this process, logged once (platform.py)
        self._platform()
        # imported lazily so the host path never pays JAX startup
        from ..solver.sharded import sharded_route

        with phase("sched.place", stage=stage, rows=pt.S) as ph:
            # pod-scale route: above the FLEET_SHARDED threshold the
            # stage's resident state lives mesh-sharded and the solve runs
            # through solver/sharded.solve_sharded (an explicit scheduler
            # mesh= means the caller chose chain sharding — leave it alone)
            with phase("sched.stage"):
                sh_mesh = sharded_route(pt) if self.mesh is None else None
                slot, resident_warm = self._stage(pt, delta, warm_start,
                                                  stage, mesh=sh_mesh)

            # cold fallback on a warm request still warm-starts from THIS
            # stage's last HOST assignment when shapes line up (the
            # pre-resident behavior; slots are per stage so the seed can
            # never come from a different stage's placement)
            with phase("sched.solve") as ph_solve:
                init = None
                if (warm_start and not resident_warm
                        and slot.last_assignment is not None
                        and slot.last_assignment.shape[0] == pt.S):
                    init = slot.last_assignment
                res = self._solve_one(pt, slot, resident_warm, sh_mesh, init,
                                      overlap_host_work=overlap_host_work)
            # solve_ms: staging + dispatch + the fetch of the result
            ms = (ph_solve.t1 - ph.t0) * 1e3
            with phase("sched.finalize", rows=pt.S) as ph_fin:
                return self._finalize(pt, res, slot, ms, stage, ph_fin)

    def reschedule(self, pt: ProblemTensors, *, delta=None,
                   overlap_host_work=None,
                   stage: Optional[str] = None) -> Placement:
        """Streaming re-solve after churn: warm-start from the previous
        assignment so only churn-forced moves happen (BASELINE config 5).
        With a resident staging the warm seed never leaves the device."""
        return self.place(pt, warm_start=True, delta=delta,
                          overlap_host_work=overlap_host_work, stage=stage)
