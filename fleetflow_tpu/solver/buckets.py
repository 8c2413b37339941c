"""Shape bucketing: the warm-path contract between fleet churn and XLA.

Every distinct (S, N, G, Gc, K, C) shape of a DeviceProblem is a distinct
XLA program: `_refine` (solver/api.py) is jitted with those extents baked
in as static/traced shapes, so a fleet drifting from 9,997 to 10,050
services — the normal churn/reschedule path — recompiles the whole fused
pipeline and pays a compile cliff of seconds for a solve of
milliseconds. This module rounds the churn-sensitive extents UP to a
geometric tier ladder so every fleet size inside a tier reuses ONE
compiled executable:

  S   (service rows)        -> next tier (x1.25 steps from ``minimum``)
  K   (conflict-id columns) -> next multiple of ``width_multiple``
  C   (coloc-id columns)    -> next multiple of ``width_multiple``
  G   (conflict-id count)   -> next tier (static: sizes the (N, G) tables)
  Gc  (coloc-id count)      -> next tier

N (node pool) is deliberately NOT bucketed: node inventories change by
operator action, not churn, and padding nodes would need phantom-capacity
semantics in every kernel. T is tied to N (node_topology defaults to
arange(N)) and follows it.

Padded service rows are PHANTOMS — the same construction the sharded
mega-solve uses (`pad_problem`, generalized here from solver/sharded.py):
zero demand, no conflict/coloc ids, no preference (the packed layout
keeps the plane absent; a present plane pads with zeros), eligible
everywhere (all-ones packed words).
A phantom parked on any *valid* node is provably inert:

  capacity     zero demand adds nothing to any load cell
  conflicts    no ids -> no (node, group) occupancy -> no pairs
  eligibility  eligible everywhere; seeds place phantoms on valid nodes
               and the anneal's W_ELIG (1e6) makes a move onto an invalid
               node unacceptable at any production temperature
  soft         zero demand/preference/coloc; only the padded-S mean
               denominators shift, so callers report the soft score of the
               REAL rows via `soft_score_host` on the original tensors

The one constraint phantoms are not inert for by construction is the
spread constraint (a parked phantom would count into per-domain totals),
so padded problems carry a traced ``n_real`` row count — the same mask the
sharded path threads statically — and the kernels exclude rows >= n_real
from topology/skew accounting. Bucketing therefore applies at
``max_skew > 0`` too (it was bypassed there before the mask existed).

Config: `bucket_config()` reads the FLEET_BUCKET* environment once per
call site; `FLEET_BUCKET=0` disables bucketing everywhere and
`FLEET_BUCKET_MIN` (default 64) is the first tier of the ladder.
docs/guide/11-performance.md covers tuning.
"""

from __future__ import annotations

import dataclasses
import math
import os
import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Optional

import numpy as np

__all__ = ["BucketConfig", "BucketInfo", "bucket_config", "bucket_size",
           "width_bucket", "subsolve_tier", "pad_problem",
           "pad_problem_tiers", "pad_assignment", "record_bucket",
           "soft_score_host", "stage_problem_tiers", "staging_arena_stats"]


# geometric tier ratio of the S ladder
BUCKET_GROWTH = 1.25
# host staging arenas kept across restages; LRU beyond this many bytes
STAGE_ARENA_BYTES = 512_000_000


@dataclass(frozen=True)
class BucketConfig:
    enabled: bool = True
    minimum: int = 64        # first S tier; G/Gc ladder starts at 16
    width_multiple: int = 4  # K / C column rounding
    align: int = 8           # every S tier is a multiple of this (lanes)


def _env_flag(name: str, default: bool) -> bool:
    v = os.environ.get(name, "").strip().lower()
    if not v:
        return default
    return v not in ("0", "false", "off", "no")


def bucket_config(default_enabled: bool = True) -> BucketConfig:
    """The process-wide bucketing knobs, read from the environment on each
    call (cheap; callers on hot paths hold the result)."""
    try:
        minimum = int(os.environ.get("FLEET_BUCKET_MIN", "64"))
    except ValueError:
        minimum = 64
    return BucketConfig(
        enabled=_env_flag("FLEET_BUCKET", default_enabled),
        minimum=max(minimum, 8),
    )


def bucket_size(n: int, *, growth: float = BUCKET_GROWTH, minimum: int = 64,
                align: int = 8) -> int:
    """Smallest tier >= n on the geometric ladder minimum, minimum*growth,
    minimum*growth^2, ... with every tier rounded up to a multiple of
    ``align``. bucket_size is idempotent: bucket_size(bucket_size(n)) ==
    bucket_size(n), which is what lets a pre-padded staging pass through
    `pad_problem_tiers` unchanged."""
    if n <= 0:
        return align
    tier = float(minimum)
    out = -((-minimum) // align) * align
    while out < n:
        tier *= growth
        out = -((-math.ceil(tier)) // align) * align  # ceil to align
    return out


def bucket_bounds(n: int, *, growth: float = BUCKET_GROWTH, minimum: int = 64,
                  align: int = 8) -> tuple[int, int]:
    """(previous tier, tier) around n: the tier n pads up to, and the
    largest smaller tier (0 below the ladder). `fleet lint` FF014 uses the
    pair to say how far past a boundary a stage's row count sits."""
    upper = bucket_size(n, growth=growth, minimum=minimum, align=align)
    lower = 0
    tier = float(minimum)
    out = -((-minimum) // align) * align
    while out < upper:
        lower = out
        tier *= growth
        out = -((-math.ceil(tier)) // align) * align
    return lower, upper


def subsolve_tier(k: int, *, minimum: int = 256, maximum: int = 4096) -> int:
    """Mini tier for the active-set sub-problem's row count
    (solver/subsolve.py): the power-of-two ladder minimum, 2*minimum,
    4*minimum, ... capped at `maximum`. Bucketed for the same reason the
    full problem is — each distinct sub shape is its own XLA program, and
    churn closure sizes drift burst to burst — but on a coarser ladder:
    a handful of mini executables covers every localized solve. Returns
    the tier, or 0 when k exceeds `maximum` (the closure is too big to
    localize; the caller falls back to the full fused path)."""
    if k <= 0:
        return minimum
    tier = minimum
    while tier < k:
        tier *= 2
    return tier if tier <= maximum else 0


def width_bucket(k: int, multiple: int = 4) -> int:
    """Id-table column widths round to a small multiple: width drift (a
    service gaining a second port) must not recompile."""
    k = max(k, 1)
    return -((-k) // multiple) * multiple


@dataclass
class BucketInfo:
    """What padding was applied, for artifacts/metrics/SolveResult."""
    orig_S: int
    padded_S: int
    G: int
    Gc: int
    hit: bool = False           # this padded shape was already compiled-for

    @property
    def pad_waste(self) -> float:
        """Fraction of service rows that are phantoms."""
        return 1.0 - self.orig_S / self.padded_S if self.padded_S else 0.0

    def to_dict(self) -> dict:
        return {"orig_S": self.orig_S, "padded_S": self.padded_S,
                "pad_waste": round(self.pad_waste, 4), "hit": self.hit}


def _pad_rows(a, pad: int, fill):
    import jax.numpy as jnp
    widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, widths, constant_values=fill)


def _pad_cols(a, pad: int, fill):
    import jax.numpy as jnp
    return jnp.pad(a, [(0, 0), (0, pad)], constant_values=fill)


def _elig_fill(eligible):
    """Phantom-row fill for the eligibility plane: all-ones words when
    bit-packed (solver/problem.py packed layout), True when dense bool.
    Pad bits of a packed row are never read (gathers index columns < N)."""
    import jax.numpy as jnp
    return (np.uint32(0xFFFFFFFF) if eligible.dtype == jnp.uint32
            else True)


def pad_problem(prob, multiple: int):
    """Pad the service axis up to a multiple of ``multiple`` with phantom
    services (zero demand, no conflict/coloc ids, eligible everywhere, no
    preference): they sit wherever the annealer leaves them without
    touching any constraint or score. Returns (padded problem, original S)
    — slice the returned assignment back to [:orig_S].

    This is the sharded mega-solve's ragged-S entry point (S must divide
    over the mesh); `pad_problem_tiers` below is the bucketing entry point
    (S rounds to a reuse tier). Both build the same phantoms."""
    S = prob.S
    pad = (-S) % multiple
    if pad == 0:
        return prob, S
    kw = {}
    if prob.preferred is not None:   # absent plane stays absent
        kw["preferred"] = _pad_rows(prob.preferred, pad, 0.0)
    return dataclasses.replace(
        prob,
        demand=_pad_rows(prob.demand, pad, 0.0),
        conflict_ids=_pad_rows(prob.conflict_ids, pad, -1),
        coloc_ids=_pad_rows(prob.coloc_ids, pad, -1),
        eligible=_pad_rows(prob.eligible, pad, _elig_fill(prob.eligible)),
        S=S + pad, **kw,
    ), S


def pad_problem_tiers(prob, cfg: Optional[BucketConfig] = None):
    """Round a DeviceProblem up to its bucket: S to the tier ladder, the
    conflict/coloc id-table widths to ``width_multiple``, and the static
    G/Gc group counts to their own (smaller-based) tier ladder. Returns
    (padded problem, BucketInfo). Idempotent: a problem already sitting on
    its tiers comes back unchanged (same object), so staged re-use across
    re-solves never re-pads."""
    cfg = cfg or bucket_config()
    S_pad = bucket_size(prob.S, minimum=cfg.minimum, align=cfg.align)
    K = prob.conflict_ids.shape[1]
    C = prob.coloc_ids.shape[1]
    K_pad = width_bucket(K, cfg.width_multiple)
    C_pad = width_bucket(C, cfg.width_multiple)
    # G/Gc ride a COARSER, power-of-two ladder: group counts drift with
    # fleet content (ports/volumes/colocations come and go service by
    # service), and any finer ladder crosses a G boundary — and recompiles
    # — while S sits comfortably in its tier. The cost of the headroom is
    # scatter-table memory ((N, G) int32), pennies next to a compile.
    G_pad = bucket_size(prob.G, growth=2.0, minimum=16, align=4)
    Gc_pad = bucket_size(prob.Gc, growth=2.0, minimum=4,
                         align=2) if prob.Gc > 0 else 0
    info = BucketInfo(orig_S=prob.S, padded_S=S_pad, G=G_pad, Gc=Gc_pad)
    if (S_pad == prob.S and K_pad == K and C_pad == C
            and G_pad == prob.G and Gc_pad == prob.Gc):
        return prob, info
    pad = S_pad - prob.S
    conflict_ids = prob.conflict_ids
    coloc_ids = prob.coloc_ids
    if K_pad > K:
        conflict_ids = _pad_cols(conflict_ids, K_pad - K, -1)
    if C_pad > C:
        coloc_ids = _pad_cols(coloc_ids, C_pad - C, -1)
    import jax.numpy as jnp
    # n_real marks rows >= it as phantoms — a TRACED scalar, so fleets
    # drifting within the tier reuse the compiled executable while the
    # kernels keep phantoms out of topology/skew accounting (what lets
    # bucketing apply at max_skew > 0). A pre-set n_real (re-padding an
    # already-resident problem) is preserved.
    n_real = (prob.n_real if prob.n_real is not None
              else jnp.asarray(prob.S, jnp.int32))
    kw = {}
    if prob.preferred is not None:   # absent plane stays absent
        kw["preferred"] = _pad_rows(prob.preferred, pad, 0.0)
    return dataclasses.replace(
        prob,
        demand=_pad_rows(prob.demand, pad, 0.0),
        conflict_ids=_pad_rows(conflict_ids, pad, -1),
        coloc_ids=_pad_rows(coloc_ids, pad, -1),
        eligible=_pad_rows(prob.eligible, pad, _elig_fill(prob.eligible)),
        S=S_pad, G=G_pad, Gc=Gc_pad, n_real=n_real, **kw,
    ), info


def pad_assignment(assignment: np.ndarray, padded_S: int,
                   node_valid: np.ndarray) -> np.ndarray:
    """Extend a real-row assignment with phantom placements on the first
    VALID node (phantoms on an invalid node would count as eligibility
    violations in the device stats — the one way a phantom can stop being
    inert)."""
    assignment = np.asarray(assignment, dtype=np.int32)
    pad = padded_S - assignment.shape[0]
    if pad <= 0:
        return assignment
    valid = np.flatnonzero(node_valid)
    fill = int(valid[0]) if valid.size else 0
    return np.concatenate(
        [assignment, np.full(pad, fill, dtype=np.int32)])


# -- compile-free padded staging -------------------------------------------
# pad_problem_tiers pads ON DEVICE: every plane pays a jnp.pad dispatch and
# — in a fresh process — a shape-specific XLA compile, several times what
# copying the actual bytes costs. stage_problem_tiers instead builds the PADDED planes on
# the host, in per-tier arena buffers reused across restages (the phantom
# region is written once per arena, not once per restage), and uploads
# them: staging becomes pure memcpy + device_put, no XLA ops at all.
# Constant (S, N) planes — eligible all-True, preferred absent — can
# additionally be served from a small immutable device-side cache, so a
# restage of the same tier re-uploads nothing for them.

_STAGE_LOCK = threading.Lock()          # arenas hand out shared buffers
_ARENAS: OrderedDict[tuple, list] = OrderedDict()   # key -> [array, rows]
_DEV_CONSTS: OrderedDict[tuple, object] = OrderedDict()
_DEV_CONST_CAP = 6                      # (S, N) planes; LRU beyond this


def _arena_take_locked(name: str, shape: tuple, dtype, fill,
                       rows_written: int) -> np.ndarray:
    """A host buffer of `shape` whose rows >= rows_written hold `fill`;
    the caller overwrites rows [0:rows_written] (and owns the buffer until
    it releases _STAGE_LOCK). Reuse resets only the rows the previous
    staging dirtied beyond the new watermark."""
    key = (name, shape, np.dtype(dtype).str, repr(fill))
    ent = _ARENAS.get(key)
    if ent is None:
        arr = np.full(shape, fill, dtype=dtype)
        ent = _ARENAS[key] = [arr, 0]
        while len(_ARENAS) > 1 and \
                sum(e[0].nbytes for e in _ARENAS.values()) \
                > STAGE_ARENA_BYTES:
            _ARENAS.popitem(last=False)
    else:
        _ARENAS.move_to_end(key)
        arr, dirty = ent
        if dirty > rows_written:
            arr[rows_written:dirty] = fill
    ent[1] = rows_written
    return ent[0]


def _device_const_locked(kind: str, shape: tuple, dtype, value,
                         device) -> object:
    """An immutable on-device constant plane, cached per shape/device.
    Rebuilt if a consumer deleted it (donation); callers that DONATE
    problem planes must not use this cache at all (a shared array donated
    by one staging would invalidate every other holder)."""
    import jax

    key = (kind, shape, None if device is None else repr(device))
    arr = _DEV_CONSTS.get(key)
    if arr is not None and not arr.is_deleted():
        _DEV_CONSTS.move_to_end(key)
        return arr
    host = _arena_take_locked(f"const:{kind}", shape, dtype, value, 0)
    arr = jax.device_put(host, device=device)
    _DEV_CONSTS[key] = arr
    while len(_DEV_CONSTS) > _DEV_CONST_CAP:
        _DEV_CONSTS.popitem(last=False)
    return arr


def staging_arena_stats() -> dict:
    with _STAGE_LOCK:
        return {
            "arenas": len(_ARENAS),
            "arena_bytes": int(sum(e[0].nbytes for e in _ARENAS.values())),
            "device_consts": len(_DEV_CONSTS),
        }


def stage_problem_tiers(pt, cfg: Optional[BucketConfig] = None,
                        device=None, reuse_device_constants: bool = True):
    """Stage a ProblemTensors DIRECTLY at its padded bucket shape.

    Equivalent to ``pad_problem_tiers(prepare_problem(pt), cfg)`` —
    bit-identical tensors, same statics — but compile-free: padded host
    planes are assembled in reusable per-tier arenas and uploaded with
    plain device_put (no jnp.pad / on-device fill ops, so a cold process
    pays zero staging compiles). The eligibility plane stages BIT-PACKED
    (solver/problem.py, 8x fewer arena/upload/sweep bytes; FLEET_PACKED=0
    restores dense bool), an absent preference stays absent (no zero
    plane at all), and the all-True eligible constant reuses an immutable
    device-side cache.

    Returns (DeviceProblem, BucketInfo). ``reuse_device_constants=False``
    opts out of the shared device cache — REQUIRED for stagings whose
    planes are later DONATED (the resident merge kernels), where a shared
    array would be invalidated under every other holder.
    """
    import jax
    import jax.numpy as jnp

    from .problem import (STRATEGY_CODES, DeviceProblem, _unify_conflict_ids,
                          pack_bool_rows, packed_enabled, packed_width,
                          record_plane_bytes)

    cfg = cfg or bucket_config()
    packed = packed_enabled()
    conflict = _unify_conflict_ids(pt)
    S, N = pt.S, pt.N
    K = conflict.shape[1]
    C = pt.coloc_ids.shape[1]
    G = max(int(conflict.max(initial=-1)) + 1, 1)
    Gc = int(pt.coloc_ids.max(initial=-1)) + 1
    T = int(pt.node_topology.max(initial=0)) + 1
    if cfg.enabled:
        S_pad = bucket_size(S, minimum=cfg.minimum, align=cfg.align)
        K_pad = width_bucket(K, cfg.width_multiple)
        C_pad = width_bucket(C, cfg.width_multiple)
        G_pad = bucket_size(G, growth=2.0, minimum=16, align=4)
        Gc_pad = bucket_size(Gc, growth=2.0, minimum=4,
                             align=2) if Gc > 0 else 0
    else:
        S_pad, K_pad, C_pad, G_pad, Gc_pad = S, K, C, G, Gc
    info = BucketInfo(orig_S=S, padded_S=S_pad, G=G_pad, Gc=Gc_pad)

    def put(x):
        return jax.device_put(x, device=device)

    def put_arena(arr):
        # jax's CPU backend MAY zero-copy device_put for large aligned
        # arrays (device_put does not promise a copy; on jax 0.9.0 a
        # 16 MB page-aligned plane was copied, on 0.4.37 it was aliased):
        # handing the shared arena buffer straight to device_put could
        # alias it into the returned DeviceProblem, and the next restage
        # of this tier would rewrite a live staging's tensors in place. Upload a private copy — the
        # fresh buffer is then solely owned by (and may be aliased by)
        # the device array. One memcpy per plane; still no XLA ops. The
        # device-CONSTANT arenas below stay zero-copy: they are written
        # once at creation and never again.
        return jax.device_put(arr.copy(), device=device)

    R = np.asarray(pt.demand).shape[1]
    with _STAGE_LOCK:
        demand = _arena_take_locked("demand", (S_pad, R), np.float32, 0.0, S)
        demand[:S] = pt.demand
        conf = _arena_take_locked("conflict", (S_pad, K_pad), np.int32,
                                  -1, S)
        conf[:S, :K] = conflict
        if K_pad > K:
            conf[:S, K:] = -1
        coloc = _arena_take_locked("coloc", (S_pad, C_pad), np.int32, -1, S)
        coloc[:S, :C] = pt.coloc_ids
        if C_pad > C:
            coloc[:S, C:] = -1

        eligible_np = np.asarray(pt.eligible)
        all_eligible = bool(eligible_np.all())
        if packed:
            # bit-packed plane: 8x fewer bytes through the arena, the
            # upload, AND every anneal sweep (solver/problem.py). Phantom
            # rows (and the all-eligible constant) are all-ones words —
            # pad bits past N are never read.
            W = packed_width(N)
            ones = np.uint32(0xFFFFFFFF)
            if all_eligible and reuse_device_constants:
                eligible_arr = _device_const_locked(
                    "eligible_true_packed", (S_pad, W), np.uint32, ones,
                    device)
            else:
                elig = _arena_take_locked("eligible_packed", (S_pad, W),
                                          np.uint32, ones,
                                          0 if all_eligible else S)
                if not all_eligible:
                    elig[:S] = pack_bool_rows(eligible_np)
                eligible_arr = put_arena(elig)
        elif all_eligible and reuse_device_constants:
            eligible_arr = _device_const_locked("eligible_true",
                                                (S_pad, N), bool, True,
                                                device)
        else:
            elig = _arena_take_locked("eligible", (S_pad, N), bool, True,
                                      0 if all_eligible else S)
            if not all_eligible:
                elig[:S] = eligible_np
            eligible_arr = put_arena(elig)

        if pt.preferred is None:
            if packed:
                # absent by design: no zero plane is ever materialized —
                # the executables for this treedef carry no pref term
                preferred_arr = None
            elif reuse_device_constants:
                preferred_arr = _device_const_locked(
                    "preferred_zero", (S_pad, N), np.float32, 0.0, device)
            else:
                preferred_arr = put_arena(_arena_take_locked(
                    "preferred", (S_pad, N), np.float32, 0.0, 0))
        else:
            pref = _arena_take_locked("preferred", (S_pad, N), np.float32,
                                      0.0, S)
            pref[:S] = pt.preferred
            preferred_arr = put_arena(pref)

        prob = DeviceProblem(
            demand=put_arena(demand),
            capacity=put(np.asarray(pt.capacity, dtype=np.float32).copy()),
            conflict_ids=put_arena(conf),
            coloc_ids=put_arena(coloc),
            eligible=eligible_arr,
            node_valid=put(np.asarray(pt.node_valid, dtype=bool).copy()),
            node_topology=put(np.asarray(pt.node_topology,
                                         dtype=np.int32).copy()),
            preferred=preferred_arr,
            S=S_pad, N=N, G=G_pad, Gc=Gc_pad, T=T,
            strategy=STRATEGY_CODES[pt.strategy],
            max_skew=int(pt.max_skew),
            # same treedef as pad_problem_tiers(prepare_problem(pt)):
            # n_real traced whenever ANY extent padded, None on-tier
            n_real=(jnp.asarray(S, jnp.int32)
                    if (S_pad, K_pad, C_pad, G_pad, Gc_pad)
                    != (S, K, C, G, Gc) else None),
        )
    record_plane_bytes(prob)
    return prob, info


# -- bucket hit/miss telemetry ---------------------------------------------
# A "hit" means this process has already solved at a padded shape with the
# same jit-relevant extents, i.e. the fused pipeline will NOT recompile.
_seen_lock = threading.Lock()
_seen_buckets: set[tuple] = set()


def record_bucket(key: tuple) -> bool:
    """Record a padded-shape key; True when it was already seen (hit)."""
    with _seen_lock:
        hit = key in _seen_buckets
        _seen_buckets.add(key)
        return hit


# -- host-side exact soft score --------------------------------------------

def soft_score_host(pt, assignment: np.ndarray) -> float:
    """numpy mirror of kernels.soft_score against the ORIGINAL (unpadded)
    ProblemTensors: bucketed solves report the real rows' soft score, not
    the padded problem's (whose /S mean denominators include phantoms)."""
    from ..core.model import PlacementStrategy

    assignment = np.asarray(assignment)
    S, N = pt.S, pt.N
    load = np.zeros((N, pt.demand.shape[1]), dtype=np.float32)
    np.add.at(load, assignment, pt.demand.astype(np.float32))
    u = load / np.maximum(pt.capacity, 1e-6)
    usq = float((u * u).sum())
    denom = float(max(N, 1))
    if pt.strategy == PlacementStrategy.SPREAD_ACROSS_POOL:
        strat = usq / denom
    elif pt.strategy == PlacementStrategy.PACK_INTO_DEDICATED:
        strat = -usq / denom
    else:
        strat = float((assignment.astype(np.float32) / denom).mean())
    if pt.preferred is not None:
        pref = -float(pt.preferred[np.arange(S), assignment].mean())
    else:
        pref = 0.0
    coloc = 0.0
    Gc = int(pt.coloc_ids.max(initial=-1)) + 1
    if Gc > 0:
        valid = pt.coloc_ids >= 0
        counts = np.zeros((N, Gc), dtype=np.int64)
        rows = np.repeat(assignment, pt.coloc_ids.shape[1])[valid.ravel()]
        cols = pt.coloc_ids.ravel()[valid.ravel()]
        np.add.at(counts, (rows, cols), 1)
        c = counts.astype(np.float64)
        coloc = -float((c * (c - 1.0) / 2.0).sum()) / max(S, 1)
    return strat + pref + coloc
