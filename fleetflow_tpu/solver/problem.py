"""Device-resident problem representation.

Converts host ProblemTensors (numpy) into a pytree of jnp arrays shaped for
the solver kernels, staged onto the device once and reused across re-solves
(SURVEY.md section 7 hard part (d): keep host↔device transfers out of the
per-reschedule path).

Key transformation: the three anti-affinity families (host ports, exclusive
volumes, explicit anti-affinity groups) are unified into ONE conflict-id
space — a service carries up to K conflict ids (padded -1); two services
conflict iff they share any id and land on the same node. This keeps the
hot kernels free of per-family branching and avoids any S×S matrix: conflict
rows are computed on the fly from the (S, K) id table, so 10k×1k fits easily
in HBM (SURVEY.md hard part (b)).
"""

from __future__ import annotations

import contextlib
import os
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.model import PlacementStrategy
from ..lower.tensors import ProblemTensors
from ..obs.metrics import REGISTRY

__all__ = ["DeviceProblem", "STRATEGY_CODES", "prepare_problem",
           "PLANE_PACK", "packed_width", "packed_enabled", "pack_bool_rows",
           "eligible_lookup", "eligible_row", "eligible_rows",
           "record_plane_bytes"]

STRATEGY_CODES = {
    PlacementStrategy.SPREAD_ACROSS_POOL: 0,
    PlacementStrategy.PACK_INTO_DEDICATED: 1,
    PlacementStrategy.FILL_LOWEST: 2,
}

# -- packed problem planes ---------------------------------------------------
# The two dense (S, N) planes dominate problem memory AND the anneal's
# sweep bandwidth (~4.7 GiB at 100k x 10k; at 10k x 1k most of a sweep is
# plane reads). The packed layout attacks both:
#
#   eligible   bit-packed (S, ceil(N/32)) uint32 — one bit per node, 8x
#              fewer bytes than the dense bool plane. Two kinds of read:
#              a POINT (s, node) gathers its one word and shifts/masks it
#              (eligible_lookup: S or proposals_per_step elements); a whole
#              ROW gathers its W words once and unpacks all 32 bits of each
#              with a broadcast shift (eligible_row/eligible_rows) — never
#              one gather per (row, node) cell, which costs a TPU ~10 ns a
#              cell (118 ms of a 2,000 x 5,000 seed, PERF.md section 6, PR 40)
#   preferred  ABSENT from the pytree (None) when no service scores nodes,
#              instead of a materialized 4*S*N zero plane every sweep then
#              streams; `prob.preferred is None` is a static treedef fact,
#              so each layout compiles its own executable variant
#
# Every eligibility read goes through eligible_lookup/eligible_row(s) below,
# which dispatch on dtype — the dense bool layout stays supported (the
# FLEET_PACKED=0 A/B and the packed-vs-unpacked parity property tests), but
# production staging is packed and `fleet audit kernels` pins the dtype so
# a dense (S, N) plane cannot silently reappear in a hot-path executable.

PLANE_PACK = 32  # bits per packed eligibility word


def packed_width(n: int) -> int:
    """Words per packed eligibility row: ceil(n / 32)."""
    return -(-max(int(n), 1) // PLANE_PACK)


def packed_enabled(default: bool = True) -> bool:
    """FLEET_PACKED gate (default on): bit-packed eligible plane + absent
    preferred plane at staging time."""
    v = os.environ.get("FLEET_PACKED", "").strip().lower()
    if not v:
        return default
    return v not in ("0", "false", "off", "no")


def pack_bool_rows(mask: np.ndarray) -> np.ndarray:
    """Host pack: (..., N) bool -> (..., ceil(N/32)) uint32, little-endian
    bit order (bit j of word w is column 32*w + j). Trailing pad bits of
    the last word are SET — never read (gathers index columns < N), and
    the all-ones convention makes the representation canonical: an
    all-True row packs to the same words as the staging arenas' constant
    0xFFFFFFFF fill, so bit-identical-tensor checks across staging paths
    stay meaningful."""
    mask = np.ascontiguousarray(np.asarray(mask, dtype=bool))
    N = mask.shape[-1]
    W = packed_width(N)
    b = np.packbits(mask, axis=-1, bitorder="little")
    pad = W * 4 - b.shape[-1]
    if pad:
        b = np.concatenate(
            [b, np.full(b.shape[:-1] + (pad,), 0xFF, np.uint8)], axis=-1)
    out = np.ascontiguousarray(b).view(np.uint32)
    rem = N % PLANE_PACK
    if rem:
        out[..., -1] |= np.uint32((0xFFFFFFFF << rem) & 0xFFFFFFFF)
    return out


def eligible_lookup(eligible: jax.Array, s, node) -> jax.Array:
    """eligible[s, node] as bool at POINTS, for either plane layout: dense
    (S, N) bool, or bit-packed (S, ceil(N/32)) uint32 — each point gathers
    its one word and shifts/masks it. `s`/`node` broadcast like fancy
    indices. For S or proposals_per_step points; a whole row is read by
    eligible_row/eligible_rows, which gather words, not cells."""
    if eligible.dtype != jnp.uint32:
        return eligible[s, node]
    node = jnp.asarray(node)
    word = eligible[s, node >> 5]
    return ((word >> (node & 31).astype(jnp.uint32))
            & jnp.uint32(1)).astype(bool)


def _unpack_words(words: jax.Array, N: int) -> jax.Array:
    """(..., W) packed words -> (..., N) bool, pack_bool_rows's bit order
    (bit j of word w is column 32*w + j): every word's 32 bits by one
    broadcast shift, the pad bits of the last word cut off."""
    bits = (words[..., None] >> jnp.arange(PLANE_PACK, dtype=jnp.uint32)
            ) & jnp.uint32(1)
    return bits.reshape(*words.shape[:-1], -1)[..., :N].astype(bool)


def eligible_row(eligible: jax.Array, s, N: int) -> jax.Array:
    """One service's full (N,) eligibility row: the dense plane's row, or
    the packed plane's W words unpacked."""
    if eligible.dtype != jnp.uint32:
        return eligible[s]
    return _unpack_words(eligible[s], N)


def eligible_rows(eligible: jax.Array, svc: jax.Array, N: int) -> jax.Array:
    """(M, N) eligibility rows for a batch of services: a row gather either
    way — M dense rows, or M rows of W packed words, unpacked."""
    if eligible.dtype != jnp.uint32:
        return eligible[svc]
    return _unpack_words(eligible[svc], N)


# metric catalog: docs/guide/10-observability.md
_M_PLANE_BYTES = REGISTRY.gauge(
    "fleet_solver_plane_bytes",
    "Device bytes of the most recent staging's dense (S, N) problem "
    "planes, by plane and layout (packed=\"true\" = bit-packed eligibility "
    "/ absent preferred plane)",
    labels=("plane", "packed"))


def record_plane_bytes(prob: "DeviceProblem") -> None:
    """Report the staged plane footprint (solver/problem.py packed layout):
    what the memory math of docs/guide/11-performance.md claims, read off
    the actual staging."""
    e = prob.eligible
    _M_PLANE_BYTES.set(float(e.size) * e.dtype.itemsize, plane="eligible",
                       packed="true" if e.dtype == jnp.uint32 else "false")
    if prob.preferred is None:
        _M_PLANE_BYTES.set(0.0, plane="preferred", packed="true")
    else:
        p = prob.preferred
        _M_PLANE_BYTES.set(float(p.size) * p.dtype.itemsize,
                           plane="preferred", packed="false")


@jax.tree_util.register_dataclass
@dataclass
class DeviceProblem:
    """Pytree of device arrays + static metadata for the solver kernels."""
    demand: jax.Array          # (S, R) f32
    capacity: jax.Array        # (N, R) f32
    conflict_ids: jax.Array    # (S, K) i32, -1 pad (ports ∪ volumes ∪ anti)
    coloc_ids: jax.Array       # (S, C) i32, -1 pad
    # bit-packed (S, ceil(N/32)) uint32 (production layout; points read
    # through eligible_lookup, rows through eligible_row/eligible_rows) or
    # dense (S, N) bool (FLEET_PACKED=0)
    eligible: jax.Array
    node_valid: jax.Array      # (N,) bool
    node_topology: jax.Array   # (N,) i32 in [0, T)
    # static (not traced)
    S: int = field(metadata=dict(static=True))
    N: int = field(metadata=dict(static=True))
    G: int = field(metadata=dict(static=True))   # number of conflict ids
    Gc: int = field(metadata=dict(static=True))  # number of coloc ids (0 = none)
    T: int = field(metadata=dict(static=True))   # number of topology domains
    strategy: int = field(metadata=dict(static=True))
    max_skew: int = field(metadata=dict(static=True))
    # (S, N) f32 soft preference plane, or None when NO service scores
    # nodes — absent by design, not an all-zero plane every sweep then
    # streams (4*S*N bytes). Absence is a static treedef fact (`preferred
    # is None` == the has_preferred flag), so each layout is its own
    # compiled executable variant.
    preferred: Optional[jax.Array] = None
    # TRACED count of real (non-phantom) service rows, or None when every
    # row is real. Rows >= n_real are bucket-padding phantoms; the kernels
    # exclude them from topology/skew accounting (the sharded path threads
    # the same mask as a static `n_real` arg). Traced — not static — so a
    # fleet drifting 9,997 -> 10,050 inside one tier does NOT recompile.
    n_real: Optional[jax.Array] = None
    # warm-start migration stickiness, folded into the proposal delta and
    # the soft ranking ON THE FLY instead of materializing a bonused
    # (S, N) preferred plane (three full-plane passes, ~37 ms of the r05
    # warm dispatch at 10k x 1k). sticky_prev is the previous assignment
    # (S,) i32; sticky_w the per-service bonus (f32 scalar). The bonus
    # only anchors services whose previous node is still eligible+valid —
    # churn-forced moves stay free, same semantics as the old plane.
    sticky_prev: Optional[jax.Array] = None
    sticky_w: Optional[jax.Array] = None

    @property
    def has_preferred(self) -> bool:
        """Static: does a preference plane exist at all? (The absent-plane
        half of the packed layout — mirrors the merge kernel's
        has_demand/has_eligible static delta flags.)"""
        return self.preferred is not None

    @property
    def eligible_packed(self) -> bool:
        """Static: is the eligibility plane bit-packed uint32?"""
        return self.eligible.dtype == jnp.uint32


def conflict_offsets(pt: ProblemTensors) -> tuple[int, int]:
    """Where the volume ids and the anti-affinity ids start in the one id
    space `_unify_conflict_ids` builds: past every port id, then past
    every volume id."""
    ports = int(pt.port_ids.max(initial=-1)) + 1
    return ports, ports + int(pt.volume_ids.max(initial=-1)) + 1


def _unify_rows(port_ids, volume_ids, anti_ids,
                offsets: tuple[int, int]) -> np.ndarray:
    """The three families of some rows in one id space, each row's ids
    in descending order and -1 after them, at the families' summed
    width."""
    merged = np.concatenate(
        [port_ids,
         np.where(volume_ids >= 0, volume_ids + offsets[0], -1),
         np.where(anti_ids >= 0, anti_ids + offsets[1], -1)], axis=1)
    # dedupe within each row (a repeated id on one service is one constraint,
    # not a self-conflict): sort descending, blank repeats, sort again
    merged = -np.sort(-merged, axis=1)
    dup = np.zeros_like(merged, dtype=bool)
    dup[:, 1:] = (merged[:, 1:] == merged[:, :-1]) & (merged[:, 1:] >= 0)
    merged = np.where(dup, -1, merged)
    return -np.sort(-merged, axis=1)


def _unify_conflict_ids(pt: ProblemTensors) -> np.ndarray:
    """Concatenate the three id families into one id space, compacting out
    unused slots per row."""
    merged = _unify_rows(pt.port_ids, pt.volume_ids, pt.anti_ids,
                         conflict_offsets(pt))
    keep = int((merged >= 0).sum(axis=1).max(initial=1))
    return merged[:, : max(keep, 1)].astype(np.int32)


def unified_conflict_rows(pt: ProblemTensors, rows: np.ndarray,
                          width: Optional[int] = None,
                          offsets: Optional[tuple[int, int]] = None,
                          ) -> Optional[np.ndarray]:
    """Rows `rows` of `_unify_conflict_ids(pt)` at `width` columns, -1
    padded — what a staging of `pt` at that width holds there — or None
    where a row carries more ids than `width` (None: as many columns as
    the rows need, at least one). `offsets` defaults to
    `conflict_offsets(pt)`."""
    rows = np.asarray(rows, dtype=np.int64)
    merged = _unify_rows(pt.port_ids[rows], pt.volume_ids[rows],
                         pt.anti_ids[rows],
                         conflict_offsets(pt) if offsets is None
                         else offsets)
    if width is None:
        width = max(int((merged >= 0).sum(axis=1).max(initial=1)), 1)
    elif (merged[:, width:] >= 0).any():
        return None
    out = np.full((rows.shape[0], width), -1, dtype=np.int32)
    k = min(width, merged.shape[1])
    out[:, :k] = merged[:, :k]
    return out


def prepare_problem(pt: ProblemTensors,
                    device: Optional[Any] = None,
                    packed: Optional[bool] = None) -> DeviceProblem:
    """Stage a ProblemTensors onto the device (or default backend).

    `packed=None` defers to FLEET_PACKED (default on): the eligibility
    plane stages bit-packed uint32 and an absent preference stays absent
    (no zero plane); `packed=False` is the legacy dense layout, kept for
    the packed-vs-unpacked parity property tests and A/B debugging."""
    if packed is None:
        packed = packed_enabled()
    conflict = _unify_conflict_ids(pt)
    G = int(conflict.max(initial=-1)) + 1
    T = int(pt.node_topology.max(initial=0)) + 1

    put = partial(jax.device_put, device=device)
    # Degenerate (S, N) planes are common: no placement preferences -> no
    # `preferred` plane at all (packed) or an all-zero one (dense), no
    # eligibility restrictions -> an all-True `eligible`. On accelerators,
    # materialize constant planes as on-device XLA fills instead of
    # host->device uploads of bytes the device can write itself. On
    # CPU the "upload" is a memcpy while the fill pays a shape-specific
    # compile (~70 ms measured in the pipeline leg), so fills are
    # accelerator-only. Keyed on the platform the arrays actually land on —
    # an explicit `device` can differ from the default backend.
    use_fills = (device.platform if device is not None
                 else jax.default_backend()) != "cpu"
    fill_ctx = (jax.default_device(device) if device is not None
                else contextlib.nullcontext())
    with fill_ctx:
        if pt.preferred is None:
            preferred_arr = (None if packed else
                             (jnp.zeros((pt.S, pt.N), dtype=jnp.float32)
                              if use_fills else
                              put(np.zeros((pt.S, pt.N), dtype=np.float32))))
        else:
            preferred_arr = put(jnp.asarray(pt.preferred, dtype=jnp.float32))
        eligible_np = np.asarray(pt.eligible)
        all_eligible = bool(eligible_np.all())
        if packed:
            W = packed_width(pt.N)
            if use_fills and all_eligible:
                # all-ones fill: pad bits of the last word are set but
                # never read (gathers index columns < N only)
                eligible_arr = jnp.full((pt.S, W), np.uint32(0xFFFFFFFF),
                                        dtype=jnp.uint32)
            else:
                eligible_arr = put(pack_bool_rows(eligible_np))
        elif use_fills and all_eligible:
            eligible_arr = jnp.ones((pt.S, pt.N), dtype=bool)
        else:
            eligible_arr = put(jnp.asarray(pt.eligible))
    prob = DeviceProblem(
        demand=put(jnp.asarray(pt.demand, dtype=jnp.float32)),
        capacity=put(jnp.asarray(pt.capacity, dtype=jnp.float32)),
        conflict_ids=put(jnp.asarray(conflict)),
        coloc_ids=put(jnp.asarray(pt.coloc_ids, dtype=jnp.int32)),
        eligible=eligible_arr,
        node_valid=put(jnp.asarray(pt.node_valid)),
        node_topology=put(jnp.asarray(pt.node_topology, dtype=jnp.int32)),
        preferred=preferred_arr,
        S=pt.S, N=pt.N, G=max(G, 1),
        Gc=int(pt.coloc_ids.max(initial=-1)) + 1,
        T=T,
        strategy=STRATEGY_CODES[pt.strategy],
        max_skew=int(pt.max_skew),
    )
    record_plane_bytes(prob)
    return prob
