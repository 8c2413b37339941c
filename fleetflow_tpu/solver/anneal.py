"""Simulated-annealing refinement: vmapped independent chains.

The pmapped/mesh-sharded annealing pass of the north star ("a pmapped
simulated-annealing pass"). Each chain keeps an incremental view of the
placement state — node loads (N, R), conflict-group occupancy (N, G),
colocation occupancy (N, Gc), topology-domain counts (T,) — so one proposal
costs O(R + K + T), not a full re-score. Chains are vmapped; sharding the
chain axis over a jax.sharding.Mesh makes the whole sweep SPMD with a single
argmin all-reduce at the end (solver/api.py), which is how the solver scales
to a v5e-8 the way the reference scales agents over QUIC fan-out.

The annealing cost mirrors kernels.total_cost in *shape* (hard >> soft) but
uses overflow mass instead of overflow cell count so moves feel a gradient.
Chain ranking and adaptive-exit checks read the carried state (cheap, exact
by construction); the WINNER's final stats are re-derived from scratch with
kernels.violation_stats so float32 drift in the carried load can never flip
the feasibility gate.

There is one anneal loop, `anneal_adaptive_states`: sweep blocks inside a
lax.while_loop that exits on device once any chain has seen a feasible
state, returning each chain's best-ever state.
"""

from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .kernels import real_row_weights
from .problem import DeviceProblem, eligible_lookup, eligible_row

__all__ = ["anneal_adaptive_states",
           "chain_states_from_assignment",
           "prerepair_state", "prerepair_state_counted",
           "state_violation_stats", "state_soft_score",
           "ChainState", "TRACE_COLS", "solve_trace_blocks"]

W_CAP = 1e3     # per-unit overflow mass (normalized units)
W_CONF = 1e4    # per conflicting co-placement
W_ELIG = 1e6    # per ineligible placement
W_SKEW = 1e3    # per unit of excess skew

# -- in-dispatch telemetry (the solver flight deck, docs/guide/10) ----------
# One fixed-shape f32 row per sweep-BLOCK, recorded inside the jitted
# dispatch and returned alongside the result, so it rides the existing
# fetch: zero extra compiles (the buffer length is the static knob below,
# not a traced shape), zero host transfers on the warm path, and no new
# donation edges. Column order is the schema `SolveResult.telemetry` and
# `fleet solve trace` speak.
TRACE_COLS = ("sweep", "temperature", "best_violations", "best_soft",
              "live_violations", "accepted")


def solve_trace_blocks(default: int = 16) -> int:
    """The telemetry buffer length (sweep-block rows) — a STATIC jit knob
    read from FLEET_SOLVE_TRACE_BLOCKS (default 16; 0 disables the
    buffer entirely, restoring the pre-telemetry program byte for byte).
    Static by design: a traced length would make tier drift a recompile
    axis, which the compile-contract auditor pins against."""
    try:
        v = int(os.environ.get("FLEET_SOLVE_TRACE_BLOCKS", "") or default)
    except ValueError:
        v = default
    return max(0, min(v, 512))


class ChainState(NamedTuple):
    assignment: jax.Array   # (S,) i32
    load: jax.Array         # (N, R) f32
    used: jax.Array         # (N, G) i32   conflict-group occupancy
    coloc: jax.Array        # (N, Gc) i32  colocation occupancy (Gc>=1)
    topo: jax.Array         # (T,) i32     services per topology domain


def chain_states_from_assignment(prob: DeviceProblem,
                                 assignment: jax.Array,
                                 base: tuple | None = None) -> ChainState:
    """Build the incremental state for one chain from a dense assignment.

    `base` is an optional frozen remainder ``(load0, used0, coloc0,
    topo0)`` the scatters accumulate ONTO instead of zeros — the active-set
    sub-solve (solver/subsolve.py) seeds the mini problem's carried state
    with the frozen rows' contribution so capacity/conflict/skew gradients
    against the untouched fleet stay exact without streaming its planes."""
    R = prob.demand.shape[1]
    load0, used0, coloc0, topo0 = (
        base if base is not None else
        (jnp.zeros((prob.N, R), jnp.float32),
         jnp.zeros((prob.N, prob.G), jnp.int32),
         jnp.zeros((prob.N, max(prob.Gc, 1)), jnp.int32),
         jnp.zeros(prob.T, jnp.int32)))
    load = load0.at[assignment].add(prob.demand)

    valid = prob.conflict_ids >= 0
    safe = jnp.where(valid, prob.conflict_ids, 0)
    nodes = jnp.broadcast_to(assignment[:, None], safe.shape)
    used = used0.at[nodes, safe].add(valid.astype(jnp.int32))

    cvalid = prob.coloc_ids >= 0
    csafe = jnp.where(cvalid, prob.coloc_ids, 0)
    cnodes = jnp.broadcast_to(assignment[:, None], csafe.shape)
    coloc = coloc0.at[cnodes, csafe].add(cvalid.astype(jnp.int32))

    # phantom rows (bucket padding, rows >= n_real) carry no topology
    # weight: a parked phantom must not shift a spread constraint
    tw = real_row_weights(prob)
    topo = topo0.at[prob.node_topology[assignment]].add(tw)
    return ChainState(assignment, load, used, coloc, topo)


def prerepair_state(prob: DeviceProblem, st: ChainState,
                    max_moves: int) -> ChainState:
    """Fused churn pre-repair (see :func:`prerepair_state_counted`);
    returns only the repaired state — the compatibility face every
    pre-telemetry caller keeps."""
    st, _moves = prerepair_state_counted(prob, st, max_moves)
    return st


def prerepair_state_counted(prob: DeviceProblem, st: ChainState,
                            max_moves: int, *, conflicted: bool = False,
                            overfull: bool = False
                            ) -> tuple[ChainState, jax.Array]:
    """Fused churn pre-repair: relocate services stranded on invalid or
    ineligible nodes, one per `lax.while_loop` iteration, entirely on
    device. This replaces the host `repair.py` pre-pass on the warm path
    (a pass of host numpy + a host->device seed upload): the resident warm
    path never leaves the device between the CP's churn delta and the
    anneal.

    Each iteration picks the first not-yet-attempted stranded service and
    moves it to the least-utilized node that fits (capacity + conflicts +
    eligibility), falling back to the least-utilized eligible node when
    nothing fits cleanly (the anneal's targeted proposals and the host
    repair backstop keep the zero-violation contract). The loop exits as
    soon as nothing is stranded, so a quiet warm solve pays one mask
    reduction; `max_moves` bounds pathological churn. Feasibility of the
    incoming state is preserved: a clean relocation only ever lands on a
    node it verified against the live carried state.

    With `conflicted` (static), a service that shares a conflict id with
    another on its node counts as stranded too, the lowest row of such a
    pair moving first: the localized sub-solve's use (solver/subsolve.py),
    whose fresh arrivals start parked together on one node and whose
    incumbents sit in the frozen base, so a clean relocation is exactly
    the arrival finding a server its key leaves free. With `overfull`
    (static) as well, so does a service that asks for something on a node
    over its capacity, until the node is within it: a priced sub-solve's
    (streaming admission preempting, cp/placement.py `admit_batch`), whose
    arrivals parked together each need a server of their own, and whose
    fallback to the full path would move incumbents that victims were
    evicted for.

    Returns ``(state, moves)`` — `moves` counts the relocations actually
    APPLIED (attempts on genuinely unplaceable services don't count):
    the prologue half of the solver flight-deck telemetry."""
    ar = jnp.arange(prob.S)

    def stranded_of(st):
        out = (~eligible_lookup(prob.eligible, ar, st.assignment)
               | ~prob.node_valid[st.assignment])
        if conflicted:
            ids = prob.conflict_ids
            held = ids >= 0
            here = st.used[st.assignment[:, None], jnp.where(held, ids, 0)]
            out = out | ((here > 1) & held).any(-1)
        if overfull:
            over = (st.load > prob.capacity * (1 + 1e-6)).any(-1)
            out = out | (over[st.assignment] & (prob.demand > 0).any(-1))
        return out

    def cond(carry):
        st, attempted, i, _moves = carry
        return (i < max_moves) & (stranded_of(st) & ~attempted).any()

    def body(carry):
        st, attempted, i, moves = carry
        todo = stranded_of(st) & ~attempted
        s = jnp.argmax(todo)
        attempted = attempted.at[s].set(True)
        d = prob.demand[s]
        ids = prob.conflict_ids[s]
        valid = ids >= 0
        safe = jnp.where(valid, ids, 0)
        cids = prob.coloc_ids[s]
        cvalid = cids >= 0
        csafe = jnp.where(cvalid, cids, 0)

        fits = ((st.load + d[None, :])
                <= prob.capacity * (1 + 1e-6)).all(-1)          # (N,)
        conf_free = ((st.used[:, safe] * valid).sum(-1) == 0)    # (N,)
        elig = eligible_row(prob.eligible, s, prob.N) & prob.node_valid  # (N,)
        ok = fits & conf_free & elig
        util = (st.load / jnp.maximum(prob.capacity, 1e-6)).max(-1)
        # clean candidates rank first; any eligible node beats staying
        # stranded (W_ELIG dwarfs a capacity/conflict residual); inf when
        # no eligible valid node exists at all (genuinely unplaceable)
        score = jnp.where(ok, util, jnp.where(elig, util + 1e6, jnp.inf))
        b = jnp.argmin(score)
        can = jnp.isfinite(score[b])
        a = st.assignment[s]
        w = can.astype(jnp.float32)
        wi = can.astype(jnp.int32)

        load = st.load.at[a].add(-d * w).at[b].add(d * w)
        vi = valid.astype(jnp.int32) * wi
        used = st.used.at[a, safe].add(-vi).at[b, safe].add(vi)
        ci = cvalid.astype(jnp.int32) * wi
        coloc = st.coloc.at[a, csafe].add(-ci).at[b, csafe].add(ci)
        r = (wi if prob.n_real is None
             else wi * (s < prob.n_real).astype(jnp.int32))
        topo = (st.topo.at[prob.node_topology[a]].add(-r)
                .at[prob.node_topology[b]].add(r))
        assignment = st.assignment.at[s].set(
            jnp.where(can, b, a).astype(jnp.int32))
        return (ChainState(assignment, load, used, coloc, topo),
                attempted, i + 1, moves + wi)

    st, _, _, moves = jax.lax.while_loop(
        cond, body,
        (st, jnp.zeros(prob.S, dtype=bool), jnp.int32(0), jnp.int32(0)))
    return st, moves


def state_violation_stats(prob: DeviceProblem, st: ChainState) -> dict:
    """Exact hard-violation stats computed from the CARRIED chain state —
    identical results to kernels.violation_stats (the state's load/used/topo
    are maintained move-by-move with the same scatter semantics used to
    build them), but without rebuilding the (N, G) occupancy: an (N, G)
    elementwise reduce instead of a scatter, ~20x cheaper on TPU. This is
    what makes cheap adaptive-exit checks possible."""
    cap_cells = (st.load > prob.capacity * (1 + 1e-6)).sum().astype(jnp.float32)
    c = st.used.astype(jnp.float32)
    conflict_pairs = (c * (c - 1.0) / 2.0).sum()
    inelig = (~eligible_lookup(prob.eligible, jnp.arange(prob.S),
                               st.assignment)).sum()
    invalid = (~prob.node_valid[st.assignment]).sum()
    elig = (inelig + invalid).astype(jnp.float32)
    if prob.max_skew > 0:
        skew = jnp.maximum(
            (st.topo.max() - st.topo.min()) - prob.max_skew, 0
        ).astype(jnp.float32)
    else:
        skew = jnp.float32(0.0)
    return {
        "capacity": cap_cells,
        "conflicts": conflict_pairs,
        "eligibility": elig,
        "skew": skew,
        "total": cap_cells + conflict_pairs + elig + skew,
    }


def violation_total_from_parts(prob: DeviceProblem, load: jax.Array,
                               used: jax.Array, topo: jax.Array,
                               inelig_count: jax.Array) -> jax.Array:
    """Total hard violations from node-state components + a precomputed
    ineligibility count. Shared by the carried-state stats above and the
    sharded adaptive exit (which psums its shard-local inelig counts) so
    the feasibility definition cannot drift between them."""
    cap_cells = (load > prob.capacity * (1 + 1e-6)).sum().astype(jnp.float32)
    c = used.astype(jnp.float32)
    conflict_pairs = (c * (c - 1.0) / 2.0).sum()
    if prob.max_skew > 0:
        skew = jnp.maximum(
            (topo.max() - topo.min()) - prob.max_skew, 0).astype(jnp.float32)
    else:
        skew = jnp.float32(0.0)
    return (cap_cells + conflict_pairs + skew
            + inelig_count.astype(jnp.float32))


def state_soft_score(prob: DeviceProblem, st: ChainState) -> jax.Array:
    """kernels.soft_score evaluated from the carried state (same formulas,
    no group_counts rebuild). Pass the ORIGINAL problem to report without a
    warm-start bonus, or one carrying `sticky_prev` for ranking
    consistency: staying on the previous (still eligible+valid) node earns
    `sticky_w` per service, computed from (S,) gathers instead of a
    materialized bonus plane."""
    u = st.load / jnp.maximum(prob.capacity, 1e-6)
    usq = (u * u).sum()
    denom = jnp.float32(max(prob.N, 1))
    if prob.strategy == 0:
        strat = usq / denom
    elif prob.strategy == 1:
        strat = -usq / denom
    else:
        strat = (st.assignment.astype(jnp.float32) / denom).mean()
    if prob.preferred is None:
        pref = jnp.float32(0.0)   # absent plane: no zeros to stream
    else:
        pref = -prob.preferred[jnp.arange(prob.S), st.assignment].mean()
    if prob.sticky_prev is not None:
        prev = prob.sticky_prev
        anchored = (eligible_lookup(prob.eligible, jnp.arange(prob.S), prev)
                    & prob.node_valid[prev])
        at_prev = ((st.assignment == prev) & anchored)
        # the materialized plane added sticky_w * S at [s, prev[s]], whose
        # pref mean contributed -sticky_w per anchored stay — same scale
        pref = pref - prob.sticky_w * at_prev.sum().astype(jnp.float32)
    if prob.Gc > 0:
        cc = st.coloc.astype(jnp.float32)
        coloc = -(cc * (cc - 1.0) / 2.0).sum() / jnp.float32(max(prob.S, 1))
    else:
        coloc = jnp.float32(0.0)
    return strat + pref + coloc


def _overflow_mass(prob: DeviceProblem, load_rows: jax.Array,
                   cap_rows: jax.Array) -> jax.Array:
    """Normalized overflow mass for the given (k, R) rows."""
    return (jnp.maximum(load_rows - cap_rows, 0.0)
            / jnp.maximum(cap_rows, 1e-6)).sum()


def _skew_pen(prob: DeviceProblem, topo: jax.Array) -> jax.Array:
    if prob.max_skew <= 0:
        return jnp.float32(0.0)
    skew = (topo.max() - topo.min()).astype(jnp.float32)
    return jnp.maximum(skew - prob.max_skew, 0.0) * W_SKEW


def spread_window(prob: DeviceProblem, topo: jax.Array):
    """The band ``[lo, hi]`` of width `max_skew` a sweep keeps every
    domain's count inside (spread stages only: callers sit behind the
    static ``prob.max_skew > 0``).

    A state within the bound has slack ``max_skew - (max - min) >= 0``;
    the band is laid over its counts with the slack split between the two
    ends, so every count is inside it. A state over the bound gets the
    band whose floor is the mean's floor: the balanced state lies in it,
    every domain above it may only lose rows and every domain below it
    only gain them, so the excess never grows and each such move is
    downhill — where ``max - min`` is flat for every move that touches
    neither the fullest nor the emptiest domain."""
    k = jnp.int32(prob.max_skew)
    lo, hi = topo.min(), topo.max()
    slack = k - (hi - lo)
    floor = jnp.where(slack >= 0, lo - slack // 2, topo.sum() // prob.T)
    return floor, floor + k


def _band_excess(count: jax.Array, window) -> jax.Array:
    """How far one domain's count lies outside the band (i32, >= 0)."""
    lo, hi = window
    return jnp.maximum(count - hi, 0) + jnp.maximum(lo - count, 0)


def _admit_spread(topo: jax.Array, window, ta: jax.Array, tb: jax.Array,
                  crossing: jax.Array) -> jax.Array:
    """Which of a step's domain-crossing moves may land together (M,) bool.

    Each move was priced alone against the pre-step counts; a domain's
    count is shared by all its nodes, so the winner-per-target-node rule
    does not bound what a step does to it. Here a domain takes at most as
    many entrants as it has room under the band's ceiling and loses at
    most as many rows as it holds above the band's floor, lowest proposal
    index first: whatever subset lands, every count that was inside the
    band stays inside it, and one outside it only moves toward it. A
    chain within the bound therefore stays within it through the whole
    anneal, and what the moves do to `topo` together is what was priced."""
    lo, hi = window
    M = ta.shape[0]
    earlier = jnp.tril(jnp.ones((M, M), bool), k=-1) & crossing[None, :]
    rank_in = ((tb[:, None] == tb[None, :]) & earlier).sum(-1)
    rank_out = ((ta[:, None] == ta[None, :]) & earlier).sum(-1)
    room_in = jnp.maximum(hi - topo, 0)[tb]
    room_out = jnp.maximum(topo - lo, 0)[ta]
    return ~crossing | ((rank_in < room_in) & (rank_out < room_out))


def _soft_rows(prob: DeviceProblem, load_rows: jax.Array,
               cap_rows: jax.Array) -> jax.Array:
    """Strategy soft term restricted to the touched node rows."""
    u = load_rows / jnp.maximum(cap_rows, 1e-6)
    usq = (u * u).sum()
    if prob.strategy == 0:
        return usq / prob.N
    if prob.strategy == 1:
        return -usq / prob.N
    return jnp.float32(0.0)


def _move_delta_core(prob: DeviceProblem, *, capacity: jax.Array,
                     node_topology: jax.Array, load: jax.Array,
                     used: jax.Array, coloc: jax.Array, topo: jax.Array,
                     a: jax.Array, b: jax.Array, d: jax.Array,
                     ids: jax.Array, cids: jax.Array, elig_a: jax.Array,
                     elig_b: jax.Array, d_pref: jax.Array,
                     r: jax.Array, window=None) -> jax.Array:
    """Annealing-cost delta of moving one service from node `a` to node `b`,
    shared term for term between the single-device sweep (_proposal_delta)
    and the service-axis sharded sweep (solver/sharded.py) — "a legal sweep
    here is a legal sweep there" is enforced by construction, not by
    parallel maintenance of two copies.

    `prob` supplies only statics (strategy, max_skew, N, S). Tensor inputs
    are the caller's views: the single-device anneal passes the problem
    planes + carried ChainState, the sharded sweep passes its shard-local
    gathers against the replicated node state. `elig_a`/`elig_b` are the
    node_valid-masked eligibility bits of the two endpoints, `d_pref` the
    preference delta (including any warm-start stickiness), `r` the row's
    topology weight (0 for bucket-padding phantoms). `window` (spread
    stages on the single-device sweep) is the step's `spread_window`: the
    skew term is then the two touched domains' distance from the band,
    which `_admit_spread` makes exact for the moves applied together; the
    sharded sweep passes none and prices ``max - min`` as before."""
    valid = (ids >= 0)
    safe = jnp.where(valid, ids, 0)
    cvalid = (cids >= 0)
    csafe = jnp.where(cvalid, cids, 0)

    cap_a, cap_b = capacity[a], capacity[b]
    load_a, load_b = load[a], load[b]

    # -- hard deltas ---------------------------------------------------------
    # capacity overflow mass on the two touched rows
    over_before = (_overflow_mass(prob, load_a, cap_a)
                   + _overflow_mass(prob, load_b, cap_b))
    load_a2, load_b2 = load_a - d, load_b + d
    over_after = (_overflow_mass(prob, load_a2, cap_a)
                  + _overflow_mass(prob, load_b2, cap_b))
    d_cap = (over_after - over_before) * W_CAP

    # conflicts: occupancy excluding s itself on its current node
    conf_a = ((used[a, safe] - 1) * valid).sum()
    conf_b = (used[b, safe] * valid).sum()
    d_conf = (conf_b - conf_a).astype(jnp.float32) * W_CONF

    # eligibility / validity
    d_elig = (elig_a.astype(jnp.float32) - elig_b.astype(jnp.float32)) * W_ELIG

    # skew (phantom rows carry no topology weight)
    ta, tb = node_topology[a], node_topology[b]
    if window is not None:
        ca, cb = topo[ta], topo[tb]
        moved = jnp.where(ta == tb, 0, r)
        d_skew = (_band_excess(ca - moved, window)
                  + _band_excess(cb + moved, window)
                  - _band_excess(ca, window)
                  - _band_excess(cb, window)).astype(jnp.float32) * W_SKEW
    else:
        topo2 = topo.at[ta].add(-r).at[tb].add(r)
        d_skew = _skew_pen(prob, topo2) - _skew_pen(prob, topo)

    # -- soft deltas ---------------------------------------------------------
    soft_before = _soft_rows(prob, jnp.stack([load_a, load_b]),
                             jnp.stack([cap_a, cap_b]))
    soft_after = _soft_rows(prob, jnp.stack([load_a2, load_b2]),
                            jnp.stack([cap_a, cap_b]))
    col_a = ((coloc[a, csafe] - 1) * cvalid).sum()
    col_b = (coloc[b, csafe] * cvalid).sum()
    d_coloc = (col_a - col_b).astype(jnp.float32) / max(prob.S, 1)

    return (d_cap + d_conf + d_elig + d_skew
            + (soft_after - soft_before) + d_pref + d_coloc)


def _proposal_delta(prob: DeviceProblem, state: ChainState,
                    s: jax.Array, b: jax.Array, window=None) -> jax.Array:
    """Annealing-cost delta of moving service s to node b (no apply)."""
    a = state.assignment[s]
    elig_a = eligible_lookup(prob.eligible, s, a) & prob.node_valid[a]
    elig_b = eligible_lookup(prob.eligible, s, b) & prob.node_valid[b]
    r = (jnp.int32(1) if prob.n_real is None
         else (s < prob.n_real).astype(jnp.int32))
    if prob.preferred is None:
        d_pref = jnp.float32(0.0)
    else:
        d_pref = (prob.preferred[s, a] - prob.preferred[s, b]) / prob.S
    if prob.sticky_prev is not None:
        # on-the-fly migration stickiness: the materialized plane's
        # bonus[s, prev[s]] = sticky_w * S contributed exactly
        # sticky_w * (at_prev(a) - at_prev(b)) through d_pref's /S
        prev = prob.sticky_prev[s]
        anchored = (eligible_lookup(prob.eligible, s, prev)
                    & prob.node_valid[prev])
        d_pref = d_pref + prob.sticky_w * (
            ((a == prev) & anchored).astype(jnp.float32)
            - ((b == prev) & anchored).astype(jnp.float32))
    return _move_delta_core(
        prob, capacity=prob.capacity, node_topology=prob.node_topology,
        load=state.load, used=state.used, coloc=state.coloc, topo=state.topo,
        a=a, b=b, d=prob.demand[s], ids=prob.conflict_ids[s],
        cids=prob.coloc_ids[s], elig_a=elig_a, elig_b=elig_b,
        d_pref=d_pref, r=r, window=window)


def _batched_step(prob: DeviceProblem, state: ChainState,
                  key: jax.Array, temp: jax.Array,
                  M: int) -> tuple[ChainState, jax.Array]:
    """One parallel-Metropolis step: M simultaneous proposals. Returns the
    stepped state plus the number of APPLIED moves (post winner-resolution)
    — the acceptance signal the adaptive path accumulates for telemetry.

    Deltas are evaluated against the shared pre-step state, so accepted
    moves that touch the same node interact slightly — the standard
    accelerator-SA approximation; the exact kernels re-rank chains and the
    repair backstop guards the zero-violation contract. Duplicate proposals
    for one service are resolved winner-takes-first so the scatter state
    update stays exact for the chosen move set.
    """
    ks, kb, ka, kt = jax.random.split(key, 4)
    # Half the proposals are TARGETED at services that currently sit on a
    # violating node (overloaded, conflicted) or an invalid/ineligible one.
    # Uniform proposals alone need O(S/M) sweeps just to *mention* each of a
    # handful of offenders (measured: 9 leftover seed violations cost ~96
    # sweeps at 10k x 1k); targeting finds them in a few sweeps, and churn
    # reschedules hit the dead node's services immediately. When nothing is
    # flagged the logits are flat and the "targeted" half is plain uniform.
    over_node = (state.load > prob.capacity * (1 + 1e-6)).any(-1)    # (N,)
    u = state.used
    conf_node = ((u * (u - 1)).sum(-1) > 0)                          # (N,)
    hot_node = over_node | conf_node
    svc_bad = (~eligible_lookup(prob.eligible, jnp.arange(prob.S),
                                state.assignment)
               | ~prob.node_valid[state.assignment])
    hot = hot_node[state.assignment] | svc_bad                       # (S,)
    logits = jnp.where(hot, 0.0, -30.0)
    s_tgt = jax.random.categorical(kt, logits, shape=(M,))
    s_uni = jax.random.randint(ks, (M,), 0, prob.S)
    half = M // 2
    s_idx = jnp.where(jnp.arange(M) < half, s_tgt, s_uni)
    b_idx = jax.random.randint(kb, (M,), 0, prob.N)
    window = None
    if prob.max_skew > 0:
        # a spread stage: the band this step holds the counts to. While a
        # domain sits above it, the targeted half lands on the valid nodes
        # of domains with room under its ceiling (one inverse-CDF draw
        # over the nodes), so the rows the band sheds are not left to find
        # a small domain by chance
        window = spread_window(prob, state.topo)
        over_band = state.topo > window[1]                           # (T,)
        room = ((state.topo < window[1])[prob.node_topology]
                & prob.node_valid).astype(jnp.float32)
        cdf = jnp.cumsum(room)
        pick = jnp.searchsorted(
            cdf, jax.random.uniform(jax.random.fold_in(kb, 1), (M,))
            * cdf[-1], side="right")
        aim = (jnp.arange(M) < half) & over_band.any() & (cdf[-1] > 0)
        b_idx = jnp.where(aim, jnp.minimum(pick, prob.N - 1), b_idx)
    a_idx = state.assignment[s_idx]

    delta = jax.vmap(
        lambda s, b: _proposal_delta(prob, state, s, b, window))(
        s_idx, b_idx)
    u = jax.random.uniform(ka, (M,))
    accept = ((delta < 0) | (u < jnp.exp(-delta / jnp.maximum(temp, 1e-8)))) \
        & (a_idx != b_idx)

    # winner-per-service: the lowest proposal index with accept wins
    order = jnp.arange(M, dtype=jnp.int32)
    winner = jnp.full((prob.S,), M, dtype=jnp.int32).at[s_idx].min(
        jnp.where(accept, order, M))
    applied = accept & (winner[s_idx] == order)
    # winner-per-TARGET-node: at most one move lands on any node per sweep.
    # This makes the sweep feasibility-preserving despite stale deltas: the
    # single entrant was evaluated against the pre-sweep node state, and
    # every other change to that node is a departure (which only frees
    # capacity and conflict groups). A feasible chain therefore stays
    # feasible through the whole anneal.
    tgt_winner = jnp.full((prob.N,), M, dtype=jnp.int32).at[b_idx].min(
        jnp.where(applied, order, M))
    applied = applied & (tgt_winner[b_idx] == order)
    if prob.max_skew > 0:
        ta, tb = prob.node_topology[a_idx], prob.node_topology[b_idx]
        crossing = applied & (ta != tb)
        if prob.n_real is not None:
            crossing = crossing & (s_idx < prob.n_real)
        applied = applied & _admit_spread(state.topo, window, ta, tb,
                                          crossing)
    w = applied.astype(jnp.float32)
    wi = applied.astype(jnp.int32)

    d = prob.demand[s_idx]                                       # (M, R)
    load = (state.load.at[a_idx].add(-d * w[:, None])
            .at[b_idx].add(d * w[:, None]))

    ids = prob.conflict_ids[s_idx]                               # (M, K)
    valid = (ids >= 0).astype(jnp.int32) * wi[:, None]
    safe = jnp.where(ids >= 0, ids, 0)
    a_rows = jnp.broadcast_to(a_idx[:, None], safe.shape)
    b_rows = jnp.broadcast_to(b_idx[:, None], safe.shape)
    used = (state.used.at[a_rows, safe].add(-valid)
            .at[b_rows, safe].add(valid))

    cids = prob.coloc_ids[s_idx]
    cvalid = (cids >= 0).astype(jnp.int32) * wi[:, None]
    csafe = jnp.where(cids >= 0, cids, 0)
    coloc = (state.coloc.at[a_rows[:, : csafe.shape[1]], csafe].add(-cvalid)
             .at[b_rows[:, : csafe.shape[1]], csafe].add(cvalid))

    wt = (wi if prob.n_real is None
          else wi * (s_idx < prob.n_real).astype(jnp.int32))
    topo = (state.topo.at[prob.node_topology[a_idx]].add(-wt)
            .at[prob.node_topology[b_idx]].add(wt))

    # .set scatters route non-applied writes to a dump row (value writes
    # from losers must not race the winner's)
    dump = prob.S
    tgt = jnp.where(applied, s_idx, dump)
    assignment = jnp.zeros((prob.S + 1,), jnp.int32).at[:prob.S].set(
        state.assignment).at[tgt].set(b_idx.astype(jnp.int32))[:prob.S]

    return ChainState(assignment, load, used, coloc, topo), wi.sum()


def default_proposals_per_step(S: int) -> int:
    """Batch width: enough parallel proposals to keep the device busy,
    capped so tiny instances don't over-propose. 256 targets the
    accelerator knee — below it a sweep costs the same fixed overhead,
    above it the sweep goes bandwidth-bound (and winner-per-target wastes
    the surplus). Hardware re-validation is pending TPU access; the CPU
    path overrides to 64, where sweep cost is ~linear in width
    (docs/guide/03-placement-and-the-tpu-solver.md tuning notes)."""
    return max(1, min(256, S // 2))


def backend_proposals_per_step(S: int) -> int:
    """The backend-aware width both the full pipeline (api._solve) and
    the active-set sub-solve derive from: the CPU knee is 64 (sweep cost
    ~linear in width there — no free MXU width), accelerators take the
    256 knee above. ONE helper so a re-tuned knee cannot update one call
    site and silently leave the other stale."""
    import jax
    if jax.default_backend() == "cpu":
        return max(1, min(64, S // 2))
    return default_proposals_per_step(S)


@partial(jax.jit, static_argnames=("max_steps", "block",
                                   "proposals_per_step",
                                   "exit_on_feasible_init", "trace_blocks"))
def anneal_adaptive_states(prob: DeviceProblem, init_assignments: jax.Array,
                           key: jax.Array, max_steps: int = 128,
                           block: int = 32, t0: float = 1.0, t1: float = 1e-3,
                           proposals_per_step: int | None = None,
                           init_states: ChainState | None = None,
                           exit_on_feasible_init: bool = False,
                           trace_blocks: int = 0):
    """Anneal in `block`-sweep chunks, stopping as soon as any chain has
    SEEN an exactly feasible state (or at max_steps). Returns
    (best_assignments (C, S), best_viols (C,), best_softs (C,),
    sweeps_run scalar, accepted (C,), telemetry), where best is each
    chain's lexicographically lowest (violations, soft) state EVER
    VISITED, not its final state, and accepted counts the applied
    Metropolis moves per chain across every sweep that ran — the
    acceptance telemetry that surfaces through SolveResult and the
    fleet_solver_* metrics.

    Each sweep evaluates `proposals_per_step` moves per chain in parallel
    (one device dispatch), so total proposals = sweeps x M x C while the
    sequential depth stays the sweep count — the shape that keeps a TPU
    fed, vs the classic one-move-per-step SA whose wall-clock is all
    dispatch latency. Temperature decays geometrically t0 → t1 (in units
    of soft score; hard violation weights are orders of magnitude above
    t0, so hard-violating moves are only ever accepted to escape an
    existing violation).

    `trace_blocks` > 0 (static — see solve_trace_blocks) additionally
    carries a fixed-shape (trace_blocks, len(TRACE_COLS)) f32 buffer
    through the block loop and writes one row per completed sweep-block:
    cumulative sweeps, the block-end temperature, the best-ever
    (violations, soft) across chains, the min LIVE violation count of the
    carried states, and the cumulative accepted-move total. The buffer is
    observation only — it never feeds back into a proposal, a key fold or
    an exit check, so the refined assignment is bit-identical to the
    trace_blocks=0 program (pinned by the telemetry parity test). Blocks
    past the buffer drop (mode="drop"): a long anneal keeps its FIRST
    trace_blocks rows, where acceptance collapse and gate rejections
    live.

    Best-ever tracking (r5): Metropolis acceptance takes uphill soft moves
    by design, so a chain's final state can be worse than one it already
    walked through — measured on the 1k x 100 instance, an 8-sweep run
    RETURNED soft 1.3714 where a 2-sweep run returned 1.3390, i.e. more
    annealing made the answer worse. Tracking argmin over visited states
    restores monotonicity (more sweeps can only help) and decouples
    `block` from quality: the block size is now purely an exit-check
    granularity / latency knob. Cost per sweep is one carried-state
    elementwise reduce per chain (the same price the per-block exit check
    already paid), not a scatter rebuild.

    The stop check runs ON DEVICE inside a lax.while_loop — no host round
    trips — so easy instances (and especially warm-start reschedules, which
    start one churn event away from feasible) pay one block instead of the
    full budget, while hard instances still get max_steps. The temperature
    schedule is fixed against max_steps, so early exit truncates the cool
    tail rather than reshaping it. When max_steps is not a block multiple
    the budget rounds UP to whole blocks; overflow sweeps hold the floor
    temperature t1 (the exponent is clamped), and sweeps_run reports what
    actually ran.
    """
    C, S = init_assignments.shape
    M = (proposals_per_step if proposals_per_step is not None
         else default_proposals_per_step(S))
    n_blocks = -(-max_steps // block)
    # init_states skips the per-chain scatter rebuild when the caller
    # already carries the states (warm fused pre-repair: every chain
    # starts from the repaired seed, so the prologue's state IS the init)
    states = (init_states if init_states is not None else
              jax.vmap(partial(chain_states_from_assignment,
                               prob))(init_assignments))
    keys = jax.random.split(key, C)
    decay = (t1 / t0) ** (1.0 / max(max_steps - 1, 1))

    def chain_scores(states):
        """(violations (C,), soft (C,)) from carried state — an
        elementwise reduce, not a scatter rebuild (an exact-kernel check
        here cost ~18 ms per block at 10k x 1k). Kept as SEPARATE scalars:
        a folded W_HARD * v + soft float32 rounds the O(1) soft term away
        entirely once v exceeds ~1e3 (ulp(2e7) = 2), which would turn the
        soft tie-break among equal-violation states into a no-op on
        heavily infeasible instances."""
        v = jax.vmap(
            lambda st: state_violation_stats(prob, st)["total"])(states)
        soft = jax.vmap(lambda st: state_soft_score(prob, st))(states)
        return v, soft

    def sweep(carry, i):
        (states, keys, best_assign, best_viol, best_soft,
         seen_feasible, accepted, *live) = carry
        # clamp: overflow sweeps of a rounded-up final block hold t1
        temp = t0 * decay ** jnp.minimum(
            i, max_steps - 1).astype(jnp.float32)
        keys = jax.vmap(lambda k: jax.random.fold_in(k, i))(keys)
        states, acc = jax.vmap(
            lambda st, k: _batched_step(prob, st, k, temp, M))(states, keys)
        accepted = accepted + acc
        viol, soft = chain_scores(states)
        # lexicographic (violations, soft) — NOT a folded cost: the
        # warm-start migration bonus can push soft below -W_HARD in
        # aggregate (bonus gap ~ migration_weight x forced moves), where a
        # folded comparison would prefer a 1-violation maximally-sticky
        # state over a feasible one; feasibility must dominate
        # unconditionally, and soft must stay a full-precision tie-break
        better = (viol < best_viol) | ((viol == best_viol)
                                       & (soft < best_soft))
        best_viol = jnp.where(better, viol, best_viol)
        best_soft = jnp.where(better, soft, best_soft)
        best_assign = jnp.where(better[:, None], states.assignment,
                                best_assign)
        seen_feasible = seen_feasible | (viol.min() == 0)
        out = (states, keys, best_assign, best_viol, best_soft,
               seen_feasible, accepted)
        if trace_blocks:
            # thread the LIVE scores this sweep already computed out to
            # the block boundary — the telemetry row reads them for free
            # instead of re-running chain_scores per block (which, at the
            # warm path's block=1, would double the per-sweep stats cost
            # — measured as the admission p99 regrowing 30 → 65 ms)
            out = out + (viol,)
        return out, None

    def best_soft_of(best_viol, best_soft):
        """Soft of the lexicographically leading chain — what one
        telemetry row can say about C chains without C columns."""
        return jnp.min(jnp.where(best_viol == best_viol.min(),
                                 best_soft, jnp.inf))

    viol0, soft0 = chain_scores(states)
    telem0 = jnp.zeros((trace_blocks, len(TRACE_COLS)), jnp.float32)
    init = (states, keys, states.assignment, viol0, soft0,
            viol0.min() == 0, jnp.zeros((C,), jnp.int32), telem0)

    def cond(carry):
        *_rest, b, done = carry
        return (~done) & (b < n_blocks)

    def body(carry):
        (states, keys, best_assign, best_viol, best_soft, seen,
         accepted, telem, b, _done) = carry
        offsets = b * block + jnp.arange(block, dtype=jnp.int32)
        inner = (states, keys, best_assign, best_viol, best_soft, seen,
                 accepted)
        if trace_blocks:
            # placeholder live scores; block >= 1 so the first sweep of
            # the block always overwrites them
            inner = inner + (best_viol,)
        res, _ = jax.lax.scan(sweep, inner, offsets)
        (states, keys, best_assign, best_viol, best_soft,
         seen, accepted) = res[:7]
        # flight-deck row for this block: PURE observation of scores the
        # sweeps already computed (no extra reduces), written with
        # mode="drop" so rows past the static buffer vanish instead of
        # clamping onto the last slot. trace_blocks == 0 (static) skips everything:
        # the pre-telemetry program, byte for byte — the parity
        # reference.
        if trace_blocks:
            live_viol = res[7]
            end_sweep = (b + 1) * block
            temp_end = t0 * decay ** jnp.minimum(
                end_sweep - 1, max_steps - 1).astype(jnp.float32)
            row = jnp.stack([end_sweep.astype(jnp.float32),
                             temp_end,
                             best_viol.min(),
                             best_soft_of(best_viol, best_soft),
                             live_viol.min(),
                             accepted.sum().astype(jnp.float32)])
            telem = telem.at[b].set(row, mode="drop")
        return (states, keys, best_assign, best_viol, best_soft, seen,
                accepted, telem, b + 1, seen)

    # done starts False: even an already-feasible start gets one block of
    # soft polish (the exit trades polish for latency only after that).
    # exit_on_feasible_init (the resident warm path) skips even that: the
    # fused pre-repair prologue hands over a feasible state whose
    # displaced services already sit on least-utilized fitting nodes, and
    # migration stickiness rejects nearly every polish proposal anyway —
    # the sweep was pure latency (~30 ms of the 10k x 1k warm dispatch).
    start_done = ((viol0.min() == 0) if exit_on_feasible_init
                  else jnp.bool_(False))
    (_, _, best_assign, best_viol, best_soft, _, accepted, telem, b,
     _) = jax.lax.while_loop(cond, body, init + (jnp.int32(0),
                                                 start_done))
    telemetry = {
        "blocks": telem,
        "filled": jnp.minimum(b, trace_blocks),
        # the prologue/seed scores: the whole story of a 0-sweep exit
        "init_violations": viol0.min(),
        "init_soft": best_soft_of(viol0, soft0),
    }
    return best_assign, best_viol, best_soft, b * block, accepted, telemetry
