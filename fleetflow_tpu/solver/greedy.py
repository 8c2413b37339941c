"""Device greedy placer: vectorized first-fit-decreasing.

The seed stage of the solve pipeline (SURVEY.md section 7 phase 2: "greedy
seed (vectorized topo-order by dependency depth)"). Replaces the reference's
sequential `order_by_dependencies` partition + per-service Docker round-trip
(engine.rs:67-85,157-167) as the placement front-end.

Two implementations:

- `greedy_place`: one lax.scan step per service — exact FFD, but S sequential
  iterations. At 10k services the loop is latency-bound even on-device
  (round-1 VERDICT: seed_ms 181 at 10k×1k dwarfed the anneal).
- `greedy_place_batched` (default in solve()): scan over batches of M
  services. Each batch scores all M×N (service, node) pairs in one shot,
  services pick their best node, and within-batch collisions are resolved
  with pairwise masks — service m may land on its chosen node only if the
  demand of earlier same-node batch-mates still fits and none of them shares
  a conflict group. Losers retry against the updated state in a second round;
  the rare still-losers are committed best-effort (the annealer repairs
  them, matching the reference's FallbackPolicy relax-order semantics,
  model.rs:49, in spirit). Sequential depth drops from S to ~2·S/M.

When no node is feasible a service is placed best-effort (least overflow,
fewest conflicts) and the annealer repairs it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .problem import DeviceProblem, eligible_row, eligible_rows

__all__ = ["greedy_place", "greedy_place_batched", "placement_order",
           "partitioned_seed", "deal_to_domains"]

_NEG = -1e30


def placement_order(demand: np.ndarray, dep_depth: np.ndarray,
                    conflict_ids: np.ndarray | None = None) -> np.ndarray:
    """Host-side placement order: most-constrained-first, then
    first-fit-decreasing. Services carrying anti-affinity constraints (host
    ports, exclusive volumes) go first — they need conflict-free nodes while
    plenty remain — then by normalized demand descending; dependency depth
    breaks ties."""
    norm = demand / np.maximum(demand.max(axis=0, keepdims=True), 1e-6)
    weight = norm.sum(axis=1)
    if conflict_ids is not None and conflict_ids.size:
        n_constraints = (conflict_ids >= 0).sum(axis=1)
        weight = weight + n_constraints * (weight.max() + 1.0)
    return np.lexsort((dep_depth, -weight)).astype(np.int32)


def _real_rows(prob: DeviceProblem, svc: jax.Array) -> jax.Array:
    """Which of the rows `svc` count toward a spread constraint: every row
    but the bucket-padding phantoms (rows >= n_real)."""
    if prob.n_real is None:
        return jnp.ones(svc.shape, bool)
    return svc < prob.n_real


def deal_to_domains(counts: jax.Array, m: jax.Array) -> jax.Array:
    """How many of `m` new rows each topology domain takes so that the
    counts stay as level as they can be: (T,) i32 summing to `m`.

    Water-filling, in closed form over the sorted counts: the emptiest
    domains are raised to one common level, the remainder goes one each to
    the first of them in (count, id) order. The counts it ends in are those
    that dealing the rows one at a time to the emptiest domain ends in
    (which of several equal domains holds the odd row may differ), without
    the `m` sequential steps: the spread never widens, and a spread wider
    than one narrows by as much as `m` rows can."""
    T = counts.shape[0]
    order = jnp.argsort(counts, stable=True)
    s = counts[order]
    j = jnp.arange(1, T + 1, dtype=jnp.int32)
    # rows it takes to raise the j emptiest domains to the j-th's count
    need = j * s - jnp.cumsum(s)
    n_fill = jnp.sum(need <= m).astype(jnp.int32)        # >= 1: need[0] = 0
    spare = m - need[n_fill - 1]
    level = s[n_fill - 1] + spare // n_fill
    extra = spare % n_fill
    pos = jnp.arange(T, dtype=jnp.int32)
    filled = jnp.where(pos < n_fill, level + (pos < extra) - s, 0)
    return jnp.zeros((T,), jnp.int32).at[order].set(filled.astype(jnp.int32))


@partial(jax.jit, static_argnames=("best_effort",))
def greedy_place(prob: DeviceProblem, order: jax.Array,
                 best_effort: bool = True) -> jax.Array:
    """Place services in `order`; returns assignment (S,) int32."""
    R = prob.demand.shape[1]
    eps = 1e-6

    spread = prob.max_skew > 0

    def step(carry, s):
        load, used, assignment, *topo = carry
        d = prob.demand[s]                      # (R,)
        ids = prob.conflict_ids[s]              # (K,)
        valid_ids = (ids >= 0)
        safe = jnp.where(valid_ids, ids, 0)

        conflict = (used[:, safe] * valid_ids[None, :]).sum(-1) > 0   # (N,)
        new_load = load + d[None, :]                                   # (N, R)
        fits = (new_load <= prob.capacity + eps).all(-1)
        elig_s = eligible_row(prob.eligible, s, prob.N)
        ok = fits & elig_s & prob.node_valid & ~conflict
        if spread:
            # the source's own filter (PodTopologySpread, DoNotSchedule): a
            # domain may take the row while its count, with the row, stays
            # within max_skew of the emptiest domain's
            (topo,) = topo
            real = _real_rows(prob, s)
            open_dom = topo + 1 - topo.min() <= prob.max_skew
            ok = ok & (open_dom[prob.node_topology] | ~real)

        u_after = new_load / jnp.maximum(prob.capacity, 1e-6)
        usq = (u_after * u_after).sum(-1)                              # (N,)
        if prob.strategy == 0:      # spread: balance → lowest resulting util²
            score = -usq
        elif prob.strategy == 1:    # pack: consolidate → highest resulting util²
            score = usq
        else:                       # fill_lowest: low node index first
            score = -jnp.arange(prob.N, dtype=jnp.float32)
        if prob.preferred is not None:
            score = score + prob.preferred[s] * 0.5

        best_ok = jnp.argmax(jnp.where(ok, score, _NEG))
        if best_effort:
            overflow = jnp.maximum(new_load - prob.capacity, 0.0).sum(-1)
            n_conf = (used[:, safe] * valid_ids[None, :]).sum(-1)
            fb_score = -(overflow * 1e3 + n_conf.astype(jnp.float32) * 1e3) + score
            fb_ok = elig_s & prob.node_valid
            best_fb = jnp.argmax(jnp.where(fb_ok, fb_score, fb_score - 1e15))
            node = jnp.where(ok.any(), best_ok, best_fb)
        else:
            node = best_ok

        load = load.at[node].add(d)
        used = used.at[node, safe].add(valid_ids.astype(used.dtype))
        assignment = assignment.at[s].set(node.astype(jnp.int32))
        if spread:
            topo = topo.at[prob.node_topology[node]].add(
                real.astype(jnp.int32))
            return (load, used, assignment, topo), None
        return (load, used, assignment), None

    init = (
        jnp.zeros((prob.N, R), dtype=jnp.float32),
        jnp.zeros((prob.N, prob.G), dtype=jnp.int32),
        jnp.full((prob.S,), -1, dtype=jnp.int32),
    )
    if spread:
        init = init + (jnp.zeros((prob.T,), jnp.int32),)
    # unroll: one fused device step per 8 services — the scan is dispatch-
    # bound at fleet scale (each step's math is tiny), so unrolling buys
    # ~40% wall-clock at 10k services
    (_, _, assignment, *_), _ = jax.lax.scan(step, init, order, unroll=8)
    return assignment


def _node_scores(prob: DeviceProblem, load: jax.Array, svc: jax.Array):
    """Score all nodes for a batch of services against shared state.

    Returns (score (M,N), fits (M,N), new_load (M,N,R)-free util term reused
    by callers is not returned — only what the batch step needs)."""
    d = prob.demand[svc]                                    # (M, R)
    new_load = load[None, :, :] + d[:, None, :]             # (M, N, R)
    fits = (new_load <= prob.capacity[None] + 1e-6).all(-1)  # (M, N)

    u_after = new_load / jnp.maximum(prob.capacity[None], 1e-6)
    usq = (u_after * u_after).sum(-1)                       # (M, N)
    if prob.strategy == 0:       # spread: lowest resulting util²
        score = -usq
    elif prob.strategy == 1:     # pack: highest resulting util²
        score = usq
    else:                        # fill_lowest: low node index first
        score = jnp.broadcast_to(-jnp.arange(prob.N, dtype=jnp.float32),
                                 usq.shape)
    if prob.preferred is not None:
        score = score + prob.preferred[svc] * 0.5
    overflow = jnp.maximum(new_load - prob.capacity[None], 0.0).sum(-1)
    return score, fits, overflow


def _conflict_rows(prob: DeviceProblem, used: jax.Array, svc: jax.Array):
    """(M, N) bool: node already occupied by a conflicting service."""
    ids = prob.conflict_ids[svc]                            # (M, K)
    valid = ids >= 0
    safe = jnp.where(valid, ids, 0)
    occ = used[:, safe]                                     # (N, M, K)
    return ((occ * valid[None, :, :]).sum(-1) > 0).T        # (M, N)


def _pairwise_ok(prob: DeviceProblem, load: jax.Array, svc: jax.Array,
                 choice: jax.Array, live: jax.Array) -> jax.Array:
    """Within-batch resolution: may service m commit to choice[m] given the
    *earlier* live batch-mates that chose the same node? (M,) bool."""
    M = svc.shape[0]
    d = prob.demand[svc] * live[:, None]                    # (M, R)
    same = (choice[:, None] == choice[None, :]) & live[:, None] & live[None, :]
    earlier = jnp.tril(jnp.ones((M, M), bool), k=-1)
    mates = same & earlier                                  # (M, M)

    # capacity: earlier same-node mates' demand must still leave room
    prefix = mates.astype(jnp.float32) @ d                  # (M, R)
    cap_c = prob.capacity[choice]                           # (M, R)
    cap_ok = (load[choice] + prefix + prob.demand[svc]
              <= cap_c + 1e-6).all(-1)

    # conflicts: no earlier same-node mate shares a conflict id
    ids = prob.conflict_ids[svc]                            # (M, K)
    v = ids >= 0
    share = ((ids[:, None, :, None] == ids[None, :, None, :])
             & v[:, None, :, None] & v[None, :, None, :]).any((-1, -2))
    conf_ok = ~(mates & share).any(-1)
    return cap_ok & conf_ok


def _commit(prob: DeviceProblem, load, used, assignment, svc, choice, mask):
    """Scatter a masked batch of placements into the shared state."""
    w = mask.astype(jnp.float32)
    wi = mask.astype(jnp.int32)
    load = load.at[choice].add(prob.demand[svc] * w[:, None])

    ids = prob.conflict_ids[svc]
    valid = (ids >= 0).astype(jnp.int32) * wi[:, None]
    safe = jnp.where(ids >= 0, ids, 0)
    rows = jnp.broadcast_to(choice[:, None], safe.shape)
    used = used.at[rows, safe].add(valid)

    # dump-row trick: non-committed writes land on a scratch row
    tgt = jnp.where(mask, svc, prob.S)
    assignment = assignment.at[tgt].set(choice.astype(jnp.int32))
    return load, used, assignment


@partial(jax.jit, static_argnames=("batch", "rounds"))
def greedy_place_batched(prob: DeviceProblem, order: jax.Array,
                         batch: int = 256, rounds: int = 2) -> jax.Array:
    """Place services in `order`, `batch` at a time; returns (S,) int32.

    Semantics match greedy_place's FFD-with-fallback except that services in
    one batch cannot see each other's *soft* influence (they do see each
    other's capacity/conflict footprint through the pairwise resolution).
    Sequential depth is ceil(S/batch) scan steps instead of S.

    `rounds=1` skips the loser-retry round: collision losers tail-commit
    immediately, leaving more seed violations for the annealer's targeted
    proposals to fix — cheaper per step, worth it when an annealer follows.
    """
    if rounds not in (1, 2):
        raise ValueError(f"rounds must be 1 or 2, got {rounds}")
    S, N = prob.S, prob.N
    M = min(batch, S)
    n_batches = -(-S // M)
    pad = n_batches * M - S
    order_p = jnp.concatenate(
        [order.astype(jnp.int32), jnp.full((pad,), -1, jnp.int32)])
    batches = order_p.reshape(n_batches, M)

    # spread strategy fans each batch over the top-W near-equal nodes
    # (without this, all M batch-mates herd onto the same lowest-util node
    # and the pairwise gate rejects most of them every round)
    W = min(M, N)

    spread = prob.max_skew > 0

    def step(carry, svc_raw):
        load, used, assignment, *topo = carry
        live0 = svc_raw >= 0
        svc = jnp.where(live0, svc_raw, 0)
        in_domain = None
        if spread:
            # a spread stage: the batch's real rows are dealt to the
            # domains so the counts stay level (deal_to_domains), each row
            # then chooses among its own domain's nodes; which row takes
            # which domain is free, the rows count alike
            (topo,) = topo
            real = live0 & _real_rows(prob, svc)
            quota = deal_to_domains(topo, real.sum().astype(jnp.int32))
            rank = jnp.cumsum(real.astype(jnp.int32)) - 1
            dom = jnp.searchsorted(jnp.cumsum(quota), rank, side="right")
            in_domain = ((prob.node_topology[None, :] == dom[:, None])
                         | ~real[:, None])                   # (M, N)

        def choose(load, used, live):
            score, fits, overflow = _node_scores(prob, load, svc)
            conflict = _conflict_rows(prob, used, svc)
            elig_b = eligible_rows(prob.eligible, svc, prob.N)   # (M, N)
            hard_ok = (fits & elig_b & prob.node_valid[None]
                       & ~conflict)
            if spread:
                hard_ok = hard_ok & in_domain
            masked = jnp.where(hard_ok, score, _NEG)
            # Anti-herding ranks: a plain argmax sends every batch-mate to
            # the same node; the pairwise gate then admits only one node's
            # worth per round and the rest tail-commit with violations.
            _, topk = jax.lax.top_k(masked, W)                # (M, W)
            count_ok = jnp.minimum(hard_ok.sum(-1), W)        # only W columns
            if prob.strategy == 0:
                # spread: batch-mate m takes a rank spread over its OWN
                # feasible list ((m mod W) mapped proportionally onto
                # [0, count_ok)). Proportional mapping matters: tenant pools
                # give same-tenant services identical ~count_ok-node feasible
                # lists, and a clamped rank would pile every high-m
                # batch-mate onto one node.
                r = jnp.arange(M, dtype=jnp.int32) % W
                r_eff = jnp.minimum((r * count_ok) // W,
                                    jnp.maximum(count_ok - 1, 0))
            else:
                # pack / fill_lowest: fill nodes in score order, about one
                # node's capacity worth of batch-mates per rank — herding
                # onto a single node per round would strand the rest on the
                # best-effort tail.
                mean_d = jnp.maximum(prob.demand[svc].mean(0), 1e-6)  # (R,)
                med_cap = jnp.median(prob.capacity, axis=0)           # (R,)
                est = jnp.clip((med_cap / mean_d).min().astype(jnp.int32),
                               1, M)
                r = jnp.arange(M, dtype=jnp.int32) // est
                r_eff = jnp.minimum(r, jnp.maximum(count_ok - 1, 0))
            best_ok = jnp.take_along_axis(topk, r_eff[:, None], 1)[:, 0]
            # fallback: least overflow / fewest conflicts among eligible
            fb_score = score - overflow * 1e3 - conflict * 1e3
            if spread:
                # a row its domain has no room for: off it, at a conflict's
                # price, and the sweeps carry the skew back
                fb_score = fb_score - (~in_domain) * 1e3
            fb_ok = elig_b & prob.node_valid[None]
            best_fb = jnp.argmax(jnp.where(fb_ok, fb_score, fb_score - 1e15),
                                 axis=-1)
            has_ok = hard_ok.any(-1)
            choice = jnp.where(has_ok, best_ok, best_fb).astype(jnp.int32)
            pair_ok = _pairwise_ok(prob, load, svc, choice, live)
            return choice, has_ok, live & pair_ok & has_ok

        # round 1: everyone proposes; winners commit
        c1, _, ok1 = choose(load, used, live0)
        load, used, assignment = _commit(prob, load, used, assignment,
                                         svc, c1, ok1)
        rest = live0 & ~ok1
        if rounds > 1:
            # round 2: losers re-propose against the updated state
            c2, _, ok2 = choose(load, used, rest)
            load, used, assignment = _commit(prob, load, used, assignment,
                                             svc, c2, ok2)
            rest, c_tail = rest & ~ok2, c2
        else:
            c_tail = c1
        # best-effort tail: anything still unplaced (no feasible node at all,
        # or collision-rejected in every round) commits at its last choice;
        # the annealer repairs (FallbackPolicy relax-order in spirit)
        load, used, assignment = _commit(prob, load, used, assignment,
                                         svc, c_tail, rest)
        if spread:
            # where the rows landed, not where they were dealt: the next
            # batch's deal levels out what a fallback left uneven
            # (a second round's winners chose c2, which is c_tail)
            landed = jnp.where(ok1, c1, c_tail)
            topo = topo.at[prob.node_topology[landed]].add(
                real.astype(jnp.int32))
            return (load, used, assignment, topo), None
        return (load, used, assignment), None

    R = prob.demand.shape[1]
    init = (
        jnp.zeros((N, R), jnp.float32),
        jnp.zeros((N, prob.G), jnp.int32),
        jnp.full((S + 1,), -1, jnp.int32),   # +1 dump row
    )
    if spread:
        init = init + (jnp.zeros((prob.T,), jnp.int32),)
    (_, _, assignment, *_), _ = jax.lax.scan(step, init, batches)
    return assignment[:S]


def partitioned_seed(pt, parts: int) -> np.ndarray:
    """Host seed for mega-scale sharded solves: service slices x disjoint
    round-robin node subsets, one full-capacity FFD per slice.

    The exact host FFD is O(S*N) sequential work — minutes at 100k x 10k,
    outweighing the sharded anneal it feeds. This slices the NODE axis
    round-robin alongside a contiguous service split: slice g FFDs its
    services onto its own nodes at full capacity, cutting the work to
    O(S*N/parts) with a union feasible by construction for both capacity
    and conflict groups (disjoint nodes cannot share a port). The residue
    left for the anneal: services whose eligible nodes all fall in other
    slices (best-effort in-slice, an eligibility violation each) and
    packing fragmentation across node subsets — the same repair contract
    as the batched device seed's best-effort tail.

    Returns (S,) int32. Uses the native C++ FFD per group when available,
    the pure-numpy host greedy otherwise.
    """
    import numpy as _np

    from ..native.lib import available_nobuild, native_place

    S = pt.demand.shape[0]
    if not available_nobuild():
        # no native library: one whole-instance host greedy (correct, just
        # not partitioned — the fallback machine is not the mega-scale one)
        from ..sched.host import greedy_host_place
        return greedy_host_place(pt)[0].astype(_np.int32)
    N = pt.capacity.shape[0]
    parts = max(1, min(parts, S, N))
    if parts == 1:
        seg, _viol = native_place(
            pt.demand, pt.capacity, pt.eligible, pt.node_valid,
            pt.dep_depth, pt.port_ids, pt.volume_ids, pt.anti_ids,
            strategy=pt.strategy.value)
        return seg

    # Partition NODES, not capacity: slice g owns every (parts)-th node
    # (round-robin, so tenant-blocked eligibility spreads over slices)
    # and a contiguous 1/parts of the services, FFD'd onto its own nodes
    # at FULL capacity. Total FFD work drops from O(S*N) to O(S*N/parts),
    # the union is feasible by construction for capacity AND conflicts
    # (slices place on disjoint nodes, so no cross-slice port collision
    # is even possible), and big services see whole nodes — the two
    # failure modes of capacity-sharing designs (an equal cap/parts
    # starves any service over 1/parts of a node; flooring the share at
    # the slice max lets small services overbook it `parts` times,
    # measured 22 capacity violations on a feasible 64x16 instance).
    # What remains for the anneal: services whose eligible nodes all
    # live in OTHER slices get best-effort in-slice placements (an
    # eligibility violation each), and packing quality is fragmented
    # across node subsets — both repaired/polished by the sweeps.
    out = _np.empty(S, dtype=_np.int32)
    bounds = _np.linspace(0, S, parts + 1, dtype=int)

    def one_slice(g: int) -> None:
        lo, hi = int(bounds[g]), int(bounds[g + 1])
        if hi <= lo:
            return
        nodes_g = _np.arange(g, N, parts)
        seg, _viol = native_place(
            pt.demand[lo:hi],
            _np.ascontiguousarray(pt.capacity[nodes_g]),
            _np.ascontiguousarray(pt.eligible[lo:hi][:, nodes_g]),
            _np.ascontiguousarray(pt.node_valid[nodes_g]),
            pt.dep_depth[lo:hi], pt.port_ids[lo:hi],
            pt.volume_ids[lo:hi], pt.anti_ids[lo:hi],
            strategy=pt.strategy.value)
        out[lo:hi] = nodes_g[seg]

    # slices are independent (disjoint services AND nodes) and ctypes
    # releases the GIL for the duration of the C call, so a thread pool
    # gives real concurrency on multi-core hosts; the 1-core dev box just
    # runs them back to back. Each worker writes a disjoint out[lo:hi].
    import os as _os
    workers = min(parts, _os.cpu_count() or 1)
    if workers > 1:
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(max_workers=workers) as ex:
            list(ex.map(one_slice, range(parts)))
    else:
        for g in range(parts):
            one_slice(g)
    return out
