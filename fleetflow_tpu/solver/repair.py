"""Host-side exact repair + verification.

The deterministic backstop behind the zero-violation contract: the device
solver (greedy + annealing) lands feasible in practice, but the contract is
exact, so any residual violations are repaired here with vectorized numpy —
move each violating service to the best feasible node, smallest first, a
bounded number of rounds, then level a spread stage's topology domains.
Also home to `verify()`, the numpy ground-truth violation accounting that
tests use to cross-check the device kernels.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass

import numpy as np

from ..lower.tensors import ProblemTensors

__all__ = ["verify", "repair", "RepairResult"]


def _group_counts(assignment: np.ndarray, ids: np.ndarray, N: int,
                  G: int) -> np.ndarray:
    valid = ids >= 0
    counts = np.zeros((N, G), dtype=np.int64)
    rows = np.repeat(assignment, ids.shape[1])[valid.ravel()]
    cols = ids.ravel()[valid.ravel()]
    np.add.at(counts, (rows, cols), 1)
    return counts


def _unified_ids(pt: ProblemTensors) -> np.ndarray:
    parts, offset = [], 0
    for arr in (pt.port_ids, pt.volume_ids, pt.anti_ids):
        parts.append(np.where(arr >= 0, arr + offset, -1))
        if arr.size:
            offset += int(arr.max(initial=-1)) + 1
    merged = np.concatenate(parts, axis=1)
    # dedupe within rows (mirrors problem._unify_conflict_ids): a repeated id
    # on one service is one constraint, not a self-conflict
    merged = -np.sort(-merged, axis=1)
    dup = np.zeros_like(merged, dtype=bool)
    dup[:, 1:] = (merged[:, 1:] == merged[:, :-1]) & (merged[:, 1:] >= 0)
    return np.where(dup, -1, merged)


def verify(pt: ProblemTensors, assignment: np.ndarray) -> dict:
    """Exact violation accounting on the host (numpy ground truth)."""
    S, N = pt.S, pt.N
    assignment = np.asarray(assignment)
    load = np.zeros((N, pt.demand.shape[1]), dtype=np.float64)
    np.add.at(load, assignment, pt.demand.astype(np.float64))
    cap_cells = int((load > pt.capacity * (1 + 1e-6)).sum())

    ids = _unified_ids(pt)
    G = int(ids.max(initial=-1)) + 1
    conflict_pairs = 0
    if G > 0:
        counts = _group_counts(assignment, ids, N, G)
        conflict_pairs = int((counts * (counts - 1) // 2).sum())

    elig = int((~pt.eligible[np.arange(S), assignment]).sum()
               + (~pt.node_valid[assignment]).sum())

    skew = 0
    if pt.max_skew > 0:
        per = np.bincount(pt.node_topology[assignment],
                          minlength=int(pt.node_topology.max()) + 1)
        skew = max(int(per.max() - per.min()) - pt.max_skew, 0)

    total = cap_cells + conflict_pairs + elig + skew
    return {"capacity": cap_cells, "conflicts": conflict_pairs,
            "eligibility": elig, "skew": skew, "total": total}


@dataclass
class RepairResult:
    assignment: np.ndarray
    moves: int
    stats: dict
    feasible: bool
    # of `moves`, those the spread pass made (fullest domain -> emptiest)
    skew_moves: int = 0


def _level_domains(pt: ProblemTensors, assignment: np.ndarray,
                   ids: np.ndarray, G: int, budget: int) -> int:
    """The spread pass: while the fullest topology domain holds more than
    `max_skew` rows over the emptiest, take from the fullest a row that
    fits on a node of the emptiest — eligible, valid, within capacity, no
    shared conflict id — smallest first, onto the least-utilized such node.
    Edits `assignment` in place and returns the moves made; stops where no
    row of the fullest domain fits anywhere in the emptiest (capacity or
    eligibility binds there: the instance is infeasible as spread)."""
    N = pt.N
    topo = np.asarray(pt.node_topology)
    T = int(topo.max(initial=0)) + 1
    demand = pt.demand.astype(np.float64)
    cap = pt.capacity.astype(np.float64) * (1 + 1e-6)
    load = np.zeros((N, demand.shape[1]), dtype=np.float64)
    np.add.at(load, assignment, demand)
    counts = (_group_counts(assignment, ids, N, G) if G > 0 else None)
    per = np.bincount(topo[assignment], minlength=T)
    size = demand.sum(axis=1)
    moves = 0
    while moves < budget:
        full, empty = int(per.argmax()), int(per.argmin())
        if per[full] - per[empty] <= pt.max_skew:
            break
        into = np.flatnonzero((topo == empty) & pt.node_valid)
        rows = np.flatnonzero(topo[assignment] == full)
        for s in rows[np.argsort(size[rows], kind="stable")]:
            mine = ids[s][ids[s] >= 0]
            ok = pt.eligible[s, into] & (
                load[into] + demand[s] <= cap[into]).all(axis=1)
            if mine.size:
                ok &= (counts[np.ix_(into, mine)] == 0).all(axis=1)
            if ok.any():
                break
        else:
            break           # nothing of the fullest fits in the emptiest
        cand = into[ok]
        n = int(cand[np.argmin(
            (load[cand] / np.maximum(cap[cand], 1e-6)).max(axis=1))])
        a = int(assignment[s])
        load[a] -= demand[s]
        load[n] += demand[s]
        if mine.size:
            counts[a, mine] -= 1
            counts[n, mine] += 1
        assignment[s] = n
        per[full] -= 1
        per[empty] += 1
        moves += 1
    return moves


def repair(pt: ProblemTensors, assignment: np.ndarray,
           max_rounds: int = 8, seed: int = 0) -> RepairResult:
    """Repair residual violations (deterministic given `seed`). Returns the
    repaired assignment (copy) and final stats; `feasible` is False when some
    violation could not be repaired (genuinely infeasible instances).

    Mechanics: worklist relocation with one-level ejection chains, plus
    min-conflicts-style randomized escape — a service that keeps bouncing
    between the same contested nodes is sent to a random eligible node so
    deterministic ejection cycles (A evicts B evicts A…) break."""
    S, N = pt.S, pt.N
    original = np.asarray(assignment)
    assignment = original.copy()
    ids = _unified_ids(pt)
    G = int(ids.max(initial=-1)) + 1
    demand = pt.demand.astype(np.float64)
    cap = pt.capacity.astype(np.float64)
    moves = 0
    rng = np.random.default_rng(seed)
    bounce = np.zeros(S, dtype=np.int64)

    # conflict-id sets are built lazily and shared across rounds (`ids`
    # never changes): the worklist touches O(|bad| + evictees) services,
    # and materializing all S sets per round costs more than the whole
    # repair on warm churn fixes
    _id_cache: dict = {}

    def id_set(s: int) -> set:
        v = _id_cache.get(s)
        if v is None:
            row = ids[s]
            v = set(row[row >= 0].tolist()) if G > 0 else set()
            _id_cache[s] = v
        return v

    for _ in range(max_rounds):
        load = np.zeros((N, demand.shape[1]), dtype=np.float64)
        np.add.at(load, assignment, demand)
        counts = (_group_counts(assignment, ids, N, G) if G > 0
                  else np.zeros((N, 1), dtype=np.int64))

        # --- collect violating services ---------------------------------
        bad = np.zeros(S, dtype=bool)
        # ineligible / invalid node
        bad |= ~pt.eligible[np.arange(S), assignment]
        bad |= ~pt.node_valid[assignment]
        # conflict groups: every service in an over-occupied (node, gid) cell
        # except the first keeper
        if G > 0:
            valid = ids >= 0
            svc_counts = np.where(
                valid, counts[assignment[:, None],
                              np.where(valid, ids, 0)], 0)
            in_conflict = (svc_counts > 1).any(axis=1)
            # keep one occupant per conflict cell: mark all, then unmark the
            # first occurrence per (node, gid). Only conflicted rows can be
            # keepers, so iterate those (ascending, same first-wins order) —
            # a warm churn repair has ~|displaced| conflicted rows, and an
            # O(S) python loop here would dominate the whole repair.
            keeper = np.zeros(S, dtype=bool)
            seen: set = set()
            for s in np.flatnonzero(in_conflict):
                cells = [(int(assignment[s]), int(g)) for g in ids[s] if g >= 0]
                if any(counts[c] > 1 for c in cells):
                    if all(c not in seen for c in cells):
                        keeper[s] = True
                        seen.update(cells)
            bad |= in_conflict & ~keeper
        # overloaded nodes: evict smallest services until the node fits.
        # The per-service inner loop is replaced by a cumulative-sum scan:
        # evicting the smallest k members leaves load[n] - csum[k-1], so
        # the minimal k is the first index where every resource fits —
        # same eviction set and order as the sequential loop.
        over = (load > cap * (1 + 1e-6)).any(axis=1)
        for n in np.flatnonzero(over):
            members = np.flatnonzero((assignment == n) & ~bad)
            if members.size == 0:
                continue
            dm = demand[members]
            asc = np.argsort(dm.sum(axis=1))
            csum = np.cumsum(dm[asc], axis=0)
            fits_k = (load[n] - csum <= cap[n] * (1 + 1e-6)).all(axis=1)
            k = (int(np.argmax(fits_k)) + 1 if fits_k.any()
                 else members.size)
            bad[members[asc[:k]]] = True

        if not bad.any():
            break

        # --- relocate, smallest first ------------------------------------
        # load/counts excluding the evicted services: subtract the |bad|
        # rows' contributions instead of rebuilding from all S rows (a
        # warm churn repair has ~14 bad rows against 10k total)
        nbad = np.flatnonzero(bad)
        np.add.at(load, assignment[nbad], -demand[nbad])
        if G > 0:
            bad_ids = ids[nbad]
            bvalid = bad_ids >= 0
            np.add.at(counts,
                      (np.repeat(assignment[nbad], bad_ids.shape[1])[
                          bvalid.ravel()],
                       bad_ids.ravel()[bvalid.ravel()]), -1)
        else:
            counts = np.zeros((N, 1), dtype=np.int64)

        # Worklist relocation with one-level ejection chains: when a service
        # has no directly-feasible node, it may evict the services blocking
        # the least-contended node; evictees rejoin the queue. `detached`
        # marks queued services — their demand/conflicts are already out of
        # load/counts and they must not be seen (or evicted) as residents.
        # Bounded by a global move budget so pathological instances terminate.
        #
        # Node membership is LAZY: the worklist touches O(|bad| + evictees)
        # services, and materializing all N resident sets up-front (a 10k-
        # iteration Python loop) cost more than the whole repair on warm
        # churn fixes. Residents are grouped once with an argsort; a node's
        # set is built on first touch and kept current from then on.
        size = demand.sum(axis=1)
        _res_rows = np.flatnonzero(~bad)
        _res_order = _res_rows[np.argsort(assignment[_res_rows],
                                          kind="stable")]
        _res_nodes = assignment[_res_order]
        node_members: dict[int, set] = {}

        def members_of(n: int) -> set:
            s = node_members.get(n)
            if s is None:
                lo = int(np.searchsorted(_res_nodes, n, side="left"))
                hi = int(np.searchsorted(_res_nodes, n, side="right"))
                s = set(_res_order[lo:hi].tolist())
                node_members[n] = s
            return s

        detached = bad.copy()

        def plan_eviction(n: int, s: int) -> list | None:
            """Residents of n to evict so s fits (conflicts + capacity);
            None when even a full conflict eviction can't make room."""
            residents = members_of(n)
            evict = [r for r in residents
                     if id_set(s) & id_set(r)] if id_set(s) else []
            new_load = load[n] + demand[s] - demand[evict].sum(axis=0)
            rest = sorted((r for r in residents if r not in evict),
                          key=size.__getitem__)
            while (new_load > cap[n] * (1 + 1e-6)).any() and rest:
                r = rest.pop(0)
                evict.append(r)
                new_load -= demand[r]
            if (new_load > cap[n] * (1 + 1e-6)).any():
                return None
            return evict

        def detach(r: int, n: int) -> None:
            load[n] -= demand[r]
            if id_set(r):
                counts[n, list(id_set(r))] -= 1
            members_of(n).discard(r)
            detached[r] = True
            queue.append(r)

        queue = deque(np.flatnonzero(bad)[np.argsort(size[bad])].tolist())
        budget = 4 * S
        # True once any placement was NOT a direct feasible one (ejection
        # chain or randomized escape): those can strand or conflict, which
        # only the next round's full rescan catches
        evicted_any = False
        while queue and budget > 0:
            s = int(queue.popleft())
            budget -= 1
            bounce[s] += 1
            my = list(id_set(s))
            fits = (load + demand[s] <= cap * (1 + 1e-6)).all(axis=1)
            ok = fits & pt.eligible[s] & pt.node_valid
            if my:
                ok &= (counts[:, my] == 0).all(axis=1)
            cand = np.flatnonzero(ok)
            if cand.size:
                # balance: least-loaded feasible node (random when escaping
                # a bounce cycle); a direct placement ends the cycle, so the
                # counter resets
                if bounce[s] > 3:
                    n = int(rng.choice(cand))
                else:
                    util = (load[cand] / np.maximum(cap[cand], 1e-6)).max(axis=1)
                    n = int(cand[np.argmin(util)])
                bounce[s] = 0
            else:
                # any NON-direct placement forfeits the clean-round
                # shortcut below, even one that evicts nothing: a
                # randomized escape may land on an overloaded node the
                # next round's rescan must re-visit
                evicted_any = True
                elig = np.flatnonzero(pt.eligible[s] & pt.node_valid)
                if elig.size == 0:
                    continue  # truly no node: infeasible service
                if bounce[s] > 3:
                    # randomized escape: random eligible node, evict blockers
                    n = int(rng.choice(elig))
                    evict = plan_eviction(n, s) or [
                        r for r in members_of(n) if id_set(s) & id_set(r)]
                else:
                    # ejection: the eligible node whose blockers are cheapest
                    best = None
                    for n in elig:
                        ev = plan_eviction(int(n), s)
                        if ev is None:
                            continue
                        cost = size[ev].sum() if ev else 0.0
                        if best is None or cost < best[1]:
                            best = (int(n), cost, ev)
                    if best is None:
                        continue
                    n, _, evict = best
                for r in evict:
                    detach(r, n)
            assignment[s] = n
            load[n] += demand[s]
            if my:
                counts[n, my] += 1
            members_of(n).add(s)
            detached[s] = False
            moves += 1

        # Every evictee re-placed and every placement was DIRECT (checked
        # feasible against live load/counts, which direct placements keep
        # consistent): the next round's full rescan would find nothing.
        # Ejection chains and randomized escapes forfeit the shortcut —
        # they can strand or conflict, which the rescan exists to catch.
        # verify() below stays the ground truth either way.
        if not queue and not evicted_any and not detached.any():
            break

    # the relocations above read no topology: what they and the device
    # left uneven is levelled last, by moves that keep the rest feasible
    skew_moves = 0
    if pt.max_skew > 0:
        skew_moves = _level_domains(pt, assignment, ids, G, budget=4 * S)
        moves += skew_moves

    stats = verify(pt, assignment)
    # Ejection leaves un-replaced evictees at stale nodes when the budget
    # exhausts; never return something worse than the input.
    if stats["total"] > 0:
        in_stats = verify(pt, original)
        if in_stats["total"] < stats["total"]:
            assignment, stats, moves = original.copy(), in_stats, 0
            skew_moves = 0
    return RepairResult(assignment=assignment, moves=moves, stats=stats,
                        feasible=stats["total"] == 0, skew_moves=skew_moves)
