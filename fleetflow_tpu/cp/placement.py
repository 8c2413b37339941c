"""Placement service: the CP's solver front-end + reservation journal.

This is the component the reference lacks (its CP picks
`stage.servers.first`, handlers/deploy.rs:386-398, with fan-out "future
work"). Here the CP lowers the fleet against its *live* server inventory
(capacity minus committed+reserved allocations, label/pool eligibility,
cordon/drain masks) and solves on-device.

The reservation journal implements the 2-phase commit the reference sketches
in `ServerAllocated` (model.rs:421-427) and solves SURVEY.md hard part (c):
a solve RESERVES its assignment; the deploy either COMMITs (moving reserved
-> committed) or RELEASEs on failure. Concurrent re-solves see reserved
capacity as occupied, so racing placements can't double-book a node.

Churn handling (BASELINE config 5): `node_event` flips the validity bit and
triggers an incremental warm-start re-solve that moves only what churn
forces (solver migration stickiness).

Conflicts reach across stages: a commitment and an open reservation record,
beside the demand they book, which conflict keys their rows hold on which
server (`Reservation.held_keys`; the keys are lower/tensors.py's), and every
lowering against live inventory bars the stage's rows from the servers on
which another stage holds one of their keys.

Priority reaches across stages too: a commitment keeps a row-level view of
itself (`Reservation.rows`), so that a stage whose rows all rank above some
of them can be lowered with what those rows hold counted as capacity, and a
commit of it evicts the fewest of them that make room (`PlacementService`'s
docstring has the rules): a stage solved whole (`solve_stage`) and a
micro-batch of streaming admission (`admit_batch`) alike.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
from dataclasses import dataclass, field, replace as _dc_replace
from typing import Optional

import numpy as np

from ..core.model import Flow, ServerLabels
from ..lower.tensors import (Node, ProblemTensors, bar_held, lower_stage,
                             with_preemptible, with_price)
from ..obs import get_logger, kv, phase
from ..obs.metrics import REGISTRY
from ..obs.slo import observe as slo_observe
from ..sched import (HostGreedyScheduler, Placement, TpuSolverScheduler,
                     level_schedule, place_with_fallback)
from .models import PlacementRecord, Server
from .store import ServerColumns, Store, booked_columns as _booked_columns

log = get_logger("cp.placement")

# metric catalog: docs/guide/10-observability.md. Churn re-solves that had
# to abandon the device solver (exception/timeout) for the greedy host
# path — self-healing must degrade, never stall (cp/reconverge.py).
_M_CHURN_FALLBACKS = REGISTRY.counter(
    "fleet_placement_churn_fallbacks_total",
    "Churn re-solves that fell back to the greedy host scheduler after a "
    "solver failure")

_M_HELD_KEYS = REGISTRY.counter(
    "fleet_placement_held_keys_total",
    "Server x conflict-key pairs held by other stages' committed and "
    "reserved placements, as handed to a lowering")

_M_PREEMPTIBLE_SERVERS = REGISTRY.counter(
    "fleet_placement_preemptible_servers_total",
    "Servers on which committed rows of lower priority hold capacity, as "
    "handed to a lowering of a stage that may evict them")

_M_VICTIMS = REGISTRY.counter(
    "fleet_placement_victims_total",
    "Committed rows evicted by the commit of a stage of higher priority")

_M_RECORD_WRITES = REGISTRY.counter(
    "fleet_placement_record_writes_total",
    "Writes of a stage's placement record, by form: whole (one put of the "
    "record) or diff (one mrg entry of the keys that changed)",
    labels=("form",))
_M_RECORD_KEYS = REGISTRY.counter(
    "fleet_placement_record_keys_total",
    "Keys of placement records journaled — rows, servers' demand and held "
    "conflict keys, set or dropped; a whole write counts every key")
_count_whole_write = _M_RECORD_WRITES.bind(form="whole")
_count_diff_write = _M_RECORD_WRITES.bind(form="diff")
_count_record_keys = _M_RECORD_KEYS.bind()

# a server is over its capacity only beyond this relative slack: the
# solver's own (solver/repair.py), so that what it calls feasible fits here
_CAP_RTOL = 1e-6

__all__ = ["PlacementService", "Reservation"]


@dataclass
class _Rows:
    """A placement row by row, for priority: which server each row is on,
    what it asks and how it ranks. All of it is the lowered problem's and
    the placement's own arrays, shared and never written; only `live` and
    `evicted` are this view's."""
    names: list[str]                # ProblemTensors.service_names
    nodes: list[str]                # ProblemTensors.node_names
    node_of: np.ndarray             # (S,) index into `nodes`
    demand: np.ndarray              # (S, R) f32
    priority: Optional[np.ndarray]  # (S,) i32, or None: every row ranks 0
    holds: dict[str, list[int]]     # conflict key -> rows (lower/tensors.py)
    floor: int                      # no row ranks below this
    # (S,) bool, the rows the commitment still books (a row of no demand
    # is an admission tombstone); built when priority first asks
    live: Optional[np.ndarray] = None
    # rows a higher stage's commit evicted, until `reinstate` or the next
    # commit of the stage
    evicted: Optional[np.ndarray] = None

    @classmethod
    def of(cls, pt: ProblemTensors, placement: Placement) -> "_Rows":
        return cls(names=pt.service_names, nodes=pt.node_names,
                   node_of=np.asarray(placement.raw), demand=pt.demand,
                   priority=pt.priority, holds=pt.holds,
                   floor=(0 if pt.priority is None
                          else int(pt.priority.min())))

    def below(self, p: int, claimed=()) -> np.ndarray:
        """(S,) bool: live rows ranking strictly below `p`, less the rows
        in the index arrays of `claimed`."""
        if self.live is None:
            self.live = np.asarray(self.demand).any(axis=1)
        m = (self.live.copy() if self.priority is None
             else self.live & (self.priority < p))
        for idx in claimed:
            m[idx] = False
        return m

    def by_node(self, rows) -> np.ndarray:
        """(len(nodes), R) f64: the demand of `rows` (a mask or an index
        array), summed by server."""
        at = self.node_of[rows]
        dem = np.asarray(self.demand)[rows].astype(np.float64)
        return np.stack([np.bincount(at, weights=dem[:, k],
                                     minlength=len(self.nodes))
                         for k in range(dem.shape[1])], axis=1)

    def onto(self, nodes: list[str]) -> Optional[np.ndarray]:
        """Index of each of this view's servers in `nodes` (-1 where it is
        not there); None when the two lists are the same, which two stages
        over one pool give."""
        if self.nodes is nodes or self.nodes == nodes:
            return None
        at = {n: j for j, n in enumerate(nodes)}
        return np.fromiter((at.get(n, -1) for n in self.nodes),
                           dtype=np.int64, count=len(self.nodes))


@dataclass
class Reservation:
    id: str
    stage_key: str                      # "{project}/{stage}"
    demand_by_node: dict[str, np.ndarray]   # node slug -> (R,) reserved demand
    assignment: dict[str, str]
    committed: bool = False
    # churn reservations hold a displaced stage's NEW nodes between the
    # burst re-solve and the redeploy that re-commits it, so an admission
    # landing in that window cannot double-book them; superseded by the
    # stage's next solve/commit/release (never committed themselves)
    churn: bool = False
    # conflict key -> servers on which a row of this placement holds it
    # (lower/tensors.py). It lives and dies with the reservation: commit
    # moves it to the committed book, release and supersession drop it.
    held_keys: dict[str, list[str]] = field(default_factory=dict)
    # row-level view, where the problem it was solved from is at hand (not
    # after a reload from the store: such a commitment is preemptible only
    # once `rehydrate` has lowered it again)
    rows: Optional[_Rows] = field(default=None, repr=False)
    # stage key -> {service: server} of that stage's commitment that the
    # commit of this reservation evicts (nothing leaves before it);
    # `victim_rows` says the same as (id of that commitment, indices into
    # its rows)
    victims: dict[str, dict[str, str]] = field(default_factory=dict)
    victim_rows: dict[str, tuple[str, np.ndarray]] = field(
        default_factory=dict, repr=False)


def _alloc_vector(s: Server) -> np.ndarray:
    """(R,) committed+reserved demand recorded on one server record: its
    row of `_booked_columns`."""
    return _booked_columns([s])[1][0]


def _by_slug(slugs: list[str], by_slug: dict[str, np.ndarray]) -> np.ndarray:
    """(len(slugs), R) float64: `by_slug`'s (R,) vector in the row of each
    slug that has one, zero elsewhere (a slug `by_slug` names and `slugs`
    lacks is dropped; one that `slugs` lists twice gets it twice)."""
    out = np.zeros((len(slugs), 3))
    if by_slug:
        hit = [i for i, slug in enumerate(slugs) if slug in by_slug]
        if hit:
            out[hit] = np.array([by_slug[slugs[i]] for i in hit])
    return out


def _moved_rows(pt: ProblemTensors, before: Placement,
                after: Placement) -> dict[str, str]:
    """{row: server} of `after` for the rows whose server differs from
    `before`'s — two placements of the one problem `pt`: one pass over the
    raw assignments, and names only for what moved. A row `after`'s public
    assignment lacks (a streaming tombstone) is not reported."""
    new = np.asarray(after.raw)
    moved = {pt.service_names[i]: pt.node_names[new[i]]
             for i in np.flatnonzero(new != np.asarray(before.raw)).tolist()}
    if len(after.assignment) != len(new):
        moved = {row: node for row, node in moved.items()
                 if row in after.assignment}
    return moved


def _demand_changed(prev: dict[str, np.ndarray], new: dict[str, np.ndarray]
                    ) -> tuple[list[str], np.ndarray]:
    """The slugs whose (R,) vector differs from `prev` to `new` (a slug one
    of them lacks counts as zero there), and `new - prev` in their rows."""
    slugs = list(set(prev) | set(new))
    d = _by_slug(slugs, new) - _by_slug(slugs, prev)
    changed = np.flatnonzero(d.any(axis=1))
    return [slugs[i] for i in changed.tolist()], d[changed]


def _row_changes(prev: "Reservation", r: "Reservation"
                 ) -> tuple[dict[str, str], list[str]]:
    """The rows of `r.assignment` that differ from `prev.assignment`, as
    {row: server} to set and [row] to drop. Where the two are placements of
    one problem's rows (the same `names` object, the same servers) and both
    show every row, it is one comparison of the two `node_of` arrays and
    names only for what moved; otherwise a dict difference."""
    a, b = prev.rows, r.rows
    if (a is not None and b is not None and a.names is b.names
            and len(prev.assignment) == len(r.assignment) == len(b.names)
            and (a.nodes is b.nodes or a.nodes == b.nodes)):
        n = len(b.names)
        moved = np.flatnonzero(a.node_of[:n] != b.node_of[:n])
        return {b.names[i]: b.nodes[j] for i, j
                in zip(moved.tolist(), b.node_of[moved].tolist())}, []
    old, new = prev.assignment, r.assignment
    return ({row: node for row, node in new.items() if old.get(row) != node},
            [row for row in old if row not in new])


@dataclass
class _RecordChange:
    """What a stage's commitment changed since its placement record was
    written from `basis`, as the writer knows it: rows to set and to drop,
    the servers whose demand may differ (each looked up in the commitment
    as it is now: set where it books something there, dropped where not)
    and the held keys the record holds."""
    basis: "Reservation"
    rows_set: dict[str, str]
    rows_drop: list[str]
    slugs: list[str]
    held: dict[str, list[str]]

    @classmethod
    def superseding(cls, prev: Optional["Reservation"], r: "Reservation",
                    slugs: Optional[list[str]] = None
                    ) -> Optional["_RecordChange"]:
        """`r` in place of `prev`; `slugs` are the servers whose demand the
        supersession found changed (`_demand_changed`), where it ran."""
        if prev is None:
            return None
        if slugs is None:
            slugs, _ = _demand_changed(prev.demand_by_node, r.demand_by_node)
        # a slug that enters or leaves at a zero vector changes no sum
        slugs = list(set(slugs).union(
            prev.demand_by_node.keys() ^ r.demand_by_node.keys()))
        return cls(prev, *_row_changes(prev, r), slugs, prev.held_keys)

    def patch(self, r: "Reservation") -> tuple[dict, dict, int]:
        """`Store.update_keys`'s `set_keys` and `drop_keys` that take the
        record from `basis` to `r`, and how many keys they name."""
        dem_set, dem_drop = {}, []
        for slug in self.slugs:
            d = r.demand_by_node.get(slug)
            if d is None:
                dem_drop.append(slug)
            else:
                dem_set[slug] = np.asarray(d, dtype=np.float64).tolist()
        held_set = {k: list(v) for k, v in r.held_keys.items()
                    if self.held.get(k) != v}
        held_drop = [k for k in self.held if k not in r.held_keys]
        set_keys = {"assignment": self.rows_set, "demand_by_node": dem_set,
                    "held_keys": held_set}
        drop_keys = {"assignment": self.rows_drop,
                     "demand_by_node": dem_drop, "held_keys": held_drop}
        n = sum(map(len, set_keys.values())) + sum(map(len,
                                                       drop_keys.values()))
        return ({k: v for k, v in set_keys.items() if v},
                {k: v for k, v in drop_keys.items() if v}, n)


def _node(s: Server) -> Node:
    """The node `lower_stage` sees for a server record: its name and the
    record's own labels, shared and only ever read. Nothing writes into a
    node's labels: `solve_stage`'s back-fill gives the node a new object, a
    store update gives the record one."""
    return Node(s.slug, s.labels)


class PlacementService:
    """Solves stages against live inventory and keeps the 2-phase book.

    Guarantee: a host port, an exclusive host volume, and an anti-affinity
    label declared to reach a stage, are held at most once per server over
    every committed and reserved placement the CP knows, not only inside
    the stage being solved. It holds on `solve_stage` (so on
    `placement.solve`, `deploy.execute`), on `rehydrate`, on the churn
    re-solve of `node_events` and on `admit_batch` (streaming admission:
    its docstring). A stage alone on its servers takes the same path with
    nothing held.

    Priority and preemption (`Service.priority`, default 0). A stage that
    fits nowhere may evict committed rows of other stages. What is kept:

    1. A row is evicted only by a stage whose rows ALL rank strictly above
       it, and only a committed row is ever a victim: an open reservation
       and a churn hold are not, nor a row another open reservation has
       already claimed. A batch of mixed priorities preempts as its lowest
       row (kube-scheduler decides pod by pod; this is the sound half).
    2. After the commit, per server, the cpu / memory / disk of everything
       that remains plus the arrivals is within capacity, counted over
       every stage the CP has placed.
    3. No needless victim per server: none on a server that took no
       arrival, and on one that did, putting any one victim back breaks
       capacity (all lower rows out, then reprieved highest priority
       first while the arrivals still fit: kube-scheduler's rule). A stage
       that fits without eviction evicts nothing and takes the path it
       took before priorities existed.
    4. The eviction is part of the acknowledged write: when `commit`
       returns True the victim stage's `placements` record has lost
       exactly the victims, the arriving stage's holds its assignment,
       every touched server's `allocated` is the sum of what remains, and
       all of it went through the store (journaled, replicated). `release`
       of the reservation leaves the book untouched. A commit whose
       victims' stage was committed anew since the solve returns False.
    5. Held conflict keys still bar: a key held by a preemptible row is
       not preempted for (kube-scheduler would evict the holder). A
       victim's keys are released with it.

    `solve_stage` (so `placement.solve`), `admit_batch` (streaming
    admission: its docstring) and `commit` hold these; `deploy.execute`
    refuses a placement that needs victims, the churn re-solve of
    `node_events` never preempts. A stage that admission streams is no
    victim (`_streamed`): its rows are the queue's book. A commitment
    reloaded from the store is no victim until `rehydrate` has lowered its
    stage again. Victims stay in their stage's retained problem as
    tombstones (no demand, masked from every view), so a later churn
    re-solve does not resurrect them; `reinstate` puts them back."""

    def __init__(self, store: Store, *, use_tpu: bool = False,
                 chains=None, steps: int = 128):
        self.store = store
        self.use_tpu = use_tpu
        self._sched_tpu = TpuSolverScheduler(chains=chains, steps=steps)
        self._sched_host = HostGreedyScheduler()
        self._lock = threading.Lock()
        self._reservations: dict[str, Reservation] = {}   # in-flight only
        self._committed: dict[str, Reservation] = {}      # stage_key -> last
        self._ids = itertools.count(1)
        self._last: dict[str, tuple[ProblemTensors, Placement]] = {}
        # stage key -> (the node names of its problem, ServerColumns.members,
        # the row of each name there): _server_rows
        self._node_rows: dict[str, tuple[list[str], int, np.ndarray]] = {}
        # streaming-admission tombstones (cp/admission.py): rows kept in
        # the problem at zero demand so the padded shape tier survives a
        # departure, but masked OUT of every public assignment view —
        # a departed service must never look placed to invariants,
        # dashboards, or deploy fan-out
        self._masked: dict[str, frozenset] = {}
        # stage key -> (the placement record this service last wrote, the
        # commitment it wrote it from): what a commit's record is written
        # by difference against (_persist_committed)
        self._recorded: dict[str, tuple[PlacementRecord, Reservation]] = {}
        # stage key -> (every other stage's placement that holds a key,
        # paired with its held-key map, as last read; what those hold):
        # _held_by_others_kept
        self._held_kept: dict[str, tuple[list, dict[str, list[str]]]] = {}
        # the stages admit_batch has solved: streaming admission keeps
        # their rows, so no stage's commit evicts any (_lower_ranking)
        self._streamed: set[str] = set()
        # the committed book explains servers.allocated: rebuild it from
        # the store's placements table so a restarted (or promoted
        # standby, docs/guide/13-cp-replication.md) CP's next commit
        # SUPERSEDES the old allocation instead of stacking on top of it
        self._load_committed()

    # ------------------------------------------------------------------
    # committed-book persistence (crash/failover-safe capacity ledger)
    # ------------------------------------------------------------------

    def _load_committed(self) -> None:
        for rec in self.store.list("placements"):
            self._committed[rec.stage_key] = Reservation(
                id=f"rsv_{next(self._ids)}", stage_key=rec.stage_key,
                demand_by_node={slug: np.asarray(d, dtype=np.float64)
                                for slug, d in rec.demand_by_node.items()},
                assignment=dict(rec.assignment), committed=True,
                held_keys={k: list(v) for k, v in rec.held_keys.items()})

    def _persist_committed(self, key: str,
                           change: Optional[_RecordChange] = None) -> None:
        """Mirror the stage's committed reservation into the store (one
        row per stage, journaled and replicated). Caller holds the lock.

        By difference where it can be: where the store's record is the one
        this service last wrote, from `change.basis`, only the keys that
        `change` names are journaled (`Store.update_keys`, one `mrg`
        entry). Whole (a `put`) where there is no record yet, where this
        service did not write it from that basis (a restart, a promoted
        standby), or where the difference names more than half of the
        record's keys: a put is no dearer then. Either way the record
        equals the commitment, as dicts."""
        r = self._committed.get(key)
        rec = self.store.find_one("placements",
                                  lambda p: p.stage_key == key)
        last = self._recorded.pop(key, None)
        if r is None:
            if rec is not None:
                self.store.delete("placements", rec.id)
            return
        size = len(r.assignment) + len(r.demand_by_node) + len(r.held_keys)
        if (change is not None and rec is not None and last is not None
                and last[0] is rec and last[1] is change.basis):
            set_keys, drop_keys, n = change.patch(r)
            if 2 * n <= size:
                self.store.update_keys("placements", rec.id,
                                       set_keys=set_keys, drop_keys=drop_keys)
                self._recorded[key] = (rec, r)
                _count_diff_write()
                _count_record_keys(n)
                return
        attrs = dict(
            assignment=dict(r.assignment),
            demand_by_node={slug: np.asarray(d, dtype=np.float64).tolist()
                            for slug, d in r.demand_by_node.items()},
            held_keys={k: list(v) for k, v in r.held_keys.items()})
        if rec is None:
            rec = self.store.create("placements",
                                    PlacementRecord(stage_key=key, **attrs))
        else:
            self.store.update("placements", rec.id, **attrs)
        self._recorded[key] = (rec, r)
        _count_whole_write()
        _count_record_keys(size)

    # ------------------------------------------------------------------
    # inventory lowering
    # ------------------------------------------------------------------

    def _inventory(self, tenant: str,
                   slugs: Optional[list[str]] = None,
                   exclude_demand: Optional[dict[str, np.ndarray]] = None,
                   preemptor: Optional[tuple[str, int]] = None,
                   ) -> tuple[list[Node], np.ndarray, np.ndarray,
                              Optional[np.ndarray]]:
        """Live nodes, what is free on each ((N, R) float64: capacity with
        reserved+committed demand subtracted, clamped at zero) and the
        validity mask.  `exclude_demand` (slug -> (R,)) is
        demand attributed to the CALLING stage itself (e.g. its own churn
        hold) — excluded BEFORE the zero-clamp, so a deficit against a
        shrunken node cannot turn into phantom free capacity the way a
        post-clamp add-back would.

        `preemptor` (stage key, the priority of its lowest row) asks, as
        the last value, for what committed rows of lower priority hold
        on each node beside that: (N, R), what the node's capacity would
        gain were they left out, before the clamp likewise; None where no
        row ranks lower."""
        # a tenant sees its own servers plus the shared "default" pool;
        # "default" solves never touch tenant-dedicated capacity
        view = self.store.server_columns()
        mine = view.tenant == "default"
        if isinstance(tenant, str):   # a request's is whatever JSON gave
            mine |= view.tenant == tenant
        if slugs:
            named = np.zeros(len(view), dtype=bool)
            named[view.holders(slugs)] = True
            mine &= named
        # the records in the order the store lists them: the solver
        # breaks its ties by a node's place in this order
        at = view.order[mine[view.order]]
        if not at.size:
            raise ValueError(f"no servers registered for tenant {tenant!r}")
        servers = [view.records[i] for i in at.tolist()]
        pre = None
        if preemptor is not None:
            with phase("cp.solve_stage.preemptible") as ph:
                pre = self._preemptible_by_node(
                    *preemptor, [s.slug for s in servers])
                if pre is not None:
                    holding = int(pre.any(axis=1).sum())
                    _M_PREEMPTIBLE_SERVERS.inc(holding)
                    ph.set(servers=holding)
        # free capacity before the clamp: what the caller calls its own
        # comes off what is spoken for first, so a deficit on a shrunken
        # node stays a deficit
        free = (view.capacity - (
            (view.booked + view.scatter(self._reserved_by_node()))
            - view.scatter(exclude_demand or {})))[at]
        clamped = np.maximum(free, 0.0)
        valid = view.schedulable[at]
        if pre is not None:
            # what each node would gain: the deficit of a shrunken node is
            # taken off it as it is off the node's own capacity
            pre = np.maximum(free + pre, 0.0) - clamped
        return [_node(s) for s in servers], clamped, valid, pre

    def _lower_ranking(self, key: str, p: int
                       ) -> list[tuple[Reservation, np.ndarray]]:
        """The commitments of stages other than `key` that have rows a
        stage of priority `p` may evict, each with the (S,) mask of those
        rows: committed, live, ranking strictly below `p`, and not yet
        claimed as a victim by an open reservation; none of a stage that
        admission streams. One truth test a commitment where no row ranks
        below `p`. Caller holds the lock."""
        out = []
        for c in self._committed.values():
            if (c.stage_key == key or c.rows is None or c.rows.floor >= p
                    or c.stage_key in self._streamed):
                continue
            claimed = [v[1] for r in self._reservations.values()
                       if (v := r.victim_rows.get(c.stage_key))
                       and v[0] == c.id]
            mask = c.rows.below(p, claimed)
            if mask.any():
                out.append((c, mask))
        return out

    def _lower_floor(self, key: str) -> Optional[int]:
        """The priority of the lowest row another stage has committed that
        could be a victim (none of a stage admission streams), or None
        where there is none: a stage whose rows all rank no higher need
        not ask what it may evict. Caller holds the lock."""
        return min((c.rows.floor for c in self._committed.values()
                    if c.rows is not None and c.stage_key != key
                    and c.stage_key not in self._streamed), default=None)

    def _preemptible_by_node(self, key: str, p: int,
                             slugs: list[str]) -> Optional[np.ndarray]:
        """(len(slugs), R) f64: what rows that stage `key`, of priority
        `p`, may evict hold on each server — an array pass over each
        commitment that has such rows. None where none has."""
        pre = None
        for c, mask in self._lower_ranking(key, p):
            held = c.rows.by_node(mask)
            at = c.rows.onto(slugs)
            if pre is None:
                pre = np.zeros((len(slugs), held.shape[1]))
            if at is None:
                pre += held
            else:
                np.add.at(pre, at[at >= 0], held[at >= 0])
        return pre

    def _reserved_by_node(self) -> dict[str, np.ndarray]:
        out: dict[str, np.ndarray] = {}
        for r in self._reservations.values():
            if r.committed:
                continue
            for node, dem in r.demand_by_node.items():
                out[node] = out.get(node, 0) + dem
        return out

    @contextlib.contextmanager
    def _locked(self):
        """`self._lock`, with the wait for it written as the phase
        `cp.wait.placement_lock`: entered after the caller's own phase
        (`with phase("cp.commit"), self._locked():`), so the wait is that
        phase's child and not its self time."""
        with phase("cp.wait.placement_lock"):
            self._lock.acquire()
        try:
            yield
        finally:
            self._lock.release()

    def _held_by_others(self, key: str) -> dict[str, list[str]]:
        """Conflict key -> servers on which a stage OTHER than `key` holds
        it, over the committed book and every open reservation (churn
        holds included: a displaced stage holds its keys on its old
        servers and on its new ones until its redeploy commits). The
        stage's own holdings are left out: its rows are the ones being
        re-placed. Caller holds the lock."""
        out: dict[str, list[str]] = {}
        for r in itertools.chain(self._committed.values(),
                                 self._reservations.values()):
            if r.stage_key == key:
                continue
            for k, slugs in r.held_keys.items():
                have = out.get(k)
                out[k] = slugs if have is None else have + slugs
        return out

    def _held_by_others_kept(self, key: str) -> dict[str, list[str]]:
        """`_held_by_others`, kept with the stage and read again only
        once another stage's placement that holds a key was committed,
        reserved, changed or dropped since the last read: the same
        object while nothing did. A held-key map is replaced, never
        written in place, so the check is by identity. Caller holds the
        lock."""
        holders = [(r, r.held_keys) for r in itertools.chain(
            self._committed.values(), self._reservations.values())
            if r.stage_key != key and r.held_keys]
        kept = self._held_kept.get(key)
        if (kept is not None and len(kept[0]) == len(holders)
                and all(a is c and b is d
                        for (a, b), (c, d) in zip(kept[0], holders))):
            return kept[1]
        held = self._held_by_others(key)
        self._held_kept[key] = (holders, held)
        return held

    def _bar_admitted(self, key: str, pt: ProblemTensors, delta
                      ) -> tuple[ProblemTensors, object]:
        """`admit_batch`'s candidate barred from the servers on which
        another stage holds a key that its rows are barred by: the rows
        the delta brings in (arrivals) — or, where what other stages hold
        changed since `pt` was barred (`pt.held`), every barred row, the
        rows whose bits moved joining the delta. The `cp.admit_batch.held`
        phase. Returns (pt, delta): pt a copy where a bit was cleared."""
        with phase("cp.admit_batch.held", stage=key) as ph:
            held = self._held_by_others_kept(key)
            if held is not pt.held and held == pt.held:
                pt = _dc_replace(pt, held=held)     # the same, read anew
            every = held is not pt.held
            new = (np.asarray(delta.eligible_rows[0], dtype=np.int64)
                   if delta is not None and delta.eligible_rows is not None
                   else np.empty(0, dtype=np.int64))
            barring = {k: (rows if every else
                           np.intersect1d(rows, new, assume_unique=True))
                       for k, rows in pt.barred_by.items() if k in held}
            barring = {k: rows for k, rows in barring.items() if len(rows)}
            if not barring:
                return (_dc_replace(pt, held=held) if every else pt), delta
            touched = np.unique(np.concatenate(
                [np.asarray(r, dtype=np.int64) for r in barring.values()]))
            eligible = pt.eligible
            standing = self._last.get(key)
            if every or (standing is not None
                         and standing[0].eligible is eligible):
                eligible = eligible.copy()
            before = eligible[touched] if every else None
            pairs = sum(map(len, held.values()))
            _M_HELD_KEYS.inc(pairs)
            cleared = bar_held(eligible, barring, pt.node_names, held)
            ph.set(keys=len(barring), rows=int(touched.size), pairs=pairs,
                   cleared=cleared)
            pt = _dc_replace(pt, eligible=eligible, held=held)
            if delta is not None:
                rows = new
                if every:
                    moved = touched[(eligible[touched] != before).any(axis=1)]
                    rows = np.union1d(new, moved)
                if rows.size:
                    delta.eligible_rows = (rows.astype(np.int32),
                                           eligible[rows])
            return pt, delta

    def _held_for_lowering(self, key: str) -> dict[str, list[str]]:
        """`_held_by_others` as the `cp.solve_stage.held` phase."""
        with phase("cp.solve_stage.held") as ph:
            held = self._held_by_others(key)
            if held:
                pairs = sum(map(len, held.values()))
                _M_HELD_KEYS.inc(pairs)
                ph.set(keys=len(held), pairs=pairs,
                       servers=len(set().union(*held.values())))
        return held

    # ------------------------------------------------------------------
    # solve + 2-phase reservation
    # ------------------------------------------------------------------

    def _apply_mask(self, key: str, placement: Placement) -> Placement:
        """Filter a stage's tombstoned (departed-but-row-retained) service
        names out of the public assignment. raw stays full-length — the
        solver's exact checker verifies every row, tombstones included."""
        mask = self._masked.get(key)
        if not mask:
            return placement
        return _dc_replace(placement, assignment={
            n: node for n, node in placement.assignment.items()
            if n not in mask})

    def solve_stage(self, flow: Flow, stage_name: str, *,
                    tenant: str = "default",
                    reserve: bool = True) -> tuple[Placement, Optional[str]]:
        """Lower the stage against live inventory and solve; optionally open
        a reservation. Returns (placement, reservation_id)."""
        stage = flow.stage(stage_name)
        key = f"{flow.name}/{stage_name}"
        with phase("cp.solve_stage", stage=key), self._locked():
            with phase("cp.solve_stage.inventory"):
                # a full re-lower rebuilds the stage from the flow, which the
                # admission controller keeps tombstone-free
                self._masked.pop(key, None)
                # This stage's own churn hold is the placement this solve
                # supersedes, so it must not count against itself — but the
                # hold is only RELEASED when a real reservation replaces it
                # (_reserve): a reserve=False preview or an infeasible solve
                # must leave the double-book protection standing.
                own_churn: dict[str, np.ndarray] = {}
                for r in self._reservations.values():
                    if r.churn and r.stage_key == key:
                        for slug, d in r.demand_by_node.items():
                            own_churn[slug] = own_churn.get(slug, 0) + d
                # only a stage that ranks above some committed row of
                # another pays for its own priority: its lowest row's
                preemptor = None
                floor = self._lower_floor(key)
                if floor is not None:
                    p = min((s.priority
                             for s in stage.resolved_services(flow)),
                            default=0)
                    if p > floor:
                        preemptor = (key, p)
                nodes, free, valid, preemptible = self._inventory(
                    tenant, stage.servers or None,
                    exclude_demand=own_churn, preemptor=preemptor)
                # Config-declared labels back-fill: agents register slug +
                # capacity only, so live store records usually carry NO labels,
                # and a blank label passes every gate (_server_matches treats
                # tier=None as match-any, tensors.py) — a tier-gated stage
                # could silently place services on a declared-off-tier node
                # (found by the full-stack smoke: api landed on the standard
                # node).  Fill per FIELD: only fields the server API has not
                # set inherit the flow's declaration; API-set fields win.
                # A flow that declares no server has nothing to fill.
                for n in (nodes if flow.servers else ()):
                    decl = flow.servers.get(n.name)
                    if decl is None:
                        continue
                    d, got = decl.labels, n.labels
                    n.labels = ServerLabels(
                        tier=got.tier if got.tier is not None else d.tier,
                        region=got.region if got.region is not None else d.region,
                        clazz=got.clazz if got.clazz is not None else d.clazz,
                        arch=got.arch if got.arch is not None else d.arch,
                        extra={**d.extra, **got.extra})
                held = self._held_for_lowering(key)
            with phase("cp.solve_stage.lower"):
                pt = lower_stage(flow, stage_name, nodes=nodes, held=held,
                                 capacity=free, valid=valid)
                if preemptible is not None and not self._fits_free(pt):
                    # a row that fits on no server as it is: the stage
                    # fits nowhere, whatever the solver would say
                    pt = with_preemptible(pt, preemptible)
            with phase("cp.solve_stage.solve"):
                placement = self._solve_lowered(key, pt)
                if (not placement.feasible and preemptible is not None
                        and pt.preemptible is None):
                    # it does not fit in what is free: with what lower
                    # ranks hold, then
                    pt = with_preemptible(pt, preemptible)
                    placement = self._solve_lowered(key, pt)
            victims = None
            if pt.preemptible is not None and placement.feasible:
                with phase("cp.solve_stage.victims") as ph:
                    victims = self._select_victims(key, pt, placement,
                                                   preemptor[1])
                    ph.set(victims=sum(len(idx) for _c, idx in victims))
            with phase("cp.solve_stage.reserve"):
                self._last[key] = (pt, placement)
                rid = None
                if reserve and placement.feasible:
                    rid = self._reserve(key, pt, placement, victims)
        return placement, rid

    def _solve_lowered(self, key: str, pt: ProblemTensors) -> Placement:
        """The solve of `solve_stage`: the device annealer, warm where the
        stage's last problem had this shape, or the host scheduler, each
        with the declared relaxation ladder behind it."""
        if not self.use_tpu:
            return place_with_fallback(self._sched_host, pt)[0]
        prev = self._last.get(key)
        warm = (prev is not None
                and prev[0].S == pt.S and prev[0].N == pt.N)
        placement = self._sched_tpu.place(pt, warm_start=warm, stage=key)
        if not placement.feasible and pt.relax_order:
            placement, _ = place_with_fallback(
                self._sched_tpu, pt, initial=placement,
                place_kwargs={"stage": key})
        return placement

    @staticmethod
    def _fits_free(pt: ProblemTensors) -> bool:
        """Whether every row of `pt` fits, alone, on some valid server:
        necessary for the stage to fit as lowered, and cheap (the distinct
        demand rows against the servers)."""
        shapes = np.unique(pt.demand, axis=0)
        room = pt.capacity[pt.node_valid] * (1 + _CAP_RTOL)
        return bool((shapes[:, None, :] <= room[None]).all(axis=2)
                    .any(axis=1).all())

    def _select_victims(self, key: str, pt: ProblemTensors,
                        placement: Placement, p: int
                        ) -> list[tuple[Reservation, np.ndarray]]:
        """Which committed rows the placement of stage `key` (priority
        `p`, lowered with `pt.preemptible`) has to evict: [(commitment,
        indices into its rows)]. Per server whose arrivals overflow what
        is truly free there: every lower-ranking row is a candidate, and
        candidates are reprieved, highest priority first, while the
        arrivals and those already reprieved still fit (kube-scheduler's
        rule), so that putting any one victim back breaks capacity. The
        servers are worked together, one candidate of each a round: array
        passes, as many as the fullest server has candidates."""
        room = np.asarray(pt.capacity, dtype=np.float64)
        load = np.zeros_like(room)
        np.add.at(load, np.asarray(placement.raw),
                  np.asarray(pt.demand, dtype=np.float64))
        free = room - pt.preemptible
        over = (load > free * (1 + _CAP_RTOL)).any(axis=1)
        if not over.any():
            return []
        found, node, prio, dem = [], [], [], []
        for c, mask in self._lower_ranking(key, p):
            at = c.rows.onto(pt.node_names)
            on = c.rows.node_of if at is None else at[c.rows.node_of]
            mask &= (on >= 0) & over[on]
            rows = np.flatnonzero(mask)
            if not rows.size:
                continue
            found.append((c, rows))
            node.append(on[rows])
            prio.append(np.zeros(rows.size, dtype=np.int64)
                        if c.rows.priority is None
                        else c.rows.priority[rows].astype(np.int64))
            dem.append(np.asarray(c.rows.demand)[rows].astype(np.float64))
        if not found:
            return []
        node, prio, dem = map(np.concatenate, (node, prio, dem))
        # by server, highest priority first, then in the book's order
        order = np.lexsort((np.arange(node.size), -prio, node))
        sorted_node = node[order]
        first = np.flatnonzero(np.r_[True, sorted_node[1:]
                                     != sorted_node[:-1]])
        rank = np.arange(node.size) - np.repeat(
            first, np.diff(np.r_[first, node.size]))
        kept = np.zeros_like(room)
        evict = np.zeros(node.size, dtype=bool)
        for k in range(int(rank.max()) + 1):
            sel = order[rank == k]          # one candidate of each server
            n, d = node[sel], dem[sel]
            fits = (load[n] + kept[n] + d
                    <= room[n] * (1 + _CAP_RTOL)).all(axis=1)
            kept[n[fits]] += d[fits]
            evict[sel[~fits]] = True
        out, lo = [], 0
        for c, rows in found:
            idx = rows[evict[lo:lo + rows.size]]
            lo += rows.size
            if idx.size:
                out.append((c, idx))
        return out

    def rehydrate(self, stage_key: str, flow: Flow,
                  tenant: str = "default") -> bool:
        """Failover/restart recovery: rebuild the stage's retained
        (problem, placement) entry by ADOPTING its committed assignment
        from the store's placements table — never by re-solving, which
        could silently diverge from what the fleet is actually running.
        Without this, a promoted standby's empty placement book would
        make every future churn re-solve skip the stage entirely
        (node_events only moves stages it has retained problems for).
        Returns False when there is nothing to adopt or the config has
        drifted past the record (the stage's next real solve rebuilds)."""
        rec = self.store.find_one("placements",
                                  lambda p: p.stage_key == stage_key)
        if rec is None:
            return False
        stage_name = stage_key.split("/", 1)[1]
        with self._lock:
            if stage_key in self._last:
                return True
            self._masked.pop(stage_key, None)   # flow carries no tombstones
            committed = self._committed.get(stage_key)
            # the committed demand is the stage's OWN load: exclude it
            # from inventory like solve_stage excludes its churn hold,
            # or the adopted placement double-counts itself
            exclude = dict(committed.demand_by_node) if committed else None
            nodes, free, valid, _ = self._inventory(
                tenant, flow.stage(stage_name).servers or None,
                exclude_demand=exclude)
            pt = lower_stage(flow, stage_name, nodes=nodes,
                             held=self._held_for_lowering(stage_key),
                             capacity=free, valid=valid)
            node_idx = {n: i for i, n in enumerate(pt.node_names)}
            raw = np.zeros(pt.S, dtype=np.int64)
            for i, row in enumerate(pt.service_names):
                idx = node_idx.get(rec.assignment.get(row, ""), -1)
                if idx < 0:
                    return False   # drifted config/inventory: solve anew
                raw[i] = idx
            # the adopted rows prove their nodes were valid AT SOLVE
            # TIME: mark them valid in the retained problem even if the
            # node is offline in today's inventory, so the failure
            # detector's verdict flip registers as a CHANGE and triggers
            # the re-solve that moves the stage off the dead node
            pt.node_valid = pt.node_valid.copy()
            pt.node_valid[np.unique(raw)] = True
            adopted = Placement(
                assignment=dict(rec.assignment),
                levels=level_schedule(pt), feasible=True,
                source="rehydrated", raw=raw)
            self._last[stage_key] = (pt, adopted)
            if committed is not None and committed.rows is None:
                # lowered again, the reloaded commitment knows its rows
                committed.rows = _Rows.of(pt, adopted)
        log.info("placement rehydrated %s", kv(stage=stage_key,
                                               rows=pt.S))
        return True

    def open_empty(self, flow: Flow, stage_name: str, *,
                   tenant: str = "default") -> ProblemTensors:
        """A stage with no service yet, lowered against live inventory to
        no row (`lower_stage(empty=True)`): what streaming admission folds
        the stage's first arrivals into (cp/admission.py). Nothing is
        solved, reserved or retained."""
        key = f"{flow.name}/{stage_name}"
        with self._locked():
            nodes, free, valid, _ = self._inventory(
                tenant, flow.stage(stage_name).servers or None)
            return lower_stage(flow, stage_name, nodes=nodes,
                               held=self._held_for_lowering(key),
                               capacity=free, valid=valid, empty=True)

    def admit_batch(self, stage_key: str, pt: ProblemTensors, delta=None,
                    *, tenant: str = "default", masked=None,
                    ) -> tuple[Placement, Optional[str], ProblemTensors]:
        """Streaming-admission micro-solve (cp/admission.py): solve a
        pre-built candidate problem — the stage's streaming pt with this
        batch's arrivals scattered in and departures tombstoned — warm
        through the resident delta path, and open a reservation for the
        whole batch. The candidate arrives in the delta shape
        (dataclasses.replace sharing every untouched tensor), so steady
        in-tier drift reuses ONE compiled executable and never crosses the
        host boundary.

        Unlike solve_stage, the stage's OWN standing demand (committed +
        in-flight) is excluded from capacity — its services are the ones
        being re-placed, and a stream that saw itself as load would choke
        on its own success. Returns (placement, reservation_id, pt_used);
        on an infeasible solve the retained (pt, placement) entry is left
        standing (the stage IS still feasibly placed without the batch)
        and reservation_id is None.

        The cross-stage guarantee of the class docstring holds for the
        candidate too, though cp/admission.py built it and nothing here
        lowers it: the rows the delta brings in are barred from the
        servers on which another stage holds a key they are barred by,
        and where what other stages hold has changed since the stage was
        last barred, every row of it is (`_bar_admitted`). A stage that
        declares no key pays one truth test. The reservation records
        what the stage's rows hold, arrivals included, so a solve of
        another stage is barred by them in turn.

        An arrival preempts under the class docstring's rules, as a stage
        solved whole does. Where the candidate's lowest live row ranks
        above some other stage's committed row (`_admit_priority`: one
        comparison where none does), what the rows it may evict hold is
        added to its capacity and priced (the phase
        `cp.admit_batch.preemptible`; lower/tensors.py `with_price`: the
        delta carries it and the merge prices on device). Solved once:
        where an arrival
        fits on no server it may use beside the stage's rows that stand
        (`_arrivals_fit`), with that capacity at once; else first against
        what is free, at a price of nothing, and only if that fails again
        with it, as solve_stage does. The victims are selected after the
        solve (`cp.admit_batch.victims`) and the reservation claims them,
        so its commit evicts them. A stage once priced stays priced (at
        nothing where it may evict nothing): its staging keeps one plane.
        Victims are never rows of a stage that admission streams."""
        with self._lock:
            self._streamed.add(stage_key)
            with phase("cp.admit_batch.refresh", stage=stage_key):
                view, row = self._server_rows(stage_key, pt)
                # a node no server carries keeps the bit it has
                known = row >= 0
                valid = np.array(pt.node_valid, dtype=bool)
                valid[known] = view.schedulable[row[known]]
                if not np.array_equal(valid, pt.node_valid):
                    pt = _dc_replace(pt, node_valid=valid)
                free = self._live_free(pt, stage_key, on=(view, row))
                pt = self._clamped(pt, free)
            if pt.barred_by:
                pt, delta = self._bar_admitted(stage_key, pt, delta)
            standing = self._last.get(stage_key)
            p = self._admit_priority(stage_key, pt)
            pre, evicts, cand = None, False, pt
            if p is not None:
                with phase("cp.admit_batch.preemptible") as ph:
                    pre = self._preemptible_by_node(stage_key, p,
                                                    pt.node_names)
                    if pre is not None:
                        holding = int(pre.any(axis=1).sum())
                        _M_PREEMPTIBLE_SERVERS.inc(holding)
                        ph.set(servers=holding)
                        # what each node would gain: a deficit is taken
                        # off first, as _inventory takes it
                        pre = np.maximum(free + pre, 0.0) - pt.capacity
                        evicts = not self._arrivals_fit(pt, delta, standing)
                        cand = with_price(pt, pre if evicts
                                          else np.zeros_like(pre))
            if pre is None and pt.priced:
                cand = with_price(pt, np.zeros_like(pt.capacity))
            new = self._admit_solve(stage_key, cand, delta)
            if not new.feasible and pre is not None and not evicts:
                # it does not fit in what is free: with what lower ranks
                # hold, then, from what stands
                self._restore(stage_key, standing)
                evicts = True
                cand = with_price(pt, pre)
                new = self._admit_solve(stage_key, cand, delta)
            if not new.feasible:
                # the candidate is dropped: the scheduler's resident state
                # goes back to what stands
                self._restore(stage_key, standing)
                return self._apply_mask(stage_key, new), None, cand
            victims = None
            if evicts:
                with phase("cp.admit_batch.victims") as ph:
                    victims = self._select_victims(stage_key, cand, new, p)
                    ph.set(victims=sum(len(idx) for _c, idx in victims))
            self._masked[stage_key] = frozenset(masked or ())
            new = self._apply_mask(stage_key, new)
            self._last[stage_key] = (cand, new)
            rid = self._reserve(stage_key, cand, new, victims)
        return new, rid, cand

    def _admit_priority(self, key: str, pt: ProblemTensors
                        ) -> Optional[int]:
        """For `admit_batch`: the priority of the candidate's lowest live
        row, where it ranks above some other stage's committed row that
        could be a victim; else None — one comparison where no such row
        ranks below any. None too for a stage that scores nodes itself: a
        price rides no plane of its own (lower/tensors.py `with_price`)."""
        floor = self._lower_floor(key)
        if floor is None or (pt.preferred is not None and not pt.priced):
            return None
        if pt.priority is None:
            p = 0
        else:
            live = np.asarray(pt.demand).any(axis=1)
            if not live.any():
                return None
            p = int(pt.priority[live].min())
        return p if p > floor else None

    @staticmethod
    def _arrivals_fit(pt: ProblemTensors, delta, standing) -> bool:
        """Whether each arrival of the candidate fits, alone, on some valid
        server it is eligible for beside the stage's rows that stand where
        they are: `solve_stage`'s `_fits_free`, but sized with the stage's
        own rows counted, which the candidate's capacity leaves out. The
        arrivals are the rows the delta gives demand; without a delta or a
        standing placement every live row is one."""
        demand = np.asarray(pt.demand, dtype=np.float64)
        arriving = demand.any(axis=1)
        room = np.asarray(pt.capacity, dtype=np.float64)
        raw = None if standing is None else standing[1].raw
        if delta is not None and delta.demand_rows is not None \
                and raw is not None:
            stay = arriving.copy()
            fresh = np.zeros(pt.S, dtype=bool)
            fresh[np.asarray(delta.demand_rows[0], dtype=np.intp)] = True
            arriving &= fresh
            stay &= ~fresh
            n = min(len(raw), pt.S)
            stay[n:] = False
            at = np.asarray(raw, dtype=np.intp)[:n][stay[:n]]
            load = np.stack([np.bincount(at, weights=col[:n][stay[:n]],
                                         minlength=pt.N)
                             for col in demand.T], axis=1)
            room = room - load
        rows = np.flatnonzero(arriving)
        if not rows.size:
            return True
        shapes, shape_of = np.unique(demand[rows], axis=0,
                                     return_inverse=True)
        fits = ((shapes[:, None, :] <= room[None] * (1 + _CAP_RTOL))
                .all(axis=2) & np.asarray(pt.node_valid, dtype=bool)[None])
        return bool((fits[shape_of.reshape(-1)]
                     & np.asarray(pt.eligible, dtype=bool)[rows])
                    .any(axis=1).all())

    def _admit_solve(self, stage_key: str, pt: ProblemTensors,
                     delta) -> Placement:
        """The solve of `admit_batch`: the resident delta path (the delta
        made coherent with the candidate it rides), or the host scheduler;
        the greedy host fallback where the device fails, and the declared
        relaxation ladder behind either."""
        if delta is not None:
            # the delta always re-ships the small planes; keep them
            # coherent with the refreshed candidate
            delta.node_valid = pt.node_valid
            delta.capacity = pt.capacity
            delta.preemptible = pt.preemptible
        degraded = False
        try:
            if self.use_tpu:
                new = self._sched_tpu.reschedule(pt, delta=delta,
                                                 stage=stage_key)
            else:
                new = self._sched_host.place(pt)
        except Exception as e:
            # same degradation contract as node_events: an admission
            # micro-solve must cost quality, not liveness
            _M_CHURN_FALLBACKS.inc()
            degraded = True
            log.error("admission solve failed; greedy host fallback %s",
                      kv(stage=stage_key, error=e))
            new = self._sched_host.place(pt)
        if not new.feasible and pt.relax_order:
            sched = (self._sched_host if degraded or not self.use_tpu
                     else self._sched_tpu)
            new, _ = place_with_fallback(
                sched, pt, initial=new,
                place_kwargs=({"stage": stage_key}
                              if sched is self._sched_tpu else None))
        return new

    def _restore(self, stage_key: str, standing) -> None:
        """A candidate of `admit_batch` was not adopted: the scheduler's
        resident state goes back to `standing`, the stage's retained
        (problem, placement), where it has one."""
        if (self.use_tpu and standing is not None
                and standing[1].raw is not None):
            self._sched_tpu.restore(stage_key, standing[0], standing[1].raw)

    @staticmethod
    def _demand_by_node(pt: ProblemTensors,
                        placement: Placement) -> dict[str, np.ndarray]:
        """Server slug -> (R,) float64 demand of `placement`'s rows on it,
        in one array pass. What the row loop it replaces gave, and callers
        lean on: a node enters at its first live row (_apply_allocation
        writes the store in this order), rows add up in row order (so the
        sums are the loop's to the last bit), and a node that carries
        only zero-demand rows (admission tombstones) has no entry. The
        values are rows of one array: consumers build new arrays from
        them and never write in place.

        No sort of the rows: a bincount per resource sums the live rows
        by server (float64, in row order, as the loop adds them), and
        each server's first live row, taken by `minimum.at`, orders the
        at most N servers present."""
        names = pt.node_names
        n = len(names)
        cols = np.asarray(pt.demand).T.astype(np.float64, order="C")
        live = np.flatnonzero((cols != 0).any(axis=0))
        at = np.asarray(placement.raw, dtype=np.intp)[live]
        acc = np.empty((n, cols.shape[0]), dtype=np.float64)
        for k, col in enumerate(cols):
            acc[:, k] = np.bincount(at, weights=col[live], minlength=n)
        first = np.full(n, live.shape[0], dtype=np.intp)
        np.minimum.at(first, at, np.arange(live.shape[0]))
        present = np.flatnonzero(first < live.shape[0])
        order = present[np.argsort(first[present])]
        return dict(zip([names[j] for j in order.tolist()], acc[order]))

    @staticmethod
    def _held_keys(pt: ProblemTensors,
                   placement: Placement) -> dict[str, list[str]]:
        """Conflict key -> servers on which a row of `placement` holds it.
        A stage that declares no key (no host port, exclusive volume or
        anti-affinity label) pays one truth test."""
        if not pt.holds:
            return {}
        raw = np.asarray(placement.raw)
        names = pt.node_names
        return {k: sorted({names[j] for j in raw[rows].tolist()})
                for k, rows in pt.holds.items()}

    def _drop_churn(self, key: str) -> None:
        """A stage's newly-created reservation (_reserve), a fresh
        commitment, or its teardown supersedes any churn reservation still
        holding its displaced placement.  Preview solves do NOT drop it —
        they add it back to their own inventory instead (solve_stage)."""
        for rid, r in list(self._reservations.items()):
            if r.churn and r.stage_key == key:
                del self._reservations[rid]

    def _reserve(self, key: str, pt: ProblemTensors, placement: Placement,
                 victims: Optional[list] = None) -> str:
        """`victims` is `_select_victims`'s answer: the reservation claims
        them, and its commit evicts them."""
        self._drop_churn(key)
        rid = f"rsv_{next(self._ids)}"
        r = self._reservations[rid] = Reservation(
            id=rid, stage_key=key,
            demand_by_node=self._demand_by_node(pt, placement),
            assignment=dict(placement.assignment),
            held_keys=self._held_keys(pt, placement),
            rows=_Rows.of(pt, placement))
        for c, idx in victims or ():
            r.victim_rows[c.stage_key] = (c.id, idx)
            r.victims[c.stage_key] = {
                c.rows.names[i]: c.rows.nodes[j]
                for i, j in zip(idx.tolist(), c.rows.node_of[idx].tolist())}
        return rid

    def victims(self, rid: str) -> list[dict]:
        """[{stage, service, server}]: the rows the commit of reservation
        `rid` evicts, or evicted if it is committed already. Empty for a
        placement that fits without eviction, and for an id nobody has."""
        with self._lock:
            r = self._reservations.get(rid) or next(
                (c for c in self._committed.values() if c.id == rid), None)
            if r is None:
                return []
            return [{"stage": vkey, "service": name, "server": slug}
                    for vkey, on in r.victims.items()
                    for name, slug in on.items()]

    def _write_allocations(self, slugs, vectors) -> int:
        """Add row i of `vectors` ((n, 3): cpu, memory, disk) to server
        `slugs[i]`'s `allocated`, clamped at 0, in ONE
        Store.book_allocated: the new `allocated` of every server goes to
        the journal and to the replication sink in one `upd` entry, under
        the store lock, before this returns. Returns the server records
        written; a slug the store no longer has is skipped."""
        return self.store.book_allocated(slugs, vectors)

    def _apply_allocation(self, r: Reservation, sign: float) -> int:
        """Add (`sign` +1) or return (-1) the whole of `r` on every node
        that carries demand of it. Returns the server records written."""
        demand = r.demand_by_node
        return self._write_allocations(
            list(demand),
            sign * np.array(list(demand.values()), dtype=np.float64))

    def _apply_allocation_delta(self, prev: Reservation,
                                new: Reservation) -> tuple[int, list[str]]:
        """Supersede `prev` by `new` touching only the nodes whose demand
        actually CHANGED; returns the server records written and the slugs
        of those nodes (`_demand_changed`). BOTH commit
        paths supersede this way: commit() (a redeploy; a streaming
        micro-solve commit per drain tick, cp/admission.py) and
        commit_retained() (the reconverger's commit after churn). Such a
        commit moves a batch's or one dead server's worth of nodes, and
        a lookup, a write and a journaled record for every server of a
        10k-service stage cost more than the solve.

        Every server's `allocated` ends where apply(prev, -1) +
        apply(new, +1) would leave it, to floating-point rounding (the
        clamp at 0 acts on the same quantity wherever the book is
        consistent: the server still holds what `prev` booked on it), and
        every changed record goes through Store.book_allocated, in one
        journal entry. A record whose value does not change is not
        rewritten — the same state, with one visible consequence: a
        server the commit does not touch does not have `updated_at`
        bumped by it. cp/autoscaler.py ages an OFFLINE server by
        max(last_heartbeat, updated_at): a dead server is written once,
        by the commit that moves its rows away, then left alone, which
        is what the reaper's clock wants."""
        slugs, d = _demand_changed(prev.demand_by_node, new.demand_by_node)
        return self._write_allocations(slugs, d), slugs

    def _supersede_allocation(self, prev: Optional[Reservation],
                              r: Reservation,
                              returned: Optional[dict] = None
                              ) -> Optional[list[str]]:
        """The `cp.commit.apply_allocation` phase of both commit paths:
        book `r` on the servers in place of the stage's previous
        commitment, if it has one. `returned` (slug -> (R,)) is what the
        victims of `r` gave up (`_evict`): it is returned in the same
        pass, by difference, so a server that loses victims and takes
        arrivals is written once. The phase's `records` field is the
        number of server records written. Returns the slugs on which the
        stage's own demand changed, where the pass found them (a
        supersession without victims), for the placement record's
        difference; None otherwise."""
        with phase("cp.commit.apply_allocation") as ph:
            if returned:
                gone = dict(returned)
                if prev is not None:
                    for slug, d in prev.demand_by_node.items():
                        gone[slug] = gone.get(slug, 0) + d
                prev = Reservation(id="", stage_key=r.stage_key,
                                   demand_by_node=gone, assignment={})
            changed = None
            if prev is None:
                written = self._apply_allocation(r, +1.0)
            else:
                written, changed = self._apply_allocation_delta(prev, r)
            ph.set(records=written)
        return None if returned else changed

    def commit(self, rid: str) -> bool:
        """Deploy succeeded: move reserved -> committed on the servers
        (2-phase step 2, model.rs:421-427). A redeploy of the same stage
        SUPERSEDES its previous commit — the old containers were stopped and
        replaced, so their allocation is returned first."""
        with phase("cp.commit"), self._locked():
            r = self._reservations.pop(rid, None)
            if r is None or r.committed:
                return False
            if any(getattr(self._committed.get(vkey), "id", None) != cid
                   for vkey, (cid, _idx) in r.victim_rows.items()):
                # a victim's stage was committed anew since the solve: the
                # rows this placement counted on are not there to evict
                return False
            prev = self._committed.pop(r.stage_key, None)
            returned = self._evict(r) if r.victim_rows else None
            changed = self._supersede_allocation(prev, r, returned)
            r.committed = True
            self._committed[r.stage_key] = r
            self._drop_churn(r.stage_key)   # commitment reflects reality now
            with phase("cp.commit.persist"):
                self._persist_committed(r.stage_key, _RecordChange.superseding(
                    prev, r, changed))
            return True

    def _evict(self, r: Reservation) -> dict[str, np.ndarray]:
        """The `cp.commit.evict` phase: take the victims of `r` out of
        their stages' commitments (assignment, demand by server, held
        keys), tombstone them in those stages' retained problems and
        persist those placement records, by difference: the victims
        dropped, the servers they were on written. Returns slug -> (R,),
        what the victims booked: the caller returns it to the servers."""
        returned: dict[str, np.ndarray] = {}
        with phase("cp.commit.evict") as ph:
            for vkey, (_cid, idx) in r.victim_rows.items():
                c = self._committed[vkey]
                held = c.held_keys
                rows = c.rows
                rows.live[idx] = False
                rows.evicted = (idx if rows.evicted is None
                                else np.concatenate([rows.evicted, idx]))
                gone = rows.by_node(idx)
                left = np.bincount(rows.node_of[rows.live],
                                   minlength=len(rows.nodes))
                at = np.unique(rows.node_of[idx]).tolist()
                slugs = [rows.nodes[j] for j in at]
                for j, slug in zip(at, slugs):
                    returned[slug] = returned.get(slug, 0) + gone[j]
                    if left[j]:
                        c.demand_by_node[slug] = np.maximum(
                            c.demand_by_node[slug] - gone[j], 0.0)
                    else:
                        del c.demand_by_node[slug]
                for name in r.victims[vkey]:
                    del c.assignment[name]
                c.held_keys = self._live_held_keys(rows)
                self._tombstone(vkey, rows, idx, True)
                self._persist_committed(vkey, _RecordChange(
                    c, {}, list(r.victims[vkey]), slugs, held))
            n = sum(map(len, r.victims.values()))
            _M_VICTIMS.inc(n)
            ph.set(victims=n, stages=len(r.victim_rows))
        return returned

    @staticmethod
    def _live_held_keys(rows: _Rows) -> dict[str, list[str]]:
        """`_held_keys` over the rows a commitment still books."""
        out = {}
        for k, held_by in rows.holds.items():
            at = rows.node_of[[i for i in held_by if rows.live[i]]]
            if at.size:
                out[k] = sorted({rows.nodes[j] for j in at.tolist()})
        return out

    def _tombstone(self, key: str, rows: _Rows, idx: np.ndarray,
                   gone: bool) -> None:
        """Rows `idx` of stage `key`'s commitment leave (`gone`) or come
        back to its retained problem, where that is still the problem the
        commitment was solved from: gone, a row asks nothing and is masked
        from every view, so a churn re-solve neither books nor shows it;
        back, it asks what it asked, on the server it was on."""
        entry = self._last.get(key)
        if entry is None or entry[0].service_names is not rows.names:
            return
        pt, placement = entry
        names = frozenset(rows.names[i] for i in idx.tolist())
        demand = np.array(pt.demand)
        if gone:
            demand[idx] = 0.0
            self._masked[key] = self._masked.get(key, frozenset()) | names
            placement = self._apply_mask(key, placement)
        else:
            demand[idx] = np.asarray(rows.demand)[idx]
            self._masked[key] = self._masked.get(key, frozenset()) - names
            raw = np.array(placement.raw)
            raw[idx] = rows.node_of[idx]
            placement = _dc_replace(placement, raw=raw, assignment={
                **placement.assignment,
                **{rows.names[i]: rows.nodes[j] for i, j
                   in zip(idx.tolist(), rows.node_of[idx].tolist())}})
        self._last[key] = (_dc_replace(pt, demand=demand), placement)

    def reinstate(self, stage_key: str) -> int:
        """Put the rows that higher stages' commits evicted from stage
        `stage_key` back where they were — an operator rolling back a
        batch that preempted: `release_stage` of the batch, then this.
        By difference, like a commit: each server that gets rows back is
        written once, and the placement record gets the rows and those
        servers back. Returns
        the rows reinstated; 0, with the book untouched, where there are
        none, where the stage was committed anew since (its old victims
        are then history), or where they no longer fit: capacity taken,
        a server gone, or a conflict key of theirs held by another stage
        on their server."""
        with phase("cp.reinstate", stage=stage_key), self._locked():
            c = self._committed.get(stage_key)
            rows = c.rows if c is not None else None
            if rows is None or rows.evicted is None:
                return 0
            idx = rows.evicted
            back = rows.by_node(idx)
            at = np.unique(rows.node_of[idx]).tolist()
            reserved = self._reserved_by_node()
            for j in at:
                s = self.store.server_by_slug(rows.nodes[j])
                if s is None:
                    return 0
                cap = np.array([s.capacity.cpu, s.capacity.memory,
                                s.capacity.disk])
                booked = _alloc_vector(s) + reserved.get(s.slug, 0) + back[j]
                if (booked > cap * (1 + _CAP_RTOL)).any():
                    return 0
            if rows.holds:
                coming = set(idx.tolist())
                others = self._held_by_others(stage_key)
                for k, held_by in rows.holds.items():
                    mine = {rows.nodes[rows.node_of[i]]
                            for i in held_by if i in coming}
                    if mine & set(others.get(k, ())):
                        return 0
            slugs = [rows.nodes[j] for j in at]
            self._write_allocations(slugs, back[at])
            for j, slug in zip(at, slugs):
                c.demand_by_node[slug] = (
                    np.asarray(c.demand_by_node.get(slug, 0.0)) + back[j])
            returning = {rows.names[i]: rows.nodes[j] for i, j
                         in zip(idx.tolist(), rows.node_of[idx].tolist())}
            c.assignment.update(returning)
            held = c.held_keys
            rows.live[idx] = True
            rows.evicted = None
            c.held_keys = self._live_held_keys(rows)
            self._tombstone(stage_key, rows, idx, False)
            self._persist_committed(stage_key, _RecordChange(
                c, returning, [], slugs, held))
            return int(idx.size)

    def release(self, rid: str, *, undo_commit: bool = False) -> bool:
        """Deploy failed or stage torn down: drop the reservation; with
        `undo_commit`, also return the stage's committed capacity. The
        victims of a dropped reservation were never touched."""
        with self._lock:
            r = self._reservations.pop(rid, None)
            if r is not None:
                return True
            if undo_commit:
                for key, c in list(self._committed.items()):
                    if c.id == rid:
                        self._apply_allocation(c, -1.0)
                        del self._committed[key]
                        self._drop_churn(key)   # torn down: nothing to hold
                        self._persist_committed(key)
                        return True
            return False

    def commit_retained(self, stage_key: str) -> bool:
        """Adopt the stage's retained placement as its committed allocation
        — the reconverger's commit path (cp/reconverge.py): a churn
        re-solve's assignment was actually redeployed to the surviving
        agents, so the churn hold graduates to the commitment, superseding
        the pre-churn one. Like commit() it supersedes by DIFFERENCE
        (_apply_allocation_delta; a first commitment is added whole): a
        commit after one server died writes the dead server and those
        that took its rows, not every server of the stage twice. When it
        returns, every server's `allocated` is what subtract-then-add
        would have left (to rounding), each changed record went through
        Store.book_allocated — journaled, replicated — and the placement
        record is persisted (by difference: the rows that moved, the servers
        whose demand changed), so a standby or a restart reloads the same
        book: the op is committed before it is acknowledged. Servers the
        commit does not touch keep their `updated_at` (what reads it:
        _apply_allocation_delta)."""
        with phase("cp.commit_retained", stage=stage_key), self._locked():
            entry = self._last.get(stage_key)
            if entry is None:
                return False
            pt, placement = entry
            if not placement.feasible:
                return False
            with phase("cp.commit.demand", rows=pt.S):
                r = Reservation(
                    id=f"rsv_{next(self._ids)}", stage_key=stage_key,
                    demand_by_node=self._demand_by_node(pt, placement),
                    assignment=dict(placement.assignment), committed=True,
                    held_keys=self._held_keys(pt, placement),
                    rows=_Rows.of(pt, placement))
            prev = self._committed.pop(stage_key, None)
            changed = self._supersede_allocation(prev, r)
            self._committed[stage_key] = r
            self._drop_churn(stage_key)
            with phase("cp.commit.persist"):
                self._persist_committed(stage_key, _RecordChange.superseding(
                    prev, r, changed))
            return True

    def release_stage(self, stage_key: str, *, forget: bool = False) -> bool:
        """Stage torn down (`fleet down` on a remote stage): return its
        committed capacity. With `forget` the stage's retained problem and
        placement and its solver slot go too: what ran there is gone, so
        node churn no longer re-solves it, `snapshot` no longer shows it,
        and a later solve under the same key is a first solve (cold: the
        rows it would warm-start from no longer exist)."""
        with self._locked():
            self._drop_churn(stage_key)
            if forget:
                self._last.pop(stage_key, None)
                self._node_rows.pop(stage_key, None)
                self._held_kept.pop(stage_key, None)
                self._masked.pop(stage_key, None)
                self._streamed.discard(stage_key)
                self._sched_tpu.forget(stage_key)
            c = self._committed.pop(stage_key, None)
            if c is None:
                return False
            self._apply_allocation(c, -1.0)
            self._persist_committed(stage_key)
            return True

    def _snapshot_locked(self) -> dict[str, dict]:
        return {key: {"assignment": pl.assignment,
                      "feasible": pl.feasible,
                      "violations": pl.violations,
                      "source": pl.source,
                      "solve_ms": round(pl.solve_ms, 2)}
                for key, (_pt, pl) in self._last.items()}

    def _reservations_locked(self) -> dict:
        def dem(d: dict[str, np.ndarray]) -> dict[str, list[float]]:
            return {slug: [round(float(x), 3)
                           for x in np.asarray(v, dtype=np.float64).ravel()]
                    for slug, v in d.items()}

        return {
            "in_flight": [
                {"id": r.id, "stage": r.stage_key, "churn": r.churn,
                 "demand_by_node": dem(r.demand_by_node),
                 "held_keys": r.held_keys,
                 **({"victims": r.victims} if r.victims else {})}
                for r in self._reservations.values()],
            "committed": [
                {"id": r.id, "stage": key,
                 "demand_by_node": dem(r.demand_by_node),
                 "held_keys": r.held_keys}
                for key, r in self._committed.items()],
        }

    def snapshot(self) -> dict[str, dict]:
        """Public view of the latest placement per stage (for REST/MCP)."""
        with self._lock:
            return self._snapshot_locked()

    def solver_slots(self) -> dict:
        """Device slot-manager occupancy (sched/tpu.py slots_status):
        per-stage resident tier/bytes/idle/evictions plus the byte
        budget — the `fleet solve slots` payload."""
        return self._sched_tpu.slots_status()

    def retained(self, stage_key: str
                 ) -> Optional[tuple[ProblemTensors, Placement]]:
        """The retained (problem, placement) pair for a stage — what
        `explain` answers from. The chaos invariant checker re-verifies
        the final assignment against the solver's own exact checker
        (solver/repair.verify) through this accessor."""
        with self._lock:
            return self._last.get(stage_key)

    def reservations_snapshot(self) -> dict:
        """Public view of the 2-phase journal: in-flight reservations
        (including churn holds awaiting a redeploy) and committed
        allocations per stage — the operator's answer to "why is this
        node's capacity spoken for?"."""
        with self._lock:
            return self._reservations_locked()

    def placement_state(self) -> dict:
        """Both views under ONE lock acquisition, so a commit landing
        between them cannot make the dashboard render a placement with a
        contradictory journal (and a long solve is only waited out once)."""
        with self._lock:
            return {"stages": self._snapshot_locked(),
                    "reservations": self._reservations_locked()}

    def explain(self, stage_key: str, service: str, top_k: int = 5) -> dict:
        """Why is `service` where it is in `stage_key`'s latest placement?
        Per-node hard/soft breakdown from the retained (pt, placement) —
        solver/explain.py — answered from memory, no re-solve. Raises
        KeyError for an unknown stage or service."""
        from ..solver.explain import explain_assignment

        with self._lock:
            entry = self._last.get(stage_key)
            if entry is None:
                raise KeyError(
                    f"no retained placement for stage {stage_key!r}; "
                    f"known: {sorted(self._last)}")
            pt, placement = entry
            if placement.raw is not None:
                assignment = np.asarray(placement.raw)
            else:
                node_idx = {n: j for j, n in enumerate(pt.node_names)}
                assignment = np.array(
                    [node_idx[placement.assignment[nm]]
                     for nm in pt.service_names], dtype=np.int64)
            out = explain_assignment(pt, assignment, service, top_k=top_k)
            out["stage"] = stage_key
            out["source"] = placement.source
            return out

    # ------------------------------------------------------------------
    # streaming re-solve (BASELINE config 5)
    # ------------------------------------------------------------------

    def _stage_demand(self, key: str) -> dict[str, np.ndarray]:
        """Per-node demand currently attributed to stage `key`: its
        committed allocation plus any of its own IN-FLIGHT reservations
        (a churn re-solve racing the stage's deploy window must not
        double-count the stage against itself)."""
        c = self._committed.get(key)
        # the commitment's own vectors, shared: read, never written
        out: dict[str, np.ndarray] = (dict(c.demand_by_node)
                                      if c is not None else {})
        for r in self._reservations.values():
            if r.stage_key == key and not r.committed:
                for slug, d in r.demand_by_node.items():
                    out[slug] = out.get(slug, 0) + d
        return out

    def _server_rows(self, key: str, pt: ProblemTensors
                     ) -> tuple[ServerColumns, np.ndarray]:
        """The servers' columns as the store states them now, and the row
        there of each node of stage `key`'s problem `pt` (-1: no server
        carries the name). The rows are kept with the stage: they stand
        while the problem's names are the same list and no server entered,
        left or was renamed. Caller holds the lock."""
        view = self.store.server_columns()
        kept = self._node_rows.get(key)
        if (kept is None or kept[0] is not pt.node_names
                or kept[1] != view.members):
            kept = self._node_rows[key] = (
                pt.node_names, view.members, view.rows(pt.node_names))
        return view, kept[2]

    def _refresh_capacity(self, pt: ProblemTensors, key: str,
                          overrides: Optional[dict[str, tuple]] = None,
                          on: Optional[tuple[ServerColumns, np.ndarray]] = None,
                          ) -> ProblemTensors:
        """Live per-node capacity for a churn re-solve of stage `key`:
        raw capacity minus committed allocations and in-flight
        reservations, plus this stage's OWN demand back (committed AND
        reserved — its services are the ones being re-placed).

        `overrides` maps stages already re-solved EARLIER IN THE SAME
        BURST to (their stage-demand snapshot, their new per-node demand):
        their store records still cite the pre-burst nodes, so without the
        substitution two stages displaced by one burst would each see the
        other at its old (dead) node and double-book the survivor.
        `on` is `_server_rows(key, pt)` where the caller has read it
        already. Returns pt unchanged (same object, so device stagings
        keyed on identity stay warm) when nothing moved; otherwise a copy
        with fresh capacity."""
        return self._clamped(pt, self._live_free(pt, key, overrides, on))

    def _live_free(self, pt: ProblemTensors, key: str,
                   overrides: Optional[dict[str, tuple]] = None,
                   on: Optional[tuple[ServerColumns, np.ndarray]] = None,
                   ) -> np.ndarray:
        """`_refresh_capacity`'s capacity before the clamp at zero, in the
        order of `pt`'s nodes and its dtype: a node no server carries keeps
        the capacity `pt` gives it."""
        view, row = on or self._server_rows(key, pt)
        # every server's, then the rows of this problem's nodes
        alloc = (view.booked + view.scatter(self._reserved_by_node())
                 - view.scatter(self._stage_demand(key)))
        for okey, (old_dem, new_dem) in (overrides or {}).items():
            if okey != key:
                alloc = (alloc - view.scatter(old_dem)
                         + view.scatter(new_dem))
        known = np.flatnonzero(row >= 0)
        free = pt.capacity.copy()
        free[known] = (view.capacity - alloc)[row[known]]
        return free

    @staticmethod
    def _clamped(pt: ProblemTensors, free: np.ndarray) -> ProblemTensors:
        """`pt` with capacity `free` clamped at zero; `pt` itself (the
        same object, so device stagings keyed on identity stay warm) where
        that is the capacity it has."""
        cap = np.maximum(free, 0.0)
        if np.array_equal(cap, pt.capacity):
            return pt
        return _dc_replace(pt, capacity=cap)

    def _rebar(self, pt: ProblemTensors, key: str) -> ProblemTensors:
        """The retained problem of stage `key` with its rows barred from
        the servers on which another stage holds one of their keys NOW
        (a stage committed since this one was lowered, a burst-mate's
        churn hold). Bars are only added: one another stage has since
        given up stays until the stage's next solve_stage lowers it anew.
        Returns pt unchanged (same object: the resident delta path) when
        no bit moves — always, for a stage that declares no key; else a
        copy with a new eligibility plane, which the scheduler stages
        cold (ProblemDelta does not cover it)."""
        if not pt.barred_by:
            return pt
        held = self._held_by_others(key)
        if not pt.barred_by.keys() & held.keys():
            return pt
        _M_HELD_KEYS.inc(sum(map(len, held.values())))
        eligible = pt.eligible.copy()
        if not bar_held(eligible, pt.barred_by, pt.node_names, held):
            return pt
        return _dc_replace(pt, eligible=eligible, held={
            k: pt.held.get(k, []) + held.get(k, [])
            for k in pt.held.keys() | held.keys()})

    def node_event(self, slug: str, *, online: bool,
                   diff: bool = False) -> list[tuple]:
        """Churn: flip the node's validity and warm-start re-solve every
        stage that had services there. Returns [(stage_key, new placement)]
        (`diff`: as in node_events). Device masks update as a small delta;
        the solver's migration stickiness keeps unaffected services in
        place."""
        return self.node_events([(slug, online)], diff=diff)

    def node_events(self, events: list[tuple[str, bool]], *,
                    diff: bool = False) -> list[tuple]:
        """Coalesced churn (VERDICT r3 item 5): apply EVERY validity flip
        of a burst first, then warm re-solve each affected stage ONCE
        against the final mask — a 3-dead-1-revived burst costs one
        re-solve per stage, not four, and the solver sees the true final
        world instead of three intermediate ones (sequential re-solves can
        bounce services onto a node that the next event kills).

        With `diff` each entry is (stage_key, new placement, moved): the
        rows whose server differs from the retained placement the burst
        started from, as {row: server} — what a client that holds that
        placement needs to hold the new one."""
        with phase("cp.node_events", events=len(events)):
            return self._node_events(events, diff)

    def _node_events(self, events: list[tuple[str, bool]],
                     diff: bool) -> list[tuple]:
        with phase("cp.node_events.mark"):
            for slug, online in events:
                s = self.store.server_by_slug(slug)
                if s is not None:
                    self.store.update(
                        "servers", s.id,
                        status="online" if online else "offline")
        moved: list[tuple] = []
        # stages re-solved earlier in THIS burst -> (stage-demand snapshot,
        # new per-node demand), so later re-solves see them at their new
        # homes instead of their stale store records (double-booking the
        # survivor node)
        overrides: dict[str, tuple] = {}
        with self._locked():
            for key, (pt, placement) in list(self._last.items()):
                needs_resolve = False
                flipped = False
                for slug, online in events:
                    if slug not in pt.node_names:
                        continue
                    j = pt.node_names.index(slug)
                    if bool(pt.node_valid[j]) == online:
                        continue
                    if not flipped:
                        pt.node_valid = pt.node_valid.copy()
                        flipped = True
                    pt.node_valid[j] = online
                    # a death with nothing placed on the node is a pure
                    # mask change; a death with services there forces a
                    # re-solve, and so does a REVIVE — the stage may be
                    # running degraded/infeasible on the shrunken pool and
                    # must get the chance to move back (the pre-coalescing
                    # behavior re-solved on every revive flip)
                    if online or np.any(np.asarray(placement.raw) == j):
                        needs_resolve = True
                if not needs_resolve:
                    continue
                # Admission-during-churn (SURVEY hard part (c)): pt's
                # capacity is a snapshot from this stage's admission;
                # stages committed SINCE then have filled nodes pt still
                # sees as free, so a warm re-solve against the stale view
                # can double-book a node (each solve is self-consistent,
                # so no violation counter would ever say so). Rebuild
                # per-node capacity from live inventory, excluding this
                # stage's own commitment + in-flight reservations (its
                # services are the ones being re-placed) and substituting
                # burst-mates' already-re-solved positions.
                with phase("cp.node_events.refresh_capacity", stage=key):
                    pt = self._refresh_capacity(pt, key, overrides)
                    pt = self._rebar(pt, key)
                degraded = False
                with phase("cp.node_events.solve", stage=key) as ph_solve:
                    try:
                        if self.use_tpu:
                            # structured churn instead of a full re-staging:
                            # validity flips + refreshed capacity ride a
                            # ProblemDelta, which the scheduler merges into
                            # its device-resident problem when the bucket
                            # identity holds (solver/resident.py) — the
                            # (S, N) problem planes never re-cross the host
                            # boundary on a reconvergence burst. Content
                            # drift beyond the delta cold-stages safely.
                            from ..solver.resident import ProblemDelta
                            new = self._sched_tpu.reschedule(
                                pt, delta=ProblemDelta(node_valid=pt.node_valid,
                                                       capacity=pt.capacity),
                                stage=key)
                        else:
                            new = self._sched_host.place(pt)
                    except Exception as e:
                        # graceful degradation: a churn re-solve is on the
                        # self-healing critical path — a solver crash/timeout
                        # must cost solution quality, not convergence. The
                        # greedy host path solves the same tensors.
                        _M_CHURN_FALLBACKS.inc()
                        degraded = True
                        log.error("churn solve failed; greedy host fallback %s",
                                  kv(stage=key, error=e))
                        new = self._sched_host.place(pt)
                    if not new.feasible and pt.relax_order:
                        # a stage placed via declared relaxation must keep its
                        # relaxation through churn re-solves (and a crashed
                        # device solver stays benched for the ladder too)
                        sched = (self._sched_host if degraded or not self.use_tpu
                                 else self._sched_tpu)
                        new, _ = place_with_fallback(
                            sched, pt, initial=new,
                            place_kwargs=({"stage": key}
                                          if sched is self._sched_tpu else None))
                # the warm-reschedule latency SLO stream (obs/slo.py):
                # one sample per stage re-solve, relax-ladder included —
                # this IS the placement-p99-ms an operator declares
                slo_observe("placement_ms", ph_solve.ms)
                with phase("cp.node_events.hold", stage=key):
                    # a streaming stage's tombstoned rows stay masked through
                    # churn re-solves too
                    new = self._apply_mask(key, new)
                    self._last[key] = (pt, new)
                    if new.feasible:
                        new_dem = self._demand_by_node(pt, new)
                        # hold the displaced stage's NEW nodes until its
                        # redeploy re-commits: an admission landing between
                        # the burst and the redeploy must not double-book
                        # them.  Reserve only the DELTA above the stage's
                        # still-standing demand (committed allocation AND any
                        # in-flight reservation of its own), so no service is
                        # counted twice.
                        self._drop_churn(key)
                        old = self._stage_demand(key)
                        delta = {}
                        for slug, d in new_dem.items():
                            extra = np.maximum(
                                np.asarray(d, dtype=np.float64)
                                - old.get(slug, 0), 0.0)
                            if extra.any():
                                delta[slug] = extra
                        held = self._held_keys(pt, new)
                        if delta or held:
                            rid = f"rsv_{next(self._ids)}"
                            self._reservations[rid] = Reservation(
                                id=rid, stage_key=key, demand_by_node=delta,
                                assignment=dict(new.assignment), churn=True,
                                held_keys=held)
                        # snapshot AFTER the churn reservation exists: burst-
                        # mates' refreshes subtract this exact view and add
                        # new_dem, cancelling the reservation they also see
                        # in _reserved_by_node
                        overrides[key] = (self._stage_demand(key), new_dem)
                if diff:
                    with phase("cp.node_events.diff", stage=key) as ph_diff:
                        changed = _moved_rows(pt, placement, new)
                        ph_diff.set(rows=len(changed))
                    moved.append((key, new, changed))
                else:
                    moved.append((key, new))
        return moved
