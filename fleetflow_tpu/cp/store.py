"""Control-plane store.

Analog of the reference's SurrealDB data layer (controlplane db.rs, 3,421
LoC of async CRUD over ~14 tables). The reference runs embedded `kv-mem`
for tests and RocksDB-backed SurrealDB in production (db.rs:41,76); here the
store keeps the same test-vs-durable split with no external database
process: in-memory tables, plus — when a path is given — an append-only
JSON-lines journal with periodic compaction into a snapshot file (the
LSM-ish shape RocksDB gives the reference).

Durability model (VERDICT r2 item 3: mutations must not rewrite the whole
database): every create/update/delete appends ONE journal line
(`{"op": "put"|"del", "t": table, ...}`), O(record) not O(database), and
`update_many` — a partial update of many records of one table — appends
one `upd` line for the lot: `{"op": "upd", "t": table, "at": updated_at,
"u": {id: {field: value}}}`, the changed fields only, O(change) not
O(records). `book_allocated`, which is how a commit books a placement on
its servers, is `update_many` of their `allocated` over arrays.
`update_keys` — a patch of the keys of one record's dict-valued fields,
which is how a commit rewrites a stage's placement record — appends one
`mrg` line: `{"op": "mrg", "t": table, "id": id, "at": updated_at,
"set": {field: {key: value}}, "drop": {field: [key]}}`, O(keys changed)
not O(record). A promotion appends `{"op": "epoch"}`. An `upd` line stays under
JOURNAL_LINE_MAX (a larger batch is cut into several entries, each with
its own sequence number), so the replication stream can frame it.
When the journal passes `journal_max_bytes` or `journal_max_entries` the
store compacts: full snapshot via tmp+rename, then journal truncate.
Recovery loads the snapshot and replays the journal; replaying a journal
that was already folded into the snapshot (crash between snapshot rename
and truncate) is idempotent — puts overwrite with identical rows, an
`upd` carries the ABSOLUTE new values of its fields (never a delta) and
sets them again, an `mrg` the absolute value of each key it sets and
drops only what is there, deletes of absent rows are no-ops. A torn final line
(crash mid-append) is detected and dropped — a torn `upd` whole, so a
batch's writes are all or nothing. Writes are flushed to the OS on every
append;
`fsync=True` (or `FLEET_STORE_FSYNC=1`, honored by every construction
site) additionally fsyncs each append and crash-orders compaction — the
snapshot bytes and directory entry reach disk before the journal is
truncated — matching the reference's RocksDB WAL guarantee at a
throughput cost.


Thread-safe: one RLock guards all tables (handler tasks run on one asyncio
loop, but the REST surface and background checkers may call from executor
threads).

Lookups: `find_one` and `list(where=...)` scan a table with a predicate.
A table's unique key (`_INDEXED`: `servers.slug`) has an in-memory index,
kept under the same lock wherever a record enters, leaves or is replaced
— create, update, delete, a replicated or replayed journal entry, a loaded
snapshot — so `server_by_slug` reads one row, and a miss is authoritative.

Columns: what placement reads of every server at every solve — capacity,
what is booked, whether it is schedulable, and who is where in the table
— is kept as arrays that outlive the solve (`server_columns`,
`ServerColumns`). A write of a server record marks it; the next read
re-reads the marked records into their rows, and nothing else.

Replication (docs/guide/13-cp-replication.md): every journal entry —
including the batched/coalesced paths — carries a monotonic sequence
number (`"q"`) and the store's fencing epoch (`"e"`), and is handed to an
optional `replication_sink` so a primary CP can stream its journal to warm
standbys. A standby applies the stream with `apply_replicated` (gap
detection by sequence, stale-epoch fencing) or bootstraps/catches up from
`snapshot_doc`/`install_snapshot`. The epoch is bumped exactly once per
primary promotion (`bump_epoch`) and persists through both the snapshot
(`_meta`) and a dedicated `{"op": "epoch"}` journal line, so a zombie
ex-primary's entries are refusable forever after a failover.
"""

from __future__ import annotations

import functools
import itertools
import json
import operator
import os
import threading
from dataclasses import fields
from json.encoder import encode_basestring_ascii
from pathlib import Path
from types import MappingProxyType
from typing import Callable, Mapping, Optional, TypeVar

import numpy as np

from .models import (Alert, BuildJob, CostEntry, Deployment, DeploymentStatus,
                     DnsRecord, ObservedContainer, ParkedArrival, ParkedWork,
                     PlacementRecord, Project, Record, Server, ServerAllocated,
                     ServiceRecord, StageRecord, Tenant, TenantUser,
                     VolumeRecord, VolumeSnapshot, WorkerPool, new_id, now_ts)
from ..core.errors import ControlPlaneError
from ..obs.metrics import REGISTRY

__all__ = ["Store", "ServerColumns", "booked_columns", "ReplicationGap",
           "ReplicationFenced"]


class ReplicationGap(ControlPlaneError):
    """The replication stream skipped a sequence number: the standby must
    catch up from a snapshot before applying further entries."""


class ReplicationFenced(ControlPlaneError):
    """A replicated entry carried a stale fencing epoch: it came from a
    zombie ex-primary and must never be applied."""

# metric catalog: docs/guide/10-observability.md. Counted via the store's
# own mutation-observer hook so the change-data-capture path and the
# metrics path can never disagree about what a mutation is.
_M_STORE_OPS = REGISTRY.counter(
    "fleet_store_ops_total", "Store mutations by table and op (put/del)",
    labels=("table", "op"))
_M_ROWS_SCANNED = REGISTRY.counter(
    "fleet_store_rows_scanned_total",
    "Rows a lookup examined, by table: once per find_one (up to its hit), "
    "per list(where=...) (the whole table) and per lookup through an index "
    "(1 for a hit, 0 for a miss) — what a lookup costs", labels=("table",))
_M_LOOKUPS = REGISTRY.counter(
    "fleet_store_lookups_total",
    "Single-record lookups by table and path: index (answered from the "
    "table's unique-key index, servers.slug) or scan (find_one with a "
    "predicate)", labels=("table", "path"))
_M_JOURNAL_BYTES = REGISTRY.counter(
    "fleet_store_journal_bytes_total",
    "Bytes of serialized journal entries handed to the local journal and "
    "to the replication sink (each counted); 0 only in a store with neither")
_M_JOURNAL_ENTRIES = REGISTRY.counter(
    "fleet_store_journal_entries_total",
    "Journal entries emitted, of any op (put/upd/del/epoch), each counted "
    "once whether it went to the local journal, the replication sink or "
    "both; beside fleet_store_ops_total it says how many records an entry "
    "carries")
# unlabelled on purpose: a ratio of two of them is read child by child
_M_COLUMNS_READS = REGISTRY.counter(
    "fleet_store_server_columns_reads_total",
    "Reads of the servers' columns (Store.server_columns): one an "
    "inventory, a capacity refresh or an admission micro-solve")
_M_COLUMNS_ROWS = REGISTRY.counter(
    "fleet_store_server_columns_rows_total",
    "Server records re-read into the columns: the records written since "
    "the last read (a patch), or the whole table (a rebuild)")
_M_COLUMNS_REBUILDS = REGISTRY.counter(
    "fleet_store_server_columns_rebuilds_total",
    "Reads that rebuilt the servers' columns whole: the first, and one "
    "after a server entered, left, or changed slug, tenant or created_at")
_M_HEARTBEATS = REGISTRY.counter(
    "fleet_heartbeats_total", "Agent heartbeats recorded")
_M_COMPACTIONS = REGISTRY.counter(
    "fleet_store_compactions_total", "Journal compactions (snapshot writes)")
_M_FENCING = REGISTRY.counter(
    "fleet_replication_fencing_rejections_total",
    "Stale-epoch writes refused after a failover, by side (store: "
    "replicated entries from a zombie ex-primary; cp: rejected "
    "replication RPCs; agent: fenced agent commands)", labels=("side",))


@functools.cache
def _store_ops(table: str, op: str) -> Callable[[], None]:
    # a commit of a 1,000-server stage notifies a thousand times: the
    # child of a (table, op) is looked up once, when it first counts
    return _M_STORE_OPS.bind(table=table, op=op)


def _count_op(op: str, table: str, _payload: object) -> None:
    _store_ops(table, op)()

R = TypeVar("R", bound=Record)

_TABLES: dict[str, type] = {
    "tenants": Tenant, "tenant_users": TenantUser, "projects": Project,
    "stages": StageRecord, "services": ServiceRecord, "servers": Server,
    "worker_pools": WorkerPool, "deployments": Deployment, "alerts": Alert,
    "observed_containers": ObservedContainer, "volumes": VolumeRecord,
    "volume_snapshots": VolumeSnapshot, "build_jobs": BuildJob,
    "cost_entries": CostEntry, "dns_records": DnsRecord,
    "parked_work": ParkedWork, "placements": PlacementRecord,
    "admission_parked": ParkedArrival,
}


# The unique key of a table that has one: table -> field. The store keeps
# an index on it (Store._index) at every place a record enters, leaves or
# is replaced, and a lookup by that key reads the index instead of the
# table. A second indexed table is one more entry here and its named
# query beside server_by_slug.
_INDEXED: dict[str, str] = {"servers": "slug"}

# a lookup and _emit run a thousand times in one commit of a 1,000-server
# stage: the counters' children are looked up here, once
_ROWS_SCANNED = {t: _M_ROWS_SCANNED.bind(table=t) for t in _TABLES}
_LOOKUPS_SCAN = {t: _M_LOOKUPS.bind(table=t, path="scan") for t in _TABLES}
_LOOKUPS_INDEX = {t: _M_LOOKUPS.bind(table=t, path="index")
                  for t in _INDEXED}
_count_journal_bytes = _M_JOURNAL_BYTES.bind()
_count_journal_entries = _M_JOURNAL_ENTRIES.bind()
_count_columns_reads = _M_COLUMNS_READS.bind()
_count_columns_rows = _M_COLUMNS_ROWS.bind()
_count_columns_rebuilds = _M_COLUMNS_REBUILDS.bind()

# The most a journal line may hold where the store can split it (an `upd`
# entry of several records): replication.SNAPSHOT_CHUNK, a quarter of
# protocol.MAX_FRAME — a `replication` `append` event ships what one sink
# call handed over, JSON-escaped once more.
JOURNAL_LINE_MAX = 256 * 1024


# A server's `allocated`, field by field in the dataclass's order: the
# three a commit books, then the three it carries. `_BOOKED_PART` is one
# server's part of an `upd` entry's "u" as json.dumps renders it.
_ALLOCATED = tuple(f.name for f in fields(ServerAllocated))
_BOOKED = operator.attrgetter(*_ALLOCATED[:3])
_CARRIED = operator.attrgetter(*_ALLOCATED[3:])
_BOOKED_PART = ('%s: {"allocated": {'
                + ", ".join(f'"{name}": %s' for name in _ALLOCATED) + "}}")


def _booked_parts(ids, columns, at: float) -> tuple[str, list[str]]:
    """`at` as json.dumps renders it, and `"<id>": {"allocated": {...}}`
    for each of `ids` from `columns` — the values of each field of the
    new `allocated`s, in `_ALLOCATED`'s order — as json.dumps of the
    entry renders each record: every number rendered by one
    `json.dumps`."""
    width = len(_ALLOCATED)
    values: list = [at] + [None] * (width * len(columns[0]))
    for k, column in enumerate(columns, 1):
        values[k::width] = column
    rendered = json.dumps(values)[1:-1].split(", ")
    if len(rendered) != len(values):
        raise TypeError("a server's allocated holds a value that is no "
                        "number")
    numbers = iter(rendered)
    stamp = next(numbers)
    return stamp, list(map(_BOOKED_PART.__mod__, zip(
        map(encode_basestring_ascii, ids), *[numbers] * width)))


def booked_columns(servers: list[Server]) -> tuple[np.ndarray, np.ndarray]:
    """((N, R) capacity, (N, R) committed+reserved demand) as the server
    records state them, float64, in the records' order — the ONE
    definition of 'how much of this node is spoken for' (the columns the
    store keeps, and so admission inventory and churn capacity refresh
    alike): one pass that gathers the records' numbers, one array, no
    numpy call per server."""
    cols = np.array(
        [(c.cpu, c.memory, c.disk, a.cpu, a.memory, a.disk,
          a.reserved_cpu, a.reserved_memory, a.reserved_disk)
         for c, a in [(s.capacity, s.allocated) for s in servers]],
        dtype=np.float64).reshape(len(servers), 9)
    return cols[:, 0:3], cols[:, 3:6] + cols[:, 6:9]


# a write of one of these moves who is where in the columns, not a row
_MEMBERSHIP = frozenset(("slug", "tenant", "created_at"))


class ServerColumns:
    """What the `servers` table states, column by column, in table order:
    one value of `Store.server_columns`, never written again — a later
    read hands out another. Row i is record `ids[i]`.

    `capacity` and `booked` are `booked_columns` of the records and
    `schedulable` their `Server.schedulable`, to the bit. `row_of` is
    slug -> row, the first in table order where two records carry one
    slug (the record `server_by_slug` returns); `also` has the later
    ones. `order` lists the rows as `Store.list` orders the records:
    ascending `created_at`, stable over table order. `members` moves when
    a record enters, leaves, or changes slug, tenant or `created_at` —
    what is keyed on rows is good for as long as it stands — and
    `version` with any write of a server."""
    __slots__ = ("ids", "slugs", "records", "id_rows", "row_of", "also",
                 "tenant", "created_at", "order", "capacity", "booked",
                 "schedulable", "members", "version")

    ids: tuple[str, ...]
    slugs: tuple[str, ...]
    records: tuple[Server, ...]
    id_rows: Mapping[str, int]
    row_of: Mapping[str, int]
    also: Mapping[str, tuple[int, ...]]
    tenant: np.ndarray          # (N,) object
    created_at: np.ndarray      # (N,) float64
    order: np.ndarray           # (N,) int64
    capacity: np.ndarray        # (N, 3) float64
    booked: np.ndarray          # (N, 3) float64
    schedulable: np.ndarray     # (N,) bool
    members: int
    version: int

    @classmethod
    def of(cls, table: dict[str, Server], members: int,
           version: int) -> "ServerColumns":
        self = cls()
        self.ids = tuple(table)
        self.records = records = tuple(table.values())
        self.slugs = tuple([s.slug for s in records])
        self.id_rows = MappingProxyType(
            dict(zip(self.ids, range(len(records)))))
        row_of: dict[str, int] = {}
        also: dict[str, tuple[int, ...]] = {}
        for i, slug in enumerate(self.slugs):
            if row_of.setdefault(slug, i) != i:
                also[slug] = also.get(slug, ()) + (i,)
        self.row_of = MappingProxyType(row_of)
        self.also = MappingProxyType(also)
        tenant = np.empty(len(records), dtype=object)
        tenant[:] = [s.tenant for s in records]
        self.tenant = tenant
        self.created_at = np.array([s.created_at for s in records],
                                   dtype=np.float64)
        self.order = np.argsort(self.created_at, kind="stable")
        capacity, self.booked = booked_columns(records)
        self.capacity = np.ascontiguousarray(capacity)
        self.schedulable = np.array([s.schedulable for s in records],
                                    dtype=bool)
        self.members, self.version = members, version
        return self._sealed()

    def patched(self, table: dict[str, Server], ids,
                version: int) -> "ServerColumns":
        """These columns with the rows of the records `ids` of `table` —
        members of them, whose slug, tenant and `created_at` stand — read
        again, in one array assignment a column."""
        new = ServerColumns()
        for name in ServerColumns.__slots__:
            setattr(new, name, getattr(self, name))
        at = list(map(self.id_rows.__getitem__, ids))
        records = list(map(table.__getitem__, ids))
        capacity, booked = booked_columns(records)
        for name, rows in (("capacity", capacity), ("booked", booked),
                           ("schedulable", [s.schedulable for s in records])):
            column = getattr(self, name).copy()
            column[at] = rows
            setattr(new, name, column)
        new.version = version
        return new._sealed()

    def _sealed(self) -> "ServerColumns":
        for name in ("tenant", "created_at", "order", "capacity", "booked",
                     "schedulable"):
            getattr(self, name).flags.writeable = False
        return self

    def __len__(self) -> int:
        return len(self.ids)

    def rows(self, slugs) -> np.ndarray:
        """(len(slugs),) int64: the row of each slug, -1 for one no
        server carries."""
        return np.fromiter(map(self.row_of.get, slugs, itertools.repeat(-1)),
                           dtype=np.int64, count=len(slugs))

    def holders(self, slugs) -> list[int]:
        """The rows of every record that carries one of `slugs`."""
        rows = [i for i in map(self.row_of.get, slugs) if i is not None]
        if self.also:
            rows.extend(i for slug in self.also.keys() & set(slugs)
                        for i in self.also[slug])
        return rows

    def scatter(self, by_slug: Mapping[str, np.ndarray]) -> np.ndarray:
        """(N, R) float64: `by_slug`'s (R,) vector in the row of every
        record that carries its slug, zero elsewhere; a slug no record
        carries is dropped. One pass over `by_slug`'s own keys."""
        out = np.zeros((len(self.ids), 3))
        if not by_slug:
            return out
        at = list(map(self.row_of.get, by_slug))
        vectors = list(by_slug.values())
        if None in at:
            known = [k for k, i in enumerate(at) if i is not None]
            at = [at[k] for k in known]
            vectors = [vectors[k] for k in known]
        if at:
            out[at] = np.array(vectors)
        for slug in self.also.keys() & by_slug.keys() if self.also else ():
            out[list(self.also[slug])] = by_slug[slug]
        return out


def _merge_keys(rec: Record, set_keys: Mapping[str, Mapping],
                drop_keys: Mapping[str, list]) -> None:
    """An `mrg` entry's patch of `rec`'s dict-valued fields, in place:
    drops first (a missing key is skipped), then sets. Applied twice it
    leaves what it left once."""
    for name, keys in drop_keys.items():
        held = getattr(rec, name)
        for k in keys:
            held.pop(k, None)
    for name, values in set_keys.items():
        getattr(rec, name).update(values)


class Store:
    def __init__(self, path: Optional[str] = None, *,
                 journal_max_bytes: int = 4 * 1024 * 1024,
                 journal_max_entries: int = 20_000,
                 fsync: Optional[bool] = None,
                 clock: Callable[[], float] = now_ts):
        self._lock = threading.RLock()
        # record timestamps come from this clock (create/update/heartbeat
        # /finish/resolve stamps): wall time in production, the virtual
        # clock in the chaos harness — so record ages are deterministic
        # under replay instead of depending on real elapsed time
        self._clock = clock
        self._tables: dict[str, dict[str, Record]] = {t: {} for t in _TABLES}
        # table -> key -> ids of the records that carry the key, in table
        # order (nothing forbids two servers with one slug; a lookup
        # returns the first, as the scan did)
        self._index: dict[str, dict[object, list[str]]] = {
            t: {} for t in _INDEXED}
        # the servers' columns as the last read left them (None: to be
        # built), the ids of the records written since, and whether one
        # of those writes moved who is where; `members` and `version`
        # count on through a view that is dropped
        self._columns: Optional[ServerColumns] = None
        self._columns_dirty: set[str] = set()
        self._columns_moved = False
        self._columns_members = 0
        self._columns_version = 0
        self._path = Path(path) if path else None
        self._journal_path = (self._path.with_name(self._path.name + ".journal")
                              if self._path else None)
        self._journal_max_bytes = journal_max_bytes
        self._journal_max_entries = journal_max_entries
        if fsync is None:   # FLEET_STORE_FSYNC=1 opts any deployment in
            fsync = os.environ.get("FLEET_STORE_FSYNC", "").strip().lower() \
                in ("1", "true", "yes", "on")
        self._fsync = fsync
        self._journal_file = None          # lazily-opened append handle
        self._journal_bytes = 0
        self._journal_entries = 0
        self._compactions = 0
        self._batch_depth = 0
        self._batch_buf: list[str] = []
        # replication: every emitted journal entry carries (seq, epoch);
        # the sink — when set — receives [(seq, line), ...] under the
        # store lock (same contract as observers: fast, no re-entry).
        # Batched mutations hand the sink ONE coalesced list on batch
        # exit, mirroring the single journal write.
        self._seq = 0
        self._epoch = 1
        self.replication_sink: Optional[
            Callable[[list[tuple[int, str]]], None]] = None
        self._repl_buf: list[tuple[int, str]] = []
        # mutation observers: fn(op, table, rec_or_id) called under the
        # store lock AFTER each create/update/delete. This is the
        # change-data-capture hook the chaos harness builds its causal
        # event log on; it doubles as a general extension point (metrics,
        # cache invalidation). Observers must be fast and must not
        # re-enter the store's mutators.
        self._observers: list[Callable[[str, str, object], None]] = [_count_op]
        if self._path and self._path.exists():
            self._load()
        if self._journal_path and self._journal_path.exists():
            self._replay_journal()
            # fold the surviving journal into a fresh snapshot so repeated
            # crash/restart cycles cannot grow an unbounded replay tail
            self.flush()

    @classmethod
    def connect_memory(cls) -> "Store":
        """Test constructor (db.rs connect_memory:76)."""
        return cls(path=None)

    def subscribe(self, fn: Callable[[str, str, object], None]) -> None:
        """Register a mutation observer: fn("put"|"del", table, rec|id)."""
        with self._lock:
            self._observers.append(fn)

    def unsubscribe(self, fn: Callable[[str, str, object], None]) -> None:
        with self._lock:
            if fn in self._observers:
                self._observers.remove(fn)

    def _notify(self, op: str, table: str, payload: object) -> None:
        for fn in self._observers:
            fn(op, table, payload)

    # ------------------------------------------------------------------
    # generic CRUD
    # ------------------------------------------------------------------

    def create(self, table: str, rec: R) -> R:
        with self._lock:
            if not rec.id:
                rec.id = new_id(table.rstrip("s"))
            rec.created_at = rec.created_at or self._clock()
            rec.updated_at = self._clock()
            self._put(table, rec)
            self._log_put(table, rec)
            self._notify("put", table, rec)
            return rec

    def get(self, table: str, rec_id: str) -> Optional[Record]:
        with self._lock:
            return self._tables[table].get(rec_id)

    def update(self, table: str, rec_id: str, **changes) -> Optional[Record]:
        with self._lock:
            rec = self._tables[table].get(rec_id)
            if rec is None:
                return None
            self._set_fields(table, rec, changes)
            rec.updated_at = self._clock()
            self._log_put(table, rec)
            self._notify("put", table, rec)
            return rec

    def update_many(self, table: str,
                    changes_by_id: dict[str, dict[str, object]]) -> int:
        """`update(table, id, **changes)` for every id of `changes_by_id`,
        under one acquisition of the lock and one reading of the clock,
        journaled as `upd` entries: the changed fields of every record in
        one line (`_log_upd`), where `update` journals each record whole.
        The index and the observers see each record as `update` shows it.
        Returns the records written; an id the table lacks is skipped."""
        with self._lock:
            rows = self._tables[table]
            journaled = self._journaled()
            now = self._clock()
            parts: list[str] = []
            written = 0
            for rec_id, changes in changes_by_id.items():
                rec = rows.get(rec_id)
                if rec is None:
                    continue
                self._set_fields(table, rec, changes)
                rec.updated_at = now
                if journaled:
                    parts.append(json.dumps(
                        {rec_id: rec.fields_dict(changes)})[1:-1])
                written += 1
                self._notify("put", table, rec)
            if parts:
                self._log_upd(table, json.dumps(now), parts)
            return written

    def book_allocated(self, slugs: list[str], vectors) -> int:
        """Add row i of `vectors` ((n, 3): cpu, memory, disk) to the
        `allocated` of the server that carries `slugs[i]`, each clamped at
        0 as `max(old + d, 0.0)` clamps it, the reserved three carried:
        how a commit books its demand on the servers. What
        `update_many("servers", {id: {"allocated": new}})` of the same
        records leaves — each record a new `ServerAllocated`, one reading
        of the clock, the observers once a record, the same `upd` entries
        — over arrays: the records found in one pass of the index on
        `servers.slug` (the first holder in table order, as
        `server_by_slug` reads it; a slug no server carries is skipped,
        and one listed twice books its last row), the new values in one
        array expression, and each record's part of the journal rendered
        once. Returns the server records written."""
        with self._lock:
            rows = self._tables["servers"]
            journaled = self._journaled()
            now = self._clock()
            at: dict[str, int] = {}
            hits = 0
            for i, ids in enumerate(map(self._index["servers"].get, slugs)):
                if ids:
                    at[ids[0]] = i
                    hits += 1
            if len(slugs):
                _LOOKUPS_INDEX["servers"](len(slugs))
            if not at:
                return 0
            _ROWS_SCANNED["servers"](hits)
            recs = list(map(rows.__getitem__, at))
            olds = [rec.allocated for rec in recs]
            new = np.array(list(map(_BOOKED, olds)), dtype=np.float64)
            if len(at) < len(slugs):     # a slug missed or came twice
                vectors = np.asarray(vectors)[list(at.values())]
            new += vectors
            new[new < 0.0] = 0.0    # max(x, 0.0): -0.0 and NaN stay
            booked = new.T.tolist()
            columns = (*booked, *zip(*map(_CARRIED, olds)))
            if journaled:
                stamp, parts = _booked_parts(at, columns, now)
            for rec, allocated in zip(recs, map(ServerAllocated, *columns)):
                rec.allocated = allocated
                rec.updated_at = now
                self._notify("put", "servers", rec)
            self._columns_dirty.update(at)
            if journaled:
                self._log_upd("servers", stamp, parts)
            return len(recs)

    def update_keys(self, table: str, rec_id: str, *,
                    set_keys: Optional[Mapping[str, Mapping]] = None,
                    drop_keys: Optional[Mapping[str, list]] = None,
                    ) -> Optional[Record]:
        """Patch dict-valued fields of one record in place: `drop_keys` is
        field -> [key] (a key the field lacks is skipped), then `set_keys`
        field -> {key: value}, the ABSOLUTE new value of each key. Journaled
        as ONE `mrg` entry of just those keys, handed to the journal and the
        sink before this returns, where `update` journals the record whole:
        a commit that moves 340 rows of a 100,000-row placement record
        writes 340 rows. Values are kept by the record as given: the caller
        hands over objects it does not write again. Returns the record; None
        where the table lacks `rec_id`."""
        set_keys = set_keys or {}
        drop_keys = drop_keys or {}
        with self._lock:
            rec = self._tables[table].get(rec_id)
            if rec is None:
                return None
            rec.updated_at = self._clock()
            _merge_keys(rec, set_keys, drop_keys)
            self._emit({"op": "mrg", "t": table, "id": rec_id,
                        "at": rec.updated_at, "set": set_keys,
                        "drop": drop_keys})
            self._notify("put", table, rec)
            return rec

    def delete(self, table: str, rec_id: str) -> bool:
        with self._lock:
            gone = self._pop(table, rec_id)
            if gone:
                self._log_del(table, rec_id)
                self._notify("del", table, rec_id)
            return gone

    def list(self, table: str,
             where: Optional[Callable[[Record], bool]] = None) -> list[Record]:
        with self._lock:
            rows = list(self._tables[table].values())
        if where is not None:
            _ROWS_SCANNED[table](len(rows))
            rows = [r for r in rows if where(r)]
        return sorted(rows, key=lambda r: r.created_at)

    def find_one(self, table: str,
                 where: Callable[[Record], bool]) -> Optional[Record]:
        # early-exit scan, no copy/sort like list(); a lookup by a
        # table's unique key does not come here (_lookup)
        found = None
        with self._lock:
            rows = iter(self._tables[table].values())
            for r in rows:
                if where(r):
                    found = r
                    break
            # rows examined = the table less what the iterator has left,
            # counted once per lookup and never per row
            _ROWS_SCANNED[table](
                len(self._tables[table]) - operator.length_hint(rows))
        _LOOKUPS_SCAN[table]()
        return found

    # ------------------------------------------------------------------
    # the unique-key index (_INDEXED)
    # ------------------------------------------------------------------

    def _lookup(self, table: str, key: object) -> Optional[Record]:
        """The record of an indexed table whose key field equals `key`,
        read from the index: what find_one with that predicate returns —
        the first in table order where several records carry the key —
        without the scan. A miss is authoritative: every path by which a
        record enters, leaves or is replaced keeps the index."""
        with self._lock:
            try:
                ids = self._index[table].get(key)
            except TypeError:   # unhashable (a malformed request): no
                ids = None      # record's key equals it
            found = self._tables[table][ids[0]] if ids else None
        _LOOKUPS_INDEX[table]()
        if found is not None:
            _ROWS_SCANNED[table](1)
        return found

    def _set_fields(self, table: str, rec: Record, changes: dict) -> None:
        """Set `changes` on `rec`, a record of `table`, and keep the index
        where they move its key. Caller holds the lock."""
        field = _INDEXED.get(table)   # None is no key of `changes`
        if field in changes and changes[field] != getattr(rec, field):
            self._index_add(table, changes[field], rec.id)
            self._index_drop(table, getattr(rec, field), rec.id)
        for k, v in changes.items():
            setattr(rec, k, v)
        if table == "servers":
            self._columns_dirty.add(rec.id)
            if not _MEMBERSHIP.isdisjoint(changes):
                self._columns_moved = True

    def _index_add(self, table: str, key: object, rec_id: str) -> None:
        # caller holds the lock; the record is in its table already (its
        # place there orders it among others that carry the key)
        ids = self._index[table].setdefault(key, [])
        ids.append(rec_id)
        if len(ids) > 1:
            place = {rid: i for i, rid in enumerate(self._tables[table])}
            ids.sort(key=place.__getitem__)

    def _index_drop(self, table: str, key: object, rec_id: str) -> None:
        ids = self._index[table][key]
        ids.remove(rec_id)
        if not ids:
            del self._index[table][key]

    def _put(self, table: str, rec: Record) -> None:
        """Place `rec` in its table under its id — a record already there
        is replaced and keeps its place in table order — and keep the
        index. Caller holds the lock."""
        rows = self._tables[table]
        field = _INDEXED.get(table)
        if field is None:
            rows[rec.id] = rec
            return
        key = getattr(rec, field)
        hash(key)   # an unhashable key raises before the table changes
        old = rows.get(rec.id)
        rows[rec.id] = rec
        if table == "servers":
            # entered, or replaced by an object that may say anything
            self._columns_moved = True
        if old is None:
            self._index_add(table, key, rec.id)
        elif getattr(old, field) != key:
            self._index_add(table, key, rec.id)
            self._index_drop(table, getattr(old, field), rec.id)

    def _pop(self, table: str, rec_id: str) -> bool:
        """Take a record out of its table and out of the index; False
        where the table has no such id. Caller holds the lock."""
        rec = self._tables[table].pop(rec_id, None)
        if rec is None:
            return False
        field = _INDEXED.get(table)
        if field is not None:
            self._index_drop(table, getattr(rec, field), rec_id)
        if table == "servers":
            self._columns_moved = True
        return True

    def _reindex(self) -> None:
        """Rebuild every index from its table, after the tables were
        loaded whole (a snapshot)."""
        for table, field in _INDEXED.items():
            index: dict[object, list[str]] = {}
            for rid, rec in self._tables[table].items():
                index.setdefault(getattr(rec, field), []).append(rid)
            self._index[table] = index
        self._columns = None

    # ------------------------------------------------------------------
    # domain queries (the named fns of db.rs)
    # ------------------------------------------------------------------

    # tenants ----------------------------------------------------------
    def tenant_by_name(self, name: str) -> Optional[Tenant]:
        return self.find_one("tenants", lambda t: t.name == name)

    def ensure_tenant(self, name: str) -> Tenant:
        """get-or-create, the way deploy.execute resolves tenants
        (handlers/deploy.rs tenant resolve)."""
        t = self.tenant_by_name(name)
        if t is None:
            t = self.create("tenants", Tenant(name=name, display_name=name))
        return t

    def tenant_users(self, tenant: str) -> list[TenantUser]:
        return self.list("tenant_users", lambda u: u.tenant == tenant)

    def user_by_email(self, tenant: str, email: str) -> Optional[TenantUser]:
        return self.find_one(
            "tenant_users", lambda u: u.tenant == tenant and u.email == email)

    # projects / stages / services ------------------------------------
    def project_by_name(self, tenant: str, name: str) -> Optional[Project]:
        return self.find_one(
            "projects", lambda p: p.tenant == tenant and p.name == name)

    def ensure_project(self, tenant: str, name: str) -> Project:
        p = self.project_by_name(tenant, name)
        if p is None:
            p = self.create("projects", Project(tenant=tenant, name=name))
        return p

    def stages_of(self, project: str) -> list[StageRecord]:
        return self.list("stages", lambda s: s.project == project)

    def stage_by_name(self, project: str, name: str) -> Optional[StageRecord]:
        return self.find_one(
            "stages", lambda s: s.project == project and s.name == name)

    def ensure_stage(self, project: str, name: str, **attrs) -> StageRecord:
        s = self.stage_by_name(project, name)
        if s is None:
            s = self.create("stages",
                            StageRecord(project=project, name=name, **attrs))
        elif attrs:
            self.update("stages", s.id, **attrs)
        return s

    def adopt_stage(self, stage_id: str) -> Optional[StageRecord]:
        """Stage adoption (db.rs:480): claim an observed stage as managed."""
        return self.update("stages", stage_id, adopted=True)

    def services_of(self, stage: str) -> list[ServiceRecord]:
        return self.list("services", lambda s: s.stage == stage)

    def upsert_service(self, stage: str, name: str, **attrs) -> ServiceRecord:
        s = self.find_one("services",
                          lambda r: r.stage == stage and r.name == name)
        if s is None:
            return self.create("services",
                               ServiceRecord(stage=stage, name=name, **attrs))
        return self.update("services", s.id, **attrs)  # type: ignore[return-value]

    # servers ----------------------------------------------------------
    def server_by_slug(self, slug: str) -> Optional[Server]:
        return self._lookup("servers", slug)  # type: ignore[return-value]

    def server_columns(self) -> ServerColumns:
        """The servers' columns as the records state them now. Every path
        that writes a server record marks it (`_set_fields`, `_put`,
        `_pop`; a table loaded whole drops the columns), and this read
        brings the columns up to date from the marked records alone: a
        patch of their rows, or a rebuild where a write moved who is
        where. Read after the write, so a record a replicated `upd` set
        from plain values has been coerced by then. The value handed out
        is not written again, and its arrays refuse a write."""
        with self._lock:
            view, dirty = self._columns, self._columns_dirty
            if view is None or self._columns_moved:
                self._columns_members += 1
                self._columns_version += 1
                view = ServerColumns.of(
                    self._tables["servers"], self._columns_members,
                    self._columns_version)
                _count_columns_rebuilds()
                _count_columns_rows(len(view))
            elif dirty:
                self._columns_version += 1
                view = view.patched(self._tables["servers"], dirty,
                                    self._columns_version)
                _count_columns_rows(len(dirty))
            self._columns = view
            self._columns_moved = False
            dirty.clear()
        _count_columns_reads()
        return view

    def register_server(self, slug: str, tenant: str = "default",
                        **attrs) -> Server:
        """Agent registration upsert (handlers/server.rs register)."""
        s = self.server_by_slug(slug)
        if s is None:
            return self.create("servers",
                               Server(slug=slug, tenant=tenant, **attrs))
        return self.update("servers", s.id, **attrs)  # type: ignore[return-value]

    def heartbeat(self, slug: str, version: str = "") -> Optional[Server]:
        """db.rs heartbeat update (handlers/agent.rs:84-91)."""
        s = self.server_by_slug(slug)
        if s is None:
            return None
        _M_HEARTBEATS.inc()
        changes: dict = {"last_heartbeat": self._clock(), "status": "online"}
        if version:
            changes["agent_version"] = version
        return self.update("servers", s.id, **changes)

    def bulk_server_status(self, statuses: dict[str, str]) -> int:
        """Health-checker bulk update (db.rs:779; fleetflowd health.rs:34-69)."""
        n = 0
        for slug, status in statuses.items():
            s = self.server_by_slug(slug)
            if s is not None and s.status != status:
                self.update("servers", s.id, status=status)
                n += 1
        return n

    def schedulable_servers(self, tenant: Optional[str] = None) -> list[Server]:
        return self.list("servers", lambda s: s.schedulable and
                         (tenant is None or s.tenant == tenant))

    # deployments ------------------------------------------------------
    def deployment_history(self, stage: Optional[str] = None,
                           limit: int = 50) -> list[Deployment]:
        rows = self.list("deployments",
                         (lambda d: d.stage == stage) if stage else None)
        return list(reversed(rows))[:limit]

    def finish_deployment(self, dep_id: str, status: DeploymentStatus,
                          log: str = "", error: str = "") -> Optional[Deployment]:
        return self.update("deployments", dep_id, status=status.value,
                           log=log, error=error, finished_at=self._clock())

    # alerts -----------------------------------------------------------
    def upsert_alert(self, server: str, container: str, kind: str,
                     message: str, tenant: str = "default") -> Alert:
        """Active-alert upsert (db.rs:1052; handlers/agent.rs:203-241)."""
        a = self.find_one("alerts", lambda r: r.server == server and
                          r.container == container and r.kind == kind and r.active)
        if a is not None:
            return self.update("alerts", a.id, message=message)  # type: ignore
        return self.create("alerts", Alert(
            tenant=tenant, server=server, container=container,
            kind=kind, message=message))

    def resolve_alert(self, server: str, container: str, kind: str) -> bool:
        a = self.find_one("alerts", lambda r: r.server == server and
                          r.container == container and r.kind == kind and r.active)
        if a is None:
            return False
        self.update("alerts", a.id, active=False, resolved_at=self._clock())
        return True

    def active_alerts(self, tenant: Optional[str] = None) -> list[Alert]:
        return self.list("alerts", lambda a: a.active and
                         (tenant is None or a.tenant == tenant))

    # observed containers ---------------------------------------------
    def replace_observed(self, server: str,
                         rows: list[ObservedContainer]) -> None:
        """Inventory report replaces that server's slice (db.rs:1153-1219).
        One journal write for the whole batch, not one per record."""
        with self._lock, self.batch():
            table = self._tables["observed_containers"]
            for rid in [k for k, v in table.items() if v.server == server]:
                self.delete("observed_containers", rid)
            for rec in rows:
                rec.server = server
                self.create("observed_containers", rec)

    def observed_on(self, server: str) -> list[ObservedContainer]:
        return self.list("observed_containers", lambda o: o.server == server)

    # cost -------------------------------------------------------------
    def monthly_cost(self, tenant: str, month: str) -> float:
        """db.rs:896-947 monthly summary."""
        return sum(c.amount for c in self.list(
            "cost_entries", lambda c: c.tenant == tenant and c.month == month))

    # ------------------------------------------------------------------
    # persistence
    # ------------------------------------------------------------------

    def batch(self):
        """Context manager coalescing journal appends for bulk mutations:
        one file write (and at most one compaction check) on exit."""
        store = self

        class _Batch:
            def __enter__(self):
                with store._lock:
                    store._batch_depth += 1
                return self

            def __exit__(self, *exc):
                with store._lock:
                    store._batch_depth -= 1
                    if store._batch_depth == 0 and store._batch_buf:
                        lines, store._batch_buf = store._batch_buf, []
                        store._append_lines(lines)
                    if store._batch_depth == 0 and store._repl_buf:
                        entries, store._repl_buf = store._repl_buf, []
                        if store.replication_sink is not None:
                            store.replication_sink(entries)
                return False

        return _Batch()

    def journal_stats(self) -> dict:
        """Write-amplification counters for tests/ops: entries and bytes
        appended since the last compaction, and compactions so far."""
        with self._lock:
            return {"entries": self._journal_entries,
                    "bytes": self._journal_bytes,
                    "compactions": self._compactions}

    def _log_put(self, table: str, rec: Record) -> None:
        self._emit({"op": "put", "t": table, "r": rec.to_dict()})

    def _log_del(self, table: str, rec_id: str) -> None:
        self._emit({"op": "del", "t": table, "id": rec_id})

    def _log_upd(self, table: str, at: str, parts: list[str]) -> None:
        """Journal a partial update of several records: `parts` is each
        record's `"<id>": {field: value as to_dict renders it}` as
        json.dumps renders it — the ABSOLUTE new values, never a delta, so
        replay over a snapshot that already holds them is idempotent as a
        put's is — and `at` their new `updated_at` as json.dumps renders
        it. One entry, one line, one sequence number; a batch whose line
        would pass JOURNAL_LINE_MAX is cut into several entries, each
        handed over on its own. A line's length is worked out from its
        parts before it is joined, so each line is serialised once, as it
        is handed over."""
        head = '{"op": "upd", "t": %s, "at": %s, "u": {' % (
            encode_basestring_ascii(table), at)
        self._log_parts(head, parts, list(map(len, parts)))

    def _log_parts(self, head: str, parts: list[str],
                   sizes: list[int]) -> None:
        tail = '}, "q": %d, "e": %d}' % (self._seq + 1, self._epoch)
        size = len(head) + sum(sizes) + 2 * (len(parts) - 1) + len(tail)
        if size <= JOURNAL_LINE_MAX or len(parts) == 1:
            self._hand_over(head + ", ".join(parts) + tail)
            return
        # even cuts by count, three quarters full at the mean record; a
        # cut of larger records that is still too long is cut again
        n = min(size // (JOURNAL_LINE_MAX * 3 // 4) + 1, len(parts))
        for k in range(n):
            cut = slice(len(parts) * k // n, len(parts) * (k + 1) // n)
            self._log_parts(head, parts[cut], sizes[cut])

    def _emit(self, entry: dict) -> None:
        """Serialize one journal entry with its sequence number and epoch,
        then hand it to the local journal and/or the replication sink.
        Caller holds the lock (all mutators do). A store with neither a
        journal nor a sink skips the serialization entirely."""
        if self._journaled():
            self._hand_over(self._serialize(entry))

    def _journaled(self) -> bool:
        return (self._journal_path is not None
                or self.replication_sink is not None)

    def _serialize(self, entry: dict) -> str:
        """`entry` as the line the next _hand_over emits."""
        entry["q"] = self._seq + 1
        entry["e"] = self._epoch
        return json.dumps(entry)

    def _hand_over(self, line: str) -> None:
        self._seq += 1
        _count_journal_entries()
        if self._journal_path is not None:
            _count_journal_bytes(len(line))
            self._log_line(line)
        if self.replication_sink is not None:
            _count_journal_bytes(len(line))
            if self._batch_depth > 0:
                self._repl_buf.append((self._seq, line))
            else:
                self.replication_sink([(self._seq, line)])

    def _log_line(self, line: str) -> None:
        # caller holds the lock (all mutators do)
        if self._batch_depth > 0:
            self._batch_buf.append(line)
            return
        self._append_lines([line])

    def _append_lines(self, lines: list[str]) -> None:
        if self._journal_file is None:
            self._journal_file = open(self._journal_path, "a",
                                      encoding="utf-8")
        data = "".join(ln + "\n" for ln in lines)
        self._journal_file.write(data)
        self._journal_file.flush()
        if self._fsync:
            os.fsync(self._journal_file.fileno())
        self._journal_entries += len(lines)
        self._journal_bytes += len(data)
        if (self._journal_bytes >= self._journal_max_bytes
                or self._journal_entries >= self._journal_max_entries):
            self.flush()

    def flush(self) -> None:
        """Compact: write the full snapshot (tmp + atomic rename), then
        truncate the journal. Also the explicit snapshot entry point the
        daemon calls on shutdown."""
        if self._path is None:
            return
        # serialize AND write under the lock: concurrent flushes from
        # executor threads must not interleave on the shared tmp file
        with self._lock:
            doc = self._snapshot_doc_locked()
            tmp = self._path.with_suffix(f".tmp{threading.get_ident()}")
            if self._fsync:
                # the WAL guarantee must survive compaction: the snapshot
                # data (and its directory entry) must be on disk BEFORE the
                # journal is unlinked, or power loss between the two loses
                # every fsynced record
                with open(tmp, "w", encoding="utf-8") as f:
                    f.write(json.dumps(doc))
                    f.flush()
                    os.fsync(f.fileno())
                tmp.replace(self._path)
                dir_fd = os.open(str(self._path.parent), os.O_RDONLY)
                try:
                    os.fsync(dir_fd)
                finally:
                    os.close(dir_fd)
            else:
                tmp.write_text(json.dumps(doc))
                tmp.replace(self._path)
            if self._journal_file is not None:
                self._journal_file.close()
                self._journal_file = None
            if self._journal_path is not None and self._journal_path.exists():
                self._journal_path.unlink()
            self._journal_entries = 0
            self._journal_bytes = 0
            self._compactions += 1
            _M_COMPACTIONS.inc()

    def _snapshot_doc_locked(self) -> dict:
        doc = {t: [r.to_dict() for r in rows.values()]
               for t, rows in self._tables.items()}
        # replication metadata rides the snapshot: a standby installing it
        # (or this store reloading it) resumes sequence numbering and the
        # fencing epoch exactly where the journal left off. Old readers
        # iterate _TABLES only, so the extra key is forward-compatible.
        doc["_meta"] = {"seq": self._seq, "epoch": self._epoch}
        return doc

    def snapshot_doc(self) -> dict:
        """Full-state snapshot for standby catch-up (the same document
        `flush` writes to disk, including the `_meta` seq/epoch)."""
        with self._lock:
            return self._snapshot_doc_locked()

    def install_snapshot(self, doc: dict) -> None:
        """Replace ALL state with a primary's snapshot (standby bootstrap
        or catch-up after a stream gap), then persist locally so a standby
        restart doesn't re-fetch. Sequence numbering and epoch resume from
        the snapshot's `_meta`."""
        with self._lock:
            self._tables = {t: {} for t in _TABLES}
            self._load_doc(doc)
            meta = doc.get("_meta") or {}
            self._seq = int(meta.get("seq", self._seq))
            self._epoch = int(meta.get("epoch", self._epoch))
            self.flush()

    # ------------------------------------------------------------------
    # replication (primary journal shipping -> standby apply)
    # ------------------------------------------------------------------

    @property
    def seq(self) -> int:
        with self._lock:
            return self._seq

    @property
    def epoch(self) -> int:
        with self._lock:
            return self._epoch

    def bump_epoch(self) -> int:
        """Primary promotion: advance the fencing epoch by one and journal
        the transition (it replicates and persists like any mutation), so
        every entry the NEW primary emits outranks the old one's."""
        with self._lock:
            self._epoch += 1
            self._emit({"op": "epoch"})
            return self._epoch

    def apply_replicated(self, entries: list[tuple[int, str]]) -> int:
        """Standby-side: apply sequence-numbered journal lines shipped by
        the primary. Enforces the two stream invariants:

          * gap detection — entries must arrive at exactly seq+1; a skip
            raises ReplicationGap (the standby re-syncs from a snapshot);
          * fencing — an entry whose epoch is below this store's raises
            ReplicationFenced (zombie ex-primary; never applied).

        Applied entries are re-journaled locally (when this store has a
        path) so a promoted standby is durable without a re-snapshot.
        Returns the number of entries applied."""
        applied = 0
        with self._lock:
            for seq, line in entries:
                entry = json.loads(line)
                epoch = int(entry.get("e", self._epoch))
                # fencing FIRST: a zombie's entry must be refused loudly
                # even when its seq falls inside already-applied history
                if epoch < self._epoch:
                    _M_FENCING.inc(side="store")
                    raise ReplicationFenced(
                        f"entry seq={seq} epoch={epoch} < local epoch "
                        f"{self._epoch}: refusing zombie write")
                if seq <= self._seq:
                    # already applied (a batch queued before a snapshot
                    # resync): replay is idempotent by sequence — skip
                    # instead of forcing another full resync
                    continue
                if seq != self._seq + 1:
                    raise ReplicationGap(
                        f"stream gap: got seq={seq}, expected "
                        f"{self._seq + 1}")
                self._apply_entry(entry)
                self._seq = seq
                self._epoch = epoch
                if self._journal_path is not None:
                    self._log_line(line)
                applied += 1
        return applied

    def _apply_entry(self, entry: dict, notify: bool = True) -> None:
        """Apply one decoded journal entry to the tables (shared by local
        replay and the replication stream). Caller holds the lock. Local
        boot replay passes notify=False — observers see live mutations,
        not recovery; the replication stream notifies (the standby's CDC
        hooks and metrics see applied entries as the mutations they are)."""
        op = entry.get("op")
        if op == "epoch":
            self._epoch = int(entry.get("e", self._epoch))
            return
        table = entry.get("t")
        cls = _TABLES.get(table)
        if cls is None:
            return
        if op == "put":
            try:
                rec = cls.from_dict(entry["r"])
            except (KeyError, TypeError):
                return
            self._put(table, rec)
            if notify:
                self._notify("put", table, rec)
        elif op == "upd":
            rows = self._tables[table]
            known = {f.name for f in fields(cls)}
            for rid, changes in entry["u"].items():
                rec = rows.get(rid)
                if rec is None:
                    continue
                self._set_fields(table, rec, {
                    k: v for k, v in changes.items() if k in known})
                rec._coerce()
                rec.updated_at = entry["at"]
                if notify:
                    self._notify("put", table, rec)
        elif op == "mrg":
            rec = self._tables[table].get(entry.get("id"))
            if rec is None:
                return
            known = {f.name for f in fields(cls)}
            _merge_keys(rec, {k: v for k, v in entry["set"].items()
                              if k in known},
                        {k: v for k, v in entry["drop"].items()
                         if k in known})
            rec.updated_at = entry["at"]
            if notify:
                self._notify("put", table, rec)
        elif op == "del":
            rid = entry.get("id")
            if self._pop(table, rid) and notify:
                self._notify("del", table, rid)

    def _load(self) -> None:
        doc = json.loads(self._path.read_text())
        self._load_doc(doc)
        meta = doc.get("_meta") or {}
        self._seq = int(meta.get("seq", 0))
        self._epoch = int(meta.get("epoch", 1))

    def _load_doc(self, doc: dict) -> None:
        for table, cls in _TABLES.items():
            for row in doc.get(table, []):
                rec = cls.from_dict(row)
                self._tables[table][rec.id] = rec
        self._reindex()

    def _replay_journal(self) -> None:
        """Apply surviving journal entries over the loaded snapshot.
        Tolerates exactly one torn FINAL line (crash mid-append); an
        undecodable line anywhere else means real corruption, and replay
        STOPS there with a loud warning — applying later entries over a
        lost one could resurrect deleted rows or drop updates silently.
        Unknown tables are skipped (forward compatibility); replay over an
        already-compacted snapshot is idempotent by construction."""
        text = self._journal_path.read_text(encoding="utf-8", errors="replace")
        lines = [ln for ln in text.splitlines() if ln.strip()]
        for i, line in enumerate(lines):
            try:
                entry = json.loads(line)
            except ValueError:
                if i == len(lines) - 1:
                    break    # torn tail: the expected crash artifact
                from ..obs import get_logger
                get_logger("cp.store").warning(
                    "journal corrupt at line %d of %d; replay stopped there "
                    "(%d trailing entries NOT applied)",
                    i + 1, len(lines), len(lines) - i - 1)
                break
            self._apply_entry(entry, notify=False)
            # resume sequence numbering past the surviving tail (entries
            # predating the seq field leave the counter where _load set it)
            if "q" in entry:
                self._seq = max(self._seq, int(entry["q"]))
