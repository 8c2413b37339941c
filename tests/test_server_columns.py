"""Store keeps the servers' columns (PR 42).

`PlacementService` read capacity, what is booked and who is schedulable by
walking every server record at every solve. The store now keeps those
columns (`Store.server_columns`): a write of a server record marks it, and
the next read re-reads the marked records into their rows. Columns are no
slower than the walk at any size: what can go wrong is a stale row, a write
the columns never heard of. What is pinned here:

  * after every kind of mutation, on the store that made it and on a store
    that reloaded, replayed, installed or was streamed it, the columns are
    `_booked_columns` and `Server.schedulable` of the table's records bit
    for bit, in table order, with the order `Store.list` gives and the
    record `server_by_slug` returns for every slug
  * two records with one slug: the first in table order, and once that is
    deleted the next
  * a few hundred random mutations interleaved with reads end equal
  * a read after k writes re-reads k records, a read after none re-reads
    none, and a server that enters, leaves or is renamed is a rebuild
  * what a read handed out is never written again, by the store or by the
    reader
  * writers on threads beside a reader lose no write
"""

from __future__ import annotations

import random
import sys
import threading

import numpy as np
import pytest

from fleetflow_tpu.cp.models import Server, ServerAllocated, ServerCapacity
from fleetflow_tpu.cp.placement import _booked_columns
from fleetflow_tpu.cp.store import ServerColumns, Store
from fleetflow_tpu.obs.metrics import REGISTRY

SLUGS = [f"n{i}" for i in range(6)]
ABSENT = ["nope", "", "n60", "renamed", "extra"]


def _same(got: np.ndarray, want: np.ndarray) -> None:
    """Bit for bit: dtype, shape and every byte."""
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


def _agrees(store: Store, slugs=SLUGS + ABSENT) -> ServerColumns:
    """The columns are the records: every column against the table as it
    stands, walked here record by record."""
    view = store.server_columns()
    records = list(store._tables["servers"].values())
    assert view.ids == tuple(store._tables["servers"]) and len(view) == len(records)
    assert all(a is b for a, b in zip(view.records, records))
    assert view.slugs == tuple(s.slug for s in records)
    capacity, booked = _booked_columns(records)
    _same(view.capacity, np.ascontiguousarray(capacity))
    _same(view.booked, booked)
    _same(view.schedulable,
          np.array([s.schedulable for s in records], dtype=bool))
    assert view.tenant.tolist() == [s.tenant for s in records]
    _same(view.created_at, np.array([s.created_at for s in records],
                                    dtype=np.float64))
    # the order store.list gives, which pt.node_names inherits
    assert [view.ids[i] for i in view.order.tolist()] \
        == [s.id for s in store.list("servers")]
    for slug in slugs:
        rec = store.server_by_slug(slug)
        row = view.row_of.get(slug)
        assert (row is None) == (rec is None), slug
        if rec is not None:
            assert view.records[row] is rec
    assert view.rows(slugs).tolist() == [view.row_of.get(s, -1) for s in slugs]
    held = {}
    for i, s in enumerate(records):
        held.setdefault(s.slug, []).append(i)
    assert dict(view.row_of) == {slug: rows[0] for slug, rows in held.items()}
    assert dict(view.also) == {slug: tuple(rows[1:])
                               for slug, rows in held.items() if rows[1:]}
    assert sorted(view.holders(slugs)) == sorted(
        i for slug in set(slugs) for i in held.get(slug, ()))
    assert dict(view.id_rows) == {s.id: i for i, s in enumerate(records)}
    return view


def _seeded(path=None) -> Store:
    store = Store(path)
    for j, slug in enumerate(SLUGS):
        store.create("servers", Server(
            slug=slug, tenant="default" if j % 2 else "acme",
            status="online" if j % 3 else "offline",
            capacity=ServerCapacity(4.0 + j, 1024.0 * (j + 1), 9e4),
            allocated=ServerAllocated(cpu=0.25 * j, memory=10.0 * j,
                                      reserved_cpu=0.125 * (j % 2))))
    return store


def _alloc(cpu: float) -> ServerAllocated:
    return ServerAllocated(cpu=cpu, memory=3.0 * cpu, disk=0.5,
                           reserved_memory=7.0)


# --------------------------------------------------------------------------
# every place a server record is written
# --------------------------------------------------------------------------

def _create(store, _tmp):
    store.create("servers", Server(slug="extra", tenant="default",
                                   status="online"))
    return store


def _register_upsert(store, _tmp):
    store.register_server("n2", hostname="again", status="online",
                          capacity=ServerCapacity(1.0, 2.0, 3.0))
    return store


def _update_allocated(store, _tmp):
    store.update("servers", store.server_by_slug("n3").id,
                 allocated=_alloc(1.5))
    return store


def _update_capacity(store, _tmp):
    store.update("servers", store.server_by_slug("n3").id,
                 capacity=ServerCapacity(0.5, 0.25, 0.125))
    return store


def _update_many(store, _tmp):
    assert store.update_many("servers", {
        store.server_by_slug(slug).id: {"allocated": _alloc(0.1 + j)}
        for j, slug in enumerate(SLUGS[1:5])} | {"server_gone": {}}) == 4
    return store


def _cordon(store, _tmp):
    store.update("servers", store.server_by_slug("n1").id,
                 scheduling_state="cordoned")
    return store


def _heartbeat(store, _tmp):
    assert not store.server_by_slug("n0").schedulable
    store.heartbeat("n0", version="1.2")        # offline -> online
    assert store.server_by_slug("n0").schedulable
    return store


def _bulk_server_status(store, _tmp):
    assert store.bulk_server_status(
        {"n0": "online", "n4": "offline", "nope": "offline"}) == 2
    return store


def _update_slug(store, _tmp):
    store.update("servers", store.server_by_slug("n3").id, slug="renamed")
    return store


def _update_tenant(store, _tmp):
    store.update("servers", store.server_by_slug("n3").id, tenant="other")
    return store


def _update_created_at(store, _tmp):
    """The listing order is by created_at: the last record goes first."""
    store.update("servers", store.server_by_slug("n5").id, created_at=1.0)
    assert store.list("servers")[0].slug == "n5"
    return store


def _delete(store, _tmp):
    assert store.delete("servers", store.server_by_slug("n1").id)
    return store


def _delete_and_create_again(store, _tmp):
    """The same id, now last in table order."""
    old = store.server_by_slug("n1")
    store.delete("servers", old.id)
    store.create("servers", Server(id=old.id, slug="n1", tenant="default"))
    assert list(store._tables["servers"])[-1] == old.id
    return store


def _create_over_an_id(store, _tmp):
    old = store.server_by_slug("n1")
    store.create("servers", Server(
        id=old.id, slug="n1", tenant=old.tenant, status="online",
        created_at=old.created_at, allocated=_alloc(2.0)))
    return store


def _duplicate_slug(store, _tmp):
    first = store.server_by_slug("n4")
    store.create("servers", Server(slug="n4", tenant="other",
                                   allocated=_alloc(3.0)))
    view = _agrees(store)
    assert view.records[view.row_of["n4"]] is first
    store.delete("servers", first.id)
    return store


def _batch(store, _tmp):
    with store.batch():
        store.delete("servers", store.server_by_slug("n0").id)
        store.register_server("extra", status="online")
        store.update("servers", store.server_by_slug("n5").id,
                     allocated=_alloc(0.75))
    return store


def _journal_replay(store, tmp):
    """A fresh Store on the same path finds no snapshot, only the journal:
    `put`, `upd` (whose `allocated` arrives as a plain dict) and `del`."""
    _update_many(store, tmp)
    _cordon(store, tmp)
    _delete(store, tmp)
    assert not (tmp / "db").exists()
    assert '"op": "upd"' in (tmp / "db.journal").read_text()
    return Store(str(tmp / "db"))


def _flush_reload(store, tmp):
    _update_many(store, tmp)
    store.flush()
    _update_allocated(store, tmp)       # journal tail
    return Store(str(tmp / "db"))


def _install_snapshot(store, _tmp):
    _update_many(store, _tmp)
    standby = _seeded()        # other ids under the same slugs, all replaced
    standby.register_server("stale")
    _agrees(standby)           # its columns are built, and then dropped
    standby.install_snapshot(store.snapshot_doc())
    return standby


def _streamed(mutate):
    """A standby fed the primary's journal, entry by entry, whose columns
    are read before and after."""
    def case(_store, tmp):
        primary, standby = Store(), Store()
        primary.replication_sink = standby.apply_replicated
        for slug in SLUGS:
            primary.create("servers", Server(slug=slug, tenant="default"))
        _agrees(standby)
        mutate(primary, tmp)
        _agrees(primary)
        return standby
    return case


CASES = {
    "create": _create,
    "register_upsert": _register_upsert,
    "update_allocated": _update_allocated,
    "update_capacity": _update_capacity,
    "update_many": _update_many,
    "cordon": _cordon,
    "heartbeat": _heartbeat,
    "bulk_server_status": _bulk_server_status,
    "update_slug": _update_slug,
    "update_tenant": _update_tenant,
    "update_created_at": _update_created_at,
    "delete": _delete,
    "delete_and_create_again": _delete_and_create_again,
    "create_over_an_id": _create_over_an_id,
    "duplicate_slug_and_its_first_holder_deleted": _duplicate_slug,
    "batch": _batch,
    "journal_replay": _journal_replay,
    "flush_reload": _flush_reload,
    "install_snapshot": _install_snapshot,
    "replicated_put": _streamed(_create),
    "replicated_upd": _streamed(_update_many),
    "replicated_put_renames": _streamed(_update_slug),
    "replicated_del": _streamed(_delete),
}


@pytest.mark.parametrize("case", CASES)
def test_columns_are_the_records_after(case, tmp_path):
    store = _seeded(str(tmp_path / "db"))
    _agrees(store)
    after = CASES[case](store, tmp_path)
    _agrees(after)
    if after is not store:
        _agrees(store)


def test_a_promoted_standby_keeps_its_columns():
    primary, standby = Store(), Store()
    primary.replication_sink = standby.apply_replicated
    for slug in SLUGS:
        primary.register_server(slug, status="online")
    _agrees(standby)
    _update_many(primary, None)
    primary.delete("servers", primary.server_by_slug("n0").id)
    standby.bump_epoch()
    _agrees(standby)
    standby.update_many("servers", {
        standby.server_by_slug("n2").id: {"allocated": _alloc(9.0)}})
    standby.register_server("n0", status="online")      # gone, so inserted
    view = _agrees(standby)
    assert view.booked[view.row_of["n2"]].tolist() == [9.0, 34.0, 0.5]


# --------------------------------------------------------------------------
# a random walk over the mutations, read as it goes
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_random_mutations_interleaved_with_reads_end_equal(seed, tmp_path):
    rng = random.Random(f"server-columns:{seed}")
    pool = [f"r{i}" for i in range(12)]        # few slugs: collisions
    standby = Store()

    def opened():
        store = Store(str(tmp_path / "db"), journal_max_entries=64)
        store.replication_sink = standby.apply_replicated
        return store

    primary = opened()

    def some_ids(k=1):
        ids = list(primary._tables["servers"])
        return rng.sample(ids, min(k, len(ids))) or ["server_none"]

    for step in range(400):
        op = rng.choice(["register", "create", "rename", "status", "book",
                         "book_many", "delete", "bulk", "heartbeat", "batch",
                         "reload", "read", "read"])
        if op == "register":
            primary.register_server(rng.choice(pool), hostname=f"h{step}")
        elif op == "create":           # a second record under a live slug
            primary.create("servers", Server(
                slug=rng.choice(pool), tenant=rng.choice(["default", "t"]),
                capacity=ServerCapacity(rng.random(), 8.0, 1.0)))
        elif op == "rename":
            primary.update("servers", some_ids()[0], slug=rng.choice(pool))
        elif op == "status":
            primary.update("servers", some_ids()[0], scheduling_state=(
                rng.choice(["schedulable", "cordoned", "draining"])))
        elif op == "book":
            primary.update("servers", some_ids()[0],
                           allocated=_alloc(rng.random()))
        elif op == "book_many":
            primary.update_many("servers", {
                i: {"allocated": _alloc(rng.random())}
                for i in some_ids(5)})
        elif op == "delete":
            primary.delete("servers", some_ids()[0])
        elif op == "bulk":
            primary.bulk_server_status(
                {s: rng.choice(["online", "offline"])
                 for s in rng.sample(pool, 4)})
        elif op == "heartbeat":
            primary.heartbeat(rng.choice(pool))
        elif op == "batch":
            with primary.batch():
                primary.delete("servers", some_ids()[0])
                primary.register_server(rng.choice(pool))
                primary.update("servers", some_ids()[0],
                               allocated=_alloc(rng.random()))
        elif op == "reload":    # a restart: snapshot + surviving journal
            if primary._journal_file is not None:
                primary._journal_file.close()
            primary = opened()
        else:
            _agrees(primary, pool)
            if step % 3 == 0:
                _agrees(standby, pool)
    _agrees(primary, pool)
    _agrees(standby, pool)


# --------------------------------------------------------------------------
# what a read costs, by the counters
# --------------------------------------------------------------------------

def _counts():
    return tuple(REGISTRY.get(f"fleet_store_server_columns_{name}_total")
                 .value() for name in ("reads", "rows", "rebuilds"))


def _spent(since):
    return tuple(int(now - then) for now, then in zip(_counts(), since))


def test_a_read_re_reads_what_was_written_and_nothing_else():
    store = Store()
    for i in range(500):
        store.register_server(f"node-{i}", status="online")
    ids = list(store._tables["servers"])
    c0 = _counts()
    first = store.server_columns()
    assert _spent(c0) == (1, 500, 1)            # the first read builds
    c0 = _counts()
    assert store.server_columns() is first      # nothing written: the same
    assert _spent(c0) == (1, 0, 0)
    # k writes, some records twice: k records re-read, once each
    store.update_many("servers", {i: {"allocated": _alloc(1.0)}
                                  for i in ids[100:225]})
    store.update_many("servers", {i: {"allocated": _alloc(2.0)}
                                  for i in ids[200:225]})
    store.heartbeat("node-7")
    c0 = _counts()
    second = _agrees(store, ["node-7", "node-100", "node-499"])
    assert _spent(c0) == (1, 126, 0)
    assert second.members == first.members and second.version > first.version
    assert second.ids is first.ids and second.row_of is first.row_of
    # a server enters, one leaves, one is renamed: each a rebuild
    for change in (lambda: store.register_server("node-new"),
                   lambda: store.delete("servers", ids[3]),
                   lambda: store.update("servers", ids[4], slug="node-x")):
        before = store.server_columns()
        change()
        c0 = _counts()
        after = _agrees(store, ["node-new", "node-3", "node-4", "node-x"])
        assert _spent(c0) == (1, len(after), 1)
        assert after.members > before.members
        assert after.version > before.version


def test_what_was_handed_out_is_never_written_again():
    store = _seeded()
    view = store.server_columns()
    for name in ("capacity", "booked", "schedulable", "tenant", "created_at",
                 "order"):
        column = getattr(view, name)
        assert not column.flags.writeable, name
        with pytest.raises(ValueError):
            column[0] = column[0]
    with pytest.raises(TypeError):
        view.row_of["n0"] = 3
    with pytest.raises(TypeError):
        view.id_rows["x"] = 3
    assert isinstance(view.ids, tuple) and isinstance(view.slugs, tuple)
    booked, schedulable = view.booked.copy(), view.schedulable.copy()
    rec = store.server_by_slug("n2")
    store.update("servers", rec.id, allocated=_alloc(5.0), status="offline")
    later = store.server_columns()
    assert later is not view and later.booked is not view.booked
    # the value read before the write still says what was true then
    _same(view.booked, booked)
    _same(view.schedulable, schedulable)
    assert later.booked[view.row_of["n2"]].tolist() == [5.0, 22.0, 0.5]
    assert not later.schedulable[view.row_of["n2"]]


def test_scatter_is_by_slug_over_its_own_keys():
    store = _seeded()
    store.create("servers", Server(slug="n4", tenant="other"))
    view = store.server_columns()
    by_slug = {"n4": np.array([1.0, 2.0, 3.0]), "gone": np.ones(3),
               "n0": np.array([4.0, 5.0, 6.0])}
    out = view.scatter(by_slug)
    want = np.zeros((7, 3))
    want[[4, 6]] = by_slug["n4"]        # every record that carries the slug
    want[0] = by_slug["n0"]
    _same(out, want)
    _same(view.scatter({}), np.zeros((7, 3)))
    _same(view.scatter({"gone": np.ones(3)}), np.zeros((7, 3)))
    assert Store().server_columns().scatter(by_slug).shape == (0, 3)


def test_an_empty_table_has_empty_columns():
    view = _agrees(Store())
    assert len(view) == 0 and view.capacity.shape == (0, 3)
    assert view.rows(["a"]).tolist() == [-1] and view.holders(["a"]) == []


# --------------------------------------------------------------------------
# writers beside a reader
# --------------------------------------------------------------------------

def test_writers_on_threads_lose_no_write():
    store = Store()
    for i in range(64):
        store.register_server(f"node-{i}", status="online")
    ids = list(store._tables["servers"])
    stop = threading.Event()
    failures: list[BaseException] = []

    def writer(k: int):
        rng = random.Random(k)
        try:
            while not stop.is_set():
                store.update_many("servers", {
                    i: {"allocated": _alloc(rng.random())}
                    for i in rng.sample(ids, 8)})
                store.heartbeat(f"node-{rng.randrange(64)}")
                if rng.random() < 0.05:
                    store.register_server(f"late-{k}-{rng.randrange(4)}")
        except BaseException as e:      # noqa: BLE001 - reported below
            failures.append(e)

    def reader():
        try:
            while not stop.is_set():
                view = store.server_columns()
                assert len(view.ids) == view.booked.shape[0]
        except BaseException as e:      # noqa: BLE001 - reported below
            failures.append(e)

    threads = [threading.Thread(target=writer, args=(k,)) for k in range(6)]
    threads += [threading.Thread(target=reader) for _ in range(2)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        stop.wait(1.0)
        stop.set()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not failures, failures
    _agrees(store, [f"node-{i}" for i in range(64)])
