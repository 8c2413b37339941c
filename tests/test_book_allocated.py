"""Store.book_allocated: a commit's demand booked on its servers over arrays.

`PlacementService._write_allocations` used to book a commitment a record
at a time: a `server_by_slug` a slug, a `ServerAllocated` built by keyword,
and one `Store.update_many`, which rendered every record through
`fields_dict`, serialised the whole `upd` line, and where it was too long
threw it away and serialised each cut again. `book_allocated` does the same
work in one pass. What is pinned here, against that loop (kept below as
the reference) on two journaled stores of 5,000 servers with a replication
sink, at a stage's 2,000 records and at a redeploy's 4:

  * every server's `allocated` equal to the loop's to the bit — exact
    cancellations, negatives clamped at 0, -0.0 and a carried int among
    them — and `updated_at` equal, a new object on each written record
  * the sink's lines and the journal's text the loop's, character for
    character, and both the lines of the rule the loop's `update_many`
    cut by; each line under JOURNAL_LINE_MAX
  * the store reopened from its journal, and a standby fed the stream,
    equal to the live store
  * the counters moved by the loop's amounts
  * a slug no server carries skipped; a slug two servers carry booked on
    the first in table order
  * the servers' columns read the new booking
  * a cut batch serialised once a line
"""

from __future__ import annotations

import json
import struct

import numpy as np
import pytest

from fleetflow_tpu.cp.models import (Server, ServerAllocated, ServerCapacity,
                                     StageRecord)
from fleetflow_tpu.cp.placement import PlacementService, Reservation
from fleetflow_tpu.cp.store import JOURNAL_LINE_MAX, Store, booked_columns
from fleetflow_tpu.obs.metrics import REGISTRY

SERVERS = 5000
FIELDS = ("cpu", "memory", "disk", "reserved_cpu", "reserved_memory",
          "reserved_disk")


def _loop_book(store: Store, slugs, vectors) -> int:
    """The booking as it was, a record at a time: the reference."""
    changes = {}
    vectors = np.asarray(vectors, dtype=np.float64).tolist()
    for slug, (cpu, memory, disk) in zip(slugs, vectors):
        s = store.server_by_slug(slug)
        if s is None:
            continue
        a = s.allocated
        changes[s.id] = {"allocated": type(a)(
            cpu=max(a.cpu + cpu, 0.0),
            memory=max(a.memory + memory, 0.0),
            disk=max(a.disk + disk, 0.0),
            reserved_cpu=a.reserved_cpu,
            reserved_memory=a.reserved_memory,
            reserved_disk=a.reserved_disk,
        )}
    return store.update_many("servers", changes)


def _cut_lines(at, changed: dict, seq: int, epoch: int,
               table: str = "servers") -> list[str]:
    """The `upd` lines of `changed` by the rule the loop's store cut by:
    the whole entry serialised, and where it passes JOURNAL_LINE_MAX,
    even cuts by count, each cut again while it is too long."""
    lines: list[str] = []

    def log(part: dict) -> None:
        line = json.dumps({"op": "upd", "t": table, "at": at, "u": part,
                           "q": seq + len(lines) + 1, "e": epoch})
        if len(line) <= JOURNAL_LINE_MAX or len(part) == 1:
            lines.append(line)
            return
        ids = list(part)
        n = min(len(line) // (JOURNAL_LINE_MAX * 3 // 4) + 1, len(ids))
        for k in range(n):
            log({i: part[i] for i in
                 ids[len(ids) * k // n:len(ids) * (k + 1) // n]})

    log(changed)
    return lines


def _bits(a: ServerAllocated) -> tuple:
    return tuple((type(v), struct.pack("<d", v)) for v in
                 (getattr(a, name) for name in FIELDS))


def _held() -> np.ndarray:
    """(SERVERS, 3): what each server holds before the booking; every
    97th server's cpu is -0.0."""
    rng = np.random.default_rng(47)
    held = rng.integers(0, 40, size=(SERVERS, 3)) * np.array(
        [0.1, 500.0, 1024.0])
    held[::97, 0] = -0.0
    return held


def _world(path):
    """A journaled store of SERVERS servers on a fixed clock, the servers
    in its snapshot, and the list its replication sink fills. Built alike,
    two worlds hold equal records."""
    store = Store(str(path), clock=lambda: 100.0, journal_max_bytes=1 << 30,
                  journal_max_entries=1 << 30)
    with store.batch():
        for i, (cpu, memory, disk) in enumerate(_held().tolist()):
            store.create("servers", Server(
                id=f"server_{i:05d}", slug=f"node-{i}", status="online",
                capacity=ServerCapacity(4.0, 32768.0, 40960.0),
                allocated=ServerAllocated(
                    cpu=cpu, memory=memory, disk=disk,
                    reserved_cpu=1 if i % 89 == 0 else 0.0,
                    reserved_memory=250.5 if i % 13 == 0 else 0.0)))
        # a slug two servers carry: the first in table order is booked
        store.create("servers", Server(id="twin-a", slug="twin",
                                       status="online"))
        store.create("servers", Server(id="twin-b", slug="twin",
                                       status="online"))
    store.flush()
    stream: list[tuple[int, str]] = []
    store.replication_sink = stream.extend
    return store, stream


def _booking(n: int):
    """`n` slugs with their vectors: exact cancellations, returns that
    clamp at 0, random sums, and first node-0, whose cpu is -0.0 and whose
    reserved cpu an int, booked -0.0; among the slugs one no server
    carries and the twin."""
    rng = np.random.default_rng(n)
    held = _held()
    at = [0] + (1 + rng.choice(SERVERS - 1, n - 3, replace=False)).tolist()
    slugs = [f"node-{i}" for i in at] + ["gone", "twin"]
    vectors = np.round(rng.normal(0, 1, (n, 3)) * [0.4, 900.0, 700.0], 3)
    for k, i in enumerate(at[1:], 1):
        if k % 5 == 0:
            vectors[k] = -held[i]                       # back to 0, exactly
        elif k % 5 == 1:
            vectors[k] = -(held[i] + [1.0, 1.0, 1.0])   # past 0: clamped
    vectors[0, 0] = -0.0
    return slugs, vectors


def _counters() -> dict:
    ops = REGISTRY.get("fleet_store_ops_total")
    lookups = REGISTRY.get("fleet_store_lookups_total")
    scanned = REGISTRY.get("fleet_store_rows_scanned_total")
    return {
        "ops": ops.value(table="servers", op="put"),
        "index": lookups.value(table="servers", path="index"),
        "scan": lookups.value(table="servers", path="scan"),
        "rows": scanned.value(table="servers"),
        "entries": REGISTRY.get("fleet_store_journal_entries_total").value(),
        "bytes": REGISTRY.get("fleet_store_journal_bytes_total").value(),
    }


def _moved(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in before}


def _doc(store: Store) -> dict:
    doc = store.snapshot_doc()
    doc.pop("_meta")
    return doc


@pytest.mark.parametrize("n", [2000, 4])
def test_booking_is_the_record_loop_to_the_bit(tmp_path, n):
    loop, loop_stream = _world(tmp_path / "loop.json")
    bulk, bulk_stream = _world(tmp_path / "bulk.json")
    slugs, vectors = _booking(n)
    for store in (loop, bulk):
        store._clock = lambda: 250.0
        store.server_columns()
    snapshot, seq = bulk.snapshot_doc(), bulk.seq
    before = {rid: s.allocated for rid, s in bulk._tables["servers"].items()}
    seen = {"loop": [], "bulk": []}
    for name, store in (("loop", loop), ("bulk", bulk)):
        store.subscribe(lambda op, table, rec, log=seen[name]: log.append(
            (op, table, rec.id, _bits(rec.allocated), rec.updated_at)))

    c0 = _counters()
    written = _loop_book(loop, slugs, vectors)
    c1 = _counters()
    assert bulk.book_allocated(slugs, vectors) == written == n - 1
    c2 = _counters()
    assert _moved(c1, c2) == _moved(c0, c1)
    assert _moved(c1, c2)["index"] == n and _moved(c1, c2)["rows"] == n - 1
    assert _moved(c1, c2)["scan"] == 0 and _moved(c1, c2)["ops"] == n - 1

    # the records: to the bit, stamped alike, a new object where written
    assert seen["bulk"] == seen["loop"] and len(seen["bulk"]) == n - 1
    for rid, s in bulk._tables["servers"].items():
        mine = loop._tables["servers"][rid]
        assert _bits(s.allocated) == _bits(mine.allocated), rid
        assert s.allocated == mine.allocated
        assert s.updated_at == mine.updated_at
        assert type(s.allocated) is ServerAllocated
        if s.updated_at == 250.0:
            assert s.allocated is not before[rid]
        else:
            assert s.allocated is before[rid]
    table = bulk._tables["servers"]
    assert table["twin-a"].updated_at == 250.0
    assert table["twin-b"].updated_at == 100.0
    first = table["server_00000"].allocated
    assert str(first.cpu) == "-0.0" and type(first.reserved_cpu) is int

    # the journal: the loop's lines, and the lines of the loop's cut rule
    lines = [line for _seq, line in bulk_stream]
    assert lines == [line for _seq, line in loop_stream]
    ids = dict.fromkeys(s.id for s in map(loop.server_by_slug, slugs) if s)
    changed = {rid: {"allocated": loop.get("servers", rid).allocated.__dict__}
               for rid in ids}
    assert lines == _cut_lines(250.0, changed, seq, 1)
    assert all(len(line) <= JOURNAL_LINE_MAX for line in lines)
    assert (len(lines) > 1) == (n == 2000)
    entries = [json.loads(line) for line in lines]
    assert [e["q"] for e in entries] == [q for q, _line in bulk_stream]
    assert [rid for e in entries for rid in e["u"]] == list(ids)
    journal = tmp_path / "bulk.json.journal"
    assert journal.read_text() == (tmp_path / "loop.json.journal").read_text()

    # the columns read the new booking
    for store in (bulk, loop):
        view = store.server_columns()
        assert np.array_equal(view.booked, booked_columns(view.records)[1])
    assert np.array_equal(bulk.server_columns().booked,
                          loop.server_columns().booked)

    # reopened from its journal, and a standby fed the stream
    want = _doc(bulk)
    assert _doc(loop) == want
    assert _doc(Store(str(tmp_path / "bulk.json"))) == want
    standby = Store()
    standby.install_snapshot(snapshot)
    assert standby.apply_replicated(bulk_stream) == len(bulk_stream)
    assert _doc(standby) == want
    assert standby.seq == bulk.seq
    assert all(_bits(standby.get("servers", rid).allocated)
               == _bits(s.allocated) for rid, s in table.items())


def test_the_clamp_is_max_at_zero_for_signed_zeros_and_nan():
    """max(x, 0.0) keeps x unless 0.0 > x: a -0.0 sum stays -0.0 and a
    NaN stays NaN, as the loop leaves them."""
    cases = [(-0.0, -0.0), (0.0, -0.0), (-0.0, 0.0), (1.5, -1.5),
             (1.0, -2.0), (float("nan"), 1.0), (1.0, float("nan")),
             (2.0, float("-inf"))]
    stores = []
    for _ in range(2):
        store = Store(clock=lambda: 7.0)
        for i, (old, _d) in enumerate(cases):
            store.create("servers", Server(
                id=f"s{i}", slug=f"n{i}",
                allocated=ServerAllocated(cpu=old, memory=old, disk=old)))
        stream: list = []
        store.replication_sink = stream.extend
        stores.append((store, stream))
    slugs = [f"n{i}" for i in range(len(cases))]
    vectors = np.array([[d, d, d] for _old, d in cases])
    (loop, loop_stream), (bulk, bulk_stream) = stores
    assert _loop_book(loop, slugs, vectors) == len(cases)
    assert bulk.book_allocated(slugs, vectors) == len(cases)
    for i in range(len(cases)):
        assert _bits(bulk.get("servers", f"s{i}").allocated) == _bits(
            loop.get("servers", f"s{i}").allocated)
    assert bulk_stream == loop_stream
    assert "NaN" in bulk_stream[0][1]


def test_a_cut_batch_is_serialised_once_a_line(tmp_path, monkeypatch):
    """The loop's store serialised the whole entry, found it too long,
    threw it away and serialised each cut again: k + 1 serialisations for
    k lines. The booking renders the batch's numbers in one json.dumps and
    joins each line once, as it hands it over."""
    store, stream = _world(tmp_path / "cut.json")
    slugs, vectors = _booking(2000)
    dumps = json.dumps
    calls: list[int] = []
    monkeypatch.setattr(json, "dumps",
                        lambda *a, **kw: calls.append(1) or dumps(*a, **kw))
    joined: list[str] = []
    hand_over = Store._hand_over
    monkeypatch.setattr(Store, "_hand_over", lambda self, line: (
        joined.append(line), hand_over(self, line)))
    assert store.book_allocated(slugs, vectors) == 1999
    assert len(joined) == len(stream) > 1
    assert calls == [1]
    assert joined == [line for _seq, line in stream]

    # update_many: each record's fields rendered once, no line twice
    calls.clear()
    joined.clear()
    changes = {f"server_{i:05d}": {"allocated": ServerAllocated(
        cpu=0.1 * i + 1e-9, memory=float(i), disk=1.0 / (i + 3))}
        for i in range(2000)}
    assert store.update_many("servers", changes) == 2000
    assert len(joined) > 1
    assert len(calls) == 2000 + 1      # a record each, and the stamp


def _padded(sizes: list[int], line: int) -> dict:
    """Changes of `stages` records, one a size in `sizes` (characters of
    a `servers` list), the last padded so that the whole `upd` line of
    them is `line` characters long."""
    def changes(pad: int) -> dict:
        return {f"st{i}": {"servers": ["x" * (size + pad * (i == last))]}
                for i, size in enumerate(sizes)}
    last = len(sizes) - 1
    whole = len(json.dumps({"op": "upd", "t": "stages", "at": 9.0,
                            "u": changes(0), "q": 1, "e": 1}))
    return changes(line - whole)


@pytest.mark.parametrize("sizes, line, cuts", [
    ([100, 100], JOURNAL_LINE_MAX, 1),           # at the limit: one line
    ([100, 100], JOURNAL_LINE_MAX + 1, 2),       # a character past it
    ([3600] * 100, 370_000, 2),                  # three quarters full
    ([3600] * 100, 400_000, 3),
    ([900] * 7 + [10], 300_000, 4),              # cuts still too long
])
def test_a_batch_is_cut_where_the_whole_line_was(sizes, line, cuts):
    """A line's length is worked out from its parts, and the cuts are
    the ones the whole line's length gave: the same number, the same ids
    in each, in order."""
    store = Store(clock=lambda: 9.0)
    for i in range(len(sizes)):
        store.create("stages", StageRecord(id=f"st{i}", name=f"st{i}"))
    stream: list = []
    store.replication_sink = stream.extend
    store._seq = 0
    changes = _padded(sizes, line)
    want = _cut_lines(9.0, {rid: {"servers": c["servers"]}
                            for rid, c in changes.items()}, 0, 1, "stages")
    assert len(json.dumps({"op": "upd", "t": "stages", "at": 9.0,
                           "u": changes, "q": 1, "e": 1})) == line
    assert store.update_many("stages", changes) == len(sizes)
    assert [got for _seq, got in stream] == want
    assert len(want) == cuts


def test_a_store_with_neither_journal_nor_sink_serialises_nothing(
        monkeypatch):
    store = Store(clock=lambda: 3.0)
    for i in range(6):
        store.create("servers", Server(id=f"s{i}", slug=f"n{i}"))
    seq = store.seq
    dumped = []
    monkeypatch.setattr(json, "dumps", lambda *a, **kw: dumped.append(a))
    assert store.book_allocated([f"n{i}" for i in range(6)],
                                np.ones((6, 3))) == 6
    assert dumped == [] and store.seq == seq
    assert store.get("servers", "s2").allocated == ServerAllocated(1.0, 1.0,
                                                                   1.0)


def test_an_empty_booking_reads_the_clock_and_writes_nothing():
    store = Store(clock=lambda: 3.0)
    store.create("servers", Server(id="s0", slug="n0"))
    stream: list = []
    store.replication_sink = stream.extend
    c0 = _counters()
    assert store.book_allocated([], np.zeros((0, 3))) == 0
    assert store.book_allocated(["gone"], np.ones((1, 3))) == 0
    assert stream == []
    assert _moved(c0, _counters()) == {"ops": 0, "index": 1, "scan": 0,
                                       "rows": 0, "entries": 0, "bytes": 0}


def test_a_whole_commitment_goes_over_in_the_dicts_order(monkeypatch):
    """`_apply_allocation` hands the store the commitment's slugs and its
    vectors, signed, as one array in the dict's own order."""
    store = Store()
    for i in range(5):
        store.create("servers", Server(id=f"s{i}", slug=f"n{i}"))
    svc = PlacementService(store)
    demand = {f"n{i}": np.array([0.5 * i, 100.0 * i, 1.0])
              for i in (3, 0, 4)}
    demand["gone"] = np.ones(3)
    handed = []
    book = Store.book_allocated
    monkeypatch.setattr(Store, "book_allocated", lambda self, s, v: (
        handed.append((s, v)), book(self, s, v))[1])
    r = Reservation("r", "p/a", demand, {})
    assert svc._apply_allocation(r, +1.0) == 3
    assert svc._apply_allocation(r, -1.0) == 3
    (slugs, up), (_, down) = handed
    assert slugs == ["n3", "n0", "n4", "gone"]
    assert up.dtype == np.float64 and up.shape == (4, 3)
    assert np.array_equal(up, np.stack(list(demand.values())))
    assert np.array_equal(down, -up)
    assert all(s.allocated == ServerAllocated() for s in store.list("servers"))
