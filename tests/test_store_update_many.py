"""Store.update_many: a batched partial update with one journal entry.

What a commit's server writes go through (cp/placement.py
`_write_allocations`): the tables, the index, `updated_at` and the
observers see what `Store.update` one by one would show them; the
journal and the replication stream get the changed fields of every
record in one `upd` entry, replayable over a snapshot that already holds
them, split where a line would pass `replication.SNAPSHOT_CHUNK`.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json

import pytest

from fleetflow_tpu.cp import ServerConfig, start
from fleetflow_tpu.cp import models
from fleetflow_tpu.cp.models import (Server, ServerAllocated, ServerCapacity,
                                     ServerLabelsRec, StageRecord)
from fleetflow_tpu.cp.replication import SNAPSHOT_CHUNK, Replicator
from fleetflow_tpu.cp.store import (JOURNAL_LINE_MAX, _TABLES,
                                    ReplicationFenced, ReplicationGap, Store)
from fleetflow_tpu.obs.metrics import REGISTRY


def _store(n: int = 6, path=None, **kw) -> Store:
    """A store on a fixed clock (100.0 while the servers are created),
    with `n` servers n0..: ids s0.. so that two stores built alike hold
    equal records."""
    store = Store(str(path) if path else None, clock=lambda: 100.0, **kw)
    for i in range(n):
        store.create("servers", Server(
            id=f"s{i}", slug=f"n{i}", status="online", hostname=f"h{i}",
            labels=ServerLabelsRec(tier="web", clazz="c5"),
            capacity=ServerCapacity(cpu=32, memory=65536, disk=99999)))
    return store


def _alloc(i: int) -> ServerAllocated:
    return ServerAllocated(cpu=0.1 * (i + 1), memory=500.0 * (i + 1),
                           disk=1.0 * i, reserved_cpu=0.5)


def _changes(ids) -> dict[str, dict]:
    return {f"s{i}": {"allocated": _alloc(i)} for i in ids}


def _tables(store: Store) -> dict:
    doc = store.snapshot_doc()
    doc.pop("_meta")
    return doc


def _stream(store: Store) -> list[tuple[int, str]]:
    stream: list[tuple[int, str]] = []
    store.replication_sink = stream.extend
    return stream


# --------------------------------------------------------------------------
# (a) the same state and the same observer calls as update, one by one
# --------------------------------------------------------------------------

def test_update_many_leaves_what_update_one_by_one_leaves():
    many, single = _store(), _store()
    seen: dict[str, list] = {"many": [], "single": []}
    for name, store in (("many", many), ("single", single)):
        store._clock = lambda: 250.0
        store.subscribe(lambda op, table, rec, log=seen[name]: log.append(
            (op, table, rec.id, rec.slug, rec.allocated, rec.updated_at)))
    # an index key among the changes: n4 is renamed in the same batch
    changes = _changes([3, 0, 4])
    changes["s4"]["slug"] = "renamed"
    assert many.update_many("servers", changes) == 3
    for rid, fields in changes.items():
        assert single.update("servers", rid, **fields) is not None
    assert _tables(many) == _tables(single)
    assert many._index == single._index
    assert seen["many"] == seen["single"] and len(seen["many"]) == 3
    assert [call[2] for call in seen["many"]] == ["s3", "s0", "s4"]
    assert many.server_by_slug("renamed").id == "s4"
    assert many.server_by_slug("n4") is None
    assert many.get("servers", "s3").allocated == _alloc(3)
    assert {s.updated_at for s in many.list("servers")} == {100.0, 250.0}
    assert many.get("servers", "s1").updated_at == 100.0


@pytest.mark.parametrize("table", sorted(_TABLES))
def test_fields_dict_is_to_dict_field_by_field(table):
    """What an `upd` entry carries of a field is what a `put` carries."""
    cls = _TABLES[table]
    rec = cls(id="x")
    if cls is Server:
        rec = Server(id="x", allocated=_alloc(2),
                     labels=ServerLabelsRec(clazz="c5", extra={"a": "b"}))
    whole = rec.to_dict()
    names = [f.name for f in dataclasses.fields(cls)]
    assert rec.fields_dict(names) == whole
    for name in names:
        assert rec.fields_dict([name]) == {name: whole[name]}
    # a nested dataclass whose values are not all atoms takes asdict's walk
    labels = ServerLabelsRec(extra={"a": "b"})
    assert models._plain(labels) == dataclasses.asdict(labels)
    assert models._plain(labels)["extra"] is not labels.extra


# --------------------------------------------------------------------------
# (b) a file-backed store, closed and reopened
# --------------------------------------------------------------------------

def test_a_reopened_store_equals_the_live_one(tmp_path):
    path = tmp_path / "cp.json"
    store = _store(path=path, journal_max_bytes=1 << 30,
                   journal_max_entries=1 << 30)
    store.flush()                           # the servers are the snapshot
    store._clock = lambda: 250.0
    changes = _changes(range(5))
    changes["s2"]["labels"] = ServerLabelsRec(tier="db", clazz="m5")
    assert store.update_many("servers", changes) == 5
    store.update("servers", "s1", status="offline")
    journal = path.with_name(path.name + ".journal")
    lines = journal.read_text().splitlines()
    assert [json.loads(ln)["op"] for ln in lines] == ["upd", "put"]
    assert json.loads(lines[0])["u"]["s2"]["labels"]["class"] == "m5"
    want = _tables(store)
    kept = journal.read_bytes()

    reopened = Store(str(path))
    assert _tables(reopened) == want
    assert reopened.seq == store.seq
    assert reopened.server_by_slug("n2").labels == ServerLabelsRec(
        tier="db", clazz="m5")
    assert isinstance(reopened.get("servers", "s0").allocated, ServerAllocated)
    # replay twice over: the crash between snapshot rename and journal
    # truncate leaves a journal the snapshot already holds
    assert not journal.exists()
    journal.write_bytes(kept)
    again = Store(str(path))
    assert _tables(again) == want
    assert again._index == store._index


# --------------------------------------------------------------------------
# (c) a standby fed the stream
# --------------------------------------------------------------------------

def test_a_standby_fed_the_stream_equals_the_primary():
    primary = Store(clock=lambda: 100.0)
    stream = _stream(primary)
    for i in range(1000):
        primary.create("servers", Server(id=f"s{i}", slug=f"n{i}"))
    primary._clock = lambda: 250.0
    seen = []
    primary.subscribe(lambda *call: seen.append(call))
    mark = len(stream)
    assert primary.update_many("servers", _changes(range(1000))) == 1000
    assert len(seen) == 1000          # observers: once a record
    assert len(stream) == mark + 1    # the sink: once, before the return
    primary.update("servers", "s7", status="offline")

    standby = Store()
    applied = []
    standby.subscribe(lambda op, table, rec: applied.append((op, rec.id)))
    for entry in stream:              # entry by entry
        assert standby.apply_replicated([entry]) == 1
    assert [s.to_dict() for s in standby.list("servers")] == [
        s.to_dict() for s in primary.list("servers")]
    assert _tables(standby) == _tables(primary)
    assert standby.seq == primary.seq
    assert standby.get("servers", "s3").updated_at == 250.0
    # the standby's observers see each applied record as a put
    assert applied[1000:2000] == [("put", f"s{i}") for i in range(1000)]
    standby.bump_epoch()              # promoted
    assert standby.server_by_slug("n999").allocated == _alloc(999)


def test_a_gap_or_a_stale_epoch_on_an_upd_entry_raises():
    primary = _store()
    stream = _stream(primary)
    primary.update_many("servers", _changes([0]))
    primary.update_many("servers", _changes([1]))
    first, second = stream

    standby = Store()
    standby.install_snapshot(_store().snapshot_doc())
    with pytest.raises(ReplicationGap):
        standby.apply_replicated([second])
    assert standby.get("servers", "s1").allocated == ServerAllocated()
    assert standby.apply_replicated([first, second]) == 2
    assert standby.apply_replicated([second]) == 0      # replayed: skipped

    fenced = Store()
    fenced.install_snapshot(_store().snapshot_doc())
    fenced.bump_epoch()
    with pytest.raises(ReplicationFenced):
        fenced.apply_replicated([first])
    assert fenced.get("servers", "s0").allocated == ServerAllocated()


# --------------------------------------------------------------------------
# (d) an id the table lacks
# --------------------------------------------------------------------------

def test_an_id_the_table_lacks_is_skipped():
    primary = _store(3)
    stream = _stream(primary)
    changes = {"gone": {"allocated": _alloc(9)}, **_changes([1])}
    assert primary.update_many("servers", changes) == 1
    assert list(json.loads(stream[-1][1])["u"]) == ["s1"]
    assert primary.update_many("servers", {"gone": {"status": "x"}}) == 0
    assert primary.update_many("servers", {}) == 0
    assert len(stream) == 1           # nothing written, nothing journaled

    # a standby that lost the record meanwhile, and a field the record
    # does not have (a newer primary's): skipped, the rest applied
    standby = Store()
    standby.install_snapshot(primary.snapshot_doc())
    standby._pop("servers", "s2")
    line = json.dumps({"op": "upd", "t": "servers", "at": 300.0, "u": {
        "s2": {"status": "offline"},
        "s0": {"status": "offline", "not_a_field": 1}},
        "q": standby.seq + 1, "e": 1})
    assert standby.apply_replicated([(standby.seq + 1, line)]) == 1
    assert standby.get("servers", "s2") is None
    assert standby.get("servers", "s0").status == "offline"
    assert standby.get("servers", "s0").updated_at == 300.0
    assert not hasattr(standby.get("servers", "s0"), "not_a_field")
    assert standby.server_by_slug("n2") is None


# --------------------------------------------------------------------------
# (e) a batch larger than one line may be
# --------------------------------------------------------------------------

def test_the_line_limit_is_the_snapshot_chunk():
    assert JOURNAL_LINE_MAX == SNAPSHOT_CHUNK


def test_a_large_batch_is_split_into_lines_under_the_limit():
    primary = _store(5000)
    stream = _stream(primary)
    primary._clock = lambda: 250.0
    seq = primary.seq
    assert primary.update_many("servers", _changes(range(5000))) == 5000
    assert len(stream) > 1
    assert all(len(line) <= SNAPSHOT_CHUNK for _seq, line in stream)
    assert [s for s, _line in stream] == list(
        range(seq + 1, seq + 1 + len(stream)))
    entries = [json.loads(line) for _seq, line in stream]
    assert [e["q"] for e in entries] == [s for s, _line in stream]
    assert all(e["op"] == "upd" and e["at"] == 250.0 for e in entries)
    # every record once, in the order given
    assert [rid for e in entries for rid in e["u"]] == [
        f"s{i}" for i in range(5000)]
    standby = Store()
    standby.install_snapshot(_store(5000).snapshot_doc())
    assert standby.apply_replicated(stream) == len(stream)
    assert _tables(standby) == _tables(primary)


def test_uneven_records_are_cut_again_and_one_record_goes_whole():
    primary = Store()
    for i in range(8):
        primary.create("stages", StageRecord(id=f"st{i}", name=f"st{i}"))
    stream = _stream(primary)
    # seven small records and one that fills most of a line: the even cut
    # leaves a part too long, which is cut again
    big = ["x" * 1000] * (SNAPSHOT_CHUNK * 9 // 10 // 1004)
    changes = {f"st{i}": {"servers": ["y" * 1000] * 40} for i in range(8)}
    changes["st5"] = {"servers": big}
    assert primary.update_many("stages", changes) == 8
    assert len(stream) > 2
    assert all(len(line) <= SNAPSHOT_CHUNK for _seq, line in stream)
    assert [rid for _seq, line in stream
            for rid in json.loads(line)["u"]] == list(changes)
    # a record that no cut makes smaller is journaled as it is, like a put
    huge = {"st0": {"servers": ["z" * 1000] * 300}}
    assert primary.update_many("stages", huge) == 1
    assert len(stream[-1][1]) > SNAPSHOT_CHUNK
    standby = Store()
    for i in range(8):
        standby.create("stages", StageRecord(id=f"st{i}", name=f"st{i}"))
    standby._seq = stream[0][0] - 1
    assert standby.apply_replicated(stream) == len(stream)
    assert standby.get("stages", "st5").servers == big


def test_a_replicator_ships_a_5000_server_batch_to_a_live_standby(tmp_path):
    """A real primary and a real standby connection: the batch arrives as
    `append` events under the frame limit, and the standby neither
    detaches nor falls back to a snapshot."""
    # the servers are on disk before the primary starts, so the standby
    # bootstraps from a snapshot, in chunks (5,000 whole records in the
    # ring would be offered as one backlog frame, which is too large)
    path = tmp_path / "cp.json"
    _store(5000, path=path).flush()

    async def go():
        primary = await start(ServerConfig(self_heal=False,
                                           db_path=str(path)))
        db = primary.state.store
        assert len(db.list("servers")) == 5000
        standby = await start(ServerConfig(
            name="cp-b", self_heal=False,
            standby_of=f"{primary.host}:{primary.port}",
            standby_ping_interval_s=0.05, standby_lease_s=5.0,
            standby_grace_s=1.0))
        mirror = standby.state.store
        for _ in range(500):
            if (mirror.seq == db.seq
                    and primary.state.replicator.status()["standbys"]):
                break
            await asyncio.sleep(0.02)
        assert primary.state.replicator.status()["standbys"]
        catchups = standby.state.standby.replica.catchups
        seq = db.seq
        assert db.update_many("servers", _changes(range(5000))) == 5000
        assert db.seq > seq + 1
        for _ in range(500):
            if mirror.seq == db.seq:
                break
            await asyncio.sleep(0.02)
        assert mirror.seq == db.seq
        assert standby.state.standby.replica.catchups == catchups
        attached = primary.state.replicator.status()["standbys"]
        assert [sb["identity"] for sb in attached] == ["cp-b"]
        assert _tables(mirror) == _tables(db)
        assert mirror.server_by_slug("n4999").allocated == _alloc(4999)
        await standby.stop()
        await primary.stop()
    asyncio.run(asyncio.wait_for(go(), 120))


# --------------------------------------------------------------------------
# (f) a torn final line
# --------------------------------------------------------------------------

def test_a_torn_final_upd_line_is_dropped_whole(tmp_path):
    path = tmp_path / "cp.json"
    store = _store(path=path, journal_max_bytes=1 << 30,
                   journal_max_entries=1 << 30)
    store.flush()
    store.update("servers", "s5", status="offline")
    seq = store.seq
    store.update_many("servers", _changes(range(5)))
    journal = path.with_name(path.name + ".journal")
    data = journal.read_bytes()
    assert data.count(b"\n") == 2
    journal.write_bytes(data[:-40])         # the crash, mid-append
    reopened = Store(str(path))
    # all or nothing: no server of the batch was written
    assert all(s.allocated == ServerAllocated()
               for s in reopened.list("servers"))
    assert reopened.get("servers", "s5").status == "offline"
    assert reopened.seq == seq


# --------------------------------------------------------------------------
# (g) (h) what is serialised, and what is counted
# --------------------------------------------------------------------------

def test_a_store_with_neither_journal_nor_sink_serialises_nothing(
        monkeypatch):
    store = _store()
    seq = store.seq
    dumped = []
    monkeypatch.setattr(json, "dumps", lambda *a, **kw: dumped.append(a))
    monkeypatch.setattr(Server, "fields_dict",
                        lambda *a: dumped.append(a))
    assert store.update_many("servers", _changes(range(6))) == 6
    assert dumped == [] and store.seq == seq == 0
    assert store.get("servers", "s2").allocated == _alloc(2)


@pytest.mark.parametrize("n, lines", [(3, 1), (5000, None)])
def test_entries_count_lines_and_ops_count_records(n, lines):
    entries = REGISTRY.get("fleet_store_journal_entries_total")
    size = REGISTRY.get("fleet_store_journal_bytes_total")
    ops = REGISTRY.get("fleet_store_ops_total")
    store = _store(n)
    stream = _stream(store)
    e0, b0 = entries.value(), size.value()
    p0 = ops.value(table="servers", op="put")
    assert store.update_many("servers", _changes(range(n))) == n
    assert entries.value() - e0 == len(stream) == (lines or len(stream))
    assert size.value() - b0 == sum(len(line) for _seq, line in stream)
    assert ops.value(table="servers", op="put") - p0 == n
    # any op counts: a put and a del are an entry each
    store.update("servers", "s0", status="offline")
    store.delete("servers", "s1")
    assert entries.value() - e0 == len(stream)
    assert [json.loads(line)["op"] for _seq, line in stream[-2:]] == [
        "put", "del"]


def test_a_replicator_ring_spends_one_entry_on_a_commit_of_1000():
    store = _store(1000)
    ring = Replicator(store)
    store.update_many("servers", _changes(range(1000)))
    assert ring.status()["ring"] == {"entries": 1, "first_seq": store.seq}
