"""Store keeps an index on servers.slug (PR 30).

`server_by_slug` used to scan the servers table with a predicate; it now
reads an in-memory index that the store keeps wherever a server record
enters, leaves or is replaced. An index is no slower than the scan at any
size: what can go wrong is a stale one, a record the scan reaches and the
slug does not (or the reverse). What is pinned here:

  * after every kind of mutation, on the store that made it and on a
    store that reloaded, replayed, installed or was streamed it, a lookup
    by slug returns the very record the scan returns, for every slug the
    test ever used, present or absent
  * two records with one slug: the first in table order, and once that is
    deleted the next
  * a few hundred random mutations end with index == scan
  * fleet_store_rows_scanned_total counts 1 for an indexed hit and 0 for
    a miss; fleet_store_lookups_total{path} says which path answered
  * a first commitment through PlacementService.commit leaves the
    `allocated` the scan-based lookup leaves, writes one record a placed
    server and examines one row for each
"""

from __future__ import annotations

import random

import pytest

from fleetflow_tpu import obs
from fleetflow_tpu.core.parser import parse_kdl_string
from fleetflow_tpu.cp.models import Server, ServerCapacity
from fleetflow_tpu.cp.placement import PlacementService
from fleetflow_tpu.cp.store import Store
from fleetflow_tpu.obs import trace as obs_trace
from fleetflow_tpu.obs.metrics import REGISTRY

SLUGS = [f"n{i}" for i in range(6)]
ABSENT = ["nope", "", "n", "n60", "renamed", "extra"]


def _by_scan(store: Store, slug):
    return store.find_one("servers", lambda s: s.slug == slug)


def _agrees(store: Store, slugs=SLUGS + ABSENT) -> None:
    """Index == scan, record for record, and the index holds nothing the
    table does not."""
    for slug in slugs:
        assert store.server_by_slug(slug) is _by_scan(store, slug), slug
    table = store._tables["servers"]
    indexed = [rid for ids in store._index["servers"].values() for rid in ids]
    assert sorted(indexed) == sorted(table)
    for key, ids in store._index["servers"].items():
        assert ids and all(table[rid].slug == key for rid in ids)


def _seeded(path=None) -> Store:
    store = Store(path)
    for slug in SLUGS:
        store.create("servers", Server(slug=slug, tenant="default",
                                       hostname=f"h-{slug}"))
    return store


# --------------------------------------------------------------------------
# every place a server record enters, leaves or is replaced
# --------------------------------------------------------------------------

def _create(store, _tmp):
    store.create("servers", Server(slug="extra", tenant="default"))
    assert store.server_by_slug("extra").slug == "extra"
    return store


def _register_insert(store, _tmp):
    rec = store.register_server("extra", hostname="hx")
    assert store.server_by_slug("extra") is rec
    return store


def _register_upsert(store, _tmp):
    rec = store.register_server("n2", hostname="again")
    assert rec is store.server_by_slug("n2") and rec.hostname == "again"
    assert len(store.list("servers")) == len(SLUGS)
    return store


def _update_other_field(store, _tmp):
    rec = store.server_by_slug("n3")
    store.update("servers", rec.id, status="offline")
    assert store.server_by_slug("n3") is rec and rec.status == "offline"
    return store


def _update_slug(store, _tmp):
    rec = store.server_by_slug("n3")
    store.update("servers", rec.id, slug="renamed", status="offline")
    assert store.server_by_slug("n3") is None
    assert store.server_by_slug("renamed") is rec
    return store


def _update_slug_to_itself(store, _tmp):
    rec = store.server_by_slug("n3")
    store.update("servers", rec.id, slug="n3")
    assert store.server_by_slug("n3") is rec
    return store


def _delete(store, _tmp):
    assert store.delete("servers", store.server_by_slug("n1").id)
    assert store.server_by_slug("n1") is None
    assert not store.delete("servers", "server_gone")
    return store


def _create_over_an_id(store, _tmp):
    """create() with an id the table holds replaces that record, in its
    place in table order."""
    old = store.server_by_slug("n1")
    new = store.create("servers", Server(id=old.id, slug="extra",
                                         tenant="default"))
    assert store.server_by_slug("n1") is None
    assert store.server_by_slug("extra") is new
    return store


def _bulk_server_status(store, _tmp):
    assert store.bulk_server_status(
        {"n0": "offline", "n4": "offline", "nope": "offline"}) == 2
    assert store.server_by_slug("n4").status == "offline"
    return store


def _batch(store, _tmp):
    with store.batch():
        store.delete("servers", store.server_by_slug("n0").id)
        store.register_server("extra")
        store.update("servers", store.server_by_slug("n5").id,
                     slug="renamed")
    return store


def _journal_replay(store, tmp):
    """A fresh Store on the same path finds no snapshot, only the
    journal."""
    store.update("servers", store.server_by_slug("n3").id, slug="renamed")
    store.delete("servers", store.server_by_slug("n1").id)
    assert not (tmp / "db").exists() and (tmp / "db.journal").exists()
    return Store(str(tmp / "db"))


def _flush_reload(store, tmp):
    store.update("servers", store.server_by_slug("n3").id, slug="renamed")
    store.flush()
    store.delete("servers", store.server_by_slug("n1").id)   # journal tail
    assert (tmp / "db").exists()
    return Store(str(tmp / "db"))


def _install_snapshot(store, _tmp):
    store.update("servers", store.server_by_slug("n3").id, slug="renamed")
    standby = _seeded()        # other ids under the same slugs, all replaced
    standby.register_server("stale")
    standby.install_snapshot(store.snapshot_doc())
    assert standby.server_by_slug("stale") is None
    assert standby.server_by_slug("n3") is None
    assert standby.server_by_slug("renamed").id == \
        store.server_by_slug("renamed").id
    return standby


def _streamed(mutate):
    """A standby fed the primary's journal, entry by entry."""
    def case(_store, _tmp):
        primary, standby = Store(), Store()
        primary.replication_sink = standby.apply_replicated
        for slug in SLUGS:
            primary.create("servers", Server(slug=slug, tenant="default"))
        mutate(primary)
        _agrees(primary)
        assert ({s.slug: s.id for s in standby.list("servers")}
                == {s.slug: s.id for s in primary.list("servers")})
        return standby
    return case


def _rename(primary):
    primary.update("servers", primary.server_by_slug("n3").id,
                   slug="renamed")


def _remove(primary):
    primary.delete("servers", primary.server_by_slug("n1").id)


CASES = {
    "create": _create,
    "register_insert": _register_insert,
    "register_upsert": _register_upsert,
    "update_other_field": _update_other_field,
    "update_slug": _update_slug,
    "update_slug_to_itself": _update_slug_to_itself,
    "delete": _delete,
    "create_over_an_id": _create_over_an_id,
    "bulk_server_status": _bulk_server_status,
    "batch": _batch,
    "journal_replay": _journal_replay,
    "flush_reload": _flush_reload,
    "install_snapshot": _install_snapshot,
    "replicated_put": _streamed(lambda p: p.register_server("extra")),
    "replicated_put_renames": _streamed(_rename),
    "replicated_del": _streamed(_remove),
}


@pytest.mark.parametrize("case", CASES)
def test_index_agrees_with_scan_after(case, tmp_path):
    store = _seeded(str(tmp_path / "db"))
    _agrees(store)
    after = CASES[case](store, tmp_path)
    _agrees(after)
    if after is not store:
        _agrees(store)


def test_a_promoted_standby_keeps_its_index():
    """The standby applied the primary's journal; promoted, it serves
    lookups and takes writes of its own."""
    primary, standby = Store(), Store()
    primary.replication_sink = standby.apply_replicated
    for slug in SLUGS:
        primary.register_server(slug)
    primary.delete("servers", primary.server_by_slug("n0").id)
    standby.bump_epoch()
    rec = standby.register_server("n2", hostname="after-failover")
    assert rec.id == primary.server_by_slug("n2").id
    assert standby.register_server("n0").id != ""       # gone, so inserted
    standby.update("servers", rec.id, slug="renamed")
    _agrees(standby)


# --------------------------------------------------------------------------
# two records with one slug
# --------------------------------------------------------------------------

@pytest.mark.parametrize("how", ["created", "renamed_earlier_row",
                                 "renamed_later_row", "reloaded",
                                 "streamed"])
def test_duplicates_return_the_first_in_table_order(how, tmp_path):
    store, standby = Store(str(tmp_path / "db")), Store()
    store.replication_sink = standby.apply_replicated
    for slug in SLUGS:
        store.create("servers", Server(slug=slug, tenant="default"))
    if how == "renamed_earlier_row":
        # n1 sits before n4 in the table: renamed to n4, it is the first
        first, second = store.server_by_slug("n1"), store.server_by_slug("n4")
        store.update("servers", first.id, slug="n4")
    elif how == "renamed_later_row":
        first, second = store.server_by_slug("n1"), store.server_by_slug("n4")
        store.update("servers", second.id, slug="n1")
    else:
        first = store.server_by_slug("n4")
        second = store.create("servers", Server(slug="n4", tenant="other"))
    slug = second.slug
    if how == "reloaded":
        store.flush()
        store = Store(str(tmp_path / "db"))
    elif how == "streamed":
        store = standby
    _agrees(store)
    assert store.server_by_slug(slug).id == first.id
    store.delete("servers", first.id)
    _agrees(store)
    assert store.server_by_slug(slug).id == second.id
    store.delete("servers", second.id)
    _agrees(store)
    assert store.server_by_slug(slug) is None


# --------------------------------------------------------------------------
# a random walk over the mutations
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_random_mutations_end_with_index_equal_to_scan(seed, tmp_path):
    rng = random.Random(f"store-index:{seed}")
    pool = [f"r{i}" for i in range(12)]        # few slugs: collisions
    standby = Store()

    def opened():
        store = Store(str(tmp_path / "db"), journal_max_entries=64)
        store.replication_sink = standby.apply_replicated
        return store

    primary = opened()

    def some_id():
        ids = list(primary._tables["servers"])
        return rng.choice(ids) if ids else "server_none"

    for step in range(400):
        op = rng.choice(["register", "create", "rename", "touch", "delete",
                         "bulk", "batch", "reload"])
        if op == "register":
            primary.register_server(rng.choice(pool), hostname=f"h{step}")
        elif op == "create":           # a second record under a live slug
            primary.create("servers", Server(slug=rng.choice(pool),
                                             tenant="default"))
        elif op == "rename":
            primary.update("servers", some_id(), slug=rng.choice(pool))
        elif op == "touch":
            primary.update("servers", some_id(), status="offline")
        elif op == "delete":
            primary.delete("servers", some_id())
        elif op == "bulk":
            primary.bulk_server_status(
                {s: rng.choice(["online", "offline"])
                 for s in rng.sample(pool, 4)})
        elif op == "batch":
            with primary.batch():
                primary.delete("servers", some_id())
                primary.register_server(rng.choice(pool))
                primary.update("servers", some_id(), slug=rng.choice(pool))
        else:           # a restart: snapshot + whatever journal survives
            if primary._journal_file is not None:
                primary._journal_file.close()
            primary = opened()
            _agrees(primary, pool)
        if step % 25 == 0:
            _agrees(primary, pool)
    _agrees(primary, pool)
    _agrees(standby, pool)
    assert ([(s.id, s.slug) for s in standby._tables["servers"].values()]
            == [(s.id, s.slug) for s in primary._tables["servers"].values()])


# --------------------------------------------------------------------------
# what the counters say
# --------------------------------------------------------------------------

def test_a_thousand_lookups_examine_a_thousand_rows():
    scanned = REGISTRY.get("fleet_store_rows_scanned_total")
    lookups = REGISTRY.get("fleet_store_lookups_total")
    store = Store()
    s0 = scanned.value(table="servers")
    i0 = lookups.value(table="servers", path="index")
    for i in range(5000):
        store.register_server(f"node-{i}")
    # 5,000 registrations of new slugs are 5,000 misses: nothing examined
    assert scanned.value(table="servers") - s0 == 0
    assert lookups.value(table="servers", path="index") - i0 == 5000
    s0 = scanned.value(table="servers")
    i0 = lookups.value(table="servers", path="index")
    n0 = lookups.value(table="servers", path="scan")
    for i in range(1000, 2000):
        assert store.server_by_slug(f"node-{i}").slug == f"node-{i}"
    assert scanned.value(table="servers") - s0 == 1000
    assert lookups.value(table="servers", path="index") - i0 == 1000
    assert lookups.value(table="servers", path="scan") - n0 == 0


@pytest.mark.parametrize("lookup,path,rows", [
    ("server_by_slug_hit", "index", 1),
    ("server_by_slug_miss", "index", 0),
    ("find_one_hit", "scan", 3),
    ("find_one_miss", "scan", 6),
])
def test_lookups_are_counted_by_path(lookup, path, rows):
    scanned = REGISTRY.get("fleet_store_rows_scanned_total")
    lookups = REGISTRY.get("fleet_store_lookups_total")
    store = _seeded()
    other = "scan" if path == "index" else "index"
    before = (scanned.value(table="servers"),
              lookups.value(table="servers", path=path),
              lookups.value(table="servers", path=other))
    if lookup == "server_by_slug_hit":
        assert store.server_by_slug("n2").slug == "n2"
    elif lookup == "server_by_slug_miss":
        assert store.server_by_slug("nope") is None
    elif lookup == "find_one_hit":
        assert store.find_one(
            "servers", lambda s: s.hostname == "h-n2").slug == "n2"
    else:
        assert store.find_one(
            "servers", lambda s: s.hostname == "nope") is None
    assert (scanned.value(table="servers") - before[0],
            lookups.value(table="servers", path=path) - before[1],
            lookups.value(table="servers", path=other) - before[2]) \
        == (rows, 1, 0)


@pytest.mark.parametrize("key", [["n1"], {"slug": "n1"}, None, 7])
def test_a_key_that_is_no_slug_is_a_miss(key):
    """A malformed request's slug reaches server_by_slug as JSON gave it:
    the scan compared it with every slug and found none; so does this."""
    store = _seeded()
    assert store.server_by_slug(key) is None
    assert _by_scan(store, key) is None


def test_an_unhashable_slug_is_refused_before_the_table_changes():
    store = _seeded()
    with pytest.raises(TypeError):
        store.create("servers", Server(slug=["n9"], tenant="default"))
    rec = store.server_by_slug("n1")
    with pytest.raises(TypeError):
        store.update("servers", rec.id, slug=["n9"])
    assert rec.slug == "n1" and len(store.list("servers")) == len(SLUGS)
    _agrees(store)


# --------------------------------------------------------------------------
# a first commitment (the k8s cell's shape, small)
# --------------------------------------------------------------------------

N_SERVERS, N_PODS = 50, 10


def _k8s_flow():
    slugs = [f"node-{i}" for i in range(N_SERVERS)]
    servers = "\n".join(
        f'server "{s}" {{ capacity {{ cpu 4; memory 32768; disk 99999 }} }}'
        for s in slugs)
    pods = "\n".join(
        f'service "pod-{i}" {{ image "x"; '
        f'resources {{ cpu 0.1; memory 500; disk 1 }} }}'
        for i in range(N_PODS))
    stage = ('stage "sched-1" {\n'
             + "\n".join(f'    service "pod-{i}"' for i in range(N_PODS))
             + "\n    servers " + " ".join(f'"{s}"' for s in slugs) + "\n}")
    return parse_kdl_string(f'project "k8s"\n{servers}\n{pods}\n{stage}\n')


def _k8s_cp():
    store = Store()
    store.replication_sink = lambda entries: None
    for i in range(N_SERVERS):
        store.register_server(
            f"node-{i}", status="online",
            capacity=ServerCapacity(cpu=4, memory=32768, disk=99999))
    return store, PlacementService(store, use_tpu=False)


def _allocated(store):
    return {s.slug: (s.allocated.cpu, s.allocated.memory, s.allocated.disk)
            for s in store.list("servers")}


def test_a_first_commitment_reads_one_row_a_server(tmp_path, monkeypatch):
    path = tmp_path / "trace.jsonl"
    monkeypatch.setenv("FLEET_TRACE_FILE", str(path))
    log = obs.get_logger("test.store_index")
    scanned = REGISTRY.get("fleet_store_rows_scanned_total")
    lookups = REGISTRY.get("fleet_store_lookups_total")
    puts = REGISTRY.get("fleet_store_ops_total")
    flow = _k8s_flow()

    # the parent's lookup, on a store of its own through the same steps
    ref_store, ref_svc = _k8s_cp()
    ref_store.server_by_slug = lambda slug: _by_scan(ref_store, slug)
    ref_placement, ref_rid = ref_svc.solve_stage(flow, "sched-1")
    assert ref_placement.feasible and ref_svc.commit(ref_rid)

    store, svc = _k8s_cp()
    placement, rid = svc.solve_stage(flow, "sched-1")
    assert placement.feasible
    assert placement.assignment == ref_placement.assignment
    placed = set(placement.assignment.values())
    s0 = scanned.value(table="servers")
    i0 = lookups.value(table="servers", path="index")
    n0 = lookups.value(table="servers", path="scan")
    p0 = puts.value(table="servers", op="put")
    with obs.span(log, "t.first_commitment"):
        assert svc.commit(rid)
    assert puts.value(table="servers", op="put") - p0 == len(placed)
    assert scanned.value(table="servers") - s0 == len(placed)
    assert lookups.value(table="servers", path="index") - i0 == len(placed)
    assert lookups.value(table="servers", path="scan") - n0 == 0
    records = [e["fields"]["records"]
               for e in obs_trace.read_trace_file(str(path))
               if e["name"] == "cp.commit.apply_allocation"]
    assert records == [len(placed)]
    got, want = _allocated(store), _allocated(ref_store)
    assert got == want
    assert {slug for slug, a in got.items() if any(a)} == placed
    for slug in placed:
        n = sum(1 for v in placement.assignment.values() if v == slug)
        assert got[slug] == pytest.approx((0.1 * n, 500.0 * n, 1.0 * n))
