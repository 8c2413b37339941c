"""PlacementService.commit_retained supersedes by difference (PR 28).

The reconverger's commit used to return the stage's whole previous
commitment to the servers and add the whole new one: every server of the
stage looked up and rewritten twice, for the handful a churn re-solve
moved. It now goes through _apply_allocation_delta, as commit() does.

What is pinned here, over four sequences of churn and commit (~40
services on 8 servers, host scheduler, no device):

  * the book and the store agree: every server's `allocated` is the sum
    over the committed reservations of their demand on that server
  * subtract-then-add, run on a second service through the same
    sequence, leaves the same `allocated` on every server
  * a commit writes exactly the server records whose demand changed:
    fleet_store_ops_total{table="servers",op="put"} and the `records`
    field of the cp.commit.apply_allocation phase both say so
  * the replication sink is handed one `upd` of the changed servers and
    the placement record (whole on a first commit and where over half of
    it changed, else the keys that did: PR 43), and a second Store fed
    the stream ends with the primary's `allocated` on every server

And, since _demand_by_node became one array pass: its keys, their order
and its float64 sums are the row loop's (kept below as `_row_loop`), on
bare arrays and through a first commitment, a churn hold and a retained
commit of a 300-row stage.
"""

from __future__ import annotations

import dataclasses
import json
from collections import Counter
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from fleetflow_tpu import obs
from fleetflow_tpu.core.parser import parse_kdl_string
from fleetflow_tpu.cp.models import Server, ServerCapacity
from fleetflow_tpu.cp.placement import PlacementService, Reservation
from fleetflow_tpu.cp.store import Store
from fleetflow_tpu.obs import trace as obs_trace
from fleetflow_tpu.obs.metrics import REGISTRY

N_SERVERS = 8
STAGES = {"live": range(0, 30), "canary": range(30, 40)}
SCENARIOS = ["kill_one", "kill_then_revive", "no_previous", "second_stage",
             "move_one_server"]


def _flow(n_servers=N_SERVERS, n_services=40, stages=STAGES):
    slugs = [f"n{i}" for i in range(n_servers)]
    servers = "\n".join(
        f'server "{s}" {{ capacity {{ cpu 32; memory 65536; disk 99999 }} }}'
        for s in slugs)
    services = "\n".join(
        f'service "s{i}" {{ image "x"; resources {{ cpu {(1, 2, 0.5)[i % 3]}; '
        f'memory {(64, 128, 256, 512)[i % 4]}; disk {1 + i % 5} }} }}'
        for i in range(n_services))
    stage_nodes = "\n".join(
        f'stage "{name}" {{\n'
        + "\n".join(f'    service "s{i}"' for i in rows)
        + "\n    servers " + " ".join(f'"{s}"' for s in slugs) + "\n}"
        for name, rows in stages.items())
    return parse_kdl_string(
        f'project "p"\n{servers}\n{services}\n{stage_nodes}\n')


class _Cp:
    """A store with a replication sink attached from its first write, and
    a PlacementService on the host scheduler."""

    def __init__(self, n_servers=N_SERVERS, flow=None):
        self.store = Store()
        self.stream: list[tuple[int, str]] = []
        self.store.replication_sink = self.stream.extend
        for i in range(n_servers):
            self.store.create("servers", Server(
                slug=f"n{i}", status="online", tenant="default",
                capacity=ServerCapacity(cpu=32, memory=65536, disk=99999)))
        self.svc = PlacementService(self.store, use_tpu=False)
        self.flow = flow if flow is not None else _flow()
        self.victim: str | None = None     # the server _run killed

    def allocated(self) -> dict[str, tuple[float, float, float]]:
        return {s.slug: (s.allocated.cpu, s.allocated.memory,
                         s.allocated.disk)
                for s in self.store.list("servers")}

    def book(self) -> dict[str, np.ndarray]:
        out = {f"n{i}": np.zeros(3) for i in range(N_SERVERS)}
        for r in self.svc._committed.values():
            for slug, d in r.demand_by_node.items():
                out[slug] = out[slug] + np.asarray(d, dtype=np.float64)
        return out

    def busiest(self, key: str) -> str:
        _pt, placement = self.svc.retained(key)
        return Counter(placement.assignment.values()).most_common(1)[0][0]


def _subtract_then_add(svc: PlacementService, key: str) -> bool:
    """commit_retained as it was before PR 28: the previous commitment
    returned whole, the new one added whole."""
    with svc._lock:
        pt, placement = svc._last[key]
        r = Reservation(
            id=f"rsv_{next(svc._ids)}", stage_key=key,
            demand_by_node=svc._demand_by_node(pt, placement),
            assignment=dict(placement.assignment), committed=True)
        prev = svc._committed.pop(key, None)
        if prev is not None:
            svc._apply_allocation(prev, -1.0)
        svc._apply_allocation(r, +1.0)
        svc._committed[key] = r
        svc._drop_churn(key)
        svc._persist_committed(key)
    return True


def _run(cp: _Cp, scenario: str, commit_retained):
    """Drive `scenario`; yields before each retained commit as
    (stage key, do), where do() performs it. The generator resumes after
    the caller has looked at what the commit did."""
    svc, flow = cp.svc, cp.flow

    def deploy(stage):
        placement, rid = svc.solve_stage(flow, stage)
        assert placement.feasible and svc.commit(rid)

    def churn(slug, online):
        moved = dict(svc.node_events([(slug, online)]))
        assert moved and all(p.feasible for p in moved.values())
        for key in moved:
            yield key, (lambda key=key: commit_retained(svc, key))

    if scenario == "no_previous":
        placement, rid = svc.solve_stage(flow, "live", reserve=False)
        assert placement.feasible and rid is None
        yield "p/live", (lambda: commit_retained(svc, "p/live"))
        return
    deploy("live")
    if scenario == "move_one_server":
        # what a sticky re-solve (the annealer's) does where the host
        # scheduler re-places the stage: one server's rows move, the
        # others stay
        _move_one_server(cp, "p/live")
        yield "p/live", (lambda: commit_retained(svc, "p/live"))
        return
    if scenario == "second_stage":
        deploy("canary")
    cp.victim = cp.busiest("p/live")
    yield from churn(cp.victim, False)
    if scenario == "kill_then_revive":
        yield from churn(cp.victim, True)


def _move_one_server(cp: _Cp, key: str) -> tuple[str, str]:
    """Retain a placement of `key` that moves the rows of its busiest
    server to the next server; returns (that server, the next)."""
    pt, placement = cp.svc.retained(key)
    src = pt.node_names.index(cp.busiest(key))
    dst = (src + 1) % len(pt.node_names)
    raw = np.where(np.asarray(placement.raw) == src, dst,
                   np.asarray(placement.raw))
    moved = dataclasses.replace(
        placement, raw=raw,
        assignment={name: pt.node_names[int(j)]
                    for name, j in zip(pt.service_names, raw)})
    with cp.svc._lock:
        cp.svc._last[key] = (pt, moved)
    return pt.node_names[src], pt.node_names[dst]


def _changed_nodes(before: dict, after: dict) -> set[str]:
    zero = np.zeros(3)
    return {slug for slug in set(before) | set(after)
            if np.any(np.asarray(after.get(slug, zero), dtype=np.float64)
                      != np.asarray(before.get(slug, zero),
                                    dtype=np.float64))}


def _stage_book(svc, key) -> dict:
    r = svc._committed.get(key)
    return dict(r.demand_by_node) if r is not None else {}


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_book_and_store_agree_after_every_commit(scenario):
    cp = _Cp()
    commits = 0
    for _key, do in _run(cp, scenario, PlacementService.commit_retained):
        assert do()
        commits += 1
        allocated, book = cp.allocated(), cp.book()
        for slug, got in allocated.items():
            assert got == pytest.approx(tuple(book[slug]), abs=1e-9), slug
        assert sum(a[0] for a in allocated.values()) > 0
    assert commits >= {"kill_then_revive": 2}.get(scenario, 1)
    if scenario in ("kill_one", "second_stage"):
        # the dead server's rows moved away, in the book and in the store
        assert not cp.book()[cp.victim].any()
        assert cp.allocated()[cp.victim] == (0.0, 0.0, 0.0)


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_same_allocated_as_subtract_then_add(scenario):
    cp, ref = _Cp(), _Cp()
    steps = zip(_run(cp, scenario, PlacementService.commit_retained),
                _run(ref, scenario, _subtract_then_add), strict=True)
    for (key, do), (ref_key, ref_do) in steps:
        assert key == ref_key
        assert do() and ref_do()
        got, want = cp.allocated(), ref.allocated()
        assert got.keys() == want.keys()
        for slug in want:
            assert got[slug] == pytest.approx(want[slug], abs=1e-9), slug
        assert (cp.svc.retained(key)[1].assignment
                == ref.svc.retained(key)[1].assignment)


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_commit_writes_exactly_the_servers_that_changed(
        scenario, tmp_path, monkeypatch):
    path = tmp_path / "trace.jsonl"
    monkeypatch.setenv("FLEET_TRACE_FILE", str(path))
    log = obs.get_logger("test.commit_retained")
    puts = REGISTRY.get("fleet_store_ops_total")
    cp = _Cp()
    expected = []
    for key, do in _run(cp, scenario, PlacementService.commit_retained):
        before = _stage_book(cp.svc, key)
        n0 = puts.value(table="servers", op="put")
        with obs.span(log, "t.commit_retained"):
            assert do()
        changed = _changed_nodes(before, _stage_book(cp.svc, key))
        assert puts.value(table="servers", op="put") - n0 == len(changed)
        # the dead server and whoever took its rows, at the least (the
        # host scheduler re-places the stage, so it may be every server)
        assert len(changed) >= 2
        if not before:
            assert len(changed) == len(_stage_book(cp.svc, key))
        expected.append(len(changed))
    records = [e["fields"]["records"]
               for e in obs_trace.read_trace_file(str(path))
               if e["name"] == "cp.commit.apply_allocation"]
    assert records == expected


def test_commit_reports_records_written_too(tmp_path, monkeypatch):
    """commit(), the other path onto the same phase: a first commitment
    writes every server it lands on, a redeploy those whose demand it
    changed."""
    path = tmp_path / "trace.jsonl"
    monkeypatch.setenv("FLEET_TRACE_FILE", str(path))
    log = obs.get_logger("test.commit_retained")
    puts = REGISTRY.get("fleet_store_ops_total")
    cp = _Cp()
    expected = []
    with obs.span(log, "t.commit"):
        for _ in range(2):
            placement, rid = cp.svc.solve_stage(cp.flow, "live")
            before = _stage_book(cp.svc, "p/live")
            n0 = puts.value(table="servers", op="put")
            assert placement.feasible and cp.svc.commit(rid)
            changed = _changed_nodes(before, _stage_book(cp.svc, "p/live"))
            assert puts.value(table="servers", op="put") - n0 == len(changed)
            expected.append(len(changed))
    assert expected[0] == len(set(placement.assignment.values()))
    records = [e["fields"]["records"]
               for e in obs_trace.read_trace_file(str(path))
               if e["name"] == "cp.commit.apply_allocation"]
    assert records == expected


def test_a_commit_leaves_untouched_servers_alone():
    """One server's rows move to one other server: two records written,
    and no other server's `updated_at` moves (cp/autoscaler.py ages an
    offline server by it)."""
    cp = _Cp()
    ticks = iter(range(1, 10_000))
    cp.store._clock = lambda: float(next(ticks))
    placement, rid = cp.svc.solve_stage(cp.flow, "live")
    assert placement.feasible and cp.svc.commit(rid)
    src, dst = _move_one_server(cp, "p/live")
    stamps = {s.slug: s.updated_at for s in cp.store.list("servers")}
    want = dict(cp.allocated())
    puts = REGISTRY.get("fleet_store_ops_total")
    n0 = puts.value(table="servers", op="put")
    assert cp.svc.commit_retained("p/live")
    assert puts.value(table="servers", op="put") - n0 == 2
    touched = {src, dst}
    for s in cp.store.list("servers"):
        assert (s.updated_at != stamps[s.slug]) == (s.slug in touched)
    got = cp.allocated()
    assert got[src] == (0.0, 0.0, 0.0)
    assert got[dst] == pytest.approx(tuple(
        a + b for a, b in zip(want[src], want[dst])), abs=1e-9)
    for slug in set(want) - touched:
        assert got[slug] == want[slug]


def _record_fields(rec) -> dict:
    return {"assignment": dict(rec.assignment),
            "demand_by_node": dict(rec.demand_by_node),
            "held_keys": dict(rec.held_keys)}


def _keys_changed(was: dict, now: dict) -> int:
    """Keys of a placement record's fields set or dropped from `was` to
    `now`."""
    return sum(len(now[f].keys() ^ was[f].keys())
               + sum(now[f][k] != was[f][k]
                     for k in now[f].keys() & was[f].keys())
               for f in now)


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_replication_stream_reproduces_allocated(scenario):
    cp = _Cp()
    for key, do in _run(cp, scenario, PlacementService.commit_retained):
        before = _stage_book(cp.svc, key)
        rec = cp.store.find_one("placements", lambda p: p.stage_key == key)
        was = _record_fields(rec) if rec is not None else None
        mark = len(cp.stream)
        assert do()
        changed = _changed_nodes(before, _stage_book(cp.svc, key))
        entries = [json.loads(line) for _seq, line in cp.stream[mark:]]
        # the changed servers' new `allocated` in one `upd` entry, then
        # the placement record: whole (`put`) on the stage's first commit
        # and where over half of its keys changed, else the keys that did
        # (`mrg`, PR 43)
        rec = cp.store.find_one("placements", lambda p: p.stage_key == key)
        now = _record_fields(rec)
        form = ("put" if was is None
                or 2 * _keys_changed(was, now) > sum(map(len, now.values()))
                else "mrg")
        assert [(e["op"], e["t"]) for e in entries] == [
            ("upd", "servers"), (form, "placements")]
        assert all(fields.keys() == {"allocated"}
                   for fields in entries[0]["u"].values())
        written = _server_writes(cp.store, cp.stream[mark:])
        assert len(written) == len(changed) and set(written) == changed
        if form == "put":
            assert entries[1]["r"]["stage_key"] == key
        else:
            assert entries[1]["id"] == rec.id
        assert set(rec.demand_by_node) == set(_stage_book(cp.svc, key))
    standby = Store()
    assert standby.apply_replicated(cp.stream) == len(cp.stream)
    got = {s.slug: (s.allocated.cpu, s.allocated.memory, s.allocated.disk)
           for s in standby.list("servers")}
    assert got == cp.allocated()
    # and the book a promoted standby reloads explains those servers
    promoted = PlacementService(standby, use_tpu=False)
    assert promoted._committed.keys() == cp.svc._committed.keys()
    for key, r in cp.svc._committed.items():
        for slug, d in r.demand_by_node.items():
            assert promoted._committed[key].demand_by_node[slug] \
                == pytest.approx(np.asarray(d, dtype=np.float64))


# --------------------------------------------------------------------------
# _demand_by_node: one array pass, held to the row loop it replaced
# --------------------------------------------------------------------------

def _row_loop(pt, placement) -> dict[str, np.ndarray]:
    """PlacementService._demand_by_node as it was before it became one
    array pass: the reference for keys, their order and the sums."""
    out: dict[str, np.ndarray] = {}
    for i, node in enumerate(placement.raw):
        dem = pt.demand[i]
        if not dem.any():
            continue
        slug = pt.node_names[int(node)]
        out[slug] = out.get(slug, 0) + dem.astype(np.float64)
    return out


def _case(S, N, R=3, *, seed=0, tombstones=(), dead=(), raw=None,
          as_raw=np.asarray):
    rng = np.random.default_rng(seed)
    demand = (rng.random((S, R)) * (4.0, 8192.0, 500.0, 7.0, 0.1)[:R]
              ).astype(np.float32)
    if raw is None:
        raw = rng.integers(0, N, size=S)
    raw = np.asarray(raw, dtype=np.int32)
    if tombstones:      # every row on these nodes departs, and a tenth
        demand[np.isin(raw, tombstones) | (rng.random(S) < 0.1)] = 0.0
    demand[list(dead)] = 0.0        # these rows depart
    pt = SimpleNamespace(demand=demand,
                         node_names=[f"n{j}" for j in range(N)])
    return pt, SimpleNamespace(raw=as_raw(raw))


DEMAND_CASES = {
    # the benchmark's nc stage: 9,660 rows x 3 resources over 1,000 nodes
    "nc_size": lambda: _case(9660, 1000, seed=1),
    # nodes 3 and 17 carry tombstones only: neither may have an entry
    "tombstones": lambda: _case(600, 40, seed=2, tombstones=(3, 17)),
    "every_row_a_tombstone": lambda: _case(5, 3, tombstones=(0, 1, 2)),
    # rd's size; node 1 is empty, node 2 enters the dict before node 0
    "four_rows_one_empty_node": lambda: _case(4, 3, raw=[2, 0, 2, 0]),
    "no_rows": lambda: _case(0, 3),
    "raw_numpy_int32": lambda: _case(200, 16, seed=3),
    "raw_device_int32": lambda: _case(200, 16, seed=3, as_raw=jnp.asarray),
    "raw_python_list": lambda: _case(200, 16, seed=3,
                                     as_raw=lambda a: a.tolist()),
    # R is the demand's second axis, not a literal 3
    "five_resources": lambda: _case(300, 20, R=5, seed=4),
    "one_resource": lambda: _case(300, 20, R=1, seed=5),
    # the benchmark's pod stage: 100,000 rows over 1,000 nodes, nodes 7
    # and 500 tombstones only, and a tenth of the others departed
    "pod_size": lambda: _case(100_000, 1000, seed=6, tombstones=(7, 500)),
    # first live rows on n15, n14, ..., n0: the dict runs against the
    # nodes' index order
    "first_rows_reverse_node_order": lambda: _case(
        320, 16, seed=7, raw=np.tile(np.arange(16)[::-1], 20)),
    # n1's rows 0 and 2 are tombstones, its row 4 is live: it enters
    # after n0 (row 1) and n2 (row 3)
    "live_row_after_tombstones": lambda: _case(
        6, 3, seed=8, raw=[1, 0, 1, 2, 1, 0], dead=(0, 2)),
}


def _assert_same_demand(got, want):
    assert list(got) == list(want)          # same nodes, in the same order
    for slug, d in want.items():
        assert np.array_equal(got[slug], d), slug


@pytest.mark.parametrize("case", DEMAND_CASES)
def test_demand_by_node_is_the_row_loops(case):
    pt, placement = DEMAND_CASES[case]()
    want = _row_loop(pt, placement)
    got = PlacementService._demand_by_node(pt, placement)
    _assert_same_demand(got, want)
    for d in got.values():
        assert d.dtype == np.float64 and d.shape == pt.demand.shape[1:]
    if case == "tombstones":
        assert want and not {"n3", "n17"} & set(got)
    if case == "four_rows_one_empty_node":
        assert list(got) == ["n2", "n0"]
    if case in ("no_rows", "every_row_a_tombstone"):
        assert got == {}
    if case == "pod_size":
        assert len(got) == 998 and not {"n7", "n500"} & set(got)
    if case == "first_rows_reverse_node_order":
        assert list(got) == [f"n{j}" for j in range(15, -1, -1)]
    if case == "live_row_after_tombstones":
        assert list(got) == ["n0", "n2", "n1"]
        assert np.array_equal(got["n1"],
                              pt.demand[4].astype(np.float64))


BIG_SERVERS, BIG_SERVICES = 24, 300


def _committed_json(cp: _Cp) -> list[str]:
    """The placements table as it is journaled, less what differs between
    two stores by construction (ids, clocks). Key order is kept."""
    out = []
    for rec in cp.store.list("placements"):
        d = rec.to_dict()
        for k in ("id", "created_at", "updated_at"):
            d.pop(k, None)
        out.append(json.dumps(d))
    return out


def _server_writes(store, stream) -> list[str]:
    """The slug of every server record written in `stream`, in the order
    the journal holds them: a `put`'s record, each id of an `upd` entry."""
    out = []
    for _seq, line in stream:
        e = json.loads(line)
        if e["t"] != "servers":
            continue
        if e["op"] == "put":
            out.append(e["r"]["slug"])
        elif e["op"] == "upd":
            out.extend(store.get("servers", rid).slug for rid in e["u"])
    return out


def test_a_300_row_stage_keeps_the_row_loops_book():
    """First commitment, kill, node_events, commit_retained — on two
    services, one of them computing demand by node with the row loop:
    the journal's server writes come in the same order, the churn hold
    reserves the same delta, and the store and the persisted record end
    bit for bit the same."""
    flow = _flow(BIG_SERVERS, BIG_SERVICES, {"live": range(BIG_SERVICES)})
    cp, ref = _Cp(BIG_SERVERS, flow), _Cp(BIG_SERVERS, flow)
    ref.svc._demand_by_node = _row_loop
    firsts = []
    for side in (cp, ref):
        mark = len(side.stream)         # past the servers' own creation
        placement, rid = side.svc.solve_stage(side.flow, "live")
        assert placement.feasible and side.svc.commit(rid)
        firsts.append(_server_writes(side.store, side.stream[mark:]))
    first = firsts[0]
    assert first == firsts[1]
    # a first commitment writes each server once, at its first live row
    pt, placement = cp.svc.retained("p/live")
    assert first == list(dict.fromkeys(
        pt.node_names[int(j)] for j in placement.raw))
    assert len(first) > 8 and pt.S >= BIG_SERVICES

    victim = cp.busiest("p/live")
    assert victim == ref.busiest("p/live")
    holds = []
    for side in (cp, ref):
        moved = dict(side.svc.node_events([(victim, False)]))
        assert moved["p/live"].feasible
        churn = [r for r in side.svc._reservations.values() if r.churn]
        assert len(churn) == 1
        holds.append(churn[0].demand_by_node)
    assert holds[0]
    _assert_same_demand(*holds)

    mark = len(cp.stream), len(ref.stream)
    assert cp.svc.commit_retained("p/live")
    assert ref.svc.commit_retained("p/live")
    assert (_server_writes(cp.store, cp.stream[mark[0]:])
            == _server_writes(ref.store, ref.stream[mark[1]:]) != [])
    assert cp.allocated() == ref.allocated()        # exact, not approx
    assert cp.allocated()[victim] == (0.0, 0.0, 0.0)
    assert _committed_json(cp) == _committed_json(ref) != []
    _assert_same_demand(cp.svc._committed["p/live"].demand_by_node,
                        ref.svc._committed["p/live"].demand_by_node)
