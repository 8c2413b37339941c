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
  * the replication sink is handed one put per changed server and the
    placement record, and a second Store fed the stream ends with the
    primary's `allocated` on every server
"""

from __future__ import annotations

import dataclasses
import json
from collections import Counter

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
SCENARIOS = ["kill_one", "kill_then_revive", "no_previous", "second_stage"]


def _flow():
    slugs = [f"n{i}" for i in range(N_SERVERS)]
    servers = "\n".join(
        f'server "{s}" {{ capacity {{ cpu 32; memory 65536; disk 99999 }} }}'
        for s in slugs)
    services = "\n".join(
        f'service "s{i}" {{ image "x"; resources {{ cpu {(1, 2, 0.5)[i % 3]}; '
        f'memory {(64, 128, 256, 512)[i % 4]}; disk {1 + i % 5} }} }}'
        for i in range(40))
    stages = "\n".join(
        f'stage "{name}" {{\n'
        + "\n".join(f'    service "s{i}"' for i in rows)
        + "\n    servers " + " ".join(f'"{s}"' for s in slugs) + "\n}"
        for name, rows in STAGES.items())
    return parse_kdl_string(f'project "p"\n{servers}\n{services}\n{stages}\n')


class _Cp:
    """A store with a replication sink attached from its first write, and
    a PlacementService on the host scheduler."""

    def __init__(self):
        self.store = Store()
        self.stream: list[tuple[int, str]] = []
        self.store.replication_sink = self.stream.extend
        for i in range(N_SERVERS):
            self.store.create("servers", Server(
                slug=f"n{i}", status="online", tenant="default",
                capacity=ServerCapacity(cpu=32, memory=65536, disk=99999)))
        self.svc = PlacementService(self.store, use_tpu=False)
        self.flow = _flow()
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
    if scenario == "second_stage":
        deploy("canary")
    cp.victim = cp.busiest("p/live")
    yield from churn(cp.victim, False)
    if scenario == "kill_then_revive":
        yield from churn(cp.victim, True)


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
    pt, placement = cp.svc.retained("p/live")
    src = pt.node_names.index(cp.busiest("p/live"))
    dst = (src + 1) % N_SERVERS
    raw = np.where(np.asarray(placement.raw) == src, dst,
                   np.asarray(placement.raw))
    moved = dataclasses.replace(
        placement, raw=raw,
        assignment={name: pt.node_names[int(j)]
                    for name, j in zip(pt.service_names, raw)})
    with cp.svc._lock:
        cp.svc._last["p/live"] = (pt, moved)
    stamps = {s.slug: s.updated_at for s in cp.store.list("servers")}
    want = dict(cp.allocated())
    puts = REGISTRY.get("fleet_store_ops_total")
    n0 = puts.value(table="servers", op="put")
    assert cp.svc.commit_retained("p/live")
    assert puts.value(table="servers", op="put") - n0 == 2
    touched = {pt.node_names[src], pt.node_names[dst]}
    for s in cp.store.list("servers"):
        assert (s.updated_at != stamps[s.slug]) == (s.slug in touched)
    got = cp.allocated()
    assert got[pt.node_names[src]] == (0.0, 0.0, 0.0)
    assert got[pt.node_names[dst]] == pytest.approx(tuple(
        a + b for a, b in zip(want[pt.node_names[src]],
                              want[pt.node_names[dst]])), abs=1e-9)
    for slug in set(want) - touched:
        assert got[slug] == want[slug]


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_replication_stream_reproduces_allocated(scenario):
    cp = _Cp()
    for key, do in _run(cp, scenario, PlacementService.commit_retained):
        before = _stage_book(cp.svc, key)
        mark = len(cp.stream)
        assert do()
        changed = _changed_nodes(before, _stage_book(cp.svc, key))
        entries = [json.loads(line) for _seq, line in cp.stream[mark:]]
        assert all(e["op"] == "put" for e in entries)
        by_table = Counter(e["t"] for e in entries)
        assert by_table == {"servers": len(changed), "placements": 1}
        assert ({e["r"]["slug"] for e in entries if e["t"] == "servers"}
                == changed)
        placement_rec = next(e["r"] for e in entries
                             if e["t"] == "placements")
        assert placement_rec["stage_key"] == key
        assert (set(placement_rec["demand_by_node"])
                == set(_stage_book(cp.svc, key)))
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
