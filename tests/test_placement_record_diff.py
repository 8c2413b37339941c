"""A stage's placement record is journaled by difference (PR 43).

`PlacementService._persist_committed` wrote the stage's whole record on
every commit: a `put` of a 100,000-row assignment for the ~340 rows a kill
moves. Once the record exists it now writes what changed, as one `mrg`
entry (`Store.update_keys`): the rows set or dropped, the servers whose
demand changed, the held keys whose servers changed. Whole where there is
no record yet, or where the difference names more than half of the record.

Pinned here, after a churn commit, an admission micro-batch commit, an
eviction, a reinstatement and a release (host scheduler, no device):

  * the store's record is what a whole write of the commitment gives;
  * a standby fed the replication stream, and a store reopened from a copy
    of the journal, hold that record too;
  * applying the stream twice leaves what applying it once leaves;
  * the form: whole on a first commit and over half, diff otherwise;

and a 100,000-row record with 340 moved rows journals an entry under
64 KiB, where the whole record is ~2.3 MB.
"""

from __future__ import annotations

import dataclasses
import json
import shutil

import numpy as np
import pytest

from benchmarks import generators_k8s_preemption as gen
from benchmarks import reference_k8s_preemption as ref
from benchmarks.reference_k8s_preemption import INIT, MEASURED
from fleetflow_tpu.core.parser import parse_kdl_string
from fleetflow_tpu.core.serialize import flow_from_dict
from fleetflow_tpu.cp.admission import AdmissionConfig, AdmissionController
from fleetflow_tpu.cp.models import PlacementRecord, Server, ServerCapacity
from fleetflow_tpu.cp.placement import (PlacementService, Reservation,
                                        _RecordChange, _Rows)
from fleetflow_tpu.cp.store import Store
from fleetflow_tpu.obs.metrics import REGISTRY

SCENARIOS = ["churn", "admission", "evict", "reinstate", "release"]


def _flow(n_servers: int, n_services: int):
    slugs = [f"n{i}" for i in range(n_servers)]
    servers = "\n".join(
        f'server "{s}" {{ capacity {{ cpu 32; memory 65536; disk 99999 }} }}'
        for s in slugs)
    services = "\n".join(
        f'service "s{i}" {{ image "x"; resources {{ cpu {(1, 2, 0.5)[i % 3]}; '
        f'memory {(64, 128, 256)[i % 3]}; disk 1 }} }}'
        for i in range(n_services))
    stage = ('stage "live" {\n'
             + "\n".join(f'    service "s{i}"' for i in range(n_services))
             + "\n    servers " + " ".join(f'"{s}"' for s in slugs) + "\n}")
    return parse_kdl_string(f'project "p"\n{servers}\n{services}\n{stage}\n')


class _Cp:
    """A file-backed store with a replication sink from its first write,
    and a PlacementService on the host scheduler."""

    def __init__(self, tmp_path, servers: dict[str, dict], use_tpu=False):
        self.dir = tmp_path
        (tmp_path / "primary").mkdir()
        self.store = Store(str(tmp_path / "primary" / "cp.json"))
        self.stream: list[tuple[int, str]] = []
        self.store.replication_sink = self.stream.extend
        for slug, cap in servers.items():
            self.store.create("servers", Server(
                slug=slug, status="online", tenant="default",
                capacity=ServerCapacity(**cap)))
        self.svc = PlacementService(self.store, use_tpu=use_tpu)

    def record(self, key: str):
        return self.store.find_one("placements",
                                   lambda p: p.stage_key == key)

    def entries(self, mark: int) -> list[dict]:
        return [json.loads(line) for _seq, line in self.stream[mark:]]


def _simple(tmp_path, n_servers=8, n_services=30,
            use_tpu=False) -> tuple[_Cp, object]:
    cp = _Cp(tmp_path, {f"n{i}": dict(cpu=32, memory=65536, disk=99999)
                        for i in range(n_servers)}, use_tpu=use_tpu)
    flow = _flow(n_servers, n_services)
    placement, rid = cp.svc.solve_stage(flow, "live")
    assert placement.feasible and cp.svc.commit(rid)
    return cp, flow


def _preemption(tmp_path, nodes=12, measured=2) -> tuple[_Cp, dict]:
    """PreemptionBasic small: four low pods a node, none fits a fifth,
    the init namespace committed; each high pod evicts three."""
    model = ref.cluster(5, nodes, 4 * nodes, measured)
    cp = _Cp(tmp_path, {slug: gen.server_capacity(node)
                        for slug, node in model["nodes"].items()})
    cp.flows = {ns: flow_from_dict(gen.solve_request(model, ns)["flow"])
                for ns in (INIT, MEASURED)}
    placement, rid = cp.svc.solve_stage(cp.flows[INIT], INIT)
    assert placement.feasible and cp.svc.commit(rid)
    return cp, model


def _move(cp: _Cp, key: str, rows: int | None = None) -> None:
    """Retain a placement that moves the rows of the busiest server (or
    the first `rows` rows) to the next server, as a churn re-solve would."""
    pt, placement = cp.svc.retained(key)
    raw = np.asarray(placement.raw)
    if rows is None:
        src = int(np.bincount(raw[:pt.S]).argmax())
        new = np.where(raw == src, (src + 1) % len(pt.node_names), raw)
    else:
        new = raw.copy()
        new[:rows] = (raw[:rows] + 1) % len(pt.node_names)
    moved = dataclasses.replace(
        placement, raw=new,
        assignment={name: pt.node_names[int(j)]
                    for name, j in zip(pt.service_names, new)})
    with cp.svc._lock:
        cp.svc._last[key] = (pt, moved)


def _do(scenario: str, tmp_path):
    """Drive `scenario` up to the write it pins; returns (cp, the stage
    key whose record that write touches, do), do() performing it."""
    if scenario == "churn":
        cp, _flow_ = _simple(tmp_path)
        _move(cp, "p/live")
        return cp, "p/live", lambda: cp.svc.commit_retained("p/live")
    if scenario == "admission":
        # the annealer's warm delta path keeps the incumbents where they
        # are (the host scheduler re-places the stage)
        cp, flow = _simple(tmp_path, use_tpu=True)
        ctrl = AdmissionController(cp.svc, config=AdmissionConfig(
            batch_max=8, shed_age_s=0.0))
        key = ctrl.attach(flow, "live")

        def admit():
            ctrl.submit("t0", arrivals=[{"name": f"a{i}"} for i in range(3)],
                        departures=["s29"])
            out = ctrl.step()
            return sorted(out["placed"]) == ["a0", "a1", "a2"]
        return cp, key, admit
    cp, model = _preemption(tmp_path)
    init, high = f"{gen.FLOW}/{INIT}", f"{gen.FLOW}/{MEASURED}"

    def evict():
        placement, rid = cp.svc.solve_stage(cp.flows[MEASURED], MEASURED)
        assert placement.feasible and cp.svc.victims(rid)
        return cp.svc.commit(rid)
    if scenario == "evict":
        return cp, init, evict
    assert evict()
    if scenario == "reinstate":
        def reinstate():
            assert cp.svc.release_stage(high)
            return cp.svc.reinstate(init) == 6
        return cp, init, reinstate
    assert scenario == "release"
    return cp, high, lambda: cp.svc.release_stage(high)


def _whole(r: Reservation) -> dict:
    """The record's fields as a whole write of commitment `r` leaves
    them."""
    return {"assignment": dict(r.assignment),
            "demand_by_node": {s: np.asarray(d, dtype=np.float64).tolist()
                               for s, d in r.demand_by_node.items()},
            "held_keys": {k: list(v) for k, v in r.held_keys.items()}}


def _fields(rec) -> dict:
    return {k: getattr(rec, k)
            for k in ("assignment", "demand_by_node", "held_keys")}


def _records(store: Store) -> dict[str, dict]:
    return {r.stage_key: r.to_dict() for r in store.list("placements")}


def _reopened(cp: _Cp, where: str) -> Store:
    """A store opened over a copy of the primary's snapshot and journal
    (opening replays the journal and compacts it: not the primary's)."""
    shutil.copytree(cp.dir / "primary", cp.dir / where)
    return Store(str(cp.dir / where / "cp.json"))


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_every_copy_holds_the_whole_writes_record(scenario, tmp_path):
    cp, key, do = _do(scenario, tmp_path)
    assert do()
    for k, r in cp.svc._committed.items():
        assert _fields(cp.record(k)) == _whole(r), k
    if scenario == "release":
        assert key not in cp.svc._committed and cp.record(key) is None
    want = _records(cp.store)
    standby = Store()
    assert standby.apply_replicated(cp.stream) == len(cp.stream)
    assert _records(standby) == want
    assert _records(_reopened(cp, "reopened")) == want
    # what a reopened or promoted CP loads explains the same book
    promoted = PlacementService(standby, use_tpu=False)
    assert ({k: _whole(r) for k, r in promoted._committed.items()}
            == {k: _whole(r) for k, r in cp.svc._committed.items()})


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_the_stream_applied_twice_is_applied_once(scenario, tmp_path):
    cp, _key, do = _do(scenario, tmp_path)
    assert do()
    want = _records(cp.store)
    twice = Store()
    with twice._lock:
        for _seq, line in cp.stream:     # each entry straight after itself
            twice._apply_entry(json.loads(line))
            twice._apply_entry(json.loads(line))
    assert _records(twice) == want
    # the whole stream again over a store that holds all of it: a journal
    # replayed over the snapshot it was folded into
    over = Store()
    over.install_snapshot(cp.store.snapshot_doc())
    with over._lock:
        for entry in cp.entries(0):
            over._apply_entry(entry)
    assert _records(over) == want


# the placement records' entries each write leaves in the stream: the
# victims' stage by difference and the arriving stage's first commit whole
FORMS = {"churn": ["mrg"], "admission": ["mrg"], "evict": ["mrg", "put"],
         "reinstate": ["del", "mrg"], "release": ["del"]}


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_the_write_takes_the_form_its_size_gives(scenario, tmp_path):
    cp, key, do = _do(scenario, tmp_path)
    writes = REGISTRY.get("fleet_placement_record_writes_total")
    keys = REGISTRY.get("fleet_placement_record_keys_total")
    diff0, whole0 = writes.value(form="diff"), writes.value(form="whole")
    keys0 = keys.value()
    mark = len(cp.stream)
    assert do()
    mine = [e for e in cp.entries(mark) if e["t"] == "placements"]
    assert [e["op"] for e in mine] == FORMS[scenario]
    whole = [e["r"] for e in mine if e["op"] == "put"]
    whole_keys = sum(len(r["assignment"]) + len(r["demand_by_node"])
                     + len(r["held_keys"]) for r in whole)
    assert writes.value(form="whole") - whole0 == len(whole)
    patched = [e for e in mine if e["op"] == "mrg"]
    assert writes.value(form="diff") - diff0 == len(patched)
    if not patched:                                 # a release: deleted
        assert keys.value() == keys0 and cp.record(key) is None
        return
    [entry] = patched
    rec = cp.record(key)
    assert entry["id"] == rec.id
    n = (sum(map(len, entry["set"].values()))
         + sum(map(len, entry["drop"].values())))
    assert 0 < 2 * n <= (len(rec.assignment) + len(rec.demand_by_node)
                         + len(rec.held_keys))
    assert keys.value() - keys0 == n + whole_keys
    if scenario == "churn":
        # the rows of one server moved to the next: that server's demand
        # dropped, the next one's set
        assert len(entry["set"]["demand_by_node"]) == 1
        assert len(entry["drop"]["demand_by_node"]) == 1
        assert "held_keys" not in entry["set"]
    if scenario == "evict":
        assert len(entry["drop"]["assignment"]) == 6
    if scenario == "reinstate":
        assert len(entry["set"]["assignment"]) == 6


def test_a_first_commit_and_a_large_difference_are_whole(tmp_path):
    cp = _Cp(tmp_path, {f"n{i}": dict(cpu=32, memory=65536, disk=99999)
                        for i in range(8)})
    flow = _flow(8, 30)
    mark = len(cp.stream)
    placement, rid = cp.svc.solve_stage(flow, "live")
    assert cp.svc.commit(rid)
    assert [e["op"] for e in cp.entries(mark)
            if e["t"] == "placements"] == ["put"]
    # every row moves: a difference of over half the record
    _move(cp, "p/live", rows=30)
    mark = len(cp.stream)
    assert cp.svc.commit_retained("p/live")
    assert [e["op"] for e in cp.entries(mark)
            if e["t"] == "placements"] == ["put"]
    assert _fields(cp.record("p/live")) == _whole(cp.svc._committed["p/live"])
    # a few rows: by difference again, against what the put wrote
    _move(cp, "p/live", rows=2)
    mark = len(cp.stream)
    assert cp.svc.commit_retained("p/live")
    assert [e["op"] for e in cp.entries(mark)
            if e["t"] == "placements"] == ["mrg"]
    assert _fields(cp.record("p/live")) == _whole(cp.svc._committed["p/live"])


def test_a_record_this_service_did_not_write_is_written_whole(tmp_path):
    """A restarted CP (a promoted standby) loads its book from the
    records: its first commit of a stage has no write of its own to
    patch, and puts the record whole."""
    cp, _flow_ = _simple(tmp_path)
    restarted = PlacementService(cp.store, use_tpu=False)
    with restarted._lock:
        restarted._last["p/live"] = cp.svc.retained("p/live")
        restarted._committed["p/live"].rows = (
            cp.svc._committed["p/live"].rows)
    mark = len(cp.stream)
    assert restarted.commit_retained("p/live")
    assert [e["op"] for e in cp.entries(mark)
            if e["t"] == "placements"] == ["put"]


def test_a_pod_scale_churn_commit_fits_a_standbys_frame(tmp_path):
    """100,000 rows on 1,000 servers, a twentieth of them holding a
    conflict key with one other row; 340 rows move to other servers. The
    record's entry stays under 64 KiB: 2A-d's frame, where the whole
    record is ~2.3 MB and no 1 MiB frame carries it."""
    S, N, moved = 100_000, 1_000, 340
    rng = np.random.default_rng(43)
    names = [f"svc-{i:06d}" for i in range(S)]
    nodes = [f"node-{j:04d}" for j in range(N)]
    node_of = rng.integers(0, N, size=S)
    demand = np.tile(np.array([[0.25, 512.0, 1.0]], dtype=np.float32),
                     (S, 1))
    holds = {f"aa:{i}": [i, i + 1] for i in range(0, S, 40)}
    store = Store()
    stream: list[tuple[int, str]] = []
    store.replication_sink = stream.extend
    svc = PlacementService(store, use_tpu=False)

    def commitment(node_of):
        rows = _Rows(names=names, nodes=nodes, node_of=node_of,
                     demand=demand, priority=None, holds=holds, floor=0)
        by_node = np.stack([np.bincount(node_of, weights=demand[:, k],
                                        minlength=N) for k in range(3)], 1)
        return Reservation(
            id="r", stage_key="p/pod", committed=True, rows=rows,
            assignment=dict(zip(names, [nodes[j] for j in node_of])),
            demand_by_node={nodes[j]: by_node[j]
                            for j in np.unique(node_of).tolist()},
            held_keys={k: sorted({nodes[node_of[i]] for i in held})
                       for k, held in holds.items()})

    prev = commitment(node_of)
    with svc._lock:
        svc._committed["p/pod"] = prev
        svc._persist_committed("p/pod")
    whole = stream[-1][1]
    assert json.loads(whole)["op"] == "put" and len(whole) > 2_000_000
    new_of = node_of.copy()
    at = rng.choice(S, size=moved, replace=False)
    new_of[at] = (node_of[at] + 1 + rng.integers(0, N - 1, size=moved)) % N
    r = commitment(new_of)
    slugs = sorted({nodes[j] for j in node_of[at].tolist()}
                   | {nodes[j] for j in new_of[at].tolist()})
    with svc._lock:
        svc._committed["p/pod"] = r
        svc._persist_committed("p/pod",
                               _RecordChange.superseding(prev, r, slugs))
    line = stream[-1][1]
    entry = json.loads(line)
    assert entry["op"] == "mrg" and len(line) < 64 * 1024
    assert len(entry["set"]["assignment"]) == moved
    rec = store.find_one("placements", lambda p: p.stage_key == "p/pod")
    assert _fields(rec) == _whole(r)
    standby = Store()
    standby.apply_replicated(stream)
    assert _records(standby) == _records(store)


def test_update_keys_patches_in_place_and_journals_one_entry(tmp_path):
    store = Store(str(tmp_path / "cp.json"))
    stream: list[tuple[int, str]] = []
    store.replication_sink = stream.extend
    rec = store.create("placements", PlacementRecord(
        stage_key="p/s", assignment={"a": "n0", "b": "n1"},
        demand_by_node={"n0": [1.0, 2.0, 3.0], "n1": [1.0, 1.0, 1.0]},
        held_keys={"port:80": ["n0"]}))
    assignment = rec.assignment
    got = store.update_keys(
        "placements", rec.id,
        set_keys={"assignment": {"b": "n2", "c": "n0"},
                  "demand_by_node": {"n2": [1.0, 1.0, 1.0]}},
        drop_keys={"demand_by_node": ["n1", "gone"],
                   "held_keys": ["port:80"]})
    assert got is rec and rec.assignment is assignment
    assert rec.assignment == {"a": "n0", "b": "n2", "c": "n0"}
    assert rec.demand_by_node == {"n0": [1.0, 2.0, 3.0],
                                  "n2": [1.0, 1.0, 1.0]}
    assert rec.held_keys == {}
    entry = json.loads(stream[-1][1])
    assert entry["op"] == "mrg" and entry["id"] == rec.id
    assert entry["at"] == rec.updated_at
    assert store.update_keys("placements", "nope",
                             set_keys={"assignment": {"x": "y"}}) is None
    reopened = Store(str(tmp_path / "cp.json"))
    assert _records(reopened) == _records(store)
