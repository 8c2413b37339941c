"""`placement_record_diff_share` and `placement_record_keys_per_write` (PR
43): their files, their entries (wherever in `per_layer` they stand) and
their reader over the counters the placement service keeps — the program's
own registry after real writes of a record, and a program that lacks the
counters."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.readers import counter_ratio                    # noqa: E402
from benchmarks.spans import Watch                              # noqa: E402

SHARE = "placement_record_diff_share"
KEYS = "placement_record_keys_per_write"
with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
    BENCH = json.load(f)


def _spec(name):
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def _read(name, counters):
    run = types.SimpleNamespace(counters=counters, count=lambda what: 0)
    return counter_ratio.read(_spec(name)["params"], run)


@pytest.mark.parametrize("name,unit,better", [(SHARE, "%", "higher"),
                                              (KEYS, "keys", "lower")])
def test_file_and_entry_agree(name, unit, better):
    [entry] = [m for m in BENCH["per_layer"] if m["name"] == name]
    spec = _spec(name)
    assert spec["name"] == name and spec["reader"] == "counter_ratio"
    assert entry["source"] == "program_counter"
    assert entry["better"] == better
    assert (spec["unit"], spec["layer"], spec["moves"]) == (
        entry["unit"], entry["layer"], entry["moves"]) == (
        unit, "CP placement", "op_p50_ms")
    # every cell PR 43 found commits a placement record; a later cell
    # joins after them
    assert entry["workloads"][:7] == [
        "mt10kx1k.node-churn", "shop-live.redeploy",
        "k8s-sp-antiaffinity-5k.measure-pods",
        "k8s-sp-preemption-5k.preempt-pods", "pod100kx1k.node-churn-moved",
        "k8s-sp-topology-spread-5k.spread-pods",
        "k8s-sp-basic-5k.admit-pods"]
    # both are after the entries that were there, and next to each other
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(KEYS) == names.index(SHARE) + 1
    assert names.index(SHARE) > names.index("server_columns_rebuilds_per_op")


def test_they_read_the_services_counters():
    from fleetflow_tpu.cp.models import PlacementRecord
    from fleetflow_tpu.cp.placement import (PlacementService, Reservation,
                                            _RecordChange)
    from fleetflow_tpu.cp.store import Store
    store = Store()
    store.replication_sink = lambda entries: None
    svc = PlacementService(store, use_tpu=False)

    def commit(assignment, basis=None):
        r = Reservation(id="r", stage_key="p/s", committed=True,
                        assignment=assignment, demand_by_node={})
        with svc._lock:
            svc._committed["p/s"] = r
            svc._persist_committed("p/s", _RecordChange.superseding(
                basis, r, []))
        return r

    c0 = Watch.counters()
    first = commit({f"s{i}": "n0" for i in range(10)})      # whole: 10 keys
    second = commit({**first.assignment, "s0": "n1"}, first)  # diff: 1 key
    commit({**second.assignment, "s1": "n1", "s2": "n1"}, second)  # 2
    c1 = Watch.counters()
    delta = {k: v - c0.get(k, 0.0) for k, v in c1.items()}
    assert _read(SHARE, delta) == pytest.approx(100 * 2 / 3)
    assert _read(KEYS, delta) == pytest.approx((10 + 1 + 2) / 3)
    [rec] = store.list("placements")
    assert isinstance(rec, PlacementRecord)
    assert rec.assignment == {**second.assignment, "s1": "n1", "s2": "n1"}


def test_a_program_without_the_counters():
    """The parent: no family to divide by, so neither reads."""
    counters = {"fleet_store_ops_total{op=\"put\",table=\"placements\"}": 9.0}
    assert _read(SHARE, counters) is None
    assert _read(KEYS, counters) is None
