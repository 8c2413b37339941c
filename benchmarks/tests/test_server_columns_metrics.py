"""`server_columns_rows_per_read` and `server_columns_rebuilds_per_op` (PR
42): their files, their entries (wherever in `per_layer` they stand) and
their reader over the counters the store keeps — the program's own registry
after real reads, and a program that lacks the counters."""

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

ROWS = "server_columns_rows_per_read"
REBUILDS = "server_columns_rebuilds_per_op"
with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
    BENCH = json.load(f)


def _spec(name):
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def _read(name, counters, ops=0):
    run = types.SimpleNamespace(counters=counters, count=lambda what: ops)
    return counter_ratio.read(_spec(name)["params"], run)


@pytest.mark.parametrize("name,unit", [(ROWS, "records"),
                                       (REBUILDS, "rebuilds")])
def test_file_and_entry_agree(name, unit):
    [entry] = [m for m in BENCH["per_layer"] if m["name"] == name]
    spec = _spec(name)
    assert spec["name"] == name and spec["reader"] == "counter_ratio"
    assert entry["source"] == "program_counter"
    assert entry["better"] == "lower"
    assert (spec["unit"], spec["layer"], spec["moves"]) == (
        entry["unit"], entry["layer"], entry["moves"]) == (
        unit, "CP placement", "op_p50_ms")
    # every cell PR 42 found reads its servers through the store's
    # columns; a later cell joins after them
    assert entry["workloads"][:7] == [
        "mt10kx1k.node-churn", "shop-live.redeploy",
        "k8s-sp-antiaffinity-5k.measure-pods",
        "k8s-sp-preemption-5k.preempt-pods", "pod100kx1k.node-churn-moved",
        "k8s-sp-topology-spread-5k.spread-pods",
        "k8s-sp-basic-5k.admit-pods"]
    # unlabelled counters: a ratio matches a child by its whole label set
    params = spec["params"]
    names = params["num"] + (params["den"] if name == ROWS else [])
    assert all("{" not in n for n in names)


def test_they_read_the_stores_counters():
    from fleetflow_tpu.cp.models import Server
    from fleetflow_tpu.cp.store import Store
    store = Store()
    for i in range(40):
        store.create("servers", Server(slug=f"n{i}", tenant="default"))
    store.server_columns()                          # built in set-up
    ids = list(store._tables["servers"])
    c0 = Watch.counters()
    for k in range(4):                              # 4 reads, 10 rows each
        store.update_many("servers", {i: {"status": "online"}
                                      for i in ids[k * 10:k * 10 + 10]})
        store.server_columns()
    store.server_columns()                          # and one of nothing
    c1 = Watch.counters()
    delta = {k: v - c0.get(k, 0.0) for k, v in c1.items()}
    assert _read(ROWS, delta) == 8.0
    assert _read(REBUILDS, delta, ops=2) == 0.0
    store.delete("servers", ids[0])                 # a membership change
    store.server_columns()
    c2 = Watch.counters()
    delta = {k: v - c0.get(k, 0.0) for k, v in c2.items()}
    assert _read(ROWS, delta) == (40 + 39) / 6
    assert _read(REBUILDS, delta, ops=2) == 0.5


def test_a_program_without_the_counters():
    """The parent: nothing to divide by for the first, 0 for the second."""
    counters = {"fleet_store_ops_total{op=\"put\",table=\"servers\"}": 12.0}
    assert _read(ROWS, counters, ops=5) is None
    assert _read(REBUILDS, counters, ops=5) == 0.0
    assert _read(REBUILDS, counters, ops=0) is None
