"""The cell `k8s-sp-preemption-5k.preempt-pods`: its rehearsal is correct
end to end with its five metrics present, its reference places the whole
rehearsal cluster, preempting, and its checker sees a victim that
outranks everything left on its node and one that did not have to go."""

import json
import os

import pytest

from benchmarks import generators_k8s_preemption as generators
from benchmarks import reference_k8s_preemption as reference
from benchmarks.reference_k8s_preemption import INIT, MEASURED
from benchmarks.tests.test_rehearsal import ROOT, check_line, run_cell

CELL = "k8s-sp-preemption-5k.preempt-pods"
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "k8s-sp-preemption-5k.json"), encoding="utf-8") as f:
    CONFIG = json.load(f)
SIZES = CONFIG["rehearsal"]["deployment"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_and_every_pod_evicts_three(trace):
    proc = run_cell(CELL, trace, "--cpu-rehearsal")
    result = check_line(proc, CELL, trace)
    info = json.loads(proc.stdout.strip().splitlines()[-2])["info"]
    assert info["notes"]["reference"] == {
        "placed": {INIT: SIZES["init_pods"],
                   MEASURED: SIZES["measure_pods"]},
        "victims": 3 * SIZES["measure_pods"], "check": 0}
    assert info["compile_in_window"]["events"] == 0
    if trace:
        metrics = result["metrics"]
        assert metrics["victims_per_op"]["value"] \
            == 3 * SIZES["measure_pods"]
        assert metrics["preemptible_servers_per_op"]["value"] \
            == SIZES["nodes"]
        for name in ("preemptible_ms_per_op", "victims_ms_per_op",
                     "commit_evict_ms_per_op"):
            assert metrics[name]["value"] > 0
        # nothing holds a key here, and no plane is staged: every node is
        # filled alike
        assert metrics["held_keys_per_op"]["value"] == 0
        assert metrics["barred_cells_per_op"]["value"] == 0


def test_the_source_sizes_fill_every_node_and_force_three_victims_a_pod():
    dep = CONFIG["deployment"]
    assert (dep["nodes"], dep["init_pods"], dep["measure_pods"]) \
        == (5000, 20000, 1000)
    assert CONFIG["reduced"] == ["measure_pods"]
    node, low, high = reference.NODE, reference.POD_LOW, reference.POD_HIGH
    assert dep["init_pods"] == 4 * dep["nodes"]
    free = node["cpu"] - 4 * low["cpu"]
    assert 4 * low["cpu"] <= node["cpu"] < 5 * low["cpu"]
    assert free + 2 * low["cpu"] < high["cpu"] <= free + 3 * low["cpu"]
    assert high["priority"] > low["priority"]


def test_the_reference_places_the_whole_rehearsal_cluster():
    model = generators.model(CONFIG, 3_000_000_017, True)
    assert len(model["nodes"]) == SIZES["nodes"]
    mine, victims = reference.schedule(model, {})
    assert list(victims) == [INIT]
    assert len(victims[INIT]) == 3 * SIZES["measure_pods"]
    assert None not in mine[MEASURED].values()
    assert len(set(mine[MEASURED].values())) == SIZES["measure_pods"]
    was = {INIT: {**mine[INIT], **victims[INIT]}, MEASURED: mine[MEASURED]}
    assert sorted(set(map(list(was[INIT].values()).count,
                          model["nodes"]))) == [4]
    assert reference.check(model, was, victims)["total"] == 0
    # scheduling the batch onto the held init pods gives the same count
    again, gone = reference.schedule(model, {INIT: was[INIT]})
    assert list(again) == [MEASURED] and len(gone[INIT]) == len(victims[INIT])
    # the same pods under the next op's names are the same answer
    batch = reference.measured_batch(model, 7)
    renamed = {p["name"]: node for p, node in
               zip(batch["namespaces"][MEASURED], mine[MEASURED].values())}
    assert reference.check(batch, {INIT: was[INIT], MEASURED: renamed},
                           victims)["total"] == 0


def test_the_checker_counts_planted_victims():
    model = generators.model(CONFIG, 11, True)
    mine, victims = reference.schedule(model, {})
    was = {INIT: {**mine[INIT], **victims[INIT]}, MEASURED: mine[MEASURED]}
    clean = dict.fromkeys(reference.KINDS, 0)
    # a victim on a node no arrival took: nothing there outranks it, and
    # it fits back
    spared = next(n for n in model["nodes"]
                  if n not in mine[MEASURED].values())
    pod = next(p for p, n in was[INIT].items() if n == spared)
    found = reference.check(model, was,
                            {INIT: {**victims[INIT], pod: spared}})
    assert found == {**clean, "victim_priority": 1, "victim_needless": 1,
                     "total": 2}
    # the fourth low pod of a node whose other three had to go: now any
    # one of the four fits back
    node = next(iter(mine[MEASURED].values()))
    last = next(p for p, n in mine[INIT].items() if n == node)
    found = reference.check(model, was,
                            {INIT: {**victims[INIT], last: node}})
    assert found == {**clean, "victim_needless": 4, "total": 4}
    # an arrival of the init pods' own priority evicts nobody rightly
    model["namespaces"][MEASURED][0]["priority"] = 0
    high = model["namespaces"][MEASURED][0]["name"]
    found = reference.check(model, was, victims)
    assert found == {**clean, "victim_priority": 3, "total": 3}
    assert mine[MEASURED][high] in victims[INIT].values()
