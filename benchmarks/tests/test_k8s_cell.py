"""The cell `k8s-sp-antiaffinity-5k.measure-pods`: its rehearsal is
correct end to end, its reference places the whole rehearsal cluster, and
its checker sees a collision across namespaces."""

import json
import os

import pytest

from benchmarks import generators_k8s, reference_k8s
from benchmarks.reference_k8s import INIT, MEASURED
from benchmarks.tests.test_rehearsal import ROOT, check_line, run_cell

CELL = "k8s-sp-antiaffinity-5k.measure-pods"
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "k8s-sp-antiaffinity-5k.json"), encoding="utf-8") as f:
    CONFIG = json.load(f)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_and_its_keys_do_the_work(trace):
    proc = run_cell(CELL, trace, "--cpu-rehearsal")
    result = check_line(proc, CELL, trace)
    info = json.loads(proc.stdout.strip().splitlines()[-2])["info"]
    sizes = CONFIG["rehearsal"]["deployment"]
    assert info["notes"]["reference"] == {
        "placed": {INIT: sizes["init_pods"], MEASURED: sizes["measure_pods"]},
        "check": 0}
    assert info["compile_in_window"]["events"] == 0
    if trace:
        metrics = result["metrics"]
        # every measured pod barred from every node that holds an init
        # pod; the init pods hold two keys each (green in sched-0, and
        # reaching into sched-1)
        assert metrics["barred_cells_per_op"]["value"] \
            == sizes["init_pods"] * sizes["measure_pods"]
        assert metrics["held_keys_per_op"]["value"] == 2 * sizes["init_pods"]
        assert metrics["held_keys_ms_per_op"]["value"] > 0


@pytest.mark.parametrize("rehearsal", [True, False],
                         ids=["rehearsal", "source"])
def test_the_reference_places_the_whole_cluster(rehearsal):
    model = generators_k8s.model(CONFIG, 3_000_000_017, rehearsal)
    sizes = dict(CONFIG["deployment"])
    if rehearsal:
        sizes.update(CONFIG["rehearsal"]["deployment"])
    assert len(model["nodes"]) == sizes["nodes"]
    init = reference_k8s.schedule(
        dict(model, namespaces={INIT: model["namespaces"][INIT],
                                MEASURED: []}), {})
    mine = reference_k8s.schedule(model, init)
    assert list(mine) == [MEASURED]
    placed = {**init, **mine}
    assert sum(v is not None for v in placed[INIT].values()) \
        == sizes["init_pods"]
    assert sum(v is not None for v in placed[MEASURED].values()) \
        == sizes["measure_pods"]
    assert reference_k8s.check(model, placed)["total"] == 0
    assert not set(placed[INIT].values()) & set(placed[MEASURED].values())


def test_the_checker_counts_a_collision_across_namespaces():
    model = generators_k8s.model(CONFIG, 11, True)
    placed = reference_k8s.schedule(model, {})
    pod = model["namespaces"][MEASURED][0]["name"]
    planted = dict(placed[MEASURED],
                   **{pod: next(iter(placed[INIT].values()))})
    found = reference_k8s.check(model, {INIT: placed[INIT],
                                        MEASURED: planted})
    assert found["anti_affinity"] == 1 and found["total"] == 1
    # the same pods under the next op's names are the same answer
    batch = reference_k8s.measured_batch(model, 7)
    renamed = {p["name"]: node for p, node in
               zip(batch["namespaces"][MEASURED], planted.values())}
    assert reference_k8s.check(batch, {INIT: placed[INIT],
                                       MEASURED: renamed})["total"] == 1
