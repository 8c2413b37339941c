"""The checker passes a known-good assignment of a tiny generated stage
and names each planted violation."""

import pytest

from benchmarks import checker, generators


@pytest.fixture()
def stage():
    """A tiny model with every constraint kind, and a good assignment."""
    services = [
        {"name": "db", "replicas": 1, "cpu": 1.0, "memory": 100.0,
         "disk": 10.0, "ports": [5432], "volumes": ["/data/db"],
         "anti_affinity": [], "eligible": ["n0", "n1"]},
        {"name": "db2", "replicas": 1, "cpu": 1.0, "memory": 100.0,
         "disk": 10.0, "ports": [5432], "volumes": ["/data/db"],
         "anti_affinity": [], "eligible": None},
        {"name": "web", "replicas": 2, "cpu": 0.5, "memory": 50.0,
         "disk": 0.0, "ports": [], "volumes": [], "anti_affinity": ["web"],
         "eligible": None},
        {"name": "batch", "replicas": 1, "cpu": 0.5, "memory": 50.0,
         "disk": 0.0, "ports": [], "volumes": [], "anti_affinity": ["db"],
         "eligible": None}]
    servers = {f"n{j}": {"cpu": 2.0, "memory": 200.0, "disk": 20.0}
               for j in range(4)}
    good = {"db": "n0", "db2": "n1", "web#0": "n2", "web#1": "n3",
            "batch": "n2"}
    return checker.Model(services, servers), good


def test_known_good_assignment_passes(stage):
    model, good = stage
    assert model.rows == ["db", "db2", "web#0", "web#1", "batch"]
    found = checker.check(model, good)
    assert found["total"] == 0, found


@pytest.mark.parametrize("change, offline, kind", [
    ({"web#0": "n0", "web#1": "n0", "batch": "n0"}, (), "capacity"),
    ({"db2": "n0"}, (), "port"),
    ({"db2": "n0"}, (), "volume"),
    ({"web#1": "n2"}, (), "anti_affinity"),      # a service's own replicas
    ({"batch": "n0"}, (), "anti_affinity"),      # a declared pair
    ({"db": "n3"}, (), "ineligible"),
    ({}, ("n2",), "offline"),
    ({"web#1": None}, (), "unplaced"),
    ({"web#1": "nowhere"}, (), "unplaced"),
])
def test_planted_violation_is_named(stage, change, offline, kind):
    model, good = stage
    bad = {k: v for k, v in {**good, **change}.items() if v is not None}
    found = checker.check(model, bad, offline=offline)
    assert found[kind] >= 1, found
    assert found["total"] >= 1


def test_generated_stage_matches_its_model():
    """The generator's model names the rows the program's lowering names:
    a greedy assignment over the model passes the checker."""
    flow, model = generators.live_stage(60, 8, seed=3)
    m = checker.Model(**model)
    assert len(m.rows) == 60 + 3           # every 20th service has 2 replicas
    assert set(flow.services) == {s["name"] for s in model["services"]}
    nodes = list(model["servers"])
    assignment = {row: nodes[i % len(nodes)] for i, row in enumerate(m.rows)}
    found = checker.check(m, assignment)
    assert found["unplaced"] == 0 and found["anti_affinity"] == 0, found


def test_registry_model_states_what_the_kdl_states():
    texts, pool, model = generators.registry(2, 40, 10, seed=5)
    for spec in model["services"]:
        fleet, stage, name = spec["name"].split(".", 2)
        assert stage == "prod" and f'service "{name}"' in texts[fleet]
        for port in spec["ports"]:
            assert f"port host={port} " in texts[fleet]
    assert all(f'server "{n}"' in pool for n in model["servers"])
