"""The deployment `k8s-sp-topology-spread-5k` as the program is given it.

The plain model is `reference_k8s_spread.cluster`'s; this module only
spells it in the program's terms, as
`configs/k8s-sp-topology-spread-5k.json` states the mapping: a namespace is
a stage of one flow `k8s`, a pod is one service of one replica, a node is a
registered online server whose record carries the zone as
`labels.extra["topology.kubernetes.io/zone"]`, and the measured pods'
`topologySpreadConstraints` entry is their stage's
`placement { spread topology_key="topology.kubernetes.io/zone" max_skew=1 }`
(the selector's reach is the namespace's blue pods, and sched-1 holds
nothing else). The stages list no servers: the pool is whatever the CP has
registered.

The constraint travels in the wire dict as the stage's `placement`
(`core/serialize.py`), which programs from before this deployment read too.
"""

from __future__ import annotations

from benchmarks import reference_k8s_spread as reference

FLOW = "k8s"
# the source's pod templates run the pause image; the tag is assumed
IMAGE = "registry.k8s.io/pause:3.9"


def model(config: dict, seed: int, rehearsal: bool) -> dict:
    dep = dict(config["deployment"])
    if rehearsal:
        dep.update(config.get("rehearsal", {}).get("deployment", {}))
    return reference.cluster(seed, dep["nodes"], dep["init_pods"],
                             dep["measure_pods"])


def server_capacity(node: dict) -> dict:
    """cpu and memory as the node states them; the server record's disk
    stays at its default and no pod asks for disk."""
    return {"cpu": node["cpu"], "memory": node["memory"]}


def server_labels(node: dict) -> dict:
    """The node's zone as the server record's free-form label; a node
    without one carries no label at all."""
    zone = node["zone"]
    return {"extra": {} if zone is None else {reference.ZONE_KEY: zone}}


def flow(model: dict, namespace: str):
    """One namespace's pods as a Flow with the one stage; the stage
    carries the spread constraint its pods name (all of them the same)."""
    from fleetflow_tpu.core.model import (Flow, PlacementPolicy,
                                          ResourceSpec, Service,
                                          SpreadConstraint, Stage)

    pods = model["namespaces"][namespace]
    out = Flow(name=FLOW)
    for pod in pods:
        out.services[pod["name"]] = Service(
            name=pod["name"], image=IMAGE,
            resources=ResourceSpec(cpu=pod["cpu"], memory=pod["memory"],
                                   disk=0.0))
    terms = {(p["spread"]["topology_key"], p["spread"]["max_skew"])
             for p in pods if "spread" in p}
    policy = None
    if terms:
        (key, max_skew), = terms
        policy = PlacementPolicy(spread_constraint=SpreadConstraint(
            topology_key=key, max_skew=max_skew))
    out.stages[namespace] = Stage(name=namespace,
                                  services=[p["name"] for p in pods],
                                  placement=policy)
    return out


def solve_request(model: dict, namespace: str) -> dict:
    """The payload of `placement.solve` for one namespace's pods."""
    from fleetflow_tpu.core.serialize import flow_to_dict

    return {"flow": flow_to_dict(flow(model, namespace)),
            "stage": namespace, "reserve": True}
