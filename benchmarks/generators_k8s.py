"""The deployment `k8s-sp-antiaffinity-5k` as the program is given it.

The plain model is `reference_k8s.cluster`'s; this module only spells it
in the program's terms, as `configs/k8s-sp-antiaffinity-5k.json` states the
mapping: a namespace is a stage of one flow `k8s`, a pod is one service of
one replica, the pod's anti-affinity term is the label-style
`anti_affinity "color=green" stages="sched-1,sched-0"`, a node is a
registered online server. The stages list no servers: the pool is whatever
the CP has registered.

The request is built as the wire dict (`core/serialize.flow_to_dict`) and
the declaration's reach is written into it as plain data, so the same
bytes can be sent to a program from before the declaration existed: it
ignores the key, and the checker says what that costs.
"""

from __future__ import annotations

from benchmarks import reference_k8s

FLOW = "k8s"
# the source's pod template runs the pause image; the tag is assumed
IMAGE = "registry.k8s.io/pause:3.9"


def model(config: dict, seed: int, rehearsal: bool) -> dict:
    dep = dict(config["deployment"])
    if rehearsal:
        dep.update(config.get("rehearsal", {}).get("deployment", {}))
    return reference_k8s.cluster(seed, dep["nodes"], dep["init_pods"],
                                 dep["measure_pods"])


def server_capacity(node: dict) -> dict:
    """cpu and memory as the node states them; the server record's disk
    stays at its default and no pod asks for disk."""
    return {"cpu": node["cpu"], "memory": node["memory"]}


def solve_request(model: dict, namespace: str) -> dict:
    """The payload of `placement.solve` for one namespace's pods."""
    from fleetflow_tpu.core.model import Flow, ResourceSpec, Service, Stage
    from fleetflow_tpu.core.serialize import flow_to_dict

    pods = model["namespaces"][namespace]
    flow = Flow(name=FLOW)
    for pod in pods:
        flow.services[pod["name"]] = Service(
            name=pod["name"], image=IMAGE,
            resources=ResourceSpec(cpu=pod["cpu"], memory=pod["memory"],
                                   disk=0.0),
            anti_affinity=[pod["anti_affinity"]["label"]])
    flow.stages[namespace] = Stage(name=namespace,
                                   services=[p["name"] for p in pods])
    wire = flow_to_dict(flow)
    for pod in pods:
        term = pod["anti_affinity"]
        wire["services"][pod["name"]]["anti_affinity_stages"] = {
            term["label"]: list(term["namespaces"])}
    return {"flow": wire, "stage": namespace, "reserve": True}
