"""The deployment `k8s-sp-preemption-5k` as the program is given it.

The plain model is `reference_k8s_preemption.cluster`'s; this module only
spells it in the program's terms, as `configs/k8s-sp-preemption-5k.json`
states the mapping: a namespace is a stage of one flow `k8s`, a pod is one
service of one replica with the pod's `priority`, a node is a registered
online server. The stages list no servers: the pool is whatever the CP has
registered.

`priority` is written into the wire dict as plain data (a template that
names none writes none), so the same bytes can be sent to a program from
before priorities existed: it ignores the key, finds no room for a high
pod on full nodes, and the checker says so.
"""

from __future__ import annotations

from benchmarks import reference_k8s_preemption

FLOW = "k8s"
# the source's pod templates run the pause image; the tag is assumed
IMAGE = "registry.k8s.io/pause:3.9"


def model(config: dict, seed: int, rehearsal: bool) -> dict:
    dep = dict(config["deployment"])
    if rehearsal:
        dep.update(config.get("rehearsal", {}).get("deployment", {}))
    return reference_k8s_preemption.cluster(
        seed, dep["nodes"], dep["init_pods"], dep["measure_pods"])


def server_capacity(node: dict) -> dict:
    """cpu and memory as the node states them; the server record's disk
    stays at its default and no pod asks for disk."""
    return {"cpu": node["cpu"], "memory": node["memory"]}


def flow(model: dict, namespace: str):
    """One namespace's pods as a Flow with the one stage."""
    from fleetflow_tpu.core.model import Flow, ResourceSpec, Service, Stage

    pods = model["namespaces"][namespace]
    out = Flow(name=FLOW)
    for pod in pods:
        out.services[pod["name"]] = Service(
            name=pod["name"], image=IMAGE,
            resources=ResourceSpec(cpu=pod["cpu"], memory=pod["memory"],
                                   disk=0.0))
    out.stages[namespace] = Stage(name=namespace,
                                  services=[p["name"] for p in pods])
    return out


def solve_request(model: dict, namespace: str) -> dict:
    """The payload of `placement.solve` for one namespace's pods."""
    from fleetflow_tpu.core.serialize import flow_to_dict

    wire = flow_to_dict(flow(model, namespace))
    for pod in model["namespaces"][namespace]:
        if pod["priority"]:
            wire["services"][pod["name"]]["priority"] = pod["priority"]
    return {"flow": wire, "stage": namespace, "reserve": True}
