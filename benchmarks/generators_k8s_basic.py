"""The deployment `k8s-sp-basic-5k` as the program is given it.

The plain model is `reference_k8s_basic.cluster`'s; this module only spells
it in the program's terms, as `configs/k8s-sp-basic-5k.json` states the
mapping: the source names no namespace, so the pods live in one stage
`default` of one flow `k8s`; a pod is one service of one replica with
resources only (what `cp/admission.py` lets stream); a node is a registered
online server. The stage lists no servers: the pool is whatever the CP has
registered.

The init pods are the flow the stage is attached with, by the first
`deploy.submit` (`flow` + `stage`); a wave of measured pods is the
`arrivals` of one `deploy.submit`, and leaves as its `departures`.
"""

from __future__ import annotations

from benchmarks import reference_k8s_basic as reference

FLOW = "k8s"
STAGE = "default"
KEY = f"{FLOW}/{STAGE}"
TENANT = "default"
# the source's pod template runs the pause image; the tag is assumed
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


def flow(model: dict):
    """The init pods as a Flow with the one stage."""
    from fleetflow_tpu.core.model import Flow, ResourceSpec, Service, Stage

    out = Flow(name=FLOW)
    for pod in model["init"]:
        out.services[pod["name"]] = Service(
            name=pod["name"], image=IMAGE,
            resources=ResourceSpec(cpu=pod["cpu"], memory=pod["memory"],
                                   disk=0.0))
    out.stages[STAGE] = Stage(name=STAGE,
                              services=[p["name"] for p in model["init"]])
    return out


def attach_request(model: dict) -> dict:
    """The payload of the first `deploy.submit`: the flow of the init pods
    and the stage to stream into, no arrival yet."""
    from fleetflow_tpu.core.serialize import flow_to_dict

    return {"tenant": TENANT, "flow": flow_to_dict(flow(model)),
            "stage": STAGE}


def arrivals(pods: list[dict]) -> list[dict]:
    """Pods as the wire specs `deploy.submit` takes for `arrivals`."""
    return [{"name": p["name"], "image": IMAGE, "cpu": p["cpu"],
             "memory": p["memory"], "disk": 0.0} for p in pods]


def submit_request(pods: list[dict], wait_s: float) -> dict:
    """The payload of `deploy.submit` for one wave of pending pods, the
    reply held until every one of them has its verdict."""
    return {"tenant": TENANT, "stage": KEY, "arrivals": arrivals(pods),
            "wait": wait_s}
