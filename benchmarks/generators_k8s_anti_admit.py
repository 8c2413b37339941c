"""The deployment `k8s-sp-antiaffinity-5k-admit` as the program is given it.

The plain model is `reference_k8s_anti_admit.cluster`'s (that is,
`reference_k8s.cluster`'s); this module only spells it in the program's
terms, as `configs/k8s-sp-antiaffinity-5k-admit.json` states the mapping:
a namespace is a stage of one flow `k8s`, a pod is one service of one
replica whose term is the label-style `anti_affinity "color=green"
stages="sched-1,sched-0"`, a node is a registered online server. The
stages list no servers: the pool is whatever the CP has registered.

sched-0's init pods are solved and committed over the wire as
`generators_k8s.solve_request` spells them; sched-1 is attached EMPTY by a
first `deploy.submit` (`flow` + `stage`), as the source creates the
namespace before any of its pods; a wave of measured pods is the
`arrivals` of one `deploy.submit`, each spec carrying the term in the wire
spelling of `core/serialize.py` (`anti_affinity`, `anti_affinity_stages`),
and leaves as its `departures`.
"""

from __future__ import annotations

from benchmarks import generators_k8s
from benchmarks import reference_k8s_anti_admit as reference
from benchmarks.reference_k8s import INIT, MEASURED

FLOW = generators_k8s.FLOW
KEY = f"{FLOW}/{MEASURED}"
TENANT = "default"
IMAGE = generators_k8s.IMAGE
server_capacity = generators_k8s.server_capacity


def model(config: dict, seed: int, rehearsal: bool) -> dict:
    dep = dict(config["deployment"])
    if rehearsal:
        dep.update(config.get("rehearsal", {}).get("deployment", {}))
    return reference.cluster(seed, dep["nodes"], dep["init_pods"],
                             dep["measure_pods"])


def init_request(model: dict) -> dict:
    """The payload of `placement.solve` for sched-0's init pods."""
    return generators_k8s.solve_request(model, INIT)


def attach_request() -> dict:
    """The payload of the `deploy.submit` that opens sched-1 empty."""
    from fleetflow_tpu.core.model import Flow, Stage
    from fleetflow_tpu.core.serialize import flow_to_dict

    flow = Flow(name=FLOW)
    flow.stages[MEASURED] = Stage(name=MEASURED, services=[])
    return {"tenant": TENANT, "flow": flow_to_dict(flow), "stage": MEASURED}


def arrivals(pods: list[dict]) -> list[dict]:
    """Pods as the wire specs `deploy.submit` takes for `arrivals`."""
    out = []
    for p in pods:
        term = p["anti_affinity"]
        out.append({"name": p["name"], "image": IMAGE, "cpu": p["cpu"],
                    "memory": p["memory"], "disk": 0.0,
                    "labels": dict(p["labels"]),
                    "anti_affinity": [term["label"]],
                    "anti_affinity_stages": {
                        term["label"]: list(term["namespaces"])}})
    return out


def submit_request(pods: list[dict], wait_s: float) -> dict:
    """The payload of `deploy.submit` for one wave of pending pods, the
    reply held until every one of them has its verdict."""
    return {"tenant": TENANT, "stage": KEY, "arrivals": arrivals(pods),
            "wait": wait_s}
