"""The deployment `k8s-sp-preemption-5k-admit` as the program is given it.

The plain model is `reference_k8s_preempt_admit.cluster`'s (that is,
`reference_k8s_preemption.cluster`'s); this module only spells it in the
program's terms, as `configs/k8s-sp-preemption-5k-admit.json` states the
mapping: a namespace is a stage of one flow `k8s`, a pod is one service of
one replica with the pod's `priority`, a node is a registered online
server. The stages list no servers: the pool is whatever the CP has
registered.

sched-0's init pods are solved and committed in-process as
`generators_k8s_preemption.flow` spells them; sched-1 is attached EMPTY by
a first `deploy.submit` (`flow` + `stage`); a wave of measured pods is the
`arrivals` of one `deploy.submit`, each wire spec carrying the pod's
`priority` as plain data, and leaves as its `departures`.
"""

from __future__ import annotations

from benchmarks import generators_k8s_preemption
from benchmarks import reference_k8s_preempt_admit as reference
from benchmarks.reference_k8s_preemption import MEASURED

FLOW = generators_k8s_preemption.FLOW
KEY = f"{FLOW}/{MEASURED}"
TENANT = "default"
IMAGE = generators_k8s_preemption.IMAGE
server_capacity = generators_k8s_preemption.server_capacity
flow = generators_k8s_preemption.flow


def model(config: dict, seed: int, rehearsal: bool) -> dict:
    dep = dict(config["deployment"])
    if rehearsal:
        dep.update(config.get("rehearsal", {}).get("deployment", {}))
    return reference.cluster(seed, dep["nodes"], dep["init_pods"],
                             dep["measure_pods"])


def attach_request() -> dict:
    """The payload of the `deploy.submit` that opens sched-1 empty."""
    from fleetflow_tpu.core.model import Flow, Stage
    from fleetflow_tpu.core.serialize import flow_to_dict

    empty = Flow(name=FLOW)
    empty.stages[MEASURED] = Stage(name=MEASURED, services=[])
    return {"tenant": TENANT, "flow": flow_to_dict(empty),
            "stage": MEASURED}


def arrivals(pods: list[dict]) -> list[dict]:
    """Pods as the wire specs `deploy.submit` takes for `arrivals`."""
    return [{"name": p["name"], "image": IMAGE, "cpu": p["cpu"],
             "memory": p["memory"], "disk": 0.0,
             "priority": p["priority"]} for p in pods]


def submit_request(pods: list[dict], wait_s: float) -> dict:
    """The payload of `deploy.submit` for one wave of pending pods, the
    reply held until every one of them has its verdict."""
    return {"tenant": TENANT, "stage": KEY, "arrivals": arrivals(pods),
            "wait": wait_s}
