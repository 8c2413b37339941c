"""Plain reference for the configuration `k8s-sp-antiaffinity-5k-admit`:
Kubernetes scheduler_perf, test case SchedulingPodAntiAffinity, workload
5000Nodes, with the measured pods handed to the control plane's admission
queue — as plain data, a one-pod-at-a-time scheduler and a checker.

Independent of the code under test: nothing here imports JAX or
`fleetflow_tpu`; the wire spelling of a pod's term (`anti_affinity`,
`anti_affinity_stages`) is plain dict keys, written by
`generators_k8s_anti_admit.py`. The cluster, the waves, the scheduler and
the check over both namespaces are `reference_k8s`'s; this module adds
what streaming admission must hold besides, as `reference_k8s_basic`
states it: what the caller was told is what is committed, a pod placed
before is where it was, a departed pod is in no view.

What is compared, and what is not. RESULTS, not node choices:
`schedule` places every pod of the same cluster with no two green pods on
a node, and so must the system; kube-scheduler's LeastAllocated and the
annealer's soft terms are different objectives.

The model (`cluster`): `reference_k8s.cluster`'s — nodes, and namespaces
sched-0 (init pods, placed before anything is measured) and sched-1 (the
measured wave, new objects every op: `wave`). Every pod is green and
anti-affine to green pods of both namespaces by hostname.
"""

from __future__ import annotations

from benchmarks import reference_k8s
from benchmarks.reference_k8s import INIT, MEASURED

KINDS = reference_k8s.KINDS + ("moved", "untold", "ghost")


def cluster(seed: int, nodes: int, init_pods: int, measure_pods: int) -> dict:
    return reference_k8s.cluster(seed, nodes, init_pods, measure_pods)


def wave(model: dict, op: int) -> dict:
    """The model with the measured pods of op `op` (fresh names), as
    `reference_k8s.measured_batch` builds it."""
    return reference_k8s.measured_batch(model, op)


def schedule(model: dict) -> dict:
    """Both namespaces placed from nothing, sched-0 first, one pod at a
    time (`reference_k8s.schedule`): {namespace: {pod: node or None}}."""
    return reference_k8s.schedule(model, {})


def check(model: dict, before: dict, after: dict, told: dict) -> dict:
    """Count violations per kind; `total` is their sum and 0 means the op
    is correct. `before` / `after` ({namespace: {pod: node}}) are the
    placement records of both namespaces read back before and after the
    op, `told` ({pod: node or None}) the verdicts the caller was given
    for the wave. The kinds of `reference_k8s.check` over `after` — every
    pod of both namespaces placed on a known, online node within cpu,
    memory and pod count, and no two green pods a node over both
    namespaces — and: `moved`, a pod of `before` that `after` holds on
    another node (in either namespace); `untold`, a pod of the wave whose
    verdict is missing or names another node than `after`; `ghost`, a pod
    in `after` that the model no longer has (a departed pod in view)."""
    out = dict.fromkeys(KINDS, 0)
    found = reference_k8s.check(model, {ns: after.get(ns, {})
                                        for ns in (INIT, MEASURED)})
    for kind in reference_k8s.KINDS:
        out[kind] = found[kind]
    for ns, was in before.items():
        now = after.get(ns, {})
        out["moved"] += sum(1 for name, node in was.items()
                            if name in now and now[name] != node)
    placed = after.get(MEASURED, {})
    for pod in model["namespaces"][MEASURED]:
        name = pod["name"]
        out["untold"] += name not in told or told[name] != placed.get(name)
    for ns, now in after.items():
        known = {p["name"] for p in model["namespaces"].get(ns, ())}
        out["ghost"] += sum(1 for name in now if name not in known)
    out["total"] = sum(out[k] for k in KINDS)
    return out
