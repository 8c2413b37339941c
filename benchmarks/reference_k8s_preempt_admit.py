"""Plain reference for the configuration `k8s-sp-preemption-5k-admit`:
Kubernetes scheduler_perf, test case PreemptionBasic, workload 5000Nodes,
with the measured pods handed to the control plane's admission queue — as
plain data, a one-pod-at-a-time scheduler that preempts, and a checker.

Independent of the code under test: nothing here imports JAX or
`fleetflow_tpu`. The cluster, the per-node rules (filter, kube-scheduler's
`selectVictimsOnNode`) and the check of capacity and victims are
`reference_k8s_preemption`'s; this module adds what streaming admission
must hold besides, as `reference_k8s_anti_admit` states it: what the
caller was told is what is committed, a pod placed before is where it
was, a pod that is gone is in no view — and, since the caller is told no
victim, the victims are read from the records: the init pods that the
record of sched-0 held before the op and does not hold after it.

What is compared, and what is not. RESULTS, not node choices: `schedule`
places every pod with `check` = 0, and so must the system; where the
instance forces the number of victims (four low pods a node, a high pod
needs exactly three of them gone), the system's count equals the
reference's. Which node a pod takes, and which three of four equal pods
leave, either may choose.

The model (`cluster`): `reference_k8s_preemption.cluster`'s — nodes, and
namespaces sched-0 (20,000 low pods, placed before anything is measured)
and sched-1 (the measured wave of high pods, new objects every op:
`wave`).
"""

from __future__ import annotations

from benchmarks import reference_k8s_preemption as preemption
from benchmarks.reference_k8s_preemption import INIT, MEASURED

KINDS = preemption.KINDS + ("victims", "allocated", "moved", "untold",
                            "ghost")


def cluster(seed: int, nodes: int, init_pods: int, measure_pods: int) -> dict:
    return preemption.cluster(seed, nodes, init_pods, measure_pods)


def wave(model: dict, op: int) -> dict:
    """The model with the measured pods of op `op` (fresh names), as
    `reference_k8s_preemption.measured_batch` builds it."""
    return preemption.measured_batch(model, op)


def schedule(model: dict) -> tuple[dict, dict]:
    """Both namespaces placed from nothing, sched-0 first, one pod at a
    time, preempting where nothing fits (`reference_k8s_preemption
    .schedule`): ({namespace: {pod: node or None}}, {namespace: {victim
    pod: node}})."""
    return preemption.schedule(model, {})


def check(model: dict, before: dict, after: dict, told: dict,
          allocated: dict | None = None,
          forced: int | None = None) -> dict:
    """Count violations per kind; `total` is their sum and 0 means the op
    is correct. `before` / `after` ({namespace: {pod: node}}) are the
    placement records of both namespaces read back before and after the
    op, `told` ({pod: node or None}) the verdicts the caller was given for
    the wave, `allocated` ({node: (cpu, memory)}) what the store says is
    allocated on the nodes the op touched (None: not checked), `forced`
    the number of victims the instance forces (None: not checked).

    The victims are the pods of sched-0 that `before` holds and `after`
    does not. The kinds of `reference_k8s_preemption.check` over them and
    `after` — every pod placed on a known, online node, capacity and pod
    count over the survivors and the arrivals, no victim of no lower
    priority or needless — and: `victims`, how far the count is from
    `forced`; `allocated`, a node the op touched (an arrival or a victim
    on it) whose `allocated` is not the sum of what remains on it;
    `moved`, a pod of `before` that `after` holds on another node;
    `untold`, a pod of the wave whose verdict is missing or names another
    node than `after`; `ghost`, a pod in `after` that the model does not
    have."""
    out = dict.fromkeys(KINDS, 0)
    was, now = before.get(INIT, {}), after.get(INIT, {})
    gone = {name: node for name, node in was.items() if name not in now}
    placed = after.get(MEASURED, {})
    found = preemption.check(model, {INIT: {**now, **gone},
                                     MEASURED: placed}, {INIT: gone})
    for kind in preemption.KINDS:
        out[kind] = found[kind]
    if forced is not None:
        out["victims"] = abs(len(gone) - forced)
    if allocated is not None:
        pods = {ns: {p["name"]: p for p in model["namespaces"][ns]}
                for ns in (INIT, MEASURED)}
        touched = set(placed.values()) | set(gone.values())
        want = {node: [0.0, 0.0] for node in touched}
        for ns, held in ((INIT, now), (MEASURED, placed)):
            for name, node in held.items():
                if node in want and name in pods[ns]:
                    want[node][0] += pods[ns][name]["cpu"]
                    want[node][1] += pods[ns][name]["memory"]
        rtol = preemption.CAPACITY_RTOL
        for node, (cpu, memory) in want.items():
            has = allocated.get(node)
            out["allocated"] += (
                has is None or abs(has[0] - cpu) > rtol * max(cpu, 1.0)
                or abs(has[1] - memory) > rtol * max(memory, 1.0))
    for ns, held in before.items():
        later = after.get(ns, {})
        out["moved"] += sum(1 for name, node in held.items()
                            if name in later and later[name] != node)
    for pod in model["namespaces"][MEASURED]:
        name = pod["name"]
        out["untold"] += name not in told or told[name] != placed.get(name)
    for ns, held in after.items():
        known = {p["name"] for p in model["namespaces"].get(ns, ())}
        out["ghost"] += sum(1 for name in held if name not in known)
    out = {k: int(v) for k, v in out.items()}
    out["total"] = sum(out[k] for k in KINDS)
    return out
