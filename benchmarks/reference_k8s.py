"""Plain reference for the configuration `k8s-sp-antiaffinity-5k`:
Kubernetes scheduler_perf, test case SchedulingPodAntiAffinity, workload
5000Nodes, as plain data, a one-pod-at-a-time scheduler and a checker.

Independent of the code under test: nothing here reads `ProblemTensors`,
`lower/` or `solver/repair.verify`, and nothing imports JAX. The tier-1
tests (tests/test_cross_stage_keys.py) and the benchmark's op kind
(ops/solve_commit.py) import this same file.

What is compared, and what is not. The same operations on the same data
give the same answers, which for a scheduler means RESULTS, not
node-for-node equality: `schedule` places every measured pod with `check`
= 0, and so must the system. The soft score is not compared:
kube-scheduler's LeastAllocated, which `schedule` follows, and the
annealer's soft terms are different objectives, and either may choose any
feasible node.

The model (`cluster`), as the source's templates state it:
    nodes       {name: {"cpu": 4.0, "memory": 32768.0 (MiB), "pods": 110}}
    namespaces  {"sched-0": [pod, ...], "sched-1": [pod, ...]}, each list
                in creation order; a pod is {"name", "cpu": 0.1,
                "memory": 500.0 (MiB), "labels": {"color": "green"},
                "anti_affinity": {"label": "color=green",
                                  "namespaces": ["sched-1", "sched-0"]}}
                with topologyKey kubernetes.io/hostname: one node is one
                topology domain.
The source draws nothing at random: the seed decides names and creation
order only.
"""

from __future__ import annotations

import random

import numpy as np

NODE = {"cpu": 4.0, "memory": 32.0 * 1024.0, "pods": 110}
POD = {"cpu": 0.1, "memory": 500.0}
LABEL = "color=green"
INIT, MEASURED = "sched-0", "sched-1"
KINDS = ("unplaced", "unknown", "offline", "cpu", "memory", "pods",
         "anti_affinity")

# demands reach the system's solver as float32 and are summed there; a node
# is over capacity only beyond this relative slack
CAPACITY_RTOL = 1e-4


def _pod(name: str) -> dict:
    key, value = LABEL.split("=")
    return {"name": name, **POD, "labels": {key: value},
            "anti_affinity": {"label": LABEL,
                              "namespaces": [MEASURED, INIT]}}


def cluster(seed: int, nodes: int, init_pods: int, measure_pods: int) -> dict:
    """createNodes `nodes`, createNamespaces sched-0 and sched-1,
    createPods `init_pods` in sched-0 and `measure_pods` in sched-1."""
    rng = random.Random(seed)
    node_ids = list(range(nodes))
    rng.shuffle(node_ids)
    init_ids = list(range(init_pods))
    rng.shuffle(init_ids)
    return {"nodes": {f"node-{i:04d}": dict(NODE) for i in node_ids},
            "namespaces": {
                INIT: [_pod(f"init-{i:04d}") for i in init_ids],
                MEASURED: [_pod(f"pod-0-{i}") for i in range(measure_pods)]}}


def measured_batch(model: dict, op: int) -> dict:
    """The model with the measured pods of op `op`: the same pods under
    fresh names, as the source's measured pods are new objects."""
    pods = [dict(p, name=f"pod-{op}-{i}")
            for i, p in enumerate(model["namespaces"][MEASURED])]
    return dict(model, namespaces=dict(model["namespaces"],
                                       **{MEASURED: pods}))


class _State:
    """What is on each node, as arrays over the model's node order."""

    def __init__(self, model: dict):
        self.names = list(model["nodes"])
        self.index = {n: j for j, n in enumerate(self.names)}
        caps = model["nodes"].values()
        self.cap_cpu = np.array([c["cpu"] for c in caps], dtype=np.float64)
        self.cap_mem = np.array([c["memory"] for c in caps],
                                dtype=np.float64)
        self.cap_pods = np.array([c["pods"] for c in caps], dtype=np.int64)
        n = len(self.names)
        self.cpu = np.zeros(n)
        self.mem = np.zeros(n)
        self.pods = np.zeros(n, dtype=np.int64)
        # labelled[namespace][label] = pods carrying the label, per node
        self.labelled: dict[str, dict[str, np.ndarray]] = {}

    def add(self, namespace: str, pod: dict, j: int) -> None:
        self.cpu[j] += pod["cpu"]
        self.mem[j] += pod["memory"]
        self.pods[j] += 1
        per_label = self.labelled.setdefault(namespace, {})
        for k, v in pod["labels"].items():
            per_label.setdefault(f"{k}={v}",
                                 np.zeros(len(self.names),
                                          dtype=np.int64))[j] += 1

    def matching(self, term: dict) -> np.ndarray:
        """Pods per node that a pod's anti-affinity term selects."""
        out = np.zeros(len(self.names), dtype=np.int64)
        for namespace in term["namespaces"]:
            counts = self.labelled.get(namespace, {}).get(term["label"])
            if counts is not None:
                out += counts
        return out


def schedule(model: dict, held: dict) -> dict:
    """Place every pod of the model that `held` ({namespace: {pod: node}})
    has not placed yet, one at a time in creation order (sched-0 before
    sched-1), as kube-scheduler does: filter — cpu, memory and pod count
    fit, no pod the anti-affinity term selects on the node — then the
    feasible node with the least allocated cpu + memory share
    (LeastAllocated), ties by index. Returns {namespace: {pod: node or
    None}} of the pods it handled. Existing pods' own terms are the same
    term here, so the symmetric check adds nothing."""
    state = _State(model)
    for namespace, pods in model["namespaces"].items():
        placed = held.get(namespace, {})
        for pod in pods:
            if pod["name"] in placed:
                state.add(namespace, pod, state.index[placed[pod["name"]]])
    out: dict[str, dict] = {}
    for namespace, pods in model["namespaces"].items():
        placed = held.get(namespace, {})
        for pod in pods:
            if pod["name"] in placed:
                continue
            feasible = ((state.cpu + pod["cpu"] <= state.cap_cpu)
                        & (state.mem + pod["memory"] <= state.cap_mem)
                        & (state.pods < state.cap_pods)
                        & (state.matching(pod["anti_affinity"]) == 0))
            if not feasible.any():
                out.setdefault(namespace, {})[pod["name"]] = None
                continue
            share = state.cpu / state.cap_cpu + state.mem / state.cap_mem
            j = int(np.argmin(np.where(feasible, share, np.inf)))
            state.add(namespace, pod, j)
            out.setdefault(namespace, {})[pod["name"]] = state.names[j]
    return out


def check(model: dict, assignment_by_namespace: dict, offline=()) -> dict:
    """Count violations per kind over BOTH namespaces together; `total` is
    their sum and 0 means the answer is correct. `unplaced`: a pod of a
    namespace the assignment covers has no node; `unknown` / `offline`: a
    pod on a node the model lacks or that is down; `cpu` / `memory`:
    nodes over capacity; `pods`: nodes with more than their pod count;
    `anti_affinity`: pods beyond the first that a node holds of those one
    term selects (two green pods on one node, whatever their namespaces,
    is 1)."""
    state = _State(model)
    down = {state.index[n] for n in offline if n in state.index}
    out = dict.fromkeys(KINDS, 0)
    for namespace, assignment in assignment_by_namespace.items():
        for pod in model["namespaces"][namespace]:
            node = assignment.get(pod["name"])
            if node is None:
                out["unplaced"] += 1
            elif node not in state.index:
                out["unknown"] += 1
            else:
                j = state.index[node]
                out["offline"] += j in down
                state.add(namespace, pod, j)
    out["cpu"] = int((state.cpu > state.cap_cpu * (1 + CAPACITY_RTOL)
                      + 1e-9).sum())
    out["memory"] = int((state.mem > state.cap_mem * (1 + CAPACITY_RTOL)
                         + 1e-9).sum())
    out["pods"] = int((state.pods > state.cap_pods).sum())
    terms = {(p["anti_affinity"]["label"],
              tuple(sorted(p["anti_affinity"]["namespaces"])))
             for pods in model["namespaces"].values() for p in pods}
    for label, namespaces in terms:
        together = state.matching({"label": label,
                                   "namespaces": namespaces})
        out["anti_affinity"] += int(np.maximum(together - 1, 0).sum())
    out["total"] = sum(out[k] for k in KINDS)
    return out
