"""Plain reference for the configuration `k8s-sp-preemption-5k`: Kubernetes
scheduler_perf, test case PreemptionBasic, workload 5000Nodes, as plain
data, a one-pod-at-a-time scheduler that preempts, and a checker.

Independent of the code under test: nothing here reads `ProblemTensors`,
`lower/`, `solver/` or `cp/`, and nothing imports JAX. The tier-1 tests
(tests/test_preemption.py) and the benchmark's op kind
(ops/solve_commit_preempt.py) import this same file.

What is compared, and what is not. RESULTS, not node-for-node choices:
`schedule` places every pod with `check` = 0, and so must the system; and
where the instance forces the number of victims (here: every node holds
four low pods, a high pod fits nowhere and needs exactly three of them
gone), the system's count equals the reference's. Which node a pod lands
on, and which three of four equal pods leave, either may choose.

The model (`cluster`), as the source's templates state it:
    nodes       {name: {"cpu": 4.0, "memory": 32768.0 (MiB), "pods": 110}}
    namespaces  {"sched-0": [pod, ...], "sched-1": [pod, ...]}, each list
                in creation order; a pod is {"name", "cpu", "memory",
                "priority"}: pod-low-priority.yaml asks cpu 0.9, memory
                500 and names no priority (0); pod-high-priority.yaml
                asks cpu 3.0, memory 500, priority 10.
The source draws nothing at random: the seed decides names and creation
order only. A test may hand `check` and `schedule` any model of this form,
pods of other sizes and priorities included.
"""

from __future__ import annotations

import random

import numpy as np

NODE = {"cpu": 4.0, "memory": 32.0 * 1024.0, "pods": 110}
POD_LOW = {"cpu": 0.9, "memory": 500.0, "priority": 0}
POD_HIGH = {"cpu": 3.0, "memory": 500.0, "priority": 10}
INIT, MEASURED = "sched-0", "sched-1"
KINDS = ("unplaced", "unknown", "offline", "cpu", "memory", "pods",
         "victim_unknown", "victim_priority", "victim_needless")

# demands reach the system's solver as float32 and are summed there; a node
# is over capacity only beyond this relative slack
CAPACITY_RTOL = 1e-4


def cluster(seed: int, nodes: int, init_pods: int, measure_pods: int) -> dict:
    """createNodes `nodes`, createPods `init_pods` from
    pod-low-priority.yaml (namespace sched-0), createPods `measure_pods`
    from pod-high-priority.yaml (namespace sched-1)."""
    rng = random.Random(seed)
    node_ids = list(range(nodes))
    rng.shuffle(node_ids)
    init_ids = list(range(init_pods))
    rng.shuffle(init_ids)
    return {"nodes": {f"node-{i:04d}": dict(NODE) for i in node_ids},
            "namespaces": {
                INIT: [dict(POD_LOW, name=f"low-{i:05d}") for i in init_ids],
                MEASURED: [dict(POD_HIGH, name=f"high-0-{i}")
                           for i in range(measure_pods)]}}


def measured_batch(model: dict, op: int, count: int | None = None) -> dict:
    """The model with the measured pods of op `op`: the first `count` of
    them (all, by default) under fresh names, as the source's measured
    pods are new objects."""
    pods = [dict(p, name=f"high-{op}-{i}")
            for i, p in enumerate(model["namespaces"][MEASURED][:count])]
    return dict(model, namespaces=dict(model["namespaces"],
                                       **{MEASURED: pods}))


def _fits(cpu, mem, pods, node: dict) -> bool:
    return (cpu <= node["cpu"] * (1 + CAPACITY_RTOL) + 1e-9
            and mem <= node["memory"] * (1 + CAPACITY_RTOL) + 1e-9
            and pods <= node["pods"])


class _State:
    """The pods on each node, {node index: {(namespace, pod name): pod}},
    and their sums as arrays over the model's node order."""

    def __init__(self, model: dict):
        self.names = list(model["nodes"])
        self.index = {n: j for j, n in enumerate(self.names)}
        self.caps = list(model["nodes"].values())
        self.cap_cpu = np.array([c["cpu"] for c in self.caps])
        self.cap_mem = np.array([c["memory"] for c in self.caps])
        self.cap_pods = np.array([c["pods"] for c in self.caps])
        n = len(self.names)
        self.cpu, self.mem = np.zeros(n), np.zeros(n)
        self.pods = np.zeros(n, dtype=np.int64)
        self.on: list[dict] = [{} for _ in range(n)]

    def add(self, key: tuple, pod: dict, j: int) -> None:
        self.on[j][key] = pod
        self.cpu[j] += pod["cpu"]
        self.mem[j] += pod["memory"]
        self.pods[j] += 1

    def remove(self, key: tuple, j: int) -> None:
        pod = self.on[j].pop(key)
        self.cpu[j] -= pod["cpu"]
        self.mem[j] -= pod["memory"]
        self.pods[j] -= 1

    def feasible(self, pod: dict) -> np.ndarray:
        return ((self.cpu + pod["cpu"]
                 <= self.cap_cpu * (1 + CAPACITY_RTOL) + 1e-9)
                & (self.mem + pod["memory"]
                   <= self.cap_mem * (1 + CAPACITY_RTOL) + 1e-9)
                & (self.pods < self.cap_pods))

    def shape(self, j: int) -> tuple:
        """What `victims_for` depends on: nodes of one shape give one
        answer, and a cluster filled alike has few shapes."""
        cap = self.caps[j]
        return (cap["cpu"], cap["memory"], cap["pods"],
                tuple((p["priority"], p["cpu"], p["memory"])
                      for p in self.on[j].values()))

    def victims_for(self, pod: dict, j: int) -> list | None:
        """kube-scheduler's selectVictimsOnNode: remove every pod of
        lower priority; if the pod still does not fit, the node is no
        candidate; else reprieve, highest priority first, each one that
        leaves the pod fitting. What stays removed are the victims."""
        here = self.on[j]
        lower = [k for k, p in here.items()
                 if p["priority"] < pod["priority"]]

        def fits(without) -> bool:
            left = [p for k, p in here.items() if k not in without]
            return _fits(sum(p["cpu"] for p in left) + pod["cpu"],
                         sum(p["memory"] for p in left) + pod["memory"],
                         len(left) + 1, self.caps[j])

        out = set(lower)
        if not fits(out):
            return None
        for k in sorted(lower, key=lambda k: -here[k]["priority"]):
            if fits(out - {k}):
                out.discard(k)
        return [k for k in lower if k in out]


def schedule(model: dict, held: dict) -> tuple[dict, dict]:
    """Place every pod of the model that `held` ({namespace: {pod: node}})
    has not placed yet, one at a time in creation order (sched-0 before
    sched-1), as kube-scheduler does: filter (cpu, memory and pod count
    fit), then the feasible node with the least allocated cpu + memory
    share (LeastAllocated), ties by index; if nothing fits, preempt: per
    node `victims_for`, the node with the fewest victims (ties by index),
    evict them, bind. Returns ({namespace: {pod: node or None}} of the
    pods it handled, {namespace: {victim pod: node}})."""
    state = _State(model)
    for namespace, pods in model["namespaces"].items():
        placed = held.get(namespace, {})
        for pod in pods:
            if pod["name"] in placed:
                state.add((namespace, pod["name"]), pod,
                          state.index[placed[pod["name"]]])
    out: dict[str, dict] = {}
    victims: dict[str, dict] = {}
    shapes = None       # per node, its `shape`; built at the first preemption
    for namespace, pods in model["namespaces"].items():
        placed = held.get(namespace, {})
        for pod in pods:
            if pod["name"] in placed:
                continue
            key = (namespace, pod["name"])
            feasible = state.feasible(pod)
            if feasible.any():
                share = (state.cpu / state.cap_cpu
                         + state.mem / state.cap_mem)
                j = int(np.argmin(np.where(feasible, share, np.inf)))
            else:
                if shapes is None:
                    shapes = [state.shape(j)
                              for j in range(len(state.names))]
                count: dict[tuple, float] = {}
                for j in {s: j for j, s in reversed(
                        list(enumerate(shapes)))}.values():
                    gone = state.victims_for(pod, j)
                    count[shapes[j]] = np.inf if gone is None else len(gone)
                need = np.array([count[s] for s in shapes])
                j = int(np.argmin(need))
                if not np.isfinite(need[j]):
                    out.setdefault(namespace, {})[pod["name"]] = None
                    continue
                for ns, name in state.victims_for(pod, j):
                    state.remove((ns, name), j)
                    victims.setdefault(ns, {})[name] = state.names[j]
                    if name in out.get(ns, {}):
                        out[ns][name] = None    # bound here, then evicted
            state.add(key, pod, j)
            if shapes is not None:
                shapes[j] = state.shape(j)
            out.setdefault(namespace, {})[pod["name"]] = state.names[j]
    return out, victims


def check(model: dict, assignment_by_namespace: dict, victims: dict,
          offline=()) -> dict:
    """Count violations per kind; `total` is their sum and 0 means the
    answer is correct. `assignment_by_namespace` ({namespace: {pod:
    node}}) is where every pod WAS before the eviction, arrivals
    included; `victims` ({namespace: {pod: node}}) are the pods evicted.
    Survivors are the assigned pods that are no victims.

    `unplaced`: a pod of a namespace the assignment covers has no node
    (a victim had one); `unknown` / `offline`: a pod on a node the model
    lacks or that is down; `cpu` / `memory` / `pods`: nodes over capacity
    or pod count, over survivors; `victim_unknown`: a victim the model
    lacks, or that was not on the node it is said to leave;
    `victim_priority`: a victim that no pod of strictly higher priority
    survives on its node; `victim_needless`: a victim that could be put
    back on its node alone with the node still within capacity and
    count."""
    nodes = model["nodes"]
    index = {n: j for j, n in enumerate(nodes)}
    down = set(offline)
    out = dict.fromkeys(KINDS, 0)
    # what survives, summed by node: one pass of lookups a namespace, the
    # sums as array passes (20,000 pods are checked after every op)
    cpu, mem = np.zeros(len(nodes)), np.zeros(len(nodes))
    count = np.zeros(len(nodes), dtype=np.int64)
    top = np.full(len(nodes), -np.inf)      # highest priority that stays
    for namespace, assignment in assignment_by_namespace.items():
        pods = model["namespaces"][namespace]
        gone = victims.get(namespace, {})
        where = [assignment.get(p["name"]) for p in pods]
        out["unplaced"] += where.count(None)
        out["offline"] += sum(w in down for w in where) if down else 0
        at = np.array([-1 if w is None else index.get(w, -2)
                       for w in where], dtype=np.int64)
        out["unknown"] += int((at == -2).sum())
        stays = (at >= 0) & ~np.array([p["name"] in gone for p in pods],
                                      dtype=bool)
        at = at[stays]
        np.add.at(cpu, at, np.array([p["cpu"] for p in pods])[stays])
        np.add.at(mem, at, np.array([p["memory"] for p in pods])[stays])
        np.add.at(count, at, 1)
        np.maximum.at(top, at,
                      np.array([p["priority"] for p in pods])[stays])
    cap_cpu = np.array([c["cpu"] for c in nodes.values()])
    cap_mem = np.array([c["memory"] for c in nodes.values()])
    cap_pods = np.array([c["pods"] for c in nodes.values()])
    out["cpu"] = (cpu > cap_cpu * (1 + CAPACITY_RTOL) + 1e-9).sum()
    out["memory"] = (mem > cap_mem * (1 + CAPACITY_RTOL) + 1e-9).sum()
    out["pods"] = (count > cap_pods).sum()
    pods_of = {ns: {p["name"]: p for p in model["namespaces"].get(ns, ())}
               for ns in victims}
    for namespace, gone in victims.items():
        was = assignment_by_namespace.get(namespace, {})
        for name, node in gone.items():
            pod = pods_of[namespace].get(name)
            if pod is None or node not in index or was.get(name) != node:
                out["victim_unknown"] += 1
                continue
            j = index[node]
            out["victim_priority"] += not top[j] > pod["priority"]
            out["victim_needless"] += _fits(
                cpu[j] + pod["cpu"], mem[j] + pod["memory"], count[j] + 1,
                nodes[node])
    out = {k: int(v) for k, v in out.items()}
    out["total"] = sum(out[k] for k in KINDS)
    return out
