"""Plain reference for the configuration `k8s-sp-topology-spread-5k`:
Kubernetes scheduler_perf, test case TopologySpreading, workload 5000Nodes,
as plain data, a one-pod-at-a-time scheduler and a checker.

Independent of the code under test: nothing here reads `ProblemTensors`,
`lower/` or `solver/repair.verify`, and nothing imports JAX or
`fleetflow_tpu`. The tier-1 tests (tests/test_topology_spread.py) and the
benchmark's op kind (ops/solve_commit_spread.py) import this same file.

What is compared, and what is not. The same operations on the same data
give the same answers, which for a scheduler means RESULTS, not
node-for-node equality: `schedule` places every measured pod with `check`
= 0, and so must the system; and since the source's filter admits a pod to
a zone only while `count(zone) + 1 - min over zones <= maxSkew`, every
order it can take ends with the zones' counts within maxSkew of each other
— for 2,000 pods over three zones, 666 / 667 / 667 in some order — so the
SORTED zone counts are compared too. Which node inside a zone a pod takes
is not: kube-scheduler's LeastAllocated, which `schedule` follows, and the
annealer's soft terms are different objectives.

The model (`cluster`), as the source's templates state it:
    nodes       {name: {"cpu": 4.0, "memory": 32768.0 (MiB), "pods": 110,
                        "zone": "moon-1" | "moon-2" | "moon-3" | None}}
                in creation order, the zone label dealt round-robin over
                that order (labelNodePrepareStrategy); None is a node
                without the label (the source has none such; the tests do)
    namespaces  {"sched-0": [pod, ...], "sched-1": [pod, ...]}, each list
                in creation order; a pod is {"name", "cpu": 0.1,
                "memory": 500.0 (MiB), "labels": {...}} and a measured pod
                carries "labels": {"color": "blue"} and
                "spread": {"max_skew": 1, "label": "color=blue",
                           "topology_key": "topology.kubernetes.io/zone"}
                (whenUnsatisfiable: DoNotSchedule). The selector's reach is
                the pod's own namespace, as the source's is.
The source draws nothing at random: the seed decides names and creation
order only.
"""

from __future__ import annotations

import random

import numpy as np

NODE = {"cpu": 4.0, "memory": 32.0 * 1024.0, "pods": 110}
POD = {"cpu": 0.1, "memory": 500.0}
ZONE_KEY = "topology.kubernetes.io/zone"
ZONES = ("moon-1", "moon-2", "moon-3")
LABEL = "color=blue"
MAX_SKEW = 1
INIT, MEASURED = "sched-0", "sched-1"
KINDS = ("unplaced", "unknown", "offline", "capacity", "pods", "unlabelled",
         "skew")

# demands reach the system's solver as float32 and are summed there; a node
# is over capacity only beyond this relative slack
CAPACITY_RTOL = 1e-4


def _spread_pod(name: str) -> dict:
    key, value = LABEL.split("=")
    return {"name": name, **POD, "labels": {key: value},
            "spread": {"max_skew": MAX_SKEW, "label": LABEL,
                       "topology_key": ZONE_KEY}}


def cluster(seed: int, nodes: int, init_pods: int, measure_pods: int) -> dict:
    """createNodes `nodes` with the zone label dealt round-robin,
    createPods `init_pods` plain pods in sched-0 and `measure_pods`
    spreading pods in sched-1."""
    rng = random.Random(seed)
    node_ids = list(range(nodes))
    rng.shuffle(node_ids)
    init_ids = list(range(init_pods))
    rng.shuffle(init_ids)
    return {"nodes": {f"node-{i:04d}": dict(NODE, zone=ZONES[at % len(ZONES)])
                      for at, i in enumerate(node_ids)},
            "namespaces": {
                INIT: [{"name": f"init-{i:04d}", **POD, "labels": {}}
                       for i in init_ids],
                MEASURED: [_spread_pod(f"pod-0-{i}")
                           for i in range(measure_pods)]}}


def with_zones(model: dict, sizes: dict) -> dict:
    """The model with its nodes relabelled, in creation order: the first
    `sizes[zone]` nodes take each zone in turn (None: no label); nodes
    beyond the sizes' sum keep their zone."""
    zones = [z for z, n in sizes.items() for _ in range(n)]
    nodes = {name: dict(node, zone=zones[j] if j < len(zones)
                        else node["zone"])
             for j, (name, node) in enumerate(model["nodes"].items())}
    return dict(model, nodes=nodes)


def measured_batch(model: dict, op: int) -> dict:
    """The model with the measured pods of op `op`: the same pods under
    fresh names, as the source's measured pods are new objects."""
    pods = [dict(p, name=f"pod-{op}-{i}")
            for i, p in enumerate(model["namespaces"][MEASURED])]
    return dict(model, namespaces=dict(model["namespaces"],
                                       **{MEASURED: pods}))


class _State:
    """What is on each node, as arrays over the model's nodes in NAME
    order (so that the first of equal nodes is the first by name)."""

    def __init__(self, model: dict):
        self.names = sorted(model["nodes"])
        self.index = {n: j for j, n in enumerate(self.names)}
        caps = [model["nodes"][n] for n in self.names]
        self.cap_cpu = np.array([c["cpu"] for c in caps], dtype=np.float64)
        self.cap_mem = np.array([c["memory"] for c in caps],
                                dtype=np.float64)
        self.cap_pods = np.array([c["pods"] for c in caps], dtype=np.int64)
        self.zones = sorted({c["zone"] for c in caps
                             if c["zone"] is not None})
        at = {z: t for t, z in enumerate(self.zones)}
        # -1: the node lacks the label
        self.zone = np.array([at.get(c["zone"], -1) for c in caps],
                             dtype=np.int64)
        n = len(self.names)
        self.cpu = np.zeros(n)
        self.mem = np.zeros(n)
        self.pods = np.zeros(n, dtype=np.int64)
        # selected[(namespace, label)] = pods carrying the label, per zone
        self.selected: dict[tuple, np.ndarray] = {}
        self.unlabelled = 0

    def counts(self, namespace: str, label: str) -> np.ndarray:
        return self.selected.setdefault(
            (namespace, label), np.zeros(len(self.zones), dtype=np.int64))

    def add(self, namespace: str, pod: dict, j: int) -> None:
        self.cpu[j] += pod["cpu"]
        self.mem[j] += pod["memory"]
        self.pods[j] += 1
        for k, v in pod["labels"].items():
            if self.zone[j] >= 0:
                self.counts(namespace, f"{k}={v}")[self.zone[j]] += 1
        if "spread" in pod and self.zone[j] < 0:
            self.unlabelled += 1


def _live_zones(state: _State, down: set) -> np.ndarray:
    """Which zones have a node that is up: only those are domains."""
    up = np.ones(len(state.names), dtype=bool)
    up[list(down)] = False
    live = np.zeros(len(state.zones), dtype=bool)
    live[state.zone[up & (state.zone >= 0)]] = True
    return live


def schedule(model: dict, placed: dict) -> dict:
    """Place every pod of the model that `placed` ({namespace: {pod:
    node}}) has not placed yet, one at a time in creation order (sched-0
    before sched-1), as kube-scheduler does: filter — cpu, memory and pod
    count fit and, for a pod with a spread constraint, the node carries the
    topology key and `count(zone(node)) + 1 - min over zones <= maxSkew`,
    counted over the pods of its namespace that its selector matches and
    that are placed so far — then the feasible node with the least
    allocated cpu + memory share (LeastAllocated), ties by name. Returns
    {namespace: {pod: node or None}} of the pods it handled."""
    state = _State(model)
    for namespace, pods in model["namespaces"].items():
        held = placed.get(namespace, {})
        for pod in pods:
            if pod["name"] in held:
                state.add(namespace, pod, state.index[held[pod["name"]]])
    live = _live_zones(state, set())
    out: dict[str, dict] = {}
    for namespace, pods in model["namespaces"].items():
        held = placed.get(namespace, {})
        for pod in pods:
            if pod["name"] in held:
                continue
            feasible = ((state.cpu + pod["cpu"] <= state.cap_cpu)
                        & (state.mem + pod["memory"] <= state.cap_mem)
                        & (state.pods < state.cap_pods))
            term = pod.get("spread")
            if term is not None:
                counts = state.counts(namespace, term["label"])
                floor = counts[live].min() if live.any() else 0
                open_zone = counts + 1 - floor <= term["max_skew"]
                feasible &= (state.zone >= 0) & open_zone[state.zone]
            if not feasible.any():
                out.setdefault(namespace, {})[pod["name"]] = None
                continue
            share = state.cpu / state.cap_cpu + state.mem / state.cap_mem
            j = int(np.argmin(np.where(feasible, share, np.inf)))
            state.add(namespace, pod, j)
            out.setdefault(namespace, {})[pod["name"]] = state.names[j]
    return out


def check(model: dict, placements: dict, offline=()) -> dict:
    """Count violations per kind over BOTH namespaces together; `total` is
    their sum and 0 means the answer is correct. `unplaced`: a pod of a
    namespace the placements cover has no node; `unknown` / `offline`: a
    pod on a node the model lacks or that is down; `capacity`: nodes over
    their cpu or memory; `pods`: nodes with more than their pod count;
    `unlabelled`: spreading pods on a node without the topology key;
    `skew`: per spread term, the excess of (most - fewest) selected pods a
    zone over maxSkew, over the zones that have a node that is up. `zones`
    gives the counts of the measured namespace's spreading pods by zone."""
    state = _State(model)
    down = {state.index[n] for n in offline if n in state.index}
    out = dict.fromkeys(KINDS, 0)
    terms: dict[tuple, int] = {}
    for namespace, assignment in placements.items():
        for pod in model["namespaces"][namespace]:
            if "spread" in pod:
                terms[(namespace, pod["spread"]["label"])] = \
                    pod["spread"]["max_skew"]
            node = assignment.get(pod["name"])
            if node is None:
                out["unplaced"] += 1
            elif node not in state.index:
                out["unknown"] += 1
            else:
                j = state.index[node]
                out["offline"] += j in down
                state.add(namespace, pod, j)
    over = ((state.cpu > state.cap_cpu * (1 + CAPACITY_RTOL) + 1e-9)
            | (state.mem > state.cap_mem * (1 + CAPACITY_RTOL) + 1e-9))
    out["capacity"] = int(over.sum())
    out["pods"] = int((state.pods > state.cap_pods).sum())
    out["unlabelled"] = state.unlabelled
    live = _live_zones(state, down)
    for (namespace, label), max_skew in terms.items():
        counts = state.counts(namespace, label)[live]
        if counts.size:
            out["skew"] += max(int(counts.max() - counts.min()) - max_skew,
                               0)
    out["total"] = sum(out[k] for k in KINDS)
    out["zones"] = {z: int(c) for z, c in
                    zip(state.zones, state.counts(MEASURED, LABEL))}
    return out
