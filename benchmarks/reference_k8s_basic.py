"""Plain reference for the configuration `k8s-sp-basic-5k`: Kubernetes
scheduler_perf, test case SchedulingBasic, workload 5000Nodes, as plain
data, a one-pod-at-a-time scheduler and a checker.

Independent of the code under test: nothing here reads `ProblemTensors`,
`cp/admission.py` or `solver/repair.verify`, and nothing imports JAX or
`fleetflow_tpu`. The tier-1 tests (tests/test_admission_served.py) and the
benchmark's op kind (ops/submit_wait.py) import this same file.

What is compared, and what is not. The source's scheduler empties a queue
of pending pods: every pod that fits is bound, a pod that fits nowhere
stays pending, and a bound pod is never moved. So must the system: the
NUMBER of pods `schedule` leaves pending is the number the system may
park, every other pod is placed within capacity, what the caller was told
is what the committed record holds, a pod that was running before is where
it was, and a pod that left is in no view. Which node a pod takes is not
compared: kube-scheduler's LeastAllocated, which `schedule` follows, and
the annealer's soft terms are different objectives.

The model (`cluster`), as the source's templates state it:
    nodes   {name: {"cpu": 4.0, "memory": 32768.0 (MiB), "pods": 110}}
            in creation order
    init    [pod, ...] in creation order; a pod is {"name", "cpu": 0.1,
            "memory": 500.0 (MiB)}: the pods that run before anything is
            measured
    wave    [pod, ...]: the measured pods, new objects every op (`wave`)
The source draws nothing at random: the seed decides names and creation
order only.
"""

from __future__ import annotations

import random

import numpy as np

NODE = {"cpu": 4.0, "memory": 32.0 * 1024.0, "pods": 110}
POD = {"cpu": 0.1, "memory": 500.0}
KINDS = ("unplaced", "unknown", "offline", "capacity", "pods", "moved",
         "untold", "ghost")

# demands reach the system's solver as float32 and are summed there; a node
# is over capacity only beyond this relative slack
CAPACITY_RTOL = 1e-4


def cluster(seed: int, nodes: int, init_pods: int, measure_pods: int,
            node: dict | None = None) -> dict:
    """createNodes `nodes` (each `node`, default the source's
    node-default.yaml), createPods `init_pods` that run before the
    measurement, and a first wave of `measure_pods` pending pods."""
    rng = random.Random(seed)
    node_ids = list(range(nodes))
    rng.shuffle(node_ids)
    init_ids = list(range(init_pods))
    rng.shuffle(init_ids)
    model = {"nodes": {f"node-{i:04d}": dict(node or NODE)
                       for i in node_ids},
             "init": [{"name": f"init-{i:04d}", **POD} for i in init_ids],
             "measure_pods": measure_pods, "wave": []}
    return wave(model, 0)


def wave(model: dict, op: int) -> dict:
    """The model with the measured pods of op `op`: the same pods under
    fresh names, as the source's measured pods are new objects."""
    return dict(model, wave=[{"name": f"pod-{op}-{i}", **POD}
                             for i in range(model["measure_pods"])])


def pods_of(model: dict) -> list[dict]:
    """Every pod the model holds, in creation order: init, then the wave."""
    return model["init"] + model["wave"]


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
        n = len(self.names)
        self.cpu = np.zeros(n)
        self.mem = np.zeros(n)
        self.pods = np.zeros(n, dtype=np.int64)

    def add(self, pod: dict, j: int) -> None:
        self.cpu[j] += pod["cpu"]
        self.mem[j] += pod["memory"]
        self.pods[j] += 1


def schedule(model: dict, placed: dict) -> dict:
    """Place every pod of the model that `placed` ({pod: node}) has not
    bound yet, one at a time in creation order (the init pods before the
    wave), as kube-scheduler does in its plainest form: filter — cpu,
    memory and pod count fit — then the feasible node with the least
    allocated cpu + memory share (LeastAllocated), ties by name. A bound
    pod is never moved. Returns {pod: node or None} of the pods it
    handled; None is a pod left pending."""
    state = _State(model)
    for pod in pods_of(model):
        if pod["name"] in placed:
            state.add(pod, state.index[placed[pod["name"]]])
    out: dict[str, str | None] = {}
    for pod in pods_of(model):
        if pod["name"] in placed:
            continue
        feasible = ((state.cpu + pod["cpu"] <= state.cap_cpu + 1e-9)
                    & (state.mem + pod["memory"] <= state.cap_mem + 1e-9)
                    & (state.pods < state.cap_pods))
        if not feasible.any():
            out[pod["name"]] = None
            continue
        share = state.cpu / state.cap_cpu + state.mem / state.cap_mem
        j = int(np.argmin(np.where(feasible, share, np.inf)))
        state.add(pod, j)
        out[pod["name"]] = state.names[j]
    return out


def check(model: dict, before: dict, after: dict, told: dict,
          offline=(), pending=()) -> dict:
    """Count violations per kind; `total` is their sum and 0 means the
    answer is correct. `after` ({pod: node}) is the committed record read
    back after the op, `before` the same before it, `told` ({pod: node or
    None}) what the caller was told of the wave, `pending` the pods the
    caller was told are parked (they may lack a node). `unplaced`: a pod
    of the model, not pending, that `after` lacks; `unknown` / `offline`:
    a pod on a node the model lacks or that is down; `capacity`: nodes
    over their cpu or memory; `pods`: nodes with more than their pod
    count; `moved`: a pod of `before` that `after` holds on another node;
    `untold`: a pod of the wave whose verdict differs from `after` (told a
    node the record does not hold it on, or told nothing); `ghost`: a pod
    in `after` that the model no longer has (a departed pod still in
    view)."""
    state = _State(model)
    down = {n for n in offline if n in state.index}
    pending = set(pending)
    out = dict.fromkeys(KINDS, 0)
    known = set()
    for pod in pods_of(model):
        known.add(pod["name"])
        node = after.get(pod["name"])
        if node is None:
            out["unplaced"] += pod["name"] not in pending
        elif node not in state.index:
            out["unknown"] += 1
        else:
            out["offline"] += node in down
            state.add(pod, state.index[node])
    over = ((state.cpu > state.cap_cpu * (1 + CAPACITY_RTOL) + 1e-9)
            | (state.mem > state.cap_mem * (1 + CAPACITY_RTOL) + 1e-9))
    out["capacity"] = int(over.sum())
    out["pods"] = int((state.pods > state.cap_pods).sum())
    out["moved"] = sum(1 for name, node in before.items()
                       if name in after and after[name] != node)
    for pod in model["wave"]:
        name = pod["name"]
        if name in pending:
            out["untold"] += told.get(name) is not None or name in after
        else:
            out["untold"] += (name not in told
                              or told[name] != after.get(name))
    out["ghost"] = sum(1 for name in after if name not in known)
    out["total"] = sum(out[k] for k in KINDS)
    return out
