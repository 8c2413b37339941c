"""The comparison that decides `correct`: a plain check of a returned
row -> node assignment against the deployment's model.

Independent of the code under test: it reads the model the generator wrote
down (benchmarks/generators.py) and the assignment the program returned,
never `ProblemTensors` and never `solver/repair.verify`. Guarantees held
(each configuration's file lists them): every row placed on a known
server; per-server cpu / memory / disk within capacity; a host port and a
host volume used at most once per server; declared anti-affinity (a
service naming itself keeps its replicas apart); eligibility; nothing on
an offline server.
"""

from __future__ import annotations

import numpy as np

KINDS = ("unplaced", "offline", "capacity", "port", "volume",
         "anti_affinity", "ineligible")

# demands reach the solver as float32 and are summed there; a server is
# over capacity only beyond this relative slack
CAPACITY_RTOL = 1e-4


class Model:
    """Rows, demands and conflict groups of one deployment, as arrays."""

    def __init__(self, services: list[dict], servers: dict[str, dict]):
        self.nodes = list(servers)
        self.node_index = {n: j for j, n in enumerate(self.nodes)}
        self.capacity = np.array(
            [[servers[n]["cpu"], servers[n]["memory"], servers[n]["disk"]]
             for n in self.nodes], dtype=np.float64)
        self.rows: list[str] = []
        demand = []
        rows_of: dict[str, list[int]] = {}
        for svc in services:
            reps = max(int(svc.get("replicas", 1)), 1)
            names = ([svc["name"]] if reps == 1
                     else [f"{svc['name']}#{r}" for r in range(reps)])
            rows_of[svc["name"]] = list(
                range(len(self.rows), len(self.rows) + reps))
            self.rows.extend(names)
            demand.extend([[svc["cpu"], svc["memory"], svc["disk"]]] * reps)
        self.demand = np.array(demand, dtype=np.float64).reshape(-1, 3)
        # exclusive-use groups: (row index, group id) pairs per kind
        self.groups: dict[str, tuple[np.ndarray, np.ndarray]] = {}
        for kind, field in (("port", "ports"), ("volume", "volumes")):
            ids: dict = {}
            pairs = [(i, ids.setdefault(key, len(ids)))
                     for svc in services for key in svc.get(field, ())
                     for i in rows_of[svc["name"]]]
            self.groups[kind] = _pairs(pairs)
        # anti-affinity: naming oneself is one group of all replicas; naming
        # another service separates the two services' rows
        self_pairs, self.anti_pairs = [], []
        for g, svc in enumerate(services):
            for other in svc.get("anti_affinity", ()):
                if other == svc["name"]:
                    self_pairs += [(i, g) for i in rows_of[other]]
                elif other in rows_of:
                    self.anti_pairs.append((rows_of[svc["name"]],
                                            rows_of[other]))
        self.groups["anti_affinity"] = _pairs(self_pairs)
        self.restricted = [
            (i, {self.node_index[n] for n in svc["eligible"]
                 if n in self.node_index})
            for svc in services if svc.get("eligible") is not None
            for i in rows_of[svc["name"]]]


def _pairs(pairs: list) -> tuple[np.ndarray, np.ndarray]:
    arr = np.array(pairs, dtype=np.int64).reshape(-1, 2)
    return arr[:, 0], arr[:, 1]


def check(model: Model, assignment: dict, offline=()) -> dict:
    """Count hard violations of `assignment` (row name -> server name) per
    kind; `total` is their sum and 0 means the answer is correct."""
    n = len(model.nodes)
    get = model.node_index.get
    idx = np.fromiter((get(assignment.get(r), -1) for r in model.rows),
                      dtype=np.int64, count=len(model.rows))
    placed = idx >= 0
    out = dict.fromkeys(KINDS, 0)
    out["unplaced"] = int((~placed).sum())

    dead = np.zeros(n + 1, dtype=bool)
    for name in offline:
        if name in model.node_index:
            dead[model.node_index[name]] = True
    out["offline"] = int(dead[idx[placed]].sum())

    load = np.zeros((n, 3))
    np.add.at(load, idx[placed], model.demand[placed])
    slack = model.capacity * CAPACITY_RTOL + 1e-9
    out["capacity"] = int((load > model.capacity + slack).any(axis=1).sum())

    for kind, (rows, gids) in model.groups.items():
        if rows.size == 0:
            continue
        on = idx[rows]
        keys = gids[on >= 0] * n + on[on >= 0]
        _, counts = np.unique(keys, return_counts=True)
        out[kind] += int((counts - 1).sum())
    for mine, theirs in model.anti_pairs:
        shared = ({int(idx[i]) for i in mine}
                  & {int(idx[i]) for i in theirs}) - {-1}
        out["anti_affinity"] += len(shared)

    out["ineligible"] = sum(1 for i, allowed in model.restricted
                            if idx[i] >= 0 and int(idx[i]) not in allowed)
    out["total"] = sum(out[k] for k in KINDS)
    return out
