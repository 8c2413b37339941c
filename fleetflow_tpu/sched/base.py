"""Scheduler interface and the Placement result."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Protocol

import numpy as np

from ..lower.tensors import ProblemTensors
from ..obs.metrics import REGISTRY

__all__ = ["Placement", "Scheduler", "assignment_names", "level_schedule",
           "record_placement"]

# one catalog entry per scheduler backend: host-greedy, native-ffd,
# partitioned, tpu-anneal, relaxation sources — whatever `source` says
_M_PLACEMENTS = REGISTRY.counter(
    "fleet_placements_total", "Placements produced, by solver source",
    labels=("source",))
_M_PLACE_S = REGISTRY.histogram(
    "fleet_placement_duration_seconds", "Placement solve wall time by source",
    labels=("source",))
_M_PLACE_VIOL = REGISTRY.gauge(
    "fleet_placement_violations",
    "Hard violations of the most recent placement, by source",
    labels=("source",))


def record_placement(placement: "Placement") -> None:
    """Fold one solved Placement into the fleet metrics (every scheduler
    backend calls this exactly once per solve)."""
    _M_PLACEMENTS.inc(source=placement.source)
    _M_PLACE_S.observe(placement.solve_ms / 1e3, source=placement.source)
    _M_PLACE_VIOL.set(placement.violations, source=placement.source)


def level_schedule(pt: ProblemTensors) -> list[list[str]]:
    """Dependency level buckets in start order: all services at depth d can
    start concurrently once depth d-1 is ready (exact Kahn levels from
    lower.tensors.dependency_depths — the vectorizable replacement for the
    reference's sequential ordering, engine.rs:67-85). One stable sort of
    the depths, sliced at each depth's cumulative count: rows in ascending
    index within a level, an empty list for a depth no row has."""
    depth = np.asarray(pt.dep_depth, dtype=np.intp)
    if not depth.size:
        return []
    order = np.argsort(depth, kind="stable")
    names = np.array(pt.service_names, dtype=object)[order].tolist()
    ends = np.cumsum(np.bincount(depth)).tolist()
    return [names[a:b] for a, b in zip([0] + ends, ends)]


def assignment_names(pt: ProblemTensors, raw: np.ndarray) -> dict[str, str]:
    """The solver's array as names: service row -> node name, in row order
    (a repeated row name keeps its last row's node). `raw` may be longer
    than `pt.S` (a bucketed, padded result) and of any dtype that holds
    whole numbers, an empty float array included. A plain dict of str:
    replies and the journal carry it, and callers copy it with `dict(...)`."""
    nodes = np.array(pt.node_names, dtype=object)
    rows = np.asarray(raw)[:pt.S].astype(np.intp, copy=False)
    return dict(zip(pt.service_names, nodes[rows].tolist(), strict=True))


@dataclass
class Placement:
    """A solved placement: where each service row runs and in what order."""
    assignment: dict[str, str]       # service row name -> node name
    # start-order level buckets. Read-only: TpuSolverScheduler hands the
    # placements of one stage the SAME lists (the schedule kept with the
    # stage's slot), so an edit in place would reach every later placement
    # of that stage; build new lists, as `node_levels` and `services_on` do
    levels: list[list[str]]
    feasible: bool
    violations: int = 0
    soft: float = 0.0
    source: str = "host-greedy"
    solve_ms: float = 0.0
    raw: np.ndarray | None = field(default=None, repr=False)  # (S,) node idx

    def services_on(self, node: str) -> list[str]:
        """Rows assigned to `node`, in level-schedule order."""
        order = {name: i for i, lvl in enumerate(self.levels) for name in lvl}
        mine = [s for s, n in self.assignment.items() if n == node]
        return sorted(mine, key=lambda s: (order.get(s, 0), s))

    def node_levels(self, node: str) -> list[list[str]]:
        """The level schedule restricted to one node (what that node's
        executor runs, wave by wave)."""
        mine = {s for s, n in self.assignment.items() if n == node}
        return [[s for s in lvl if s in mine] for lvl in self.levels
                if any(s in mine for s in lvl)]


def assemble_placement(pt: ProblemTensors, assignment: np.ndarray,
                       violations: int, source: str,
                       solve_ms: float) -> Placement:
    """Shared Placement assembly for greedy backends (host + native)."""
    placement = Placement(
        assignment=assignment_names(pt, assignment),
        levels=level_schedule(pt),
        feasible=violations == 0,
        violations=violations,
        source=source,
        solve_ms=solve_ms,
        raw=assignment,
    )
    record_placement(placement)
    return placement


class Scheduler(Protocol):
    """Placement backend: ProblemTensors in, Placement out."""

    def place(self, pt: ProblemTensors) -> Placement: ...
