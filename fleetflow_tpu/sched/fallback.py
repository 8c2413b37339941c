"""Fallback-policy relaxation: the retry ladder for infeasible placements.

Reference model.rs:49 FallbackPolicy: when a stage cannot be placed under
its full policy, constraint classes are relaxed in the declared order and
the solve retried — preferences first (free), then spread, then the
eligibility classes (tier / required labels) as a last resort. The relax
order rides on ProblemTensors.relax_order (lowered from the stage's
`placement { fallback ... }` block).

`place_with_fallback` wraps any Scheduler: it returns the first feasible
placement plus the list of classes that had to be relaxed (empty on a
clean solve), annotating Placement.source so operators can see a degraded
placement at a glance.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from .base import Placement, Scheduler
from ..lower.tensors import (ELIGIBILITY_RELAX_CLASSES as _ELIG,
                             PREF_RELAX_CLASSES as _PREF,
                             SPREAD_RELAX_CLASSES as _SPREAD,
                             ProblemTensors, bar_held)
from ..obs import get_logger, kv
from ..obs.metrics import REGISTRY

__all__ = ["place_with_fallback", "relax_problem"]

log = get_logger("sched")

# metric catalog: docs/guide/10-observability.md
_M_RELAXED = REGISTRY.counter(
    "fleet_sched_relaxed_total",
    "Rungs of a stage's fallback ladder taken: a constraint class relaxed "
    "and the stage solved again", labels=("what",))


def relax_problem(pt: ProblemTensors, what: str) -> Optional[ProblemTensors]:
    """A copy of `pt` with the `what` constraint class relaxed, or None when
    that class is absent/already relaxed (nothing to retry)."""
    if what in _PREF:
        if pt.preferred is None:
            return None
        return dataclasses.replace(pt, preferred=None, priced=False)
    if what in _SPREAD:
        if pt.max_skew <= 0:
            return None
        return dataclasses.replace(pt, max_skew=0)
    if what in _ELIG:
        # what another stage holds on a server is physical, like the
        # stage's own conflicts: the relaxed plane keeps those bars
        eligible = np.ones_like(pt.eligible)
        bar_held(eligible, pt.barred_by, pt.node_names, pt.held)
        if pt.max_skew > 0 and pt.topology_keyless is not None:
            # while the spread constraint stands, so does its bar on the
            # servers that lack its key
            eligible[:, pt.topology_keyless] = False
        if np.array_equal(eligible, pt.eligible):
            return None
        return dataclasses.replace(pt, eligible=eligible)
    log.warning("unknown fallback class %s", kv(what=what))
    return None


def place_with_fallback(scheduler: Scheduler, pt: ProblemTensors, *,
                        initial: Optional[Placement] = None,
                        place_kwargs: Optional[dict] = None,
                        ) -> tuple[Placement, list[str]]:
    """Solve; on infeasibility walk pt.relax_order, relaxing one class at a
    time (cumulative) and re-solving. Returns (placement, relaxed classes).
    The final placement may still be infeasible when even the fully relaxed
    problem has no solution (capacity/conflicts are never relaxed — they
    are physical). `initial` skips the first solve when the caller already
    has an (infeasible) result for the un-relaxed problem. `place_kwargs`
    forwards scheduler-specific keywords through the ladder's re-solves
    (the TPU scheduler's `stage=` resident-slot key: without it a relaxed
    re-solve would land in an anonymous slot and the stage's resident warm
    seed would keep pointing at the pre-relaxation infeasible winner)."""
    kw = place_kwargs or {}
    placement = initial if initial is not None else scheduler.place(pt, **kw)
    relaxed: list[str] = []
    for what in pt.relax_order:
        if placement.feasible:
            break
        pt2 = relax_problem(pt, what)
        if pt2 is None:
            continue
        pt = pt2
        relaxed.append(what)
        _M_RELAXED.inc(what=what)
        log.info("placement infeasible; relaxing %s",
                 kv(what=what, order=",".join(pt.relax_order)))
        placement = scheduler.place(pt, **kw)
    if relaxed:
        placement = dataclasses.replace(
            placement, source=f"{placement.source}+relaxed:{','.join(relaxed)}")
    return placement, relaxed
