"""Reader: a phase's self time, read from the program's span tree, in
milliseconds per op (or solve).

Since the ring keeps, for every finished phase, its id, its parent's and its
trace's (`fleetflow_tpu.obs.trace.tree_between`: `(name, t0, t1, thread id,
id, parent id, trace id)`), a layer's self time needs no hand-written
`minus` list: it is the phase's duration less the union of the intervals of
its direct children, on whatever thread they ran, each clipped to the phase
(a wait recorded before its parent opened, a child that outlives it).
Phases named together are summed; where one is the other's child, the
child's time is counted once, as its own.

params: `spans`, the phases read; `part`, "self" (default) or "total";
`per`, "ops" (default) or "solves". The window is `program_span`'s: first
`op` span's start to the last one's end. Nothing where the program has no
`tree_between` (a commit from before the tree), where no op ran, or where
none of the phases opened in the window — never 0 for a missing source. A
ring that overwrote spans inside the window raises (`SpansDropped`).

A new per-layer metric over a phase's self time is one file,
`layer_metrics/<metric>.json`, naming this reader and the phase, and one
entry in BENCHMARK.json's `per_layer` with `source` `program_span`.
"""

from collections import defaultdict

from benchmarks.readers.program_span import window
from benchmarks.trace_reduce import _union as union


def read(params: dict, run) -> float | None:
    try:
        from fleetflow_tpu.obs.trace import tree_between
    except ImportError:
        return None
    w = window(run)
    per = run.count(params.get("per", "ops"))
    if w is None or not per:
        return None
    records = tree_between(*w)
    wanted = set(params["spans"])
    mine = [r for r in records if r[0] in wanted]
    if not mine:
        return None
    seconds = sum(t1 - t0 for _name, t0, t1, *_ in mine)
    if params.get("part", "self") == "self":
        children = defaultdict(list)
        for _name, t0, t1, _tid, _pid, parent, _trace in records:
            children[parent].append((t0, t1))
        for _name, t0, t1, _tid, pid, _parent, _trace in mine:
            clipped = [(max(c0, t0), min(c1, t1))
                       for c0, c1 in children.get(pid, ())
                       if c1 > t0 and c0 < t1]
            seconds -= sum(e - s for s, e in union(clipped))
    return seconds * 1e3 / per
