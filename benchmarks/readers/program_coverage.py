"""Reader: the share of the ops' wall time that the program's own spans
cover, in percent.

Takes the program's ring over the window (`program_span.ring_spans`),
unions the spans of all threads, clips the union to the benchmark's `op`
spans and divides by the ops' summed wall time. What is left uncovered is
time on the served path inside no phase of the program: the socket, the
event loop, code nobody has put a span around yet.

params: none. No ring or no op, no value; a ring that overwrote spans
inside the window raises (`program_span`).
"""

from benchmarks.readers.program_span import ring_spans
from benchmarks.trace_reduce import _union as union


def read(params: dict, run) -> float | None:
    spans = ring_spans(run)
    if spans is None:
        return None
    covered = union([(t0, t1) for _name, t0, t1, _tid in spans])
    ops = union([(t0, t1) for name, t0, t1 in run.spans.events
                 if name == "op"])
    wall = sum(e - s for s, e in ops)
    if not wall:
        return None
    inside = sum(max(0.0, min(e, oe) - max(s, os_))
                 for s, e in covered for os_, oe in ops)
    return 100.0 * inside / wall
