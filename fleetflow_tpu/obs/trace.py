"""Trace correlation + the flight recorder.

The span half of `obs` times individual operations; this module ties them
together: a contextvar-carried `trace_id` follows one logical operation (a
deploy, a solve, a CLI invocation) across modules, threads and — via
`DeployRequest.trace_id` on the CP->agent wire — across machines, and an
opt-in JSON-lines sink (`FLEET_TRACE_FILE`) records every span begin/end/
fail event with durations, so a single `fleet deploy` can be replayed as a
timeline afterwards (`fleet events --trace-file`).

Contextvars propagate through async/await but NOT into
`loop.run_in_executor` threads: a callable handed to a pool goes through
`bound()`, which carries the caller's context (trace id, open phase) into
the pool thread. Across machines the id is carried (`DeployRequest.trace_id`,
the `trace` key of a request or event frame) and re-entered with
`use_trace`.

`Phase` is the one primitive every span goes through, `obs.span` included.
It is always on and has no switch: on exit it has written the span (a) into
the profiler's trace as `fleet/<name>` (`jax.profiler.TraceAnnotation`, the
clock the device trace shares; only where `jax` is already imported and a
profiler session is collecting), (b) into a
bounded in-memory ring on `time.perf_counter()` (`spans_between`,
`tree_between`), (c) into the `fleet_phase_ms{phase}` histogram, and (d)
into the flight recorder where `FLEET_TRACE_FILE` is set (looked up when
the trace is entered) and a trace id is active (an enclosing `obs.span` is
its `parent`). A phase has a
process-wide integer id and knows the phase that was open in its context
when it opened (its parent; 0 at a root) and the active trace id: the ring
holds the span tree, and a layer's self time is read from it. A phase logs
nothing; it costs a few microseconds, so it belongs around a step of a
request and never inside a loop over servers, rows or records (count those
instead). Time a request spends waiting, not working, is written as a
finished interval by whoever waited (`record_interval`).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import gc
import itertools
import json
import os
import sys
import threading
import time
import uuid
from collections import deque
from typing import Iterator, Optional

from .metrics import REGISTRY

__all__ = ["new_trace_id", "new_span_id", "current_trace_id",
           "current_span_id", "use_trace", "FlightRecorder",
           "flight_recorder", "record_span_event", "read_trace_file",
           "read_trace_files", "Phase", "SpanRing", "SpansDropped",
           "spans_between", "tree_between", "RING_CAPACITY",
           "PROFILER_PREFIX", "PROCESS_TOKEN", "bound", "bound_as",
           "in_trace", "record_interval", "wire_span", "watch_collector"]

_trace_id: contextvars.ContextVar[str] = contextvars.ContextVar(
    "fleet_trace_id", default="")
_span_id: contextvars.ContextVar[str] = contextvars.ContextVar(
    "fleet_span_id", default="")
# the id of the phase open in this context; 0 outside any
_phase_id: contextvars.ContextVar[int] = contextvars.ContextVar(
    "fleet_phase_id", default=0)
_phase_ids = itertools.count(1)
# names this process in the `span` key of a frame: a phase id means
# something only to the process whose counter minted it
PROCESS_TOKEN = uuid.uuid4().hex[:8]
# whether FLEET_TRACE_FILE was set when a trace was last entered: a phase
# under a trace asks this, not the environment (a lookup that misses costs
# a microsecond, and a request opens tens of phases)
_recording = False


def new_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def new_span_id() -> str:
    return uuid.uuid4().hex[:8]


def current_trace_id() -> str:
    """The active trace id, or '' outside any trace."""
    return _trace_id.get()


def current_span_id() -> str:
    return _span_id.get()


def use_trace(trace_id: Optional[str] = None,
              span: Optional[str] = None):
    """Enter a trace context: adopt `trace_id`, keep the already-active
    trace when none is given, or mint a fresh id. Restores the previous
    context on exit, so nested/sequential operations cannot leak ids into
    each other. `span` is what a frame carried (`wire_span` on the
    sending side): where it names a phase of this process, that phase is
    the parent of what opens here, as if the caller's context had come
    along. Looks up `FLEET_TRACE_FILE` once (the flight recorder's
    switch); `in_trace` is the same without the lookup."""
    global _recording
    _recording = bool(os.environ.get("FLEET_TRACE_FILE"))
    return in_trace(trace_id, span)


@contextlib.contextmanager
def in_trace(trace_id: Optional[str] = None,
             span: Optional[str] = None) -> Iterator[str]:
    """`use_trace` for what enters a trace once a frame: an event's
    handler, whose trace the frame carried. It reads no environment."""
    tid = trace_id or _trace_id.get() or new_trace_id()
    token = _trace_id.set(tid)
    parent = _local_phase(span) if span else 0
    adopted = _phase_id.set(parent) if parent else None
    try:
        yield tid
    finally:
        if adopted is not None:
            _phase_id.reset(adopted)
        _trace_id.reset(token)


def wire_span() -> str:
    """The phase open in this context as a frame carries it: `<process
    token>:<id>`."""
    return f"{PROCESS_TOKEN}:{_phase_id.get()}"


def _local_phase(span) -> int:
    """The id in a frame's `span` where this process minted it, else 0."""
    token, _, pid = str(span).partition(":")
    return int(pid) if token == PROCESS_TOKEN and pid.isdigit() else 0


def bound(fn, /, *args, **kwargs):
    """`fn(*args, **kwargs)` as a zero-argument callable for a pool
    (`loop.run_in_executor(None, bound(fn, ...))`), run in a copy of the
    caller's context: the phases it opens in the pool thread have the
    caller's open phase as parent and the caller's trace id. The time
    between this call and the pool thread picking the callable up is
    written as `cp.wait.executor`."""
    return bound_as("cp.wait.executor", fn, *args, **kwargs)


def bound_as(wait: str, fn, /, *args, **kwargs):
    """`bound`, with the pool's queue written as `wait`: a node agent's
    hops are `agent.wait.executor`, apart from the CP's."""
    ctx = contextvars.copy_context()
    asked = time.perf_counter()

    def run():
        record_interval(wait, asked)
        return fn(*args, **kwargs)

    return functools.partial(ctx.run, run)


@contextlib.contextmanager
def _use_span(span_id: str) -> Iterator[str]:
    """Internal: obs.span() sets the current span id for its body."""
    token = _span_id.set(span_id)
    try:
        yield span_id
    finally:
        _span_id.reset(token)


# --------------------------------------------------------------------------
# phases: the profiler's clock, the ring, the histogram
# --------------------------------------------------------------------------

PROFILER_PREFIX = "fleet/"
# a served op opens some tens of phases, so a 10 s window of 70 ms ops is
# about 5,000 spans; 2**17 holds minutes of a busy CP (~12 MB when full)
RING_CAPACITY = 1 << 17
PHASE_LOGGER = "fleetflow.phase"

# metric catalog: docs/guide/10-observability.md
_M_PHASE_MS = REGISTRY.histogram(
    "fleet_phase_ms",
    "Wall milliseconds of every phase and span the program opened, by "
    "name (obs.phase / obs.span)", labels=("phase",),
    buckets=(0.01, 0.05, 0.25, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0,
             250.0, 500.0, 1000.0, 2500.0, 10000.0))
_M_SPANS_DROPPED = REGISTRY.counter(
    "fleet_obs_spans_dropped_total",
    "Spans the in-memory ring overwrote before anyone read them")
_count_dropped = _M_SPANS_DROPPED.bind()


class SpansDropped(RuntimeError):
    """The ring overwrote a span that ended inside the window asked for."""


class SpanRing:
    """The last `capacity` finished spans as `(name, t0, t1, thread id, id,
    parent id, trace id)` on `time.perf_counter()`, in the order they
    ended. Appends come from any thread (`deque.append` is atomic); readers
    take a copy."""

    def __init__(self, capacity: int = RING_CAPACITY):
        self._spans: deque = deque(maxlen=capacity)
        self._evicted_until = 0.0    # latest end of an overwritten span

    def append(self, name: str, t0: float, t1: float, tid: int,
               pid: int = 0, parent: int = 0, trace: str = "") -> None:
        spans = self._spans
        if len(spans) == spans.maxlen:
            self._evicted_until = max(self._evicted_until, spans[0][2])
            _count_dropped()
        spans.append((name, t0, t1, tid, pid, parent, trace))

    def tree_between(self, t0: float, t1: float) -> list[tuple]:
        """The records of the spans that lie wholly inside [t0, t1]. Raises
        SpansDropped when a span that ended after t0 has been overwritten:
        a sum over the window would then be short without saying so."""
        if self._evicted_until > t0:
            raise SpansDropped(
                f"the span ring ({self._spans.maxlen} spans) overwrote "
                f"spans that ended after t0={t0:.6f}")
        return [s for s in list(self._spans) if s[1] >= t0 and s[2] <= t1]

    def between(self, t0: float, t1: float) -> list[tuple]:
        """`tree_between` as `(name, t0, t1, thread id)`: what a reader that
        sums by name needs."""
        return [s[:4] for s in self.tree_between(t0, t1)]


RING = SpanRing()


def spans_between(t0: float, t1: float) -> list[tuple]:
    """`RING.between`: what the program did between two readings of
    `time.perf_counter()`, for a benchmark reader or a debugger."""
    return RING.between(t0, t1)


def tree_between(t0: float, t1: float) -> list[tuple]:
    """`RING.tree_between`: the same spans with who caused each and whose
    it is — `(name, t0, t1, thread id, id, parent id, trace id)`. A span's
    self time is its duration less the union of its children's intervals,
    whatever thread they ran on."""
    return RING.tree_between(t0, t1)


_annotation = None      # jax.profiler.TraceAnnotation, once jax is loaded


def _profiler_annotation():
    """The profiler's annotation class where `jax` is already imported;
    the CLI and host-only paths import nothing for a phase's sake."""
    global _annotation
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is not None:
        _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


_observe: dict = {}     # phase name -> fleet_phase_ms{phase=name}.observe


def _bind_observe(name: str):
    return _observe.setdefault(name, _M_PHASE_MS.bind(phase=name))


class Phase:
    """Context manager around one step of a request: `with
    phase("cp.commit.persist", records=n) as ph: ...`; afterwards `ph.ms`
    is its wall time, `ph.id` its id and `ph.parent` the id of the phase
    that was open in this context when it opened (0: none). See the module
    docstring for where it is written."""

    __slots__ = ("name", "fields", "t0", "t1", "id", "parent", "_ann",
                 "_token", "_trace", "_owner")

    def __init__(self, name: str, /, **fields):
        self.name = name
        self.fields = fields
        self.t0 = self.t1 = 0.0
        self.id = self.parent = 0
        self._ann = None
        self._token = None
        self._trace = ""        # adopt(): the trace it learned in its body
        # obs.span owns its phase: (logger, trace id, span id, parent span
        # id, the dict of fields collected in the body): the recorder's
        # `span` and `parent` keys and the log lines keep the hex ids
        self._owner: Optional[tuple] = None

    @property
    def ms(self) -> float:
        return (self.t1 - self.t0) * 1e3

    def set(self, **fields) -> None:
        """Fields known only inside the body (a frame's size, a count)."""
        self.fields.update(fields)
        if self._ann is not None:
            self._ann.set_metadata(**fields)

    def adopt(self, trace, span) -> None:
        """For a phase that learns in its body whose it is — a frame's
        decode, from the frame: file it under `trace`, as a child of the
        phase a frame's `span` names (`wire_span`), where this
        process minted it. Anything else leaves it as it opened."""
        if trace and isinstance(trace, str):
            self._trace = trace
        self.parent = _local_phase(span) or self.parent

    def __enter__(self) -> "Phase":
        self.id = pid = next(_phase_ids)
        self.parent = _phase_id.get()
        self._token = _phase_id.set(pid)
        # an annotation only while a profiler session is collecting them:
        # the profiler's own switch, read in tens of nanoseconds
        ann = _annotation or _profiler_annotation()
        if ann is not None and ann.is_enabled():
            self._ann = ann(PROFILER_PREFIX + self.name, **self.fields)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t0, t1 = self.t0, time.perf_counter()
        self.t1 = t1
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        try:
            _phase_id.reset(self._token)
        except ValueError:
            # closed in another context than it opened in (a generator
            # finalised elsewhere): that context never saw this phase
            pass
        name, trace = self.name, self._trace or _trace_id.get()
        RING.append(name, t0, t1, threading.get_ident(), self.id,
                    self.parent, trace)
        (_observe.get(name) or _bind_observe(name))((t1 - t0) * 1e3)
        # the recorder files a phase under its trace; outside any there is
        # nothing to hang it on
        if trace and _recording:
            self._record(exc, trace)
        return False

    def _record(self, exc, trace: str) -> None:
        """The flight recorder's `end` (or `fail`) event. A bare phase has
        no span id of its own: its `parent` is the enclosing obs.span,
        where there is one; `id` and `parent_id` are the tree's."""
        fields = self.fields
        if self._owner is not None:
            logger, trace, span, parent, extra = self._owner
            fields = {**fields, **extra}
        else:
            logger, span, parent = PHASE_LOGGER, "", _span_id.get()
        record_span_event(
            "end" if exc is None else "fail", self.name, logger,
            trace=trace, span=span, parent=parent, duration_ms=self.ms,
            error=None if exc is None else str(exc), fields=fields or None,
            phase_ids=(self.id, self.parent))


def record_interval(name: str, t0: float, t1: Optional[float] = None) -> None:
    """A finished interval `[t0, t1]` (`t1`: now) on `time.perf_counter()`,
    written by whoever waited through it — for a queue, a pool thread, a
    collection — as a phase of the ring, with the phase open in this
    context as parent and the active trace id, and of the histogram. An
    interval that began in another thread, or is over, cannot be an
    annotation of the profiler's trace, and the flight recorder is not
    written: a collection can interrupt the recorder's own write."""
    if t1 is None:
        t1 = time.perf_counter()
    RING.append(name, t0, t1, threading.get_ident(), next(_phase_ids),
                _phase_id.get(), _trace_id.get())
    (_observe.get(name) or _bind_observe(name))((t1 - t0) * 1e3)


# --------------------------------------------------------------------------
# the collector: every collection counted, a full one a phase
# --------------------------------------------------------------------------

_M_GC_COLLECTIONS = REGISTRY.counter(
    "fleet_gc_collections_total",
    "Collections of CPython's cyclic collector since the process settled "
    "it (cp.server.settle_collector), by generation", labels=("generation",))
_M_GC_PAUSE_MS = REGISTRY.counter(
    "fleet_gc_pause_ms_total",
    "Wall milliseconds the interpreter spent inside collections, by "
    "generation: every thread waits through them", labels=("generation",))


class _CollectorWatch:
    """A `gc.callbacks` entry. The interpreter runs one collection at a
    time, in whatever thread crossed the threshold, so one start time is
    enough. Young collections are counted, not spanned (a solve of 1,000
    rows runs about a hundred); a collection of the oldest generation is
    also the phase `runtime.gc`, child of whatever phase it interrupted.
    It may interrupt a thread inside a metric family's lock, which is why
    that lock is re-entrant (obs/metrics.py)."""

    GENERATIONS = 3

    def __init__(self):
        self._t0 = 0.0
        self._ann = None
        gens = [str(g) for g in range(self.GENERATIONS)]
        self._count = [_M_GC_COLLECTIONS.bind(generation=g) for g in gens]
        self._pause = [_M_GC_PAUSE_MS.bind(generation=g) for g in gens]

    def __call__(self, when: str, info: dict) -> None:
        gen = info["generation"]
        full = gen == self.GENERATIONS - 1
        if when == "start":
            ann = (_annotation or _profiler_annotation()) if full else None
            if ann is not None and ann.is_enabled():
                self._ann = ann(PROFILER_PREFIX + "runtime.gc")
                self._ann.__enter__()
            self._t0 = time.perf_counter()
            return
        t0, t1 = self._t0, time.perf_counter()
        self._count[gen]()
        self._pause[gen]((t1 - t0) * 1e3)
        if full:
            if self._ann is not None:
                self._ann.set_metadata(collected=info["collected"])
                self._ann.__exit__(None, None, None)
                self._ann = None
            record_interval("runtime.gc", t0, t1)


_COLLECTOR_WATCH = _CollectorWatch()


def watch_collector() -> None:
    """Install the process's one collector watch; again is a no-op."""
    if _COLLECTOR_WATCH not in gc.callbacks:
        gc.callbacks.append(_COLLECTOR_WATCH)


# --------------------------------------------------------------------------
# flight recorder: JSONL span events
# --------------------------------------------------------------------------

class FlightRecorder:
    """Append-only JSON-lines sink for span events. One line per event:

        {"ts": ..., "kind": "begin"|"end"|"fail"|"telemetry",
         "name": ..., "logger": ..., "trace": ..., "span": ...,
         "parent": ..., "duration_ms": ...?, "error": ...?,
         "fields": {...}?, "id": ...?, "parent_id": ...?}

    (`id` / `parent_id`: a finished phase's place in its process's span
    tree; `span` / `parent` are obs.span's hex ids.)

    Thread-safe (one lock around write+flush); line-buffered so a crashed
    process leaves at most one torn final line, which readers skip.

    Rotation: ``FLEET_TRACE_MAX_MB`` (unset/0 = unbounded) caps the file
    size with a keep-1 rollover — when the next line would cross the
    cap, the current file atomically becomes ``<path>.1`` (replacing any
    previous generation) and a fresh file starts. Hours of admission
    micro-solve spans can no longer grow the recorder without bound, and
    rotation happens BETWEEN lines so both generations stay
    well-formed JSONL; readers span the boundary via
    :func:`read_trace_files`."""

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        self._f = None

    @staticmethod
    def _max_bytes() -> int:
        """Rotation cap, re-read per record so tests (and operators
        adjusting a live process) see the change without a restart."""
        raw = os.environ.get("FLEET_TRACE_MAX_MB", "").strip()
        try:
            mb = float(raw) if raw else 0.0
        except ValueError:
            mb = 0.0
        return int(mb * 1024 * 1024) if mb > 0 else 0

    def _open_locked(self):
        if self._f is None:
            d = os.path.dirname(self.path)
            if d:
                os.makedirs(d, exist_ok=True)
            self._f = open(self.path, "a", encoding="utf-8")
        return self._f

    def record(self, event: dict) -> None:
        line = json.dumps(event, default=str) + "\n"
        cap = self._max_bytes()
        with self._lock:
            f = self._open_locked()
            if cap and f.tell() > 0 and f.tell() + len(line) > cap:
                # keep-1 rollover: the full generation becomes .1
                # (atomic replace of the previous one), a fresh file
                # continues the stream
                f.close()
                self._f = None
                os.replace(self.path, self.path + ".1")
                f = self._open_locked()
            f.write(line)
            f.flush()

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None


_recorder: Optional[FlightRecorder] = None
_recorder_lock = threading.Lock()


def flight_recorder() -> Optional[FlightRecorder]:
    """The process-wide recorder for FLEET_TRACE_FILE, or None when the
    env var is unset. Re-resolved on every call so tests (and operators
    toggling the env between operations) get the path they asked for."""
    global _recorder
    path = os.environ.get("FLEET_TRACE_FILE", "").strip()
    if not path:
        return None
    with _recorder_lock:
        if _recorder is None or _recorder.path != path:
            if _recorder is not None:
                _recorder.close()
            _recorder = FlightRecorder(path)
        return _recorder


def record_span_event(kind: str, name: str, logger: str, *,
                      trace: str, span: str, parent: str = "",
                      duration_ms: Optional[float] = None,
                      error: Optional[str] = None,
                      fields: Optional[dict] = None,
                      phase_ids: Optional[tuple[int, int]] = None) -> None:
    """Write one span event if the flight recorder is active; no-op (and
    near-free: one env lookup) otherwise. `phase_ids` is a finished
    phase's (id, parent id) in the process's span tree."""
    rec = flight_recorder()
    if rec is None:
        return
    event: dict = {"ts": round(time.time(), 6), "kind": kind, "name": name,
                   "logger": logger, "trace": trace, "span": span}
    if parent:
        event["parent"] = parent
    if duration_ms is not None:
        event["duration_ms"] = round(duration_ms, 3)
    if error is not None:
        event["error"] = error
    if fields:
        event["fields"] = fields
    if phase_ids is not None:
        event["id"], event["parent_id"] = phase_ids
    rec.record(event)


def read_trace_files(path: str) -> list[dict]:
    """Read a flight-recorder stream ACROSS the keep-1 rollover: the
    rotated generation (`<path>.1`, if present) followed by the live
    file — a span whose begin predates the rollover and whose end
    followed it reads back whole. The viewers (`fleet events`,
    `fleet solve trace`) use this; :func:`read_trace_file` stays the
    single-file primitive."""
    out: list[dict] = []
    rotated = path + ".1"
    if os.path.exists(rotated):
        out.extend(read_trace_file(rotated))
    out.extend(read_trace_file(path))
    return out


def read_trace_file(path: str) -> list[dict]:
    """Parse a flight-recorder file; a torn final line (crash mid-append)
    is skipped, an undecodable line elsewhere raises."""
    out: list[dict] = []
    with open(path, encoding="utf-8") as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    for i, ln in enumerate(lines):
        try:
            out.append(json.loads(ln))
        except ValueError:
            if i == len(lines) - 1:
                break
            raise
    return out
