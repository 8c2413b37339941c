"""Structured logging, metrics, and trace correlation (the `#[instrument]`
analog, grown into a flight recorder).

The reference instruments its whole load/deploy pipeline with tracing spans
(fleetflow-core loader.rs:24-41 `#[instrument]`, fleetflowd main.rs tracing
subscriber configured from env). This package is the Python analog, plus
the aggregation layer the reference leaves to its operators:

- `get_logger("engine")` returns a named logger under the `fleetflow.`
  namespace, configured once from the `FLEET_LOG` environment variable.
- `span(log, "deploy", stage="live")` is a context manager that logs
  entry at DEBUG, exit at the span's level with a duration, and failures
  at ERROR with the exception — one line per event, `key=value` fields.
  Every span carries a contextvar trace_id/span_id (obs.trace): ids are
  minted on entry when absent, rendered by `kv()` into every log line
  inside the span, and — when `FLEET_TRACE_FILE` is set — recorded as
  begin/end/fail JSONL events in the flight recorder.
- `phase("cp.commit.persist", records=n)` is the span's light half and
  the one timing primitive (obs.trace.Phase; `span` is built on it): no
  log line, a few microseconds, always on. Every phase lands in
  the profiler's trace as `fleet/<name>` (same clock as the device
  trace), in a bounded in-memory ring with its id, its parent's and the
  trace id (`obs.trace.spans_between`, `tree_between`), in
  the `fleet_phase_ms{phase}` histogram, and in the flight recorder
  under its trace. A callable handed to a thread pool goes through
  `obs.trace.bound`, which carries the caller's phase and trace along.
- `obs.metrics.REGISTRY` is the process-wide metrics registry
  (Counter/Gauge/Histogram, Prometheus text exposition at the daemon's
  `GET /metrics`).
- `profile_trace()` wraps a block in `jax.profiler.trace` when
  `FLEET_PROFILE_DIR` is set (opt-in, zero cost otherwise); point
  TensorBoard or `xprof` at the directory to see the solve timeline.

`FLEET_LOG` grammar (tracing-subscriber EnvFilter analog, simplified):
    FLEET_LOG=debug                    # everything under fleetflow.* at DEBUG
    FLEET_LOG=info,solver=debug        # default INFO, fleetflow.solver DEBUG
    FLEET_LOG=engine=debug,cp=warning  # per-module levels, rest untouched
Levels: trace (5, below DEBUG — registered via logging.addLevelName),
debug, info, warn[ing], error, off. Unset/empty leaves the `fleetflow`
logger un-configured (library mode: the host application owns logging
config, handlers propagate as usual).
"""

from __future__ import annotations

import contextlib
import logging
import os
from typing import Iterator, Optional

from . import metrics  # noqa: F401  (re-export: obs.metrics.REGISTRY)
from .metrics import REGISTRY
from .trace import (Phase, _span_id, _trace_id, _use_span, current_span_id,
                    current_trace_id, new_span_id, new_trace_id,
                    record_span_event, use_trace)

# `with obs.phase("cp.commit.persist", records=n): ...` — the one timing
# primitive (obs/trace.py); obs.span is a phase with ids and log lines
phase = Phase

__all__ = ["get_logger", "span", "phase", "configure", "profile_trace", "kv",
           "TRACE", "REGISTRY", "metrics", "use_trace", "new_trace_id",
           "current_trace_id", "current_span_id"]

_ROOT = "fleetflow"
_configured = False

# A real TRACE level below DEBUG, so FLEET_LOG=solver=trace is
# distinguishable from solver=debug (the stdlib has no TRACE; the
# reference's tracing crate does, and the log router's level vocabulary
# already includes it)
TRACE = 5
logging.addLevelName(TRACE, "TRACE")

_LEVELS = {
    "trace": TRACE,
    "debug": logging.DEBUG,
    "info": logging.INFO,
    "warn": logging.WARNING,
    "warning": logging.WARNING,
    "error": logging.ERROR,
    "off": logging.CRITICAL + 10,
}


def kv(**fields) -> str:
    """Render key=value fields the way the reference's tracing output does.
    Values containing whitespace are quoted; None fields are dropped.
    Inside an active trace (obs.use_trace / span), trace=/span= ids are
    appended so every line of one operation grep-correlates."""
    tid = _trace_id.get()
    if tid and "trace" not in fields:
        fields["trace"] = tid
        sid = _span_id.get()
        if sid and "span" not in fields:
            fields["span"] = sid
    parts = []
    for k, v in fields.items():
        if v is None:
            continue
        s = str(v)
        if any(c.isspace() for c in s) or s == "":
            s = repr(s)
        parts.append(f"{k}={s}")
    return " ".join(parts)


def configure(spec: Optional[str] = None, *, force: bool = False,
              stream=None) -> None:
    """Apply a FLEET_LOG spec to the `fleetflow` logger tree. Called lazily
    by get_logger(); call directly (force=True) to re-apply after mutating
    the environment (tests do this)."""
    global _configured
    if _configured and not force:
        return
    _configured = True
    if spec is None:
        spec = os.environ.get("FLEET_LOG", "")
    spec = (spec or "").strip()
    if not spec:
        return

    root = logging.getLogger(_ROOT)
    if force:
        for h in list(root.handlers):
            root.removeHandler(h)
    handler = logging.StreamHandler(stream)  # None -> stderr
    handler.setFormatter(logging.Formatter(
        "%(asctime)s %(levelname)-5s %(name)s: %(message)s",
        datefmt="%H:%M:%S"))
    root.addHandler(handler)
    root.propagate = False

    default_level = None
    per_module: dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" in part:
            mod, _, lvl = part.partition("=")
            level = _LEVELS.get(lvl.strip().lower())
            if level is not None:
                per_module[mod.strip()] = level
        else:
            default_level = _LEVELS.get(part.lower())
    root.setLevel(default_level if default_level is not None else logging.INFO)
    for mod, level in per_module.items():
        logging.getLogger(f"{_ROOT}.{mod}").setLevel(level)


def get_logger(name: str) -> logging.Logger:
    """Named logger under the fleetflow namespace: get_logger('engine') ->
    `fleetflow.engine`. First call applies FLEET_LOG."""
    configure()
    return logging.getLogger(f"{_ROOT}.{name}")


@contextlib.contextmanager
def span(log: logging.Logger, name: str, level: int = logging.INFO,
         **fields) -> Iterator[dict]:
    """Timed span: DEBUG on entry, `level` with duration_ms on exit, ERROR
    with the exception on failure. The yielded dict collects extra fields to
    report at exit (span['placed'] = 12).

    Trace correlation: joins the active trace (minting a trace_id when none
    is active), mints a span_id, and records the enclosing span as parent.
    The ids render via kv() in the span's own lines and every kv() line
    inside its body, and land in the flight recorder when FLEET_TRACE_FILE
    is set. The timing, the profiler annotation, the ring, the histogram
    and the recorder's end/fail event are the phase's (obs.trace.Phase):
    a span is a phase with ids and log lines."""
    extra: dict = {}
    parent = _span_id.get()
    sid = new_span_id()
    with use_trace() as tid, _use_span(sid):
        head = kv(**fields)
        log.debug("%s started%s", name, f" {head}" if head else "")
        record_span_event("begin", name, log.name, trace=tid, span=sid,
                          parent=parent, fields=fields or None)
        ph = Phase(name, **fields)
        ph._owner = (log.name, tid, sid, parent, extra)
        try:
            with ph:
                yield extra
        except Exception as e:
            log.error("%s failed %s", name,
                      kv(duration_ms=f"{ph.ms:.1f}", error=e, **fields,
                         **extra))
            raise
        log.log(level, "%s %s", name,
                kv(duration_ms=f"{ph.ms:.1f}", **fields, **extra))


@contextlib.contextmanager
def profile_trace(label: str = "solve") -> Iterator[None]:
    """Opt-in jax.profiler trace: active only when FLEET_PROFILE_DIR is set.
    Import of jax.profiler is deferred so non-solver callers never pay it."""
    prof_dir = os.environ.get("FLEET_PROFILE_DIR", "")
    if not prof_dir:
        yield
        return
    import jax

    os.makedirs(prof_dir, exist_ok=True)
    with jax.profiler.trace(prof_dir):
        with jax.profiler.TraceAnnotation(label):
            yield
