"""What a run observes: benchmark spans, counters and compile events.

Spans are recorded from the benchmark's own files, around the calls into a
layer of the program (`Spans.wrap` swaps an attribute for a timed wrapper
at run time; nothing inside the program is edited). Each span is kept in
memory on the host clock and also written into the profiler's trace as
`bench/<name>` (`jax.profiler.TraceAnnotation`), so that idle gaps of the
device can be named after what the host was doing.

`Watch` is `chip_smoke.Watch`'s pattern: compile-or-cache-load events from
`jax.monitoring`, everything else as deltas of the program's own metrics
registry (`fleetflow_tpu.obs.metrics.REGISTRY`).
"""

from __future__ import annotations

import asyncio
import contextlib
import functools
import time
from collections import defaultdict

PREFIX = "bench/"


class Spans:
    def __init__(self):
        self.events: list[tuple[str, float, float]] = []   # name, t0, t1
        self.samples: dict[str, list[float]] = defaultdict(list)

    def reset(self) -> None:
        self.events = []
        self.samples = defaultdict(list)

    @contextlib.contextmanager
    def span(self, name: str):
        import jax.profiler
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(PREFIX + name):
            try:
                yield
            finally:
                self.events.append((name, t0, time.perf_counter()))

    def wrap(self, owner, attr: str, name: str, after=None) -> None:
        """Replace `owner.attr` (a function, method or coroutine function;
        `owner[attr]` where the owner is a dict) with a wrapper that records
        span `name` around each call. `after(result)` runs once the call
        has returned, to take samples from what it returned."""
        inner = owner[attr] if isinstance(owner, dict) else getattr(owner, attr)
        if asyncio.iscoroutinefunction(inner):
            @functools.wraps(inner)
            async def wrapper(*a, **kw):
                with self.span(name):
                    result = await inner(*a, **kw)
                if after is not None:
                    after(result)
                return result
        else:
            @functools.wraps(inner)
            def wrapper(*a, **kw):
                with self.span(name):
                    result = inner(*a, **kw)
                if after is not None:
                    after(result)
                return result
        if isinstance(owner, dict):
            owner[attr] = wrapper
        else:
            setattr(owner, attr, wrapper)

    def total(self, name: str) -> tuple[float, int]:
        """(seconds, count) of the spans called `name`."""
        durs = [t1 - t0 for n, t0, t1 in self.events if n == name]
        return sum(durs), len(durs)


class Watch:
    """Process-wide compile events and registry counters."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == self.COMPILE:
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1

    def compile_stats(self) -> dict:
        return {"events": self.compiles, "seconds": self.compile_s,
                "cache_hits": self.hits, "cache_misses": self.misses}

    @staticmethod
    def counters() -> dict[str, float]:
        """Every counter sample of the program's registry, keyed
        `name{label="value",...}` as the registry renders it."""
        from fleetflow_tpu.obs.metrics import REGISTRY
        return REGISTRY.counter_values()

    @staticmethod
    def histograms() -> dict[str, tuple[float, int]]:
        """(sum, count) of every histogram of the program's registry,
        summed over its label sets."""
        from fleetflow_tpu.obs.metrics import REGISTRY
        return {name: (sum(v["sum"] for v in m["values"]),
                       sum(v["count"] for v in m["values"]))
                for name, m in REGISTRY.snapshot().items()
                if m["type"] == "histogram"}


def counter_sum(values: dict[str, float], name: str) -> float:
    """Sum of the samples of counter `name`; `name` may carry a label set
    (`family{outcome="delta"}`) to take one child only."""
    if "{" in name:
        return values.get(name, 0.0)
    return sum(v for k, v in values.items()
               if k == name or k.startswith(name + "{"))
