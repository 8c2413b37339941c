"""The reduction from a profiler trace (.xplane.pb) to device numbers.

    busy_s     union of the intervals in which an operation ran on the
               device, averaged over the device planes found
    window_s   length of the traced window (the `bench/trace_window` span)
    ops        `bench/op` spans inside the traced window
    device_ops the executables (`module:`) and operations (`op:`) that took
               most device time, under short names
    idle_gaps  device-idle seconds by the innermost benchmark span the host
               was in

A trace with no device plane, or with a device plane and no operation on
it, is an error: it is never read as a device that was idle all the time.
"""

from __future__ import annotations

import re

from .spans import PREFIX

WINDOW = PREFIX + "trace_window"
OP = PREFIX + "op"
TOP = 10
# a lone device operation is microseconds long; gaps shorter than this are
# pipeline bubbles inside one executable, not the host holding the chip back
SHORT_GAP_NS = 10_000


class TraceError(RuntimeError):
    pass


def short_name(name: str) -> str:
    """`%fusion.65 = s32[512000]{...} fusion(...)` -> `fusion.65`;
    `jit_subsolve(12284469197797409617)` -> `jit_subsolve`."""
    name = name.strip()
    m = re.match(r"%?([\w.\-]+)\s*=", name)
    if m:
        return m.group(1)
    return re.sub(r"[(_]\d{6,}[)_]?$", "", name)[:64]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _device_lines(planes, platform: str):
    """Per device plane: (operation events, executable events)."""
    found = []
    if platform == "cpu":
        # rehearsal only: XLA's CPU client runs the thunks on its own
        # threads of the host plane; those stand in for a device
        for plane in planes:
            if plane.name != "/host:CPU":
                continue
            ops = [e for line in plane.lines
                   if line.name.startswith("tf_XLAPjRtCpuClient")
                   for e in line.events
                   if not e.name.startswith(("ThreadpoolListener",
                                             "SlinkyThreadPool", "end: "))]
            if ops:
                found.append((ops, []))
        return found
    for plane in planes:
        if not plane.name.startswith(f"/device:{platform.upper()}:"):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = lines.get("XLA Modules", [])
        ops = lines.get("XLA Ops") or modules
        found.append((ops, modules))
    return found


def _bench_spans(planes) -> list[tuple[str, float, float]]:
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIX)]


def _top(totals: dict[str, float], prefix: str = "", k: int = TOP) -> list[list]:
    rows = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
    return [[prefix + name, ns / 1e9] for name, ns in rows]


def _attribute_gaps(gaps, spans) -> dict[str, float]:
    """Idle nanoseconds by the innermost (latest-started) benchmark span
    covering each piece of each gap."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    totals: dict[str, float] = {}
    for g0, g1 in gaps:
        if g1 - g0 < SHORT_GAP_NS:
            key = "between_device_ops_under_10us"
            totals[key] = totals.get(key, 0.0) + (g1 - g0)
            continue
        edges = [g0] + [t for t in cuts if g0 < t < g1] + [g1]
        for a, b in zip(edges, edges[1:]):
            mid = (a + b) / 2
            cover = [(s, n) for n, s, e in spans
                     if s <= mid < e and n not in (WINDOW,)]
            key = max(cover)[1] if cover else "outside_any_benchmark_span"
            totals[key] = totals.get(key, 0.0) + (b - a)
    return totals


def reduce_trace(path: str, platform: str) -> dict:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    devices = _device_lines(planes, platform)
    if not devices:
        raise TraceError(
            f"no {platform} device plane in {path}: planes "
            f"{[p.name for p in planes]}")
    spans = _bench_spans(planes)
    window = [(s, e) for n, s, e in spans if n == WINDOW]
    if len(window) != 1:
        raise TraceError(f"{len(window)} {WINDOW} spans in {path}")
    w0, w1 = window[0]

    busy_ns = []
    op_totals: dict[str, float] = {}
    module_totals: dict[str, float] = {}
    gaps_by_span: dict[str, float] = {}
    for ops, modules in devices:
        if not ops:
            raise TraceError(f"a device plane of {path} has no operation")
        busy = _union([(e.start_ns, e.start_ns + e.duration_ns)
                       for e in ops])
        busy_ns.append(sum(e - s for s, e in busy))
        for e in ops:
            k = short_name(e.name)
            op_totals[k] = op_totals.get(k, 0.0) + e.duration_ns
        for e in modules:
            k = short_name(e.name)
            module_totals[k] = module_totals.get(k, 0.0) + e.duration_ns
        # device and host clocks agree to about a millisecond, so the
        # window's edges are taken from the host span and clipped
        edges = [w0] + [t for s, e in busy for t in (s, e)] + [w1]
        gaps = [(max(a, w0), min(b, w1))
                for a, b in zip(edges[::2], edges[1::2])
                if min(b, w1) > max(a, w0)]
        for name, ns in _attribute_gaps(gaps, spans).items():
            gaps_by_span[name] = gaps_by_span.get(name, 0.0) + ns
    n = len(devices)
    return {
        "busy_s": sum(busy_ns) / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "ops": sum(1 for name, s, _ in spans
                   if name == OP and w0 <= s <= w1),
        "device_ops": (_top(module_totals, "module:", 4)
                       + _top(op_totals, "op:", TOP - 4)),
        "idle_gaps": _top({k: v / n for k, v in gaps_by_span.items()}),
    }
