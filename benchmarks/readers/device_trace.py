"""Reader: numbers of the profiler trace's reduction (trace_reduce.py).

params: `which` is "idle_share" (% of the traced window in which no
operation ran on the device) or "busy_ms_per_op" (device-busy
milliseconds per op of the traced window). Without a trace, nothing.
"""


def read(params: dict, run) -> float | None:
    t = run.trace
    if t is None:
        return None
    if params["which"] == "idle_share":
        return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
    if params["which"] == "busy_ms_per_op":
        return t["busy_s"] * 1e3 / t["ops"] if t["ops"] else None
    raise ValueError(f"unknown device_trace value {params['which']!r}")
