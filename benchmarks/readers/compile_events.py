"""Reader: backend compile-or-cache-load events (`jax.monitoring`).

params: `which` is "in_window" (events counted inside the measured window,
expected 0) or "setup_seconds" (their summed duration before it).
"""


def read(params: dict, run) -> float | None:
    if params["which"] == "in_window":
        return float(run.compile_window["events"])
    if params["which"] == "setup_seconds":
        return float(run.compile_setup["seconds"])
    raise ValueError(f"unknown compile_events value {params['which']!r}")
