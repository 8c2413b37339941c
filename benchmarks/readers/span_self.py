"""Reader: benchmark spans' self time in milliseconds per op (or solve).

params: `spans`, the names of the spans summed; `minus`, spans that run
inside them and belong to another layer; `per`, "ops" or "solves". Spans
that never opened in the window return nothing.
"""


def read(params: dict, run) -> float | None:
    totals = [run.spans.total(name) for name in params["spans"]]
    per = run.count(params.get("per", "ops"))
    if not sum(calls for _, calls in totals) or not per:
        return None
    seconds = sum(s for s, _ in totals)
    for child in params.get("minus", ()):
        seconds -= run.spans.total(child)[0]
    return seconds * 1e3 / per
