"""Reader: the program's own spans, in milliseconds per op (or solve).

The program times its phases itself (`fleetflow_tpu.obs.phase`) and keeps
the finished ones in a bounded in-memory ring as `(name, t0, t1, thread
id)` on `time.perf_counter()` — the clock the benchmark's `op` spans are
on. This reader takes the ring between the first `op` span's start and the
last one's end, sums the spans named in `spans`, subtracts those in `minus`
(phases of another layer that run inside them) and divides by `per`
("ops" or "solves"), like `span_self` does for the benchmark's wrappers.

params: `spans`, `minus` (optional), `per` (default "ops"). Spans that
never opened in the window return nothing, and so does a program that has
no ring (a commit from before the spans went in). A ring that overwrote
spans inside the window raises: a sum over it would be short.

A new per-layer metric over a program span is one file,
`layer_metrics/<metric>.json`, naming this reader and the span, and one
entry in BENCHMARK.json's `per_layer` with `source` `program_span`.
"""


def window(run) -> tuple[float, float] | None:
    """First `op` span's start to the last one's end."""
    ops = [(t0, t1) for name, t0, t1 in run.spans.events if name == "op"]
    if not ops:
        return None
    return min(t0 for t0, _ in ops), max(t1 for _, t1 in ops)


def ring_spans(run) -> list[tuple] | None:
    """The program's `(name, t0, t1, thread id)` spans inside the window;
    nothing where the program keeps none."""
    try:
        from fleetflow_tpu.obs.trace import spans_between
    except ImportError:
        return None
    w = window(run)
    return None if w is None else spans_between(*w)


def read(params: dict, run) -> float | None:
    spans = ring_spans(run)
    per = run.count(params.get("per", "ops"))
    if spans is None or not per:
        return None
    wanted, minus = set(params["spans"]), set(params.get("minus", ()))
    if not any(s[0] in wanted for s in spans):
        return None
    seconds = sum((t1 - t0) * ((name in wanted) - (name in minus))
                  for name, t0, t1, _tid in spans)
    return seconds * 1e3 / per
