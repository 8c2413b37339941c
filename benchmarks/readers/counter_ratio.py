"""Reader: a ratio of counter deltas over the window.

params: `num` and `den` are lists of counter names of the program's
registry, each a whole family or one child (`family{outcome="delta"}`);
`den` may instead be "ops" or "solves"; `scale` multiplies (100 for %).
A denominator of 0 means nothing fired: the reader returns nothing.
"""

from benchmarks.spans import counter_sum


def read(params: dict, run) -> float | None:
    num = sum(counter_sum(run.counters, n) for n in params["num"])
    den = params["den"]
    den = (run.count(den) if isinstance(den, str)
           else sum(counter_sum(run.counters, n) for n in den))
    if not den:
        return None
    return params.get("scale", 1.0) * num / den
