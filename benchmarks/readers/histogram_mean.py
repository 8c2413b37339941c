"""Reader: the mean of what a histogram of the program's registry observed
in the window (its sum's delta over its count's delta).

params: `histogram`, the family's name. Nothing observed, no value.
"""


def read(params: dict, run) -> float | None:
    total, count = run.histograms.get(params["histogram"], (0.0, 0))
    return total / count if count else None
