"""Reader: the mean of the samples a span wrapper took in the window
(`Placement.solve_ms`, `Placement.soft`, the delta-staging gauge).

params: `sample`, the sample's name. No sample, no value.
"""


def read(params: dict, run) -> float | None:
    values = run.spans.samples.get(params["sample"])
    if not values:
        return None
    return sum(values) / len(values)
