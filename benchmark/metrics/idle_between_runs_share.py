"""Share of the traced window in which the device ran nothing and no `run`
span was open: between the batch driver's invocations
(`harness/idle_by_span.py`, class `between_runs`).  A capture with no `run`
span at all (the served path) has no such class and reads nothing."""

from harness import idle_by_span


def read(inp):
    return idle_by_span.share(inp, "between_runs")
