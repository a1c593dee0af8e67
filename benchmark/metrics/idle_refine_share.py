"""Share of the traced window in which the device ran nothing inside a
`polish.refine` span (its straggler continuation included): the loop's
fetches and launches (`harness/idle_by_span.py`, class `refine`)."""

from harness import idle_by_span


def read(inp):
    return idle_by_span.share(inp, "refine")
