"""Share of the traced window in which the device ran nothing and none of
the spans `harness/idle_by_span.py` knows was open: the measure of their
coverage.  With the six classes before it, it sums to `device_idle_share`."""

from harness import idle_by_span


def read(inp):
    return idle_by_span.share(inp, "unexplained")
