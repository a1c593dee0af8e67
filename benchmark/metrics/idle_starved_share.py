"""Share of the traced window in which the device ran nothing while its
owner thread sat in a `device.starved` span, its queue empty
(`harness/idle_by_span.py`, class `starved`)."""

from harness import idle_by_span


def read(inp):
    return idle_by_span.share(inp, "starved")
