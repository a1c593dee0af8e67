"""Share of the traced window in which the device ran nothing while a
`polish.setup` span was open: the owner thread marshalling a batch
(`harness/idle_by_span.py`, class `setup`)."""

from harness import idle_by_span


def read(inp):
    return idle_by_span.share(inp, "setup")
