"""Share of the traced window in which the device ran nothing while a
`serve.complete` span was open: a flush's requests completed on the thread
that polished (`harness/idle_by_span.py`, class `complete`)."""

from harness import idle_by_span


def read(inp):
    return idle_by_span.share(inp, "complete")
