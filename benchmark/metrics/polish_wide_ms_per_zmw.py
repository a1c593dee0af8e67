"""Milliseconds a ZMW inside `polish.wide` spans: the wide-band retry's refine
and QV sweep between `polish.refine` and `polish.qv`.  A window of a program
that has the span and retried nothing reads 0.0."""

from harness import idle_by_span


def read(inp):
    return idle_by_span.ms_per_zmw(inp, "polish.wide")
