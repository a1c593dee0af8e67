"""Milliseconds a ZMW inside `serve.complete` spans: a flush's requests
completed, at one device on the thread that polished them."""

from harness import idle_by_span


def read(inp):
    return idle_by_span.ms_per_zmw(inp, "serve.complete")
