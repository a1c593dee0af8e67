"""Seconds inside `serve.warm` spans: the synthetic flushes `ccs serve
--bucket` drives through draft and polish before it is ready, each the
first of its shape set, so tracing, lowering and loading its programs is
most of it."""


def read(inp):
    return inp.span_seconds("serve.warm") or None
