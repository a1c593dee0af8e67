"""Milliseconds a ZMW inside `read` spans: BAM decode and chunking of each
batch, on the invocation's main thread."""


def read(inp):
    seconds = inp.span_seconds("read")
    if not seconds or not inp.zmws:
        return None
    return seconds * 1e3 / inp.zmws
