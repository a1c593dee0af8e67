"""Milliseconds a ZMW inside `emit` spans: encoding and writing the BAM and the report."""


def read(inp):
    seconds = inp.span_seconds("emit")
    if not seconds or not inp.zmws:
        return None
    return seconds * 1e3 / inp.zmws
