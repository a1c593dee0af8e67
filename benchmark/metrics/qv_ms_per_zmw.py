"""Milliseconds a ZMW inside `polish.qv` spans: the final all-mutation sweep for the QVs."""


def read(inp):
    seconds = inp.span_seconds("polish.qv")
    if not seconds or not inp.zmws:
        return None
    return seconds * 1e3 / inp.zmws
