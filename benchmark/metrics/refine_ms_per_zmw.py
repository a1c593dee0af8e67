"""Milliseconds a ZMW inside `polish.refine` spans: the device-resident refine
loop with its host fetches."""


def read(inp):
    seconds = inp.span_seconds("polish.refine")
    if not seconds or not inp.zmws:
        return None
    return seconds * 1e3 / inp.zmws
