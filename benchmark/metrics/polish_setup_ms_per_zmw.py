"""Milliseconds a ZMW inside `polish.setup` spans: premarshalling a batch for the device."""


def read(inp):
    seconds = inp.span_seconds("polish.setup")
    if not seconds or not inp.zmws:
        return None
    return seconds * 1e3 / inp.zmws
