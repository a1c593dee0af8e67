"""Milliseconds a ZMW inside `polish.refine.straggler` spans: the small-Z
continuation that finishes the few ZMWs the lockstep loop left behind.
A window whose refines left none behind reads 0.0.  A program older than
the span (it came with `run`) cannot say, and reads nothing."""


def read(inp):
    names = {e["name"] for e in inp.spans}
    if not inp.zmws or "polish.refine" not in names or "run" not in names:
        return None
    return inp.span_seconds("polish.refine.straggler") * 1e3 / inp.zmws
