"""Host thread-milliseconds a ZMW inside `draft` spans (POA draft and read
mapping); threads overlap, so this is work, not wall."""


def read(inp):
    seconds = inp.span_seconds("draft")
    if not seconds or not inp.zmws:
        return None
    return seconds * 1e3 / inp.zmws
