"""Host milliseconds a ZMW inside `filter` spans (read gates, SNR and length
filters), summed over threads."""


def read(inp):
    seconds = inp.span_seconds("filter")
    if not seconds or not inp.zmws:
        return None
    return seconds * 1e3 / inp.zmws
