"""Share of the traced window in which no operation ran on the device:
1 - union of the device operations' intervals over the capture."""


def read(inp):
    window = inp.trace.window_s
    if not window:
        return None
    return 100.0 * (1.0 - inp.trace.busy_s() / window)
