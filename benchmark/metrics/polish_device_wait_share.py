"""Share of the `polish` spans' wall time that the host spent blocked on
a device fetch (`device_wait_ms` of `polish` and every `polish.*` span
under it; the tracer books a wait on the innermost open span)."""


def read(inp):
    wall = inp.span_seconds("polish")
    if not wall:
        return None
    waited = sum(e["args"].get("device_wait_ms", 0.0) for e in inp.spans
                 if e["name"] == "polish" or e["name"].startswith("polish.")) / 1e3
    return 100.0 * waited / wall
