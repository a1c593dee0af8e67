"""Seconds the window spent bringing programs up: every phase of
ccs_program_load_seconds_total as it moved inside the window (trace,
lower, compile; cache_read lies inside compile and is not added again).
`window_compiles` counts cache misses and backend compiles and reads 0 for
a family of programs that loads from cache hits; tracing and lowering one
still stops a 2 kb run for 85-100 s.  Should read 0."""

LOAD_SECONDS = "ccs_program_load_seconds_total"


def read(inp):
    if not inp.counters.has(LOAD_SECONDS):
        return None
    return sum(inp.counters.moved(LOAD_SECONDS, phase=phase)
               for phase in ("trace", "lower", "compile"))
