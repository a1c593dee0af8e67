"""Backend compiles plus persistent-cache misses inside the window.
Every shape is warmed up in set-up, so this should read 0."""


def read(inp):
    if not inp.counters.has("ccs_compile_cache_events_total"):
        return None
    _hits, misses, compiles = inp.counters.programs()
    return misses + compiles
