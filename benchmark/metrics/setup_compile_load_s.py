"""Seconds of set-up inside jax's backend-compile step: XLA compiles on a
cache miss, reading and loading the executable on a hit.
ccs_program_load_seconds_total, phase compile, as it stood when the window
began; in jax 0.9 that event wraps the cache read, so phase cache_read (the
hits' part of it) is not added again."""

LOAD_SECONDS = "ccs_program_load_seconds_total"


def read(inp):
    found = [v for (name, labels), v in inp.counters.before.items()
             if name == LOAD_SECONDS and dict(labels).get("phase") == "compile"]
    return sum(found) if found else None
