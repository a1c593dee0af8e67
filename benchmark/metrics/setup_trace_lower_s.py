"""Seconds of set-up spent tracing programs and lowering them to MLIR:
ccs_program_load_seconds_total, phases trace and lower, as they stood when
the window began.  The cache holds executables, not these."""

LOAD_SECONDS = "ccs_program_load_seconds_total"


def read(inp):
    found = [v for (name, labels), v in inp.counters.before.items()
             if name == LOAD_SECONDS and dict(labels).get("phase") in ("trace", "lower")]
    return sum(found) if found else None
