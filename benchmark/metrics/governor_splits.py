"""Batches the memory governor split before dispatch, plus batches split
after an out-of-memory error, in the window."""


def read(inp):
    names = ("ccs_resource_presplit_batches_total", "ccs_resource_oom_splits_total")
    if not any(inp.counters.has(n) for n in names):
        return None
    return sum(inp.counters.moved(n) for n in names)
