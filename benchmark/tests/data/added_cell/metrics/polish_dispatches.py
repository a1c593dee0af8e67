"""Polish dispatches in the window: ccs_batch_polishes_total as it moved."""


def read(inp):
    if not inp.counters.has("ccs_batch_polishes_total"):
        return None
    return inp.counters.moved("ccs_batch_polishes_total")
