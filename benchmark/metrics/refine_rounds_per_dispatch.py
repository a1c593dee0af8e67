"""Refine rounds a polish dispatch: ccs_refine_rounds_total over
ccs_batch_polishes_total, both as they moved in the window."""


def read(inp):
    dispatches = inp.counters.moved("ccs_batch_polishes_total")
    if not dispatches:
        return None
    return inp.counters.moved("ccs_refine_rounds_total") / dispatches
