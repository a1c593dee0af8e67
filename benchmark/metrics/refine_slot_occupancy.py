"""Mean share of the refine loop's Z slots that held an unconverged ZMW,
over every round of the window: ccs_refine_slot_rounds_total live over
capacity, both as they moved."""

SLOT_ROUNDS = "ccs_refine_slot_rounds_total"


def read(inp):
    capacity = inp.counters.moved(SLOT_ROUNDS, kind="capacity")
    if not capacity:
        return None
    return 100.0 * inp.counters.moved(SLOT_ROUNDS, kind="live") / capacity
