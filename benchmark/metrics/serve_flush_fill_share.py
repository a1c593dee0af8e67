"""Share of the flushes' ZMW slots that held a ZMW:
ccs_serve_flush_slots_total{kind="used"} over {kind="capacity"} as they
moved in the window.  Every flush polishes at Z = --maxBatch, so an
under-full flush pays the round's XLA work over dead slots."""

SLOTS = "ccs_serve_flush_slots_total"


def read(inp):
    capacity = inp.counters.moved(SLOTS, kind="capacity")
    if not capacity:
        return None
    return 100.0 * inp.counters.moved(SLOTS, kind="used") / capacity
