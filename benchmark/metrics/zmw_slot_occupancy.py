"""Share of the ZMW slots the window's polishers padded to that held a
ZMW: ccs_batch_slots_used_total{axis="zmw"} over
ccs_batch_slots_total{axis="zmw"}, both as they moved.  Where every
dispatch runs at its pin's one Z, the last part of a chunk and the last
chunk of a file leave slots empty (and the wide-band retry's and the
straggler continuation's sub-batches count with theirs)."""

SLOTS = "ccs_batch_slots_total"
USED = "ccs_batch_slots_used_total"


def read(inp):
    slots = inp.counters.moved(SLOTS, axis="zmw")
    if not slots:
        return None
    return 100.0 * inp.counters.moved(USED, axis="zmw") / slots
