"""Share of the read lanes the window's polishers padded to that held a
read: ccs_batch_slots_used_total{axis="read"} over
ccs_batch_slots_total{axis="read"}, both as they moved.  A ragged library
(3-10 passes in an R = 12 bucket) leaves about half of them empty; 30
passes fill 30 of 32."""

SLOTS = "ccs_batch_slots_total"
USED = "ccs_batch_slots_used_total"


def read(inp):
    slots = inp.counters.moved(SLOTS, axis="read")
    if not slots:
        return None
    return 100.0 * inp.counters.moved(USED, axis="read") / slots
