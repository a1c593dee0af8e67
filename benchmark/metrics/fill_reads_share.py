"""Share of the reads a refill of every lane would have taken that the
refine loop's rebuilds did refill: ccs_refine_fill_reads_total filled over
capacity, both as they moved.  A rebuild refills the real reads of the ZMWs
that applied a mutation that round; capacity is Z * R a rebuild.  A program
without the counter (it refilled every lane at every rebuild) reads nothing."""

FILL_READS = "ccs_refine_fill_reads_total"


def read(inp):
    capacity = inp.counters.moved(FILL_READS, kind="capacity")
    if not capacity:
        return None
    return 100.0 * inp.counters.moved(FILL_READS, kind="filled") / capacity
