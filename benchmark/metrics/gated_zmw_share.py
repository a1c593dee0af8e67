"""Share of the window's ZMWs that the reader's gates turned away before
any draft: ccs_reader_gated_zmws_total (gate = snr, read_score, passes)
as it moved, over the ZMWs attempted.  They cost a read and a line of the
report and count in zmws_per_s like every other outcome.  A program
without the counter (before PR 46) reports nothing."""

GATED = "ccs_reader_gated_zmws_total"


def read(inp):
    if not inp.counters.has(GATED) or not inp.zmws:
        return None
    return 100.0 * inp.counters.moved(GATED) / inp.zmws
