"""Milliseconds a ZMW waited in the batcher for its flush, drafted and not
yet dispatched: ccs_serve_stage_latency_seconds{stage="queue"}, sum over
count, as they moved in the window.  What --maxWaitMs and the fill of a
flush trade against each other."""

STAGES = "ccs_serve_stage_latency_seconds"


def read(inp):
    count = inp.counters.moved(STAGES + "_count", stage="queue")
    if not count:
        return None
    return 1e3 * inp.counters.moved(STAGES + "_sum", stage="queue") / count
