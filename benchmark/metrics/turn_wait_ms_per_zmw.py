"""Milliseconds a ZMW that prepared batches spent in `dispatch.turn_wait`
spans, waiting for the device turn: long waits say the device is the
bottleneck, none (with an idle device) says the host's drafts are."""


def read(inp):
    if not inp.zmws or not any(e["name"] == "dispatch.turn_wait" for e in inp.spans):
        return None
    return inp.span_seconds("dispatch.turn_wait") * 1e3 / inp.zmws
