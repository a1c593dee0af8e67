"""Milliseconds a ZMW of the window that the thread that owns the device sat
in `device.starved` spans, its queue empty (an invocation's head wait
included): with `refine_`, `polish_setup_` and `qv_ms_per_zmw` it accounts
for that thread's 1000 / `zmws_per_s` milliseconds a ZMW."""

from harness import idle_by_span


def read(inp):
    return idle_by_span.ms_per_zmw(inp, "device.starved")
