"""Pins of the program's shape menu opened or widened inside the window:
ccs_menu_pins_total{kind="new"} + {kind="grown"} as they moved.  Each is a
family of programs to trace, lower and load where the run stands, so with
the file's pins opened by the first batch of set-up this should read 0.
A program without the counter (before PR 46) reports nothing."""

PINS = "ccs_menu_pins_total"


def read(inp):
    if not inp.counters.has(PINS):
        return None
    return inp.counters.moved(PINS)
