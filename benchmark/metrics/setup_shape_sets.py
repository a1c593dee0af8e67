"""Polish shape sets the process had built when the window began:
ccs_polish_shape_sets_total, one for each (Imax, Jmax, R, Z) at one band
width that a polisher was first built at.  Each is a family of programs
that set-up traced, lowered and loaded; a file's batches and their
straggler continuation should need two."""

SHAPE_SETS = "ccs_polish_shape_sets_total"


def read(inp):
    found = [v for (name, _labels), v in inp.counters.before.items()
             if name == SHAPE_SETS]
    return sum(found) if found else None
