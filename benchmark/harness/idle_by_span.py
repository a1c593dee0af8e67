"""The device's idle time, split by what the program says it was doing.

`device_idle_share` says how much of the capture the chip ran nothing.
This gives each idle instant to one class, by the program's spans that
hold it: by NAME, not by thread (the innermost span of any thread is
what `reduce._idle_gaps` labels a gap with, and a prepare worker always
has a draft open).  The idle intervals are those of the first used chip
over the capture (`Trace.idle_gaps(0, window_s)`), moved to the wall
clock by `Trace.start_unix`, the clock the spans are on.  An instant
belongs to the first class of `CLASSES` that holds it:

    setup          inside a `polish.setup` span
    refine         inside a `polish.refine` span (its straggler included)
    polish_rest    inside any other part of a `polish` span
    complete       inside a `serve.complete` span
    starved        inside a `device.starved` span
    between_runs   inside no `run` span, in a capture that holds one (the
                   batch drivers' invocations back to back)
    unexplained    none of these

so the seven sum to the idle time.  A capture of a program without the
`device.starved` site (it came with the other two) reads nothing.
"""

from __future__ import annotations

from . import arith

CLASSES = ("setup", "refine", "polish_rest", "complete", "starved",
           "between_runs", "unexplained")
HELD_BY = (("setup", "polish.setup"), ("refine", "polish.refine"),
           ("polish_rest", "polish"), ("complete", "serve.complete"),
           ("starved", "device.starved"))


def merged(intervals: list) -> list:
    """Sorted, disjoint intervals covering the same instants."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        elif b > a:
            out.append((a, b))
    return out


def overlap(xs: list, ys: list) -> list:
    """The instants two lists of sorted, disjoint intervals share."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] <= ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def has_sites(spans: list) -> bool:
    """Whether the program that wrote these spans has the owner thread's
    sites: `device.starved` came with `polish.wide` and `serve.complete`,
    and every invocation and every served flush opens one."""
    return any(e["name"] == "device.starved" for e in spans)


def spans_named(spans: list, name: str) -> list:
    """The merged (start, end) of the spans of one name, wall-clock seconds."""
    return merged([(e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6)
                   for e in spans if e["name"] == name])


def split(trace, spans: list) -> dict | None:
    """Idle seconds of the capture by class (`between_runs` None in a
    capture with no `run` span), or None where the capture cannot say:
    no device trace, no wall-clock start, or a program without the sites."""
    if trace is None or not trace.window_s or trace.start_unix is None:
        return None
    if not has_sites(spans):
        return None
    t0 = trace.start_unix
    lo, hi = t0, t0 + trace.window_s
    left = merged([(t0 + a, t0 + b) for a, b in trace.idle_gaps(0.0, trace.window_s)])
    out = dict.fromkeys(CLASSES, 0.0)

    def give(cls: str, holders: list) -> None:
        nonlocal left
        out[cls] = arith.union_seconds(overlap(left, holders))
        left = overlap(left, arith.gaps(holders, lo, hi))

    for cls, name in HELD_BY:
        give(cls, spans_named(spans, name))
    runs = spans_named(spans, "run")
    if runs:
        give("between_runs", arith.gaps(runs, lo, hi))
    else:
        out["between_runs"] = None
    out["unexplained"] = arith.union_seconds(left)
    return out


def share(inp, cls: str) -> float | None:
    """A class's idle time as a percentage of the capture: what
    `metrics/idle_<class>_share.py` reports."""
    found = split(inp.trace, inp.spans)
    if found is None or found[cls] is None:
        return None
    return 100.0 * found[cls] / inp.trace.window_s


def ms_per_zmw(inp, name: str) -> float | None:
    """Summed spans of one name over the window's ZMWs, 0.0 where a
    program that has the new sites opened none of that name."""
    if not inp.zmws or not has_sites(inp.spans):
        return None
    return inp.span_seconds(name) * 1e3 / inp.zmws
