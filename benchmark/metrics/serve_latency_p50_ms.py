"""Median of the window's `serve.request` spans, ms: one span a request,
from the arrival of its frame at the session to its reply's write on the
socket (`pbccs_tpu/serve/server.py`).  The driver holds it to the client's
own clock within 5 ms + 2 %, so a wait at the socket cannot hide."""

SPAN = "serve.request"


def of(ordered: list, q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    at = q * (len(ordered) - 1)
    lo = int(at)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (at - lo)


def quantile(inp, q: float):
    ms = sorted(e["dur"] / 1e3 for e in inp.spans if e["name"] == SPAN)
    return of(ms, q) if ms else None


def read(inp):
    return quantile(inp, 0.50)
