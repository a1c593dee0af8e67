"""The traced run: from counters, spans and the device trace to the
cell's per-layer metrics, `device.busy_s` / `window_s` and `breakdown`.

Each metric is a reader, `metrics/<name>.py`, with one function
`read(inp) -> float | None`.  A reader that finds nothing to read returns
None and its metric is left out of the line."""

from __future__ import annotations

import dataclasses
import json
import os

from . import manifest, xplane
from .common import BenchFailure, Context, Window, say


@dataclasses.dataclass
class ReaderInput:
    counters: object            # prom.Counters over the window
    spans: list                 # chrome "X" events, ts in wall-clock us
    trace: object               # xplane.Trace or None
    zmws: int                   # ZMWs the spans and counters cover
    device_kind: str
    peaks: dict                 # this device's row of peaks.json
    cell: object

    def span_seconds(self, name: str) -> float:
        return sum(e["dur"] for e in self.spans if e["name"] == name) / 1e6

    def span_device_wait_seconds(self, name: str) -> float:
        return sum(e["args"].get("device_wait_ms", 0.0) for e in self.spans
                   if e["name"] == name) / 1e3

    def kernel(self, name: str):
        return manifest.load_by_path("kernels", name)


def _peaks(kind: str, rehearse: bool) -> dict:
    with open(os.path.join(manifest.HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        if rehearse:
            return {}
        raise BenchFailure(f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]


def per_layer(ctx: Context, win: Window, facts: dict):
    trace = xplane.load(win.xplane, rehearsal=ctx.rehearse) if win.xplane else None
    if trace is None or not any(c.ops for c in trace.chips):
        raise BenchFailure("the traced run saw no operation on the device")
    inp = ReaderInput(counters=win.counters, spans=win.spans, trace=trace,
                      zmws=win.traced_zmws, device_kind=facts["kind"],
                      peaks=_peaks(facts["kind"], ctx.rehearse), cell=ctx.cell)
    metrics = {}
    for m in ctx.cell.per_layer:
        value = manifest.load_by_path("metrics", m["name"]).read(inp)
        if value is None:
            say(f"metric {m['name']}: nothing to read, left out")
        else:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    window_s = trace.window_s or (win.trace_wall[1] - win.trace_wall[0])
    device = {"busy_s": trace.busy_s(), "window_s": window_s}
    say(f"trace: {len(trace.chips)} chip plane(s), "
        f"{sum(len(c.ops) for c in trace.chips)} device operations, busy "
        f"{device['busy_s']:.4f} s of {window_s:.4f} s")
    return metrics, device, {"device_ops": trace.top_ops(10),
                             "idle_gaps": _idle_gaps(trace, win.spans, window_s)}


def _idle_gaps(trace, spans: list, window_s: float) -> list:
    """The ten longest idle gaps of the device, each named by the innermost
    program span open on the host over the gap's middle (both clocks are
    the wall clock: the profiler's start time and the tracer's origin)."""
    gaps = sorted(trace.idle_gaps(0.0, window_s), key=lambda g: g[0] - g[1])[:10]
    out = []
    for a, b in gaps:
        label = "no program span open"
        if trace.start_unix is not None:
            mid_us = (trace.start_unix + (a + b) / 2) * 1e6
            open_ = [e for e in spans if e["ts"] <= mid_us <= e["ts"] + e["dur"]]
            if open_:
                label = min(open_, key=lambda e: e["dur"])["name"]
        out.append([label, b - a])
    return out
