#!/usr/bin/env python
"""What a `--trace-out` capture of the batch CLI leaves unexplained.

    python tools/trace_cover.py SPANS.json [SPANS.json ...] [--xplane F.xplane.pb]

For every batch (the spans that carry one `batch=` index: its `prepare`
slices on the prepare pool's threads, its `dispatch.turn_wait`, its `polish`
on the device's thread): the share of the time from its first `prepare`
opening to its `polish` closing that the union of those spans covers; for
every `polish` span: the share `polish.setup`, `polish.gates`,
`polish.refine`, `polish.wide` (where a batch had mating failures),
`polish.qv`, `polish.finish` and (in a shape set's first polish)
`polish.warm` cover (the children are sequential on one thread, so a share
is a sum); for every thread that owns a device (one that ran a `polish`): the
share of the time from its first `polish` opening to its last one closing
that `polish` and `device.starved` cover on that thread (a served flush's
`serve.complete` runs on the completion thread at every device count, and
is no part of an owner's time).  The least covered and the median of each
are printed; what runs in the rest has no span (in a batch: joining the
slices, the pinned shapes, the budget gate and the prebake between the last
`prepare` and the submit; on the owner thread: the pool's own bookkeeping,
or the served executor's hand-off, between a task and the next wait).

With `--xplane` (a jax.profiler capture taken while the same run was traced:
`--profile-dir`, or the benchmark's `--trace 1`): the skew between each
span on the tracer's clock and its `ccs:` annotation on the profiler's, as
start-of-annotation minus start-of-span, median and worst.  A span is paired
with the annotation of its name and its duration (within 5 %, or a
millisecond) that starts nearest it, within a second; only spans that lie
between the first and the last annotation are paired (the profiler drops
one that opened before it recorded or closed after it stopped).
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys

BATCH_PARTS = ("prepare", "dispatch.turn_wait", "polish")
POLISH_PARTS = ("polish.setup", "polish.gates", "polish.refine", "polish.wide",
                "polish.qv", "polish.finish", "polish.warm")
OWNER_PARTS = ("polish", "device.starved")


def coverage(events: list[dict], parent: str, parts: tuple) -> list[float]:
    """For each `parent` span, the summed duration of its children named in
    `parts` over its own duration (children hang by `args.parent`)."""
    covered = {e["id"]: 0.0 for e in events if e["name"] == parent}
    for e in events:
        up = e["args"].get("parent")
        if up in covered and e["name"] in parts:
            covered[up] += e["dur"]
    return [covered[e["id"]] / e["dur"] for e in events
            if e["name"] == parent and e["dur"] > 0]


def batch_coverage(events: list[dict]) -> list[float]:
    """For each batch that reached `polish`: the union of its `BATCH_PARTS`
    spans (tied by `args.batch`) over the time from the first one's start
    to the last one's end."""
    by_batch: dict[int, list[tuple[float, float]]] = {}
    polished = set()
    for e in events:
        idx = e["args"].get("batch")
        if idx is None or e["name"] not in BATCH_PARTS:
            continue
        by_batch.setdefault(idx, []).append((e["ts"], e["ts"] + e["dur"]))
        if e["name"] == "polish":
            polished.add(idx)
    shares = []
    for idx in sorted(polished):
        begin = min(a for a, _b in by_batch[idx])
        reach = max(b for _a, b in by_batch[idx])
        if reach > begin:
            shares.append(_union(by_batch[idx]) / (reach - begin))
    return shares


def _union(spans: list[tuple[float, float]]) -> float:
    covered, reach = 0.0, float("-inf")
    for a, b in sorted(spans):
        if b > reach:
            covered, reach = covered + b - max(a, reach), b
    return covered


def owner_coverage(events: list[dict]) -> list[float]:
    """For each thread that ran a `polish` (it owns a device): the union of
    its `OWNER_PARTS` spans over the time from its first `polish` opening to
    its last one closing."""
    by_thread: dict[int, list[dict]] = {}
    for e in events:
        if e["name"] in OWNER_PARTS:
            by_thread.setdefault(e["tid"], []).append(e)
    shares = []
    for spans in by_thread.values():
        polishes = [e for e in spans if e["name"] == "polish"]
        if not polishes:
            continue
        begin = min(e["ts"] for e in polishes)
        end = max(e["ts"] + e["dur"] for e in polishes)
        if end > begin:
            shares.append(_union(
                [(max(e["ts"], begin), min(e["ts"] + e["dur"], end))
                 for e in spans if e["ts"] < end and e["ts"] + e["dur"] > begin]
            ) / (end - begin))
    return shares


def annotation_skews(events: list[dict], origin_unix: float, notes: list
                     ) -> list[float]:
    """Seconds from a span's start to its annotation's.  `notes`: (name,
    unix start, duration) of the capture's `ccs:` events.  Only a span
    that lies between the first annotation's start and the last one's end
    is paired: the profiler records an annotation when it closes, and only
    if it was already recording when it opened."""
    if not notes:
        return []
    by_name: dict[str, list] = {}
    for name, start, dur in notes:
        by_name.setdefault(name, []).append((start, dur))
    recorded_from = min(start for _n, start, _d in notes)
    recorded_to = max(start + dur for _n, start, dur in notes)
    skews = []
    for e in events:
        start = origin_unix + e["ts"] / 1e6
        dur = e["dur"] / 1e6
        if start < recorded_from or start + dur > recorded_to:
            continue
        # the annotation opens and closes inside its span, microseconds in:
        # it has the span's duration, and of those the nearest start
        near = [s - start for s, d in by_name.get(e["name"], [])
                if abs(s - start) < 1.0 and abs(d - dur) < max(1e-3, 0.05 * dur)]
        if near:
            skews.append(min(near, key=abs))
    return skews


def load_annotations(path: str) -> list:
    """The capture's `ccs:` events as (name, unix start, duration)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    t0 = None
    for plane in data.planes:
        if plane.name == "Task Environment":
            t0 = dict(plane.stats).get("profile_start_time")
    if t0 is None:
        raise SystemExit(f"{path}: no profile_start_time: the capture's start "
                         "on the wall clock is unknown")
    return [(e.name.removeprefix("ccs:"), (t0 + e.start_ns) / 1e9,
             e.duration_ns / 1e9)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("ccs:")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("spans", nargs="+")
    ap.add_argument("--xplane", default=None)
    args = ap.parse_args(argv)
    notes = load_annotations(args.xplane) if args.xplane else []
    if args.xplane:
        print(f"{args.xplane}: {len(notes)} ccs: annotations on host lines")
    skews = []
    for path in args.spans:
        with open(path) as f:
            doc = json.load(f)
        events = doc["traceEvents"]
        for what, parts, shares in (
                ("batches", BATCH_PARTS, batch_coverage(events)),
                ("polish spans", POLISH_PARTS,
                 coverage(events, "polish", POLISH_PARTS)),
                ("owner threads", OWNER_PARTS, owner_coverage(events))):
            if shares:
                print(f"{path}: {len(shares)} {what}, "
                      f"{' + '.join(parts)} cover {min(shares):.4f} of the "
                      f"least covered, {statistics.median(shares):.4f} of the median")
        skews += annotation_skews(events, doc["meta"]["origin_unix"], notes)
    if skews:
        mags = sorted(abs(s) for s in skews)
        print(f"skew, annotation start less span start, over {len(skews)} pairs: "
              f"median {statistics.median(skews) * 1e6:.1f} us, median magnitude "
              f"{statistics.median(mags) * 1e6:.1f} us, worst {mags[-1] * 1e6:.1f} us")
    elif args.xplane:
        print("skew: no span of these captures has an annotation in the capture")
    return 0


if __name__ == "__main__":
    sys.exit(main())
