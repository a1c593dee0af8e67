"""From a profiler trace (.xplane.pb) to numbers, on jax.profiler.ProfileData.

A device plane is one chip.  Its line of XLA operations holds one event
for each operation that ran, with a start and a duration; times are
nanoseconds from the start of the capture, and the capture's own start
and stop on the wall clock are stats of the `Task Environment` plane.

    busy        union of the operations' intervals on a chip
    idle share  1 - busy / window, the window being the capture
    kernel time summed duration of the events a kernel's pattern matches

The line's events nest (a `while` holds its body's operations), so busy
is a union and "time by operation" is self time.  An event's name is the
text of its HLO instruction, shapes included; a Pallas kernel is a
`custom-call` whose target is `tpu_custom_call`.

`python3 benchmark/run.py --describe-trace FILE` prints what a trace holds,
for reading one by hand before trusting any of this.
"""

from __future__ import annotations

import collections
import dataclasses
import re
import sys

from . import arith

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
# a CPU rehearsal has no device plane: the XLA CPU client's threads stand in
REHEARSAL_LINE = re.compile(r"^tf_XLA(Eigen|PjRtCpuClient)")


@dataclasses.dataclass
class Op:
    name: str            # short label: instruction name without its number, and its kind
    start_s: float
    dur_s: float
    detail: str          # the HLO instruction's text, as the trace names the event
    self_s: float = 0.0  # dur_s less the operations nested inside it


@dataclasses.dataclass
class Chip:
    name: str
    ops: list


@dataclasses.dataclass
class Trace:
    chips: list
    start_unix: float | None     # wall clock at trace time 0
    window_s: float | None       # length of the capture

    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips that ran anything."""
        used = [c for c in self.chips if c.ops]
        if not used:
            return 0.0
        return sum(arith.union_seconds([(o.start_s, o.start_s + o.dur_s)
                                        for o in c.ops]) for c in used) / len(used)

    def top_ops(self, n: int = 10) -> list:
        """The operations that took most time themselves (a `while` or a
        `conditional` without what runs inside it), summed over chips."""
        total = collections.Counter()
        for c in self.chips:
            for o in c.ops:
                total[o.name] += o.self_s
        return [[k, v] for k, v in total.most_common(n)]

    def matching(self, pattern: str) -> list:
        rx = re.compile(pattern)
        return [o for c in self.chips for o in c.ops if rx.search(o.detail)]

    def idle_gaps(self, lo: float, hi: float) -> list:
        """Idle intervals of the first used chip inside [lo, hi] seconds."""
        used = [c for c in self.chips if c.ops]
        if not used:
            return []
        return arith.gaps([(o.start_s, o.start_s + o.dur_s) for o in used[0].ops],
                          lo, hi)


_KIND = re.compile(r"\)?\s([a-z][a-z\-]*)\(")


def short_name(text: str) -> str:
    """`%fusion.1318 = f32[..] fusion(...)` -> `fusion`: the instruction's
    name without its number, then its kind where that says more
    (`dense_interior_scores_batch custom-call`)."""
    head, sep, rest = text.partition(" = ")
    base = re.sub(r"[.\d]+$", "", head.strip().lstrip("%"))
    if not sep:
        return base[:80]
    m = _KIND.search(rest)
    kind = m.group(1) if m else ""
    return (base if not kind or base.startswith(kind) else f"{base} {kind}")[:80]


def set_self_times(ops: list) -> None:
    """self_s of every operation of one line: its duration less that of the
    operations that run inside it (a loop's body, a conditional's branch)."""
    stack = []
    for o in sorted(ops, key=lambda o: (o.start_s, -o.dur_s)):
        o.self_s = o.dur_s
        end = o.start_s + o.dur_s
        while stack and stack[-1][0] <= o.start_s:
            stack.pop()
        if stack:           # the part of it inside the enclosing operation
            stack[-1][1].self_s -= min(end, stack[-1][0]) - o.start_s
        stack.append((end, o))


def load(path: str, rehearsal: bool = False) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips, start_unix, window_s = [], None, None
    for plane in data.planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            if "profile_start_time" in stats and "profile_stop_time" in stats:
                start_unix = stats["profile_start_time"] / 1e9
                window_s = (stats["profile_stop_time"] - stats["profile_start_time"]) / 1e9
        elif DEVICE_PLANE.match(plane.name):
            ops = [Op(short_name(e.name), e.start_ns / 1e9, e.duration_ns / 1e9, e.name)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            set_self_times(ops)
            chips.append(Chip(plane.name, ops))
        elif rehearsal and plane.name == "/host:CPU":
            ops = [Op(e.name, e.start_ns / 1e9, e.duration_ns / 1e9, e.name,
                      e.duration_ns / 1e9)
                   for line in plane.lines if REHEARSAL_LINE.match(line.name)
                   for e in line.events]
            chips.append(Chip(plane.name, ops))
    return Trace(chips, start_unix, window_s)


def describe(path: str, out=sys.stdout) -> None:
    """Planes, lines, the heaviest events of each line and one event's
    stats: what to read by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r} stats={dict(plane.stats)}", file=out)
        for line in plane.lines:
            events = list(line.events)
            if not events or line.name == "python":
                print(f"  LINE {line.name!r}: {len(events)} events", file=out)
                continue
            total = collections.Counter()
            count = collections.Counter()
            sample = {}
            for e in events:
                total[e.name] += e.duration_ns
                count[e.name] += 1
                sample.setdefault(e.name, e)
            t0 = min(e.start_ns for e in events)
            t1 = max(e.start_ns + e.duration_ns for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, {len(total)} names, "
                  f"span {t0 / 1e9:.4f}..{t1 / 1e9:.4f} s, summed "
                  f"{sum(total.values()) / 1e9:.4f} s", file=out)
            for name, ns in total.most_common(12):
                print(f"    {ns / 1e9:10.5f} s  x{count[name]:<6d} {name[:100]}", file=out)
                stats = {k: (str(v)[:300]) for k, v in sample[name].stats}
                print(f"        stats {stats}", file=out)

