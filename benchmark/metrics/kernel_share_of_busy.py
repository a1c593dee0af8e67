"""Time inside Pallas kernels (every `tpu_custom_call` of the trace) as a
share of the device's busy time."""

from harness.common import say

PALLAS = r'custom_call_target="tpu_custom_call"'


def read(inp):
    busy = inp.trace.busy_s()
    if not busy:
        return None
    chips = max(1, sum(1 for c in inp.trace.chips if c.ops))
    calls = inp.trace.matching(PALLAS)
    by_name = {}
    for o in calls:
        by_name[o.name] = by_name.get(o.name, 0.0) + o.dur_s
    say("kernels: " + ", ".join(f"{k} {v:.4f} s" for k, v in sorted(by_name.items())))
    return 100.0 * sum(o.dur_s for o in calls) / chips / busy
