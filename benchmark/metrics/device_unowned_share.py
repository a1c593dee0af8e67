"""Share of the invocations' wall in which no batch held the device turn:
1 - union of the `polish` spans' intervals over the summed `run` spans."""

from harness import arith


def read(inp):
    run_s = inp.span_seconds("run")
    if not run_s:
        return None
    held = arith.union_seconds([(e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6)
                                for e in inp.spans if e["name"] == "polish"])
    return 100.0 * (1.0 - held / run_s)
