"""Share of the `draft` spans' wall in which their threads ran on a CPU
(`cpu_ms` over duration, both summed): what a faster POA could remove;
the rest is waiting for the interpreter lock or for a core."""


def read(inp):
    drafts = [e for e in inp.spans if e["name"] == "draft" and "cpu_ms" in e["args"]]
    wall_us = sum(e["dur"] for e in drafts)
    if not wall_us:
        return None
    return 100.0 * sum(e["args"]["cpu_ms"] for e in drafts) * 1e3 / wall_us
