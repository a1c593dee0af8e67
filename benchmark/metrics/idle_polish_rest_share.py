"""Share of the traced window in which the device ran nothing inside a
`polish` span but outside its `.setup` and `.refine`: gates, the wide-band
retry, the QV fetch, result assembly, what no child covers
(`harness/idle_by_span.py`, class `polish_rest`)."""

from harness import idle_by_span


def read(inp):
    return idle_by_span.share(inp, "polish_rest")
