"""Prometheus text exposition -> {(name, labels): value}: the form in
which the program's registry gives its counters."""

from __future__ import annotations

import re

_LINE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line.strip())
        if not m:
            continue
        labels = tuple(sorted(_LABEL.findall(m.group(2) or "")))
        try:
            out[(m.group(1), labels)] = float(m.group(3))
        except ValueError:
            pass
    return out


class Counters:
    """Counter movement over the window (after - before) and the gauges as
    they stood after it."""

    def __init__(self, before: dict, after: dict):
        self.before, self.after = before, after

    def moved(self, name: str, **labels) -> float:
        """Summed movement of every series of `name` whose labels include
        the ones given."""
        want = set(labels.items())
        return sum(v - self.before.get(k, 0.0) for k, v in self.after.items()
                   if k[0] == name and want <= set(k[1]))

    def programs(self) -> tuple[float, float, float]:
        """(persistent-cache hits, misses, backend compiles) as they moved:
        every program that was loaded or compiled."""
        return (self.moved("ccs_compile_cache_events_total", kind="hit"),
                self.moved("ccs_compile_cache_events_total", kind="miss"),
                self.moved("ccs_compiles_total"))

    def programs_text(self) -> str:
        hits, misses, compiles = self.programs()
        return (f"compile cache hits {hits:.0f}, misses {misses:.0f}, "
                f"backend compiles {compiles:.0f}")

    def has(self, name: str) -> bool:
        return any(k[0] == name for k in self.after)
