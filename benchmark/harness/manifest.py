"""Reads BENCHMARK.json and finds each cell's files by name."""

from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))   # benchmark/
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def _need(ok: bool, what: str) -> None:
    if not ok:
        raise ManifestError(what)


def check_name(name, what: str) -> None:
    _need(isinstance(name, str) and NAME.match(name) is not None,
          f"{what} {name!r}: a name starts with a letter, a digit or _ and has "
          "at most 64 letters, digits, _ . -")


def validate(doc: dict) -> None:
    """The part of the driver's contract that a typing slip breaks:
    names, units, sources, references between entries."""
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        _need(isinstance(doc.get(key), list) and doc[key], f"{key} is missing or empty")
        names = [e.get("name") for e in doc[key]]
        for n in names:
            check_name(n, key)
        _need(len(set(names)) == len(names), f"{key}: a name appears twice")
    configs = {c["name"] for c in doc["configs"]}
    cells = {w["name"] for w in doc["workloads"]}
    for w in doc["workloads"]:
        check_name(w["traffic"], "traffic")
        _need(w["config"] in configs, f"cell {w['name']}: no config {w['config']!r}")
        _need(w["chips"] in (1, 4), f"cell {w['name']}: chips is 1 or 4")
    e2e = {m["name"] for m in doc["end_to_end"]}
    _need("setup_s" in e2e, "end_to_end lacks setup_s")
    for m in doc["end_to_end"] + doc["per_layer"]:
        _need(isinstance(m.get("unit"), str) and UNIT.match(m["unit"]) is not None,
              f"metric {m['name']}: unit {m.get('unit')!r} has a character the "
              "driver refuses")
        _need(m.get("better") in ("lower", "higher"), f"metric {m['name']}: better")
        _need(m.get("source") in SOURCES, f"metric {m['name']}: source")
        for c in m.get("workloads", []):
            _need(c in cells, f"metric {m['name']}: no cell {c!r}")
    for m in doc["per_layer"]:
        _need(m.get("moves") in e2e, f"metric {m['name']}: moves {m.get('moves')!r}")


def load(path: str | None = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    validate(doc)
    return doc


def _json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


class Cell:
    """One entry of `workloads` with its configuration, its traffic mix and
    the metrics it reports, each found by the name in BENCHMARK.json."""

    def __init__(self, doc: dict, name: str):
        found = [w for w in doc["workloads"] if w["name"] == name]
        if not found:
            raise ManifestError(f"no cell {name!r} in BENCHMARK.json; it has "
                                + ", ".join(w["name"] for w in doc["workloads"]))
        self.name = name
        self.entry = found[0]
        self.chips = self.entry["chips"]
        self.config_name = self.entry["config"]
        entry = [c for c in doc["configs"] if c["name"] == self.config_name][0]
        with open(os.path.join(ROOT, entry["file"])) as f:
            self.config = json.load(f)
        self.traffic_name = self.entry["traffic"]
        self.traffic = _json("traffic", self.traffic_name + ".json")

        def mine(m: dict) -> bool:
            return name in m.get("workloads", [name])

        self.end_to_end = [m for m in doc["end_to_end"] if mine(m)]
        self.per_layer = [m for m in doc["per_layer"] if mine(m)]


def load_by_path(kind: str, name: str):
    """The module benchmark/<kind>/<name>.py (a metric reader, a kernel's
    count, a traffic driver), loaded by its file name."""
    check_name(name, kind)
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise ManifestError(f"{kind} {name!r} has no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
