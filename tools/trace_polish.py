#!/usr/bin/env python
"""Capture a jax.profiler trace of one full polish and attribute device time
per HLO op via xprof's hlo_stats converter (no TensorBoard UI needed).

Usage:
  python tools/trace_polish.py [outdir]          # capture + parse
  PBCCS_TRACE_PARSE_ONLY=1 python tools/trace_polish.py [outdir]  # parse only

Env: BENCH_ZMWS/BENCH_TPL_LEN/BENCH_PASSES/BENCH_CORRUPTIONS as bench.py.
Prints a category rollup and the top ops by device self-time, plus one JSON
summary line.
"""

from __future__ import annotations

import collections
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def capture(outdir: str):
    import numpy as np

    from pbccs_tpu.runtime.cache import enable_compilation_cache

    enable_compilation_cache()

    import jax

    from bench import build_tasks
    from pbccs_tpu.models.arrow.refine import RefineOptions
    from pbccs_tpu.parallel.batch import BatchPolisher

    Z = int(os.environ.get("BENCH_ZMWS", 128))
    L = int(os.environ.get("BENCH_TPL_LEN", 300))
    P = int(os.environ.get("BENCH_PASSES", 8))
    NC = int(os.environ.get("BENCH_CORRUPTIONS", 2))

    def run():
        tasks = build_tasks(np.random.default_rng(20260729), Z, L, P, NC)[0]
        p = BatchPolisher(tasks)
        p.refine(RefineOptions(max_iterations=10))
        p.consensus_qvs()

    run()  # warmup: compile everything
    with jax.profiler.trace(outdir):
        run()


def parse(outdir: str):
    from xprof.convert import raw_to_tool_data as r

    paths = glob.glob(os.path.join(outdir, "**", "*.xplane.pb"),
                      recursive=True)
    assert paths, f"no xplane.pb under {outdir}"
    paths = [max(paths, key=os.path.getmtime)]
    data, _ = r.xspace_to_tool_data(paths, "hlo_stats", {})
    table = json.loads(data if isinstance(data, str) else data.decode())
    cols = [c["id"] for c in table["cols"]]
    idx = {c: i for i, c in enumerate(cols)}
    rows = []
    for row in table["rows"]:
        v = [c.get("v") for c in row["c"]]
        rows.append({
            "category": v[idx["category"]],
            "name": v[idx["hlo_op_name"]],
            "expr": v[idx["hlo_op_expression"]] or "",
            "frame_op": v[idx["tf_op_name"]] or "",
            "occurrences": v[idx["occurrences"]] or 0,
            "self_us": v[idx["total_self_time"]] or 0.0,
        })
    return paths[0], rows


# PROFILE_r0N region buckets: keyword -> region, FIRST match wins (order
# matters: "dynamic-slice" must hit before "slice").  Shared by this
# tool's rollup and bench.py's per-row device_regions_ms attribution.
_REGION_KEYS = [
    ("kernels", ("custom-call", "custom call", "mosaic", "pallas")),
    ("dynamic_slice", ("dynamic-slice", "dynamic slice",
                       "dynamic-update-slice", "gather", "scatter")),
    ("data_formatting", ("copy", "transpose", "concatenate", "convert",
                         "reshape", "bitcast")),
    ("slice_pad", ("slice", "pad")),
    ("fusion", ("fusion", "loop", "while", "conditional")),
]


def region_rollup(rows) -> dict:
    """Collapse hlo_stats rows into the PROFILE region buckets.

    Returns {"total_ms", "kernel_fraction", "regions": {region: ms}} --
    the per-BENCH-row attribution that makes kernel-share regressions
    visible round over round (a polish whose kernel_fraction drops is
    re-growing the layout/pad overhead this round removed)."""
    per = {name: 0.0 for name, _ in _REGION_KEYS}
    per["other"] = 0.0
    for r in rows:
        hay = " ".join((r.get("category") or "",
                        r.get("name") or "",
                        r.get("frame_op") or "")).lower()
        for name, keys in _REGION_KEYS:
            if any(k in hay for k in keys):
                per[name] += r["self_us"]
                break
        else:
            per["other"] += r["self_us"]
    total = sum(per.values())
    return {
        "total_ms": round(total / 1e3, 1),
        "kernel_fraction": round(per["kernels"] / total, 4) if total else 0.0,
        "regions": {k: round(v / 1e3, 1) for k, v in per.items()},
    }


def main():
    outdir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/pbccs_trace"
    if not os.environ.get("PBCCS_TRACE_PARSE_ONLY"):
        capture(outdir)
    path, rows = parse(outdir)
    total = sum(r["self_us"] for r in rows)
    per_cat = collections.defaultdict(float)
    for r in rows:
        per_cat[r["category"]] += r["self_us"]
    print(f"# parsed {path}", file=sys.stderr)
    print(f"# total device self time: {total / 1e3:.1f} ms", file=sys.stderr)
    print("\n== category rollup (ms, % of device) ==", file=sys.stderr)
    rollup = sorted(per_cat.items(), key=lambda kv: -kv[1])
    for cat, us in rollup:
        print(f"{cat:28s} {us / 1e3:10.1f}  {100 * us / total:5.1f}%",
              file=sys.stderr)
    print("\n== top ops by self time (ms | % | occurrences) ==",
          file=sys.stderr)
    ops = sorted(rows, key=lambda r: -r["self_us"])[:40]
    for r in ops:
        label = r["frame_op"] or r["name"]
        print(f"{r['self_us'] / 1e3:9.2f} {100 * r['self_us'] / total:5.1f}% "
              f"x{r['occurrences']:<6} {r['category']:16s} {label[:90]}",
              file=sys.stderr)
    print(json.dumps({
        "total_device_ms": round(total / 1e3, 1),
        "region_rollup": region_rollup(rows),
        "categories": {k: round(v / 1e3, 1) for k, v in rollup},
        "top_ops": [{"name": (r["frame_op"] or r["name"])[:160],
                     "category": r["category"],
                     "ms": round(r["self_us"] / 1e3, 2),
                     "n": r["occurrences"]} for r in ops[:15]],
    }))


if __name__ == "__main__":
    main()
