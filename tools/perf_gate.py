#!/usr/bin/env python
"""Noise-aware performance-regression sentinel over perf-ledger records.

Compares a fresh run's ledger (pbccs_tpu/obs/ledger.py NDJSON) against
the committed PERF_BASELINE.json with PER-METRIC-CLASS tolerances, so
the gate is strict exactly where determinism makes strictness honest:

  counter   CPU-deterministic counts (polish dispatches, refine rounds,
            slot totals, governor interventions): exact match,
            enforced EVERYWHERE -- a drifted counter is a behavior
            change, not noise;
  ratio     CPU-deterministic ratios (fill ratio, padding waste):
            absolute band (default 0.02), enforced everywhere;
  compile   compile/cache counts: exact, but only when the ledger's
            jax_version matches the baseline's (a jax upgrade
            legitimately changes compile behavior -- the mismatch is
            printed as a note, never a silent pass);
  wall      wall-clock figures (wall_s, zmws_per_sec, device waits):
            MEDIAN across the ledger's matching records vs a relative
            band (default 35%), enforced only when the observed
            platform matches the baseline's AND is not "cpu" --
            CPU wall time in CI is noise, accelerator wall time is the
            product;
  resource  peak RSS: median vs a wide relative band (default 50%),
            same platform rule as wall.

Exit 0 clean; exit 1 with ONE structured JSON diff line per violation
(metric, class, baseline, observed, tolerance); exit 2 on usage errors
(no ledger, no matching records, bad baseline).

``--update-baseline`` rewrites PERF_BASELINE.json from the observed
ledger and REFUSES to loosen silently: every accepted change is printed
as `perf_gate: accepting <metric>: <old> -> <new>` before the write.

Usage:
    python tools/perf_gate.py LEDGER.ndjson
    python tools/perf_gate.py LEDGER.ndjson --counters-only
    python tools/perf_gate.py LEDGER.ndjson --update-baseline
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
from typing import Any

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pbccs_tpu.obs.ledger import LEDGER_FIELDS  # noqa: E402

DEFAULT_BASELINE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "PERF_BASELINE.json")

BASELINE_VERSION = 1

DEFAULT_TOLERANCES = {
    "counter": 0.0,    # allowed absolute count difference
    "ratio": 0.02,     # allowed absolute ratio difference
    "compile": 0.0,    # allowed absolute count difference (same jax)
    "wall": 0.35,      # allowed relative regression
    "resource": 0.5,   # allowed relative regression
}

# wall/resource metrics regress in a direction; improvements never fail
_LOWER_IS_BETTER = {"wall_s", "device_wait_s", "device_step_ms",
                    "compile_s", "peak_rss_bytes"}

# classes the gate may enforce (meta/live are recorded, never gated)
_GATED = ("counter", "ratio", "compile", "wall", "resource")


def _select_records(records: list[dict], select: dict) -> list[dict]:
    out = []
    for rec in records:
        if all(rec.get(k) == v for k, v in select.items()):
            out.append(rec)
    return out


def _numeric(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def observed_metrics(records: list[dict]) -> dict[str, Any]:
    """Collapse matching records into one observed-metric map: the LAST
    record for deterministic classes, the MEDIAN across records for the
    noisy wall/resource classes (median-of-N is the committed
    statistic)."""
    out: dict[str, Any] = {}
    last = records[-1]
    for field, cls in LEDGER_FIELDS.items():
        if cls in ("counter", "ratio", "compile"):
            if _numeric(last.get(field)):
                out[field] = last[field]
        elif cls in ("wall", "resource"):
            vals = [r[field] for r in records if _numeric(r.get(field))]
            if vals:
                out[field] = statistics.median(vals)
    return out


def bad_baseline_reason(baseline: dict) -> str | None:
    """Why this baseline document is unusable (None = fine): a corrupt
    or hand-mangled baseline must be a clean exit-2 diagnostic, never a
    TypeError traceback mid-compare."""
    metrics = baseline.get("metrics")
    if not isinstance(metrics, dict):
        return "metrics must be an object"
    for name, val in metrics.items():
        if not _numeric(val):
            return (f"metrics.{name} must be a number, got "
                    f"{type(val).__name__}")
    tolerances = baseline.get("tolerances")
    if tolerances is not None:
        if not isinstance(tolerances, dict):
            return "tolerances must be an object"
        for cls, tol in tolerances.items():
            if not _numeric(tol):
                return (f"tolerances.{cls} must be a number, got "
                        f"{type(tol).__name__}")
    select = baseline.get("select")
    if select is not None and not isinstance(select, dict):
        return "select must be an object"
    return None


def _violation(metric: str, cls: str, base, obs, tol) -> dict:
    return {"metric": metric, "class": cls, "baseline": base,
            "observed": obs, "tolerance": tol}


def compare(baseline: dict, records: list[dict], *,
            counters_only: bool = False,
            ignore: set[str] | frozenset[str] | None = None
            ) -> tuple[list[dict], list[str]]:
    """(violations, notes) of the observed ledger records vs baseline.

    `records` are the selector-matched records the class bands run
    over.  `ignore` names metrics exempt from enforcement (noted, not
    silently dropped) -- the ccs-tune referee uses it for fields a
    candidate knob legitimately perturbs (e.g. band_w changes compile
    counts)."""
    tol = {**DEFAULT_TOLERANCES, **(baseline.get("tolerances") or {})}
    base_metrics = baseline.get("metrics") or {}
    obs = observed_metrics(records)
    last = records[-1]
    notes: list[str] = []
    violations: list[dict] = []
    if ignore:
        exempt = sorted(set(ignore) & set(base_metrics))
        if exempt:
            base_metrics = {k: v for k, v in base_metrics.items()
                            if k not in ignore}
            notes.append("metrics exempted by --ignore: "
                         + ", ".join(exempt))

    jax_match = (last.get("jax_version") == baseline.get("jax_version"))
    platform = last.get("platform")
    wall_enforced = (not counters_only
                     and platform == baseline.get("platform")
                     and platform not in (None, "cpu"))
    if not jax_match:
        notes.append(
            f"compile-class metrics skipped: ledger jax_version "
            f"{last.get('jax_version')!r} != baseline "
            f"{baseline.get('jax_version')!r}")
    if not wall_enforced and not counters_only:
        notes.append(
            f"wall/resource classes skipped on platform {platform!r} "
            f"(baseline platform {baseline.get('platform')!r}; "
            "wall-clock is enforced on matching accelerator hosts only)")

    for metric, base_val in sorted(base_metrics.items()):
        cls = LEDGER_FIELDS.get(metric)
        if cls not in _GATED:
            notes.append(f"baseline metric {metric!r} has no gated "
                         "class; ignored")
            continue
        if cls == "compile" and not jax_match:
            continue
        if cls in ("wall", "resource") and not wall_enforced:
            continue
        obs_val = obs.get(metric)
        if not _numeric(base_val):
            # defense in depth for library callers that skipped the
            # bad_baseline_reason gate; main() exits 2 before this
            notes.append(f"baseline metric {metric!r} is non-numeric; "
                         "skipped")
            continue
        if obs_val is None:
            violations.append(_violation(
                metric, cls, base_val, None, tol[cls]))
            continue
        if cls in ("counter", "compile", "ratio"):
            if abs(obs_val - base_val) > tol[cls]:
                violations.append(_violation(metric, cls, base_val,
                                             obs_val, tol[cls]))
        else:  # wall / resource: relative band, regression direction only
            if base_val == 0:
                continue
            if metric in _LOWER_IS_BETTER:
                rel = (obs_val - base_val) / base_val
            else:
                rel = (base_val - obs_val) / base_val
            if rel > tol[cls]:
                violations.append(_violation(metric, cls, base_val,
                                             round(obs_val, 4),
                                             tol[cls]))
    return violations, notes


def build_baseline(records: list[dict], select: dict,
                   tolerances: dict | None = None) -> dict:
    """A fresh baseline document from observed records."""
    last = records[-1]
    return {
        "baseline_version": BASELINE_VERSION,
        "select": select,
        "jax_version": last.get("jax_version"),
        "platform": last.get("platform"),
        "tolerances": {**DEFAULT_TOLERANCES, **(tolerances or {})},
        "metrics": observed_metrics(records),
    }


def update_baseline(path: str, baseline: dict | None,
                    records: list[dict], select: dict) -> dict:
    """--update-baseline: rewrite `path` from the observed ledger,
    printing every accepted change (never a silent loosening).  A
    corrupt old baseline is replaced wholesale (its unusable sections
    are ignored, not crashed on)."""
    old_metrics = (baseline or {}).get("metrics")
    if not isinstance(old_metrics, dict):
        old_metrics = {}
    old_tol = (baseline or {}).get("tolerances")
    fresh = build_baseline(records, select,
                           old_tol if isinstance(old_tol, dict)
                           and all(_numeric(v) for v in old_tol.values())
                           else None)
    for metric in sorted(set(old_metrics) | set(fresh["metrics"])):
        old, new = old_metrics.get(metric), fresh["metrics"].get(metric)
        if old != new:
            print(f"perf_gate: accepting {metric}: {old} -> {new}")
    if baseline is not None \
            and baseline.get("jax_version") != fresh.get("jax_version"):
        print(f"perf_gate: accepting jax_version: "
              f"{baseline.get('jax_version')} -> "
              f"{fresh.get('jax_version')}")
    from pbccs_tpu.resilience.resources import atomic_output

    with atomic_output(path, "perf_baseline") as fh:
        json.dump(fresh, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(f"perf_gate: baseline written to {path}")
    return fresh


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="perf_gate",
        description="Gate a perf-ledger against PERF_BASELINE.json with "
                    "noise-aware per-metric-class tolerances.")
    p.add_argument("ledger", help="Perf-ledger NDJSON path.")
    p.add_argument("--baseline", default=DEFAULT_BASELINE,
                   help="Baseline JSON. Default = %(default)s")
    p.add_argument("--counters-only", action="store_true",
                   help="Enforce only the CPU-deterministic classes "
                        "(counter/ratio/compile); the tier-1 CI mode.")
    p.add_argument("--ignore", action="append", default=[],
                   metavar="METRIC",
                   help="Exempt a metric from enforcement (repeatable; "
                        "noted on stderr, never silent). The ccs-tune "
                        "referee's escape hatch for fields a candidate "
                        "knob legitimately perturbs.")
    p.add_argument("--kind", default=None,
                   help="Override the baseline's record-kind selector.")
    p.add_argument("--source", default=None,
                   help="Override the baseline's record-source selector.")
    p.add_argument("--update-baseline", action="store_true",
                   help="Rewrite the baseline from this ledger, printing "
                        "every accepted delta (no silent loosening).")
    args = p.parse_args(argv)

    from pbccs_tpu.obs.ledger import read_ledger

    records, skipped = read_ledger(args.ledger)
    if skipped:
        print(f"perf_gate: note: {skipped} unparseable ledger line(s) "
              "skipped (torn tail?)", file=sys.stderr)
    if not records:
        print(f"perf_gate: no records in {args.ledger}", file=sys.stderr)
        return 2

    baseline = None
    if os.path.exists(args.baseline):
        try:
            with open(args.baseline) as fh:
                baseline = json.load(fh)
        except (OSError, json.JSONDecodeError) as e:
            print(f"perf_gate: bad baseline {args.baseline}: {e}",
                  file=sys.stderr)
            return 2
        if not isinstance(baseline, dict):
            print(f"perf_gate: bad baseline {args.baseline}: not a "
                  "JSON object", file=sys.stderr)
            return 2
        reason = bad_baseline_reason(baseline)
        if reason is not None and not args.update_baseline:
            print(f"perf_gate: bad baseline {args.baseline}: {reason}",
                  file=sys.stderr)
            return 2

    raw_select = (baseline or {}).get("select")
    select = (dict(raw_select) if isinstance(raw_select, dict)
              and raw_select else {"kind": "batch_run"})
    if args.kind:
        select["kind"] = args.kind
    if args.source:
        select["source"] = args.source
    matching = _select_records(records, select)
    if not matching:
        print(f"perf_gate: no ledger records match selector {select} "
              f"({len(records)} record(s) total)", file=sys.stderr)
        return 2

    if args.update_baseline:
        update_baseline(args.baseline, baseline, matching, select)
        return 0

    if baseline is None:
        print(f"perf_gate: no baseline at {args.baseline}; run with "
              "--update-baseline to create one", file=sys.stderr)
        return 2

    violations, notes = compare(baseline, matching,
                                counters_only=args.counters_only,
                                ignore=set(args.ignore) or None)
    for note in notes:
        print(f"perf_gate: note: {note}", file=sys.stderr)
    if violations:
        for v in violations:
            print(json.dumps({"perf_gate_violation": v},
                             sort_keys=True))
        print(f"perf_gate: FAIL: {len(violations)} regression(s) vs "
              f"{args.baseline} over {len(matching)} record(s)",
              file=sys.stderr)
        return 1
    print(f"perf_gate: OK: {len(matching)} record(s) within tolerance "
          f"of {args.baseline}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
