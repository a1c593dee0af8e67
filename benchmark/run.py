#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json and, by the names there, its configuration
(configs/), its traffic mix (traffic/, which names a driver in drivers/),
its per-layer metric readers (metrics/) and kernel counts (kernels/).
Everything worth reading goes on earlier lines; the last line of standard
output is the result, one JSON object.  Without a TPU (or with fewer chips
than the cell asks for) the run exits non-zero and prints no result.

    --rehearse          no chip: tiny sizes, CPU, interpreted kernels, the
                        configuration's rehearsal limits; the platform is
                        printed, and no metric's value
    --check-seeds a,b   one warm set-up, then a short window and the whole
                        output check for each seed; one CHECKSEED line each
    --control NAME      switch on the degraded path controls/NAME.json names
                        (its runs have to come out not correct)
    --describe-trace F  print what a .xplane.pb holds, and exit
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)                      # harness, reference
sys.path.insert(1, os.path.dirname(HERE))     # the program

from harness import checks, common, manifest, reduce  # noqa: E402
from harness.common import BenchFailure, say  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--check-seeds", default=None)
    ap.add_argument("--control", default=None)
    ap.add_argument("--describe-trace", default=None)
    return ap.parse_args(argv)


def run(args) -> int:
    doc = manifest.load()
    cell = manifest.Cell(doc, args.workload)
    control = None
    if args.control:
        manifest.check_name(args.control, "control")
        with open(os.path.join(HERE, "controls", args.control + ".json")) as f:
            control = json.load(f)
    seconds = args.seconds if args.seconds is not None else doc["run_seconds"]
    ctx = common.Context(cell=cell, seed=args.seed, seconds=seconds,
                         trace=bool(args.trace), rehearse=args.rehearse,
                         control=control, t_process=T_PROCESS)
    say(f"cell {cell.name}: config {cell.config_name}, traffic {cell.traffic_name} "
        f"(driver {cell.traffic['driver']}), chips {cell.chips}, seed {args.seed}, "
        f"{seconds:g} s, trace {args.trace}"
        + (", REHEARSAL on the CPU: control flow only, no metric is reported"
           if args.rehearse else "")
        + (f", CONTROL {args.control}: {control['breaks']}" if control else ""))
    driver = manifest.load_by_path("drivers", cell.traffic["driver"])
    session = driver.Session(ctx)
    seeds = ([int(s) for s in args.check_seeds.split(",")] if args.check_seeds
             else [args.seed])
    try:
        facts = session.setup()
        setup_s = time.monotonic() - T_PROCESS
        parts = ", ".join(f"{k} {v:.3f}" for k, v in ctx.setup_parts.items())
        say(f"setup: {setup_s:.3f} s from process start to the window: {parts}, "
            f"other {setup_s - sum(ctx.setup_parts.values()):.3f}")
        for seed in seeds:
            win = session.window(seed, seconds, ctx.trace)
            for note in win.notes:
                say(note)
            say(f"window: {win.counters.programs_text()} inside the window")
            verdict = checks.Verdict()
            if args.rehearse:
                say(f"check: platform_is_tpu_and_kernels_compiled skipped: rehearsal on "
                    f"{facts['platform']}")
            else:
                verdict.hold("platform_is_tpu_and_kernels_compiled",
                             int(facts["platform"] == "tpu" and not facts["interpreted"]),
                             "==", 1)
            failed = checks.check_results(win.results, win.zmws, win.attempted,
                                          ctx.check, seed, verdict)
            if args.check_seeds and not control and seed == seeds[0]:
                verdict.hold("warmup_file_again_gives_the_same_bytes",
                             int(session.repeat_check()), "==", 1)
            verdict.print()
            if args.check_seeds:
                say("CHECKSEED " + json.dumps({
                    "seed": seed, "correct": verdict.correct,
                    "end_to_end": {} if args.rehearse else win.end_to_end,
                    "numbers": {r[0]: r[1] for r in verdict.rows}}))
        device = {"platform": facts["platform"], "kind": facts["kind"],
                  "count": facts["count"],
                  "memory_peak_bytes": session.memory_peak_bytes()}
    finally:
        session.close()

    if ctx.trace:
        metrics, device_extra, breakdown = reduce.per_layer(ctx, win, facts)
        device.update(device_extra)
    else:
        values = dict(win.end_to_end, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
        breakdown = None
    last = {"correct": verdict.correct, "attempted": win.attempted,
            "failed": failed, "metrics": metrics, "device": device}
    if breakdown and not args.rehearse:
        last["breakdown"] = breakdown
    if args.rehearse:
        # a CPU run reports no number under a metric's name
        say("rehearsal: the cell reports " + ", ".join(sorted(metrics)) + "; no value is printed")
        last.update(rehearsal=True, metrics={})
    print(json.dumps(last), flush=True)
    return 0 if verdict.correct else 1


def main() -> int:
    args = parse_args()
    if args.describe_trace:
        from harness import xplane

        xplane.describe(args.describe_trace)
        return 0
    try:
        return run(args)
    except (BenchFailure, manifest.ManifestError) as e:
        say(f"FAILED: {e}")
        return 3


if __name__ == "__main__":
    sys.exit(main())
