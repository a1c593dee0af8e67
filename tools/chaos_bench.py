#!/usr/bin/env python
"""Chaos bench: the full resilience harness (superset of chaos_smoke).

Injects every fault class the resilience subsystem handles and asserts
the recovery contract, including the process-level legs the smoke test
skips:

  1. IN-PROCESS FAULT MATRIX -- poison ZMW (bisect + serial + degrade),
     transient device error, hung dispatch vs watchdog: surviving-ZMW
     outputs must be byte-identical to a fault-free run (chaos_smoke's
     checks, at bench scale).
  2. KILL -9 / RESUME -- a real `ccs` subprocess with --checkpoint is
     SIGKILLed after its first journaled chunk; rerunning with --resume
     must produce byte-identical output + yield report vs an
     uninterrupted run, restoring (not recomputing) the journaled
     chunks.
  3. SERVE WATCHDOG -- a live engine with a short polish deadline fed a
     hung dispatch: the affected requests fail with a structured
     timeout, the engine keeps serving, and a follow-up request
     succeeds.
  4. OOM MATRIX (--ooms) -- injected device OOMs at the dispatch site:
     full output parity every round (never a quarantined healthy
     batch), governor ceilings recorded, later rounds pre-split at
     admission.
  5. INPUT FUZZ -- the randomized long leg of tools/fuzz_inputs.py:
     --fuzzRounds seeded structured corruptions over the BAM decode
     classes (bit flips, truncation, length-field lies, tag mutations),
     asserting the hardening invariant at bench scale (process
     survives, valid records byte-identical, rejections counted).

Reports JSON (stdout, plus --out FILE).

Usage:
    JAX_PLATFORMS=cpu python tools/chaos_bench.py --zmws 10
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, ".")  # runnable as tools/chaos_bench.py from the repo root

from pbccs_tpu.models.arrow.params import decode_bases
from pbccs_tpu.pipeline import Chunk, Failure, Subread, process_chunks
from pbccs_tpu.resilience import faults, watchdog
from pbccs_tpu.runtime.logging import Logger, LogLevel
from pbccs_tpu.simulate import simulate_zmw


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--zmws", type=int, default=10)
    p.add_argument("--tplLen", type=int, default=80)
    p.add_argument("--passes", type=int, default=5)
    p.add_argument("--chunkSize", type=int, default=2,
                   help="CLI work-item size (small: many journal records)")
    p.add_argument("--seed", type=int, default=20260803)
    p.add_argument("--skip-subprocess", action="store_true",
                   help="skip the kill -9 / crash CLI legs (fast mode)")
    p.add_argument("--fuzzRounds", type=int, default=40,
                   help="randomized input-fuzz rounds (0 disables)")
    p.add_argument("--ooms", type=int, default=3,
                   help="injected device-OOM rounds (governor split "
                        "parity + admission pre-split; 0 disables)")
    p.add_argument("--out", default=None, help="also write the JSON here")
    return p


def make_chunks(args) -> list[Chunk]:
    rng = np.random.default_rng(args.seed)
    out = []
    for i in range(args.zmws):
        _, reads, _, snr = simulate_zmw(rng, args.tplLen, args.passes)
        out.append(Chunk(
            f"bench/{i}",
            [Subread(f"bench/{i}/{k}", r) for k, r in enumerate(reads)],
            snr))
    return out


def write_fasta_workload(chunks: list[Chunk], path: str) -> None:
    with open(path, "w") as f:
        for c in chunks:
            movie, hole = c.id.split("/")
            for k, r in enumerate(c.reads):
                f.write(f">{movie}/{hole}/{k}_{k + 1}\n"
                        f"{decode_bases(r.seq)}\n")


def outputs(tally) -> dict[str, tuple[str, str]]:
    return {r.id: (r.sequence, r.qualities) for r in tally.results}


class CheckFailed(AssertionError):
    pass


def check(report: dict, name: str, ok: bool, detail: str = "") -> None:
    report[name] = bool(ok) if not detail else f"{bool(ok)} ({detail})"
    print(f"  {'PASS' if ok else 'FAIL'}  {name}"
          + (f"  ({detail})" if detail else ""))
    if not ok:
        raise CheckFailed(name)


# ------------------------------------------------------ 1. in-process matrix

def leg_fault_matrix(chunks, report: dict) -> None:
    print("== leg 1: in-process fault matrix ==")
    poison = chunks[len(chunks) // 2].id
    base = process_chunks(list(chunks))
    base_out = outputs(base)
    survivors = {k: v for k, v in base_out.items() if k != poison}
    report["baseline_successes"] = base.counts[Failure.SUCCESS]

    with faults.active(f"polish.dispatch:error~{poison}"):
        pois = process_chunks(list(chunks))
    check(report, "bisect_survivor_parity", outputs(pois) == survivors)
    check(report, "bisect_quarantined",
          pois.counts[Failure.OTHER] == 1)

    with faults.active(f"polish.dispatch:error~{poison}"):
        ser = process_chunks(list(chunks), on_error="serial")
    check(report, "serial_survivor_parity", outputs(ser) == survivors)

    with faults.active("polish.dispatch:error=transient@1*1"):
        tr = process_chunks(list(chunks))
    check(report, "transient_full_parity", outputs(tr) == base_out)

    # deadline well above a legitimate re-dispatch, hang longer than the
    # process lifetime (the abandoned thread stays in time.sleep, never
    # re-entering XLA at interpreter teardown)
    watchdog.configure(20.0)
    try:
        with faults.active("polish.dispatch:delay=3600@1*1"):
            hung = process_chunks(list(chunks))
    finally:
        watchdog.configure(None)
    check(report, "watchdog_recovery_parity", outputs(hung) == base_out)


# ------------------------------------------------------- 2. kill -9 / resume

def _cli_cmd(out_path, fasta, args, extra=()):
    return [sys.executable, "-m", "pbccs_tpu.cli", "--skipChemistryCheck",
            "--chunkSize", str(args.chunkSize),
            "--reportFile", out_path + ".csv",
            *extra, out_path, fasta]


def _run_cli(cmd, timeout=900):
    return subprocess.run(cmd, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=timeout)


def _journal_chunks(path: str) -> int:
    if not os.path.exists(path):
        return 0
    n = 0
    with open(path) as f:
        for line in f:
            try:
                n += json.loads(line).get("type") == "chunk"
            except ValueError:
                pass
    return n


def leg_kill9_resume(args, tmp, fasta, report: dict) -> None:
    print("== leg 2: kill -9 mid-run, then --resume ==")
    ref = os.path.join(tmp, "ref.fasta")
    r = _run_cli(_cli_cmd(ref, fasta, args))
    check(report, "uninterrupted_run_ok", r.returncode == 0,
          r.stderr[-300:] if r.returncode else "")

    out = os.path.join(tmp, "killed.fasta")
    ckpt = os.path.join(tmp, "killed.ckpt")
    proc = subprocess.Popen(
        _cli_cmd(out, fasta, args, ("--checkpoint", ckpt)),
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    # wait for the first journaled chunk, then kill -9 (no cleanup runs)
    deadline = time.monotonic() + 600
    while time.monotonic() < deadline and proc.poll() is None:
        if _journal_chunks(ckpt) >= 1:
            break
        time.sleep(0.2)
    journaled = _journal_chunks(ckpt)
    if proc.poll() is None:
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(30)
    check(report, "killed_with_journaled_chunks", journaled >= 1,
          f"{journaled} chunk(s) journaled before SIGKILL")
    check(report, "kill_was_mid_run", proc.returncode != 0,
          f"exit {proc.returncode}")

    r = _run_cli(_cli_cmd(out, fasta, args,
                          ("--checkpoint", ckpt, "--resume")))
    check(report, "resume_run_ok", r.returncode == 0,
          r.stderr[-300:] if r.returncode else "")
    check(report, "resume_restored_chunks",
          f"restored {journaled} completed chunk" in r.stderr
          or journaled == 0, f"journal had {journaled}")
    check(report, "resume_output_identical",
          open(ref).read() == open(out).read())
    check(report, "resume_report_identical",
          open(ref + ".csv").read() == open(out + ".csv").read())
    check(report, "journal_removed_after_success",
          not os.path.exists(ckpt))


# --------------------------------------------------------- 3. serve watchdog

def leg_serve_watchdog(chunks, report: dict) -> None:
    """Engine-level watchdog semantics (stubbed pipeline: the engine's
    behavior is under test here; the REAL pipeline's hang recovery is
    leg 1's watchdog_recovery_parity).  A polish deadline short enough
    to catch the injected 30 s hang would also catch a legitimate
    cold-compile CPU polish, so the stub keeps the leg deterministic."""
    print("== leg 3: serve engine watchdog ==")
    from pbccs_tpu.pipeline import PreparedZmw
    from pbccs_tpu.serve.engine import CcsEngine, ServeConfig

    def stub_prep(chunk, settings):
        return None, PreparedZmw(chunk, np.zeros(64, np.int8), [],
                                 len(chunk.reads), 0, 0.0)

    def stub_polish(preps, settings):
        # the injected delay=30@1 hangs the FIRST dispatch only
        faults.maybe_fail("polish.dispatch",
                          keys=[p.chunk.id for p in preps])
        from pbccs_tpu.pipeline import Failure as F
        return [(F.SUCCESS, None) for _ in preps]

    cfg = ServeConfig(max_batch=2, max_wait_ms=100.0,
                      polish_timeout_ms=1500.0)
    with faults.active("polish.dispatch:delay=30@1"):
        with CcsEngine(config=cfg, prep_fn=stub_prep,
                       polish_fn=stub_polish) as eng:
            hung = [eng.submit(c) for c in chunks[:2]]
            for h in hung:
                check(report, f"hung_request_completed_{h.chunk.id}",
                      h.wait(60.0))
            check(report, "hung_requests_failed_structured",
                  all(h.error is not None and "watchdog" in h.error
                      for h in hung))
            # the SAME engine keeps serving: the delay spec fired on @1
            # only, so the follow-up polish completes normally
            ok = eng.submit(chunks[2])
            check(report, "engine_serves_after_timeout",
                  ok.wait(60.0) and ok.error is None)
            check(report, "engine_status_alive",
                  eng.status()["engine"] == "ccs-serve")


# ------------------------------------------------- 4. OOM-adaptive dispatch

def leg_oom_matrix(chunks, args, report: dict) -> None:
    """--ooms rounds of injected device OOMs at the dispatch site: every
    round must complete with FULL output parity (a capacity failure
    costs wall time, never results, and never quarantines a healthy
    batch), the memory governor must record a shape ceiling, and later
    rounds must pre-split at admission instead of re-discovering the
    OOM."""
    print(f"== leg 4: OOM-adaptive dispatch ({args.ooms} rounds) ==")
    from pbccs_tpu.obs.metrics import default_registry
    from pbccs_tpu.resilience import resources

    base = process_chunks(list(chunks))
    base_out = outputs(base)
    reg = default_registry()
    for rnd in range(args.ooms):
        scope = reg.scope()
        with faults.active("polish.dispatch:oom@1*1", seed=rnd):
            oomed = process_chunks(list(chunks))
        check(report, f"oom_round{rnd}_full_parity",
              outputs(oomed) == base_out)
        check(report, f"oom_round{rnd}_never_quarantines",
              scope.counter_value("ccs_quarantined_zmws_total") == 0)
        if rnd == 0:
            check(report, "oom_split_redispatch",
                  scope.counter_value(
                      "ccs_resource_oom_splits_total") >= 1)
        else:
            check(report, f"oom_round{rnd}_admission_presplit",
                  scope.counter_value(
                      "ccs_resource_presplit_batches_total") >= 1)
    check(report, "oom_governor_ceiling_recorded",
          bool(resources.default_governor().snapshot()))


# ---------------------------------------------------------- 5. input fuzz

def leg_input_fuzz(args, report: dict) -> None:
    """The randomized long leg of the structured input fuzzer: every
    decode corruption class re-rolled --fuzzRounds times (fuzz_inputs
    --smoke is the deterministic tier-1 subset of this)."""
    print(f"== leg 5: randomized input fuzz ({args.fuzzRounds} rounds) ==")
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import fuzz_inputs

    rc = fuzz_inputs.main(["--seed", str(args.seed),
                           "--rounds", str(args.fuzzRounds)])
    check(report, "input_fuzz_rounds", rc == 0,
          f"{args.fuzzRounds} rounds, seed {args.seed}")


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from pbccs_tpu.runtime.cache import enable_compilation_cache

    enable_compilation_cache()
    Logger.default(Logger(level=LogLevel.ERROR))
    report: dict = {"workload": vars(args).copy()}
    chunks = make_chunks(args)
    tmp = tempfile.mkdtemp(prefix="chaos_bench_")
    fasta = os.path.join(tmp, "workload.fasta")
    write_fasta_workload(chunks, fasta)

    failed = False
    try:
        leg_fault_matrix(chunks, report)
        if not args.skip_subprocess:
            leg_kill9_resume(args, tmp, fasta, report)
        leg_serve_watchdog(chunks, report)
        if args.ooms:
            leg_oom_matrix(chunks, args, report)
        if args.fuzzRounds:
            leg_input_fuzz(args, report)
    except CheckFailed as e:
        report["failed"] = str(e)
        failed = True

    out = json.dumps(report, indent=2, default=str)
    print(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
    print("chaos bench:", "FAILED" if failed else "all checks passed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
