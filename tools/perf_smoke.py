#!/usr/bin/env python
"""Perf-ledger smoke for the tier-1 gate: determinism + sentinel wiring.

Runs ONE small fixed workload TWICE through the batch CLI (fresh
subprocess + fresh compile cache each time, so the two runs are
byte-equivalent experiments), then asserts the three properties the
performance-observability layer is trusted for:

  1. SCHEMA: every ledger record is schema-versioned and every field it
     carries is declared in obs.ledger.LEDGER_FIELDS (the REG011
     drift-checked schema);
  2. DETERMINISM: the CPU-deterministic classes (counter / ratio /
     compile) are IDENTICAL across the two runs -- the property that
     makes enforcing them everywhere honest;
  3. SENTINEL: tools/perf_gate.py passes the fresh ledger against the
     committed PERF_BASELINE.json in --counters-only mode, and a
     deliberately perturbed ledger (counter bump + padding-waste shift)
     makes it exit nonzero with a structured diff naming the metric.

The fresh ledger is copied to $ARTIFACTS_DIR (default
/tmp/ccs-perf-artifacts) for CI upload.

Usage:  JAX_PLATFORMS=cpu python tools/perf_smoke.py
        ... --update-baseline   # regenerate PERF_BASELINE.json from
                                # run 1 (prints every accepted delta)
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_ZMWS = 8
TPL_LEN = 120
N_PASSES = 5
CHUNK = 4
SEED = 20260804

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "PERF_BASELINE.json")


def _child_env(cache_dir: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache_dir,
               # host refinement loop: sane CPU compile budget, and the
               # ledger's refine_rounds_host counter gets real rounds
               PBCCS_DEVICE_REFINE="0")
    return env


def write_workload(path: str) -> None:
    import numpy as np

    from pbccs_tpu.models.arrow.params import decode_bases
    from pbccs_tpu.simulate import build_tasks

    tasks, _ = build_tasks(np.random.default_rng(SEED), N_ZMWS, TPL_LEN,
                           str(N_PASSES), 1)
    with open(path, "w") as f:
        for t in tasks:
            z = t.id.split("/")[1]
            start = 0
            for read in t.reads:
                seq = decode_bases(read)
                f.write(f">perf/{z}/{start}_{start + len(seq)}\n{seq}\n")
                start += len(seq) + 50


def run_once(tmp: str, fasta: str, tag: str) -> str:
    """One fresh `ccs` subprocess writing its own ledger; returns the
    ledger path."""
    cache = os.path.join(tmp, f"cache_{tag}")
    ledger = os.path.join(tmp, f"ledger_{tag}.ndjson")
    out = os.path.join(tmp, f"out_{tag}.bam")
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "pbccs_tpu.cli", out, fasta,
         "--skipChemistryCheck", "--chunkSize", str(CHUNK),
         "--numThreads", "2", "--zmws", "all",
         "--reportFile", os.path.join(tmp, f"report_{tag}.csv"),
         "--perfLedger", ledger, "--logLevel", "WARN"],
        env=_child_env(cache), capture_output=True, text=True,
        timeout=480)
    dt = time.monotonic() - t0
    if proc.returncode != 0:
        raise AssertionError(
            f"run {tag} failed rc={proc.returncode}:\n"
            f"{proc.stderr[-2000:]}")
    print(f"perf_smoke: run {tag} OK in {dt:.1f}s")
    return ledger


def load_single_record(ledger: str) -> dict:
    from pbccs_tpu.obs.ledger import read_ledger

    records, skipped = read_ledger(ledger)
    assert skipped == 0, f"{ledger}: {skipped} unparseable line(s)"
    runs = [r for r in records if r.get("kind") == "batch_run"]
    assert len(runs) == 1, \
        f"{ledger}: want exactly 1 batch_run record, got {len(runs)}"
    return runs[0]


def assert_schema(rec: dict, ledger: str) -> None:
    from pbccs_tpu.obs.ledger import LEDGER_FIELDS, LEDGER_SCHEMA_VERSION

    assert rec.get("schema_version") == LEDGER_SCHEMA_VERSION, rec
    alien = sorted(set(rec) - set(LEDGER_FIELDS))
    assert not alien, f"{ledger}: fields outside LEDGER_FIELDS: {alien}"
    for required in ("kind", "t_unix", "source", "zmws", "results",
                     "polish_dispatches", "refine_rounds_host",
                     "zmw_slots", "peak_rss_bytes", "wall_s"):
        assert required in rec, f"{ledger}: missing field {required}"
    print(f"perf_smoke: schema OK ({len(rec)} fields)")


def assert_deterministic(rec1: dict, rec2: dict) -> None:
    from pbccs_tpu.obs.ledger import LEDGER_FIELDS

    gated = {f for f, c in LEDGER_FIELDS.items()
             if c in ("counter", "ratio", "compile")}
    diffs = []
    for field in sorted(gated):
        if rec1.get(field) != rec2.get(field):
            diffs.append(f"{field}: {rec1.get(field)!r} != "
                         f"{rec2.get(field)!r}")
    assert not diffs, ("CPU-deterministic ledger counters drifted "
                       "between two identical runs:\n  "
                       + "\n  ".join(diffs))
    n = sum(1 for f in gated if f in rec1)
    print(f"perf_smoke: determinism OK ({n} gated fields identical "
          "across runs)")


def run_gate(argv: list[str]) -> tuple[int, str]:
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "perf_gate.py")]
        + argv,
        capture_output=True, text=True, timeout=120)
    return proc.returncode, proc.stdout + proc.stderr


def main() -> int:
    # the parent only SIMULATES the workload (numpy + task dataclasses),
    # but the import chain touches jax -- pin it to CPU when unset
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    update = "--update-baseline" in sys.argv[1:]
    t0 = time.monotonic()
    tmp = tempfile.mkdtemp(prefix="pbccs_perf_smoke_")
    try:
        fasta = os.path.join(tmp, "perf_smoke.fasta")
        write_workload(fasta)
        ledger1 = run_once(tmp, fasta, "a")
        rec1 = load_single_record(ledger1)
        assert_schema(rec1, ledger1)

        if update:
            rc, out = run_gate([ledger1, "--update-baseline",
                                "--baseline", BASELINE])
            print(out, end="")
            return rc

        ledger2 = run_once(tmp, fasta, "b")
        rec2 = load_single_record(ledger2)
        assert_schema(rec2, ledger2)
        assert_deterministic(rec1, rec2)

        # the sentinel itself, in tier-1's counters-only mode
        rc, out = run_gate([ledger1, "--counters-only",
                            "--baseline", BASELINE])
        assert rc == 0, f"perf_gate failed on a clean ledger:\n{out}"
        print("perf_smoke: perf_gate OK vs committed PERF_BASELINE.json")

        # a perturbed ledger MUST fail with a structured diff: a
        # counter bump (always enforced) + a padding-waste shift
        perturbed = dict(rec1)
        perturbed["refine_rounds_host"] = \
            int(perturbed.get("refine_rounds_host", 0)) + 7
        perturbed["padding_waste"] = round(
            float(perturbed.get("padding_waste", 0.0)) + 0.25, 4)
        bad = os.path.join(tmp, "perturbed.ndjson")
        with open(bad, "w") as f:
            f.write(json.dumps(perturbed) + "\n")
        rc, out = run_gate([bad, "--counters-only",
                            "--baseline", BASELINE])
        assert rc == 1, f"perf_gate must fail a perturbed ledger: {out}"
        assert "refine_rounds_host" in out and "padding_waste" in out, \
            f"structured diff must name the perturbed metrics:\n{out}"
        assert "perf_gate_violation" in out, out
        print("perf_smoke: perturbed ledger correctly rejected with a "
              "structured diff")

        art_dir = os.environ.get("ARTIFACTS_DIR",
                                 "/tmp/ccs-perf-artifacts")
        os.makedirs(art_dir, exist_ok=True)
        shutil.copy(ledger1, os.path.join(art_dir, "perf_ledger.ndjson"))
        print(f"perf_smoke: ledger artifact -> "
              f"{os.path.join(art_dir, 'perf_ledger.ndjson')}")
        print(f"perf_smoke: PASS in {time.monotonic() - t0:.1f}s")
        return 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
