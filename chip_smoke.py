#!/usr/bin/env python3
"""Chip smoke: the Arrow main path, end to end, on one TPU v5e chip.

    python chip_smoke.py            # one chip: batch CLI, kernel check, ccs serve
    python chip_smoke.py --chips 4  # four chips: --devices 4 against --devices 1

Simulated 2 kb x 3-10 pass ZMWs (BASELINE.json config 2, made from --seed)
go in as a subread BAM through the batch CLI with default flags and as
client frames through `ccs serve`; every consensus is held to its simulated
template.  Any failed check exits non-zero WITHOUT the last-line JSON.

One process for each chip: this parent never initialises a JAX backend.
Every device phase is a child process, run one after the other, and the
device named on the last line is the one a child reported.

`--rehearse` is for a machine with no chip: it shrinks the sizes, runs the
kernels in interpret mode and relaxes only the platform assertion.  Its
numbers are CPU numbers and its last line names the platform it ran on.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
MOVIE = "m140905_042212_sidney_c100564852550000001823085912221377_s1_X0"
RESULT_TAG = "CHIP-SMOKE-RESULT "
CHILD_TIMEOUT_S = 1000

# Sizes.  "real" is BASELINE.json config 2 (2 kb inserts, 3-10 passes) at
# the CLI's default batch of 64 ZMWs; the kernel check is one fill bucket
# of that configuration (256 reads, Jmax 2112, W 96).
REAL = dict(n_zmws=256, tpl_len=2000, passes=(3, 10), serve_zmws=32,
            serve_args=("--bucket", "16x10x2000"), kernel=(256, 2112, 96),
            # (Z, R, Jm) of the benchmark's three cells' score grids
            score_grids=((16, 12, 2304), (32, 12, 2304), (32, 32, 576)))
TINY = dict(n_zmws=8, tpl_len=120, passes=(3, 4), serve_zmws=4,
            serve_args=("--bucket", "4x4x120", "--maxBatch", "4"),
            kernel=(8, 192, 64), score_grids=((2, 12, 192),))
SERVE_SESSIONS = 4
SERVE_LEDGER_INTERVAL_S = 5.0


def sizes_for(rehearse: bool) -> dict:
    return TINY if rehearse else REAL


# A consensus may differ from its template by what its own predicted
# accuracy allows: twice the expected error count (1 - pq) * length, plus
# 2 edits (at <= 8 passes a 1-2 bp residual is consistent with pq ~0.99).
def allowed_edits(pred_acc: float, length: int) -> int:
    return 2 + math.ceil(round(2.0 * (1.0 - pred_acc) * length, 6))

# The pipeline's own yield gates may turn a ZMW away: at 3-4 passes one
# read below the AddRead z-score gate (minZScore -5) leaves too few full
# passes.  On the default seed that is 2 of 256 ZMWs, the same two on the
# CPU's pure-JAX path (PR 23).  Such a ZMW is a counted yield outcome, not
# a failure; an exception ("Other"), a quarantine or a draft always fails.
MIN_SUCCESS_FRACTION = 0.95


def check_yield(what: str, n: int, n_success: int, n_other: int) -> None:
    check(n_other == 0, f"{what}: {n_other} ZMW(s) failed with an exception")
    check(n_success >= math.ceil(MIN_SUCCESS_FRACTION * n),
          f"{what}: only {n_success} of {n} ZMWs succeeded; the yield gates "
          f"may turn away at most {1 - MIN_SUCCESS_FRACTION:.0%}")


# Pallas fill vs the pure-JAX fill on the same inputs: both accumulate
# ~2000 f32 column log-scales, so log-likelihoods of magnitude ~1e3 agree
# to a relative 2e-4.
KERNEL_LL_RTOL = 2e-4


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


def check_same_bytes(a: str, b: str, why: str) -> None:
    with open(a, "rb") as fa, open(b, "rb") as fb:
        check(fa.read() == fb.read(), f"{a} and {b} differ: {why}")


# --------------------------------------------------------------- the data

def _simulate_one(job):
    """One ZMW from its own generator: the draw does not depend on how
    many workers made the others."""
    import numpy as np

    from pbccs_tpu.simulate import simulate_zmw

    seed, z, tpl_len, lo, hi = job
    rng = np.random.default_rng([seed, z])
    tpl, reads, _strands, snr = simulate_zmw(
        rng, tpl_len, int(rng.integers(lo, hi + 1)))
    return z, tpl, reads, snr


def make_zmws(seed: int, sizes: dict) -> list:
    lo, hi = sizes["passes"]
    jobs = [(seed, z, sizes["tpl_len"], lo, hi)
            for z in range(sizes["n_zmws"])]
    with concurrent.futures.ProcessPoolExecutor(
            max_workers=min(16, os.cpu_count() or 1)) as pool:
        return sorted(pool.map(_simulate_one, jobs, chunksize=4),
                      key=lambda t: t[0])


def write_subread_bam(path: str, zmws: list) -> None:
    from pbccs_tpu.io.bam import (BamHeader, BamRecord, BamWriter,
                                  ReadGroupInfo, make_read_group_id)
    from pbccs_tpu.models.arrow.params import decode_bases

    header = BamHeader(read_groups=[ReadGroupInfo(
        MOVIE, "SUBREAD", binding_kit="100356300",
        sequencing_kit="100356200", basecaller_version="2.3.0")])
    rg = make_read_group_id(MOVIE, "SUBREAD")
    with BamWriter(path, header) as bw:
        for z, _tpl, reads, snr in zmws:
            start = 0
            for read in reads:
                seq = decode_bases(read)
                bw.write(BamRecord(
                    name=f"{MOVIE}/{z}/{start}_{start + len(seq)}", seq=seq,
                    tags={"RG": rg, "zm": z, "cx": 3, "rq": 0.85,
                          "sn": [float(s) for s in snr]}))
                start += len(seq) + 50


# ---------------------------------------------------------- truth checks

def edit_distance(a, b) -> int:
    """Levenshtein distance of two base-code vectors, one numpy row at a
    time (the in-row insertion chain is a running minimum)."""
    import numpy as np

    idx = np.arange(len(b) + 1)
    prev = idx.copy()
    for i, ai in enumerate(a, 1):
        cur = np.empty_like(prev)
        cur[0] = i
        cur[1:] = np.minimum(prev[:-1] + (b != ai), prev[1:] + 1)
        prev = np.minimum.accumulate(cur - idx) + idx
    return int(prev[-1])


def check_consensus(what: str, zmw: int, seq: str, qual: str,
                    pred_acc: float, truth: dict) -> int:
    """Hold one consensus to its simulated template (either strand: the
    orientation follows the first POA read).  Returns its edit count."""
    from pbccs_tpu.models.arrow.params import encode_bases, revcomp

    check(len(qual) == len(seq),
          f"{what}: ZMW {zmw} has {len(qual)} QVs for {len(seq)} bases")
    tpl = truth[zmw]
    codes = encode_bases(seq)
    bound = allowed_edits(pred_acc, len(tpl))
    edits = edit_distance(codes, tpl)
    if edits > bound:
        edits = min(edits, edit_distance(codes, revcomp(tpl)))
    check(edits <= bound,
          f"{what}: ZMW {zmw} consensus is {edits} edits from its template; "
          f"its predicted accuracy {pred_acc:.5f} allows {bound}")
    return edits


def check_bam(what: str, bam: str, report: str, truth: dict) -> dict:
    """Every ZMW counted in the report, none an exception, the yield
    within bound, and every BAM record within its error bound."""
    from pbccs_tpu.io.bam import BamReader

    with open(report) as f:
        rows = {r[0]: int(r[1]) for r in
                (line.strip().split(",") for line in f) if len(r) == 3}
    n = len(truth)
    check(sum(rows.values()) == n,
          f"{what}: the report counts {sum(rows.values())} of {n} ZMWs")
    n_success = rows.get("Success -- CCS generated", 0)
    check_yield(what, n, n_success, rows.get("Failed -- Exception thrown", 0))
    with BamReader(bam) as br:
        recs = list(br)
    check(len(recs) == n_success,
          f"{what}: {len(recs)} BAM records for {n_success} successes")
    edits = []
    for rec in recs:
        check("df" not in rec.tags,
              f"{what}: {rec.name} is a draft-only (degraded) record")
        edits.append(check_consensus(what, int(rec.tags["zm"]), rec.seq,
                                     rec.qual, float(rec.tags["pq"]), truth))
    return {"zmws": n, "records": len(recs),
            "yield_gated": {k: v for k, v in rows.items()
                            if v and not k.startswith("Success")},
            "exact": sum(e == 0 for e in edits), "max_edits": max(edits),
            "mean_predicted_accuracy": round(
                sum(float(r.tags["pq"]) for r in recs) / len(recs), 6)}


# ------------------------------------------------------- child processes

def child_env(rehearse: bool) -> dict:
    env = dict(os.environ)
    if rehearse:
        # the CPU backend would choose the pure-JAX paths; switch the
        # kernels on so the same programs run, interpreted
        env.update(JAX_PLATFORMS="cpu", PBCCS_PALLAS="1", PBCCS_DENSE="1")
    else:
        # jax falls back to the CPU without a word when it cannot get the
        # chip, and the pure-JAX paths would still give correct consensus:
        # with the platform pinned a child raises at start-up instead
        env["JAX_PLATFORMS"] = "tpu"
    return env


def run_child(phase: str, args, extra: list[str]) -> dict:
    """Run one device phase as a child of its own, echo what it prints,
    and return the result it reports."""
    cmd = [sys.executable, os.path.join(HERE, "chip_smoke.py"),
           "--phase", phase, "--workdir", args.workdir,
           "--seed", str(args.seed)] + extra
    if args.rehearse:
        cmd.append("--rehearse")
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                            env=child_env(args.rehearse), cwd=HERE)
    timer = threading.Timer(CHILD_TIMEOUT_S, proc.kill)
    timer.start()
    result = None
    try:
        for line in proc.stdout:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            else:
                say(line.rstrip("\n"))
        rc = proc.wait()
    finally:
        timer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    check(rc == 0, f"phase {phase} exited with code {rc}")
    check(result is not None, f"phase {phase} reported no result")
    return result


# ---- inside a child: everything below may touch the device -------------

def device_facts(rehearse: bool) -> dict:
    """Print the set-up, and fail unless this is the chip with both
    kernels compiled for it."""
    import jax

    from pbccs_tpu import native
    from pbccs_tpu.ops import dense_score_pallas, fwdbwd_pallas
    from pbccs_tpu.resilience.resources import device_bytes_limit
    from pbccs_tpu.runtime.cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    jmax = sizes_for(rehearse)["kernel"][1]
    facts = {
        "jax": jax.__version__, "device": dev, "compile_cache": cache_dir,
        "JAX_COMPILATION_CACHE_DIR":
            os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        "device_bytes_limit": device_bytes_limit(),
        "native_library": native.available(),
        "fills_use_pallas": fwdbwd_pallas.fills_use_pallas(),
        "dense_score_enabled": dense_score_pallas.dense_score_enabled(jmax),
        "fill_interpret": fwdbwd_pallas._interpret(),
        "dense_interpret": dense_score_pallas._interpret(),
    }
    say("setup: " + json.dumps(facts))
    if not rehearse:
        check(dev["platform"] == "tpu",
              f"no TPU: jax reports platform {dev['platform']!r}")
        check(not facts["fill_interpret"] and not facts["dense_interpret"],
              "a Pallas kernel is in interpret mode")
    check(facts["native_library"], "the native host library is not loaded")
    check(facts["fills_use_pallas"], "the Pallas fill kernel is off")
    check(facts["dense_score_enabled"], "the dense scoring kernel is off")
    return dev


def _counters(scope, name: str) -> dict:
    return {",".join(f"{k}={v}" for k, v in labels): int(v_)
            for labels, v_ in scope.counters(name).items() if v_}


def run_cli_once(workdir: str, tag: str, n_zmws: int, cli_args: list[str],
                 dev: dict) -> dict:
    """One batch-CLI run in this process, with its own counter window.
    cli.run returns after the BAM and the report are on disk, so the
    host clock closes on written results."""
    from pbccs_tpu import cli
    from pbccs_tpu.obs.metrics import default_registry

    out = os.path.join(workdir, f"{tag}.bam")
    report = os.path.join(workdir, f"{tag}.csv")
    log = os.path.join(workdir, f"{tag}.log")
    scope = default_registry().scope()
    t0 = time.monotonic()
    rc = cli.run([out, os.path.join(workdir, "subreads.bam"),
                  "--reportFile", report, "--logFile", log,
                  "--logLevel", "DEBUG"] + cli_args)
    wall = time.monotonic() - t0
    try:
        return _check_cli_run(tag, rc, wall, n_zmws, scope, dev, out, report)
    except SmokeFailure:
        # the recovery paths absorb exceptions into the debug log: show
        # the first of them, which is the one to repair
        with open(log, errors="replace") as f:
            text = f.read()
        at = text.find("absorbed")
        say(f"---- {tag}: first absorbed exception in the CLI's log ----\n"
            + (text[max(0, at - 200): at + 3000] if at >= 0
               else text[-3000:]))
        raise


def _check_cli_run(tag, rc, wall, n_zmws, scope, dev, out, report) -> dict:
    check(rc == 0, f"{tag}: the batch CLI returned {rc}")
    c = {name: _counters(scope, name) for name in (
        "ccs_zmw_failures_total", "ccs_quarantined_zmws_total",
        "ccs_degraded_zmws_total", "ccs_resource_oom_splits_total",
        "ccs_resource_presplit_batches_total", "ccs_batch_polishes_total",
        "ccs_refine_rounds_total", "ccs_compile_cache_events_total",
        "ccs_compiles_total", "ccs_sched_tasks_total")}
    say(f"timing: {tag} on {dev['platform']} {dev['kind']} x{dev['count']}: "
        f"{wall:.3f} s wall, {n_zmws / wall:.3f} ZMW/s; counters "
        + json.dumps({k: v for k, v in c.items() if v}))
    for name in ("ccs_zmw_failures_total", "ccs_quarantined_zmws_total",
                 "ccs_degraded_zmws_total", "ccs_resource_oom_splits_total"):
        check(not c[name], f"{tag}: {name} moved: {c[name]}")
    check(c["ccs_refine_rounds_total"].get("source=device", 0) > 0,
          f"{tag}: the device-resident refine loop ran no round: "
          f"{c['ccs_refine_rounds_total']}")
    return {"wall_s": wall, "bam": out, "report": report, "counters": c}


def kernel_check(seed: int, rehearse: bool, dev: dict) -> None:
    """The Pallas fill against the pure-JAX fill, same inputs, on the
    device: one bucket of the batch phase's configuration."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pbccs_tpu.models.arrow.params import (snr_to_transition_table_host,
                                               template_transition_params)
    from pbccs_tpu.models.arrow.scorer import fill_alpha_beta_batch
    from pbccs_tpu.parallel.batch import _imax_bucket
    from pbccs_tpu.simulate import (make_transition_track, random_snr,
                                    random_template, sample_read)

    n, jmax, width = sizes_for(rehearse)["kernel"]
    tpl_len = sizes_for(rehearse)["tpl_len"]
    imax = _imax_bucket(jmax)
    rng = np.random.default_rng([seed, 1 << 20])
    reads = np.full((n, imax), 4, np.int8)
    rlens = np.zeros(n, np.int32)
    tpls = np.full((n, jmax), 4, np.int8)
    tables = np.zeros((n, 8, 4), np.float32)
    for r in range(0, n, 8):      # 8 reads of each template
        tpl, snr = random_template(rng, tpl_len), random_snr(rng)
        track = make_transition_track(tpl, snr)
        for k in range(r, min(r + 8, n)):
            read = sample_read(rng, tpl, track)[:imax]
            reads[k, :len(read)], rlens[k] = read, len(read)
            tpls[k, :tpl_len] = tpl
            tables[k] = snr_to_transition_table_host(snr)
    tlens = jnp.full(n, tpl_len, jnp.int32)
    tpls = jnp.asarray(tpls)
    trans = jax.jit(jax.vmap(template_transition_params))(
        tpls, jnp.asarray(tables), tlens)
    fill = jax.jit(fill_alpha_beta_batch,
                   static_argnames=("width", "use_pallas"))
    inputs = (jnp.asarray(reads), jnp.asarray(rlens), tpls, trans, tlens)
    lls = {}
    for use_pallas in (True, False):
        t0 = time.monotonic()
        out = fill(*inputs, width=width, use_pallas=use_pallas)
        lls[use_pallas] = [np.asarray(out[2]), np.asarray(out[3])]
        first = time.monotonic() - t0
        t0 = time.monotonic()
        jax.block_until_ready(
            fill(*inputs, width=width, use_pallas=use_pallas))
        say(f"timing: fill {n}x{jmax}xW{width} use_pallas={use_pallas} on "
            f"{dev['platform']} {dev['kind']}: first call {first:.3f} s "
            f"(compile included), second {time.monotonic() - t0:.4f} s")
    worst = 0.0
    for name, got, ref in (("ll_a", lls[True][0], lls[False][0]),
                           ("ll_b", lls[True][1], lls[False][1])):
        check(bool(np.isfinite(got).all() and np.isfinite(ref).all()),
              f"kernel check: {name} is not finite")
        rel = float(np.max(np.abs(got - ref) / np.abs(ref)))
        worst = max(worst, rel)
        check(rel <= KERNEL_LL_RTOL,
              f"kernel check: Pallas {name} is {rel:.3g} (relative) from "
              f"the pure-JAX fill; the tolerance is {KERNEL_LL_RTOL}")
    say(f"kernel check: Pallas fill agrees with the pure-JAX fill on {n} "
        f"reads, worst relative difference {worst:.3g} "
        f"(tolerance {KERNEL_LL_RTOL}), mean ll {lls[True][0].mean():.1f}")


def score_grid_check(seed: int, rehearse: bool, dev: dict) -> None:
    """The score grid between the dense kernel and the (Z, M) totals, on
    the device: the slot-major splice and the totals kernel (a reversal
    and a dynamic rotate along the lanes, the masks, the baselines, the
    written-out reduction over reads) against the NumPy transcription of
    the gather formulation with NumPy's float32 adds in the same order,
    bit for bit, at the cells' shapes.  (A `reverse` of read windows read
    wrong on the chip only: PR 28.)"""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pbccs_tpu.ops import dense_score_pallas as dsp

    sys.path.insert(0, os.path.join(HERE, "tests"))
    try:   # the reference lives with the parity tests
        from test_dense_score import (_random_windows, _slot_planes,
                                      splice_reference, totals_reference)
    finally:
        sys.path.pop(0)

    @jax.jit
    def on_device(grid, e6, wl, strand, ts, te, live, base, valid, *planes):
        return dsp.slot_grid_totals(
            dsp.slot_major_spliced(grid, e6, wl), strand, ts, te, live, base,
            jnp.swapaxes(valid, 1, 2), *planes)

    for z, r, jm in sizes_for(rehearse)["score_grids"]:
        n = z * r
        rng = np.random.default_rng([seed, n, jm])
        grid = (rng.normal(size=(n, jm, 9)) * 40).astype(np.float32)
        e6 = (rng.normal(size=(n, 6, 9)) * 40).astype(np.float32)
        base = (rng.normal(size=n) * 40).astype(np.float32)
        strand, ts, te = _random_windows(rng, n, jm, jm)
        wl = np.clip(te - ts, 0, jm)
        live = rng.random(n) > 0.2
        valid = rng.random((z, jm, 9)) > 0.3
        t0 = time.monotonic()
        got = np.asarray(on_device(*map(jnp.asarray, (
            grid, e6, wl, strand, ts, te, live, base, valid)
            + _slot_planes(jm)))).transpose(0, 2, 1)
        wall = time.monotonic() - t0
        want = totals_reference(
            np.stack([splice_reference(grid[k], e6[k], wl[k])
                      for k in range(n)]), strand, ts, te, live, base, valid)
        bad = np.argwhere(got != want)
        check(bad.size == 0,
              f"score grid check {z}x{r}x{jm}: {len(bad)} of {want.size} "
              f"totals differ from the gather reference, the first at "
              f"(ZMW, position, slot) {bad[:1].tolist()}")
        say(f"score grid check: {z}x{r}x{jm} on {dev['platform']}: "
            f"{want.size} totals over {n} reads equal the reference bit "
            f"for bit ({wall:.1f} s, compile included)")


def phase_device(args) -> dict:
    """One chip: set-up, the kernel and score grid checks, then the batch
    CLI cold and warm."""
    sizes = sizes_for(args.rehearse)
    dev = device_facts(args.rehearse)
    kernel_check(args.seed, args.rehearse, dev)
    score_grid_check(args.seed, args.rehearse, dev)
    cold = run_cli_once(args.workdir, "batch_cold", sizes["n_zmws"], [], dev)
    warm = run_cli_once(args.workdir, "batch_warm", sizes["n_zmws"], [], dev)
    for key in ("bam", "report"):
        check_same_bytes(cold[key], warm[key],
                         "the same input gave two outputs")
    say(f"timing: batch phase on {dev['platform']} {dev['kind']}: set-up "
        f"(first run less steady run, compile and cache load) "
        f"{cold['wall_s'] - warm['wall_s']:.3f} s; steady "
        f"{warm['wall_s']:.3f} s, {sizes['n_zmws'] / warm['wall_s']:.3f} "
        "ZMW/s from subread BAM to written BAM + report")
    return {"device": dev, "bam": warm["bam"], "report": warm["report"]}


def phase_fleet(args) -> dict:
    """Four chips visible: one batch-CLI run at --devices N."""
    sizes = sizes_for(args.rehearse)
    dev = device_facts(args.rehearse)
    check(dev["count"] == 4, f"{dev['count']} devices are visible, not 4")
    # four batches, one for each device: 64 ZMWs, the CLI's default
    cli_args = ["--devices", str(args.devices),
                "--chunkSize", str(sizes["n_zmws"] // 4)]
    if args.devices > 1:
        cli_args += ["--prepareWorkers", str(args.devices)]
    tag = f"fleet_dev{args.devices}"
    run = run_cli_once(args.workdir, tag, sizes["n_zmws"], cli_args, dev)
    tasks = run["counters"]["ccs_sched_tasks_total"]
    if args.devices > 1:
        say(f"fleet: batches executed by device: {json.dumps(tasks)}")
        check(len(tasks) == args.devices and all(tasks.values()),
              f"not every one of {args.devices} devices executed a batch: "
              f"{tasks}")
    return {"device": dev, "bam": run["bam"], "report": run["report"]}


# -------------------------------------------------------- the serve phase

def serve_phase(args, zmws: list, truth: dict, dev: dict) -> None:
    """`ccs serve` as its own process (the only one on the chip), this
    parent its client.  The server declares its deployment (`--bucket`),
    so its ready line comes once the programs are loaded and names them;
    the first wave then loads nothing."""
    from pbccs_tpu.obs.metrics import parse_exposition
    from pbccs_tpu.pipeline import Chunk, Subread
    from pbccs_tpu.serve.client import CcsClient

    sizes = sizes_for(args.rehearse)
    n = sizes["serve_zmws"]
    chunks = [Chunk(f"{MOVIE}/{z}",
                    [Subread(f"{MOVIE}/{z}/{i}", r)
                     for i, r in enumerate(reads)], snr)
              for z, _tpl, reads, snr in zmws[:n]]
    log_path = os.path.join(args.workdir, "serve.log")
    t_start = time.monotonic()
    with open(log_path, "w") as log:
        # the perf ledger is how the server names the platform IT runs
        # on: its records carry it, and the status verb the newest record
        proc = subprocess.Popen(
            [sys.executable, "-m", "pbccs_tpu.cli", "serve", "--port", "0",
             "--perfLedger", os.path.join(args.workdir, "serve_perf.ndjson"),
             "--perfLedgerInterval", str(SERVE_LEDGER_INTERVAL_S),
             *sizes["serve_args"]],
            stdout=subprocess.PIPE, stderr=log, text=True,
            env=child_env(args.rehearse), cwd=HERE)
    timer = threading.Timer(CHILD_TIMEOUT_S, proc.kill)
    timer.start()
    try:
        port = None
        for line in proc.stdout:
            m = re.search(r"CCS-SERVE-READY \S+ (\d+)(.*)", line)
            if m:
                port, warmed = int(m.group(1)), m.group(2).strip()
                break
        check(port is not None, "ccs serve never printed its ready line")
        check(re.search(r"shape_sets=[1-9]", warmed) is not None,
              f"the ready line names no warmed shape set: {warmed!r}")
        # keep the pipe drained so the server never blocks on its stdout
        tail: list[str] = []
        drain = threading.Thread(
            target=lambda: tail.extend(proc.stdout), daemon=True)
        drain.start()
        ready_s = time.monotonic() - t_start

        def wave(tag: str) -> float:
            """All n ZMWs from SERVE_SESSIONS concurrent sessions; the
            clock closes when the last reply has been read."""
            def session(k: int) -> list:
                with CcsClient("127.0.0.1", port) as client:
                    return [client.submit_with_retry(c, deadline_ms=900_000,
                                                     reply_timeout=900.0)
                            for c in chunks[k::SERVE_SESSIONS]]

            t0 = time.monotonic()
            with concurrent.futures.ThreadPoolExecutor(SERVE_SESSIONS) as ex:
                replies = [r for rs in ex.map(session, range(SERVE_SESSIONS))
                           for r in rs]
            wall = time.monotonic() - t0
            check(len(replies) == n, f"{tag}: {len(replies)} of {n} replies")
            for r in replies:
                check(r.get("type") == "result" and not r.get("draft_only"),
                      f"{tag}: ZMW {r.get('zmw')} came back "
                      f"{ {k: r.get(k) for k in ('type', 'status', 'code', 'error', 'draft_only')} }")
            check(len({r["zmw"] for r in replies}) == n,
                  f"{tag}: replies do not cover {n} distinct ZMWs")
            good = [r for r in replies if r["status"] == "Success"]
            check_yield(tag, n, len(good),
                        sum(r["status"] == "Other" for r in replies))
            for r in good:
                check_consensus(tag, int(r["zmw"].split("/")[1]),
                                r["sequence"], r["qual"],
                                float(r["predicted_accuracy"]), truth)
            return wall

        cold = wave("serve_cold")
        warm = wave("serve_warm")
        with CcsClient("127.0.0.1", port) as client:
            status = client.status()
            metrics = parse_exposition(client.metrics())
        check(status.get("completed") == 2 * n and status.get("errors") == 0,
              f"serve status: completed={status.get('completed')} "
              f"errors={status.get('errors')}, expected {2 * n} and 0")
        for name in ("ccs_zmw_failures_total", "ccs_quarantined_zmws_total",
                     "ccs_degraded_zmws_total"):
            moved = {k: v for k, v in metrics.items() if k[0] == name and v}
            check(not moved, f"serve: {name} moved: {moved}")
        # a snapshot is due every SERVE_LEDGER_INTERVAL_S: wait for one
        deadline = time.monotonic() + 6 * SERVE_LEDGER_INTERVAL_S
        while not (status.get("perf") or {}).get("last_record") \
                and time.monotonic() < deadline:
            time.sleep(0.5)
            with CcsClient("127.0.0.1", port) as client:
                status = client.status()
        record = (status.get("perf") or {}).get("last_record") or {}
        serve_platform = record.get("platform")
        say(f"serve status: completed={status['completed']} errors=0 "
            f"device_fetches={status.get('device_fetches')} "
            f"platform={serve_platform} (its own perf-ledger record)")
        check(serve_platform == ("cpu" if args.rehearse else "tpu"),
              f"ccs serve ran on {serve_platform!r}: its newest perf-ledger "
              f"record is {record}")
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        drain.join(timeout=10)
        check(rc == 0, f"ccs serve exited {rc} after SIGTERM")
        check(any("CCS-SERVE-DRAINING" in line for line in tail),
              "ccs serve did not announce its drain")
    except BaseException:
        with open(log_path) as f:
            sys.stderr.write("---- ccs serve log tail ----\n"
                             + f.read()[-6000:] + "\n")
        raise
    finally:
        timer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    say(f"timing: serve phase on {serve_platform} (the batch child saw "
        f"{dev['kind']}): ready after {ready_s:.3f} s, its programs loaded "
        f"({warmed}); first wave of {n} ZMWs {cold:.3f} s (nothing left to "
        f"compile or load); steady wave {warm:.3f} s, "
        f"{n / warm:.3f} ZMW/s from {SERVE_SESSIONS} sessions; "
        "SIGTERM drained, exit 0")


# ---------------------------------------------------------------- parent

def parent(args) -> dict:
    sizes = sizes_for(args.rehearse)
    t0 = time.monotonic()
    # the library on disk may have been built for another machine's CPU
    # (-march=native); build it here before anything loads it
    make = subprocess.run(["make", "-B", "-C", os.path.join(HERE, "native")],
                          capture_output=True, text=True)
    check(make.returncode == 0,
          f"native library build failed:\n{make.stderr[-2000:]}")
    say(f"setup: native library rebuilt in {time.monotonic() - t0:.1f} s")

    t0 = time.monotonic()
    zmws = make_zmws(args.seed, sizes)
    truth = {z: tpl for z, tpl, _reads, _snr in zmws}
    write_subread_bam(os.path.join(args.workdir, "subreads.bam"), zmws)
    say(f"setup: {len(zmws)} ZMWs x {sizes['tpl_len']} bp x "
        f"{sizes['passes'][0]}-{sizes['passes'][1]} passes "
        f"({sum(len(r) for _, _, r, _ in zmws)} subreads) from seed "
        f"{args.seed} in {time.monotonic() - t0:.1f} s (host)")

    if args.chips == 4:
        many = run_child("fleet", args, ["--devices", "4"])
        one = run_child("fleet", args, ["--devices", "1"])
        for key in ("bam", "report"):
            check_same_bytes(many[key], one[key],
                             "--devices 4 against --devices 1")
        say("fleet: --devices 4 and --devices 1 wrote byte-identical BAM "
            "and report")
        res = check_bam("fleet", many["bam"], many["report"], truth)
        say(f"fleet: truth check {json.dumps(res)}")
        check(many["device"] == one["device"], "the two runs saw two devices")
        return many["device"]

    batch = run_child("device", args, [])
    res = check_bam("batch", batch["bam"], batch["report"], truth)
    say(f"batch: truth check {json.dumps(res)}")
    serve_phase(args, zmws, truth, batch["device"])
    return batch["device"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260927)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the --devices 4 against --devices 1 "
                         "batch comparison, on a host with four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="no chip: tiny sizes, interpreted kernels, CPU")
    ap.add_argument("--phase", choices=("device", "fleet"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--workdir", help=argparse.SUPPRESS)
    ap.add_argument("--devices", type=int, default=1, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.phase:
        try:
            result = {"device": phase_device, "fleet": phase_fleet}[
                args.phase](args)
        except SmokeFailure as e:
            say(f"FAILED: {e}")
            return 1
        say(RESULT_TAG + json.dumps(result))
        return 0

    if args.rehearse and args.chips == 4:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
    args.workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    t0 = time.monotonic()
    try:
        dev = parent(args)
        import jax._src.xla_bridge as xb

        check(not xb._backends,
              "this parent initialised a JAX backend; a child needs the chip")
    except SmokeFailure as e:
        say(f"FAILED: {e}")
        return 1
    finally:
        shutil.rmtree(args.workdir, ignore_errors=True)
    say(f"timing: chip_smoke whole run {time.monotonic() - t0:.1f} s")
    last = {"ok": True, "device": dev}
    if args.rehearse:
        last["rehearsal"] = True
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
