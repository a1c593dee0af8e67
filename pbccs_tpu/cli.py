"""`ccs`-equivalent command line driver.

    python -m pbccs_tpu.cli [OPTIONS] OUTPUT FILES...

Reads subreads from BAM (PacBio conventions) or FASTA (records named
movie/zmw[/s_e], grouped by ZMW), runs the consensus pipeline through the
scheduled driver (pbccs_tpu.sched: host drafts ahead of the device, results
in reading order), and writes a CCS BAM plus a CSV yield report.  Flags,
defaults, CLI-level filters (whitelist, chemistry, SNR,
read score, pass count) and output tags mirror the reference driver
(reference src/main/ccs.cpp:284-519).
"""

from __future__ import annotations

import argparse
import math
import os
import sys

import numpy as np

from pbccs_tpu import __version__
from pbccs_tpu.io.bam import (
    BamDecodeError,
    BamHeader,
    BamReader,
    BamRecord,
    BamWriter,
    ReadGroupInfo,
    make_read_group_id,
)
from pbccs_tpu.io.fasta import flatten_fofn, read_fasta
from pbccs_tpu.io.report import write_report_file as write_results_report_file
from pbccs_tpu.models.arrow.params import encode_bases
from pbccs_tpu.obs.metrics import default_registry
from pbccs_tpu.pipeline import (
    Chunk,
    ConsensusSettings,
    Failure,
    ResultTally,
    Subread,
)
from pbccs_tpu.runtime.chemistry import verify_chemistry
from pbccs_tpu.runtime.logging import (
    Logger,
    LogLevel,
    dump_stacks_on_crash,
    install_signal_handlers,
)
from pbccs_tpu.runtime.whitelist import Whitelist

DESCRIPTION = ("Generate circular consensus sequences (ccs) from subreads "
               "-- TPU-native implementation.")

FASTA_EXTS = (".fa", ".fasta", ".fsa", ".fa.gz", ".fasta.gz", ".fsa.gz")


def add_consensus_args(p: argparse.ArgumentParser) -> None:
    """The consensus-gate flags shared verbatim by `ccs` and `ccs serve`
    (serve.server.build_serve_parser): one definition, one set of
    defaults, so the two drivers cannot desynchronize."""
    p.add_argument("--minSnr", type=float, default=4.0,
                   help="Minimum SNR of input subreads. Default = %(default)s")
    p.add_argument("--minReadScore", type=float, default=0.75,
                   help="Minimum read score of input subreads. Default = %(default)s")
    p.add_argument("--minLength", type=int, default=10,
                   help="Minimum length of subreads. Default = %(default)s")
    p.add_argument("--minPasses", type=int, default=3,
                   help="Minimum number of subreads required. Default = %(default)s")
    p.add_argument("--minPredictedAccuracy", type=float, default=0.90,
                   help="Minimum predicted accuracy. Default = %(default)s")
    p.add_argument("--minZScore", type=float, default=-5.0,
                   help="Minimum subread z-score; NaN disables. Default = %(default)s")
    p.add_argument("--maxDropFraction", type=float, default=0.34,
                   help="Maximum fraction of droppable subreads. Default = %(default)s")
    p.add_argument("--model", choices=("arrow", "quiver"), default="arrow",
                   help="Polish model family (default: arrow, the ccs "
                        "model; quiver is the QV-feature model -- reads "
                        "without QV tracks use flat default tracks).")
    p.add_argument("--degradeQuarantined", action="store_true",
                   help="Emit quarantined poison ZMWs (batch AND serial "
                        "polish failed) as draft-only consensus with a "
                        "`df` tag and capped QVs instead of dropping "
                        "them as Other.")


def add_resilience_args(p: argparse.ArgumentParser) -> None:
    """Fault-handling knobs shared by `ccs` and `ccs serve`."""
    p.add_argument("--faults", default=None, metavar="SPEC",
                   help="Arm deterministic fault injection (chaos "
                        "testing), e.g. 'polish.dispatch:error~m/3'. "
                        "See pbccs_tpu/resilience/faults.py for the "
                        "grammar; PBCCS_FAULTS is the env equivalent.")
    p.add_argument("--faultSeed", type=int, default=0,
                   help="Seed for probabilistic fault specs. "
                        "Default = %(default)s")
    p.add_argument("--polishTimeout", type=float, default=None,
                   metavar="SECONDS",
                   help="Watchdog deadline per device dispatch: a hung "
                        "polish becomes a structured timeout and the "
                        "affected ZMWs quarantine instead of stalling "
                        "the run (default: PBCCS_WATCHDOG_S, else off).")


def apply_resilience_args(args) -> None:
    from pbccs_tpu.resilience import faults, watchdog

    if args.faults is not None:
        faults.configure(args.faults, seed=args.faultSeed)
    if args.polishTimeout is not None:
        watchdog.configure(args.polishTimeout)


def consensus_settings_from_args(args) -> ConsensusSettings:
    return ConsensusSettings(
        min_length=args.minLength,
        min_passes=args.minPasses,
        min_snr=args.minSnr,
        min_predicted_accuracy=args.minPredictedAccuracy,
        min_zscore=args.minZScore,
        max_drop_fraction=args.maxDropFraction,
        model=args.model,
        degrade_quarantined=args.degradeQuarantined)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ccs", description=DESCRIPTION,
        epilog="`ccs serve [OPTIONS]` starts the long-lived online serving "
               "engine instead, and `ccs router [OPTIONS]` the "
               "multi-replica front door over N serve processes; both "
               "take --tlsCert/--tlsKey/--authTokens for a TLS + "
               "token-authenticated multi-tenant edge (see "
               "`ccs serve --help` / `ccs router --help`).")
    p.add_argument("--version", action="version", version=__version__)
    p.add_argument("--zmws", default="all",
                   help="ZMWs to process: all, or ranges like 1-3,5 or "
                        "movie:1-3,5;movie2:*. Default = %(default)s")
    add_consensus_args(p)
    p.add_argument("--numThreads", type=int, default=0,
                   help="Host threads of the prepare (POA draft) pool "
                        "(0 = auto); the reference's spelling of "
                        "--prepareWorkers, which wins where both are "
                        "given. Default = %(default)s")
    p.add_argument("--chunkSize", type=int, default=64,
                   help="ZMWs per work item; each work item polishes as one "
                        "lockstep device batch. Default = %(default)s")
    p.add_argument("--devices", type=int, default=1,
                   help="Devices to polish on (pbccs_tpu.sched, one "
                        "executor thread each): the first N visible "
                        "devices, 0 = all of them.  The output is "
                        "byte-identical at every count. "
                        "Default = %(default)s")
    p.add_argument("--prepareWorkers", type=int, default=0,
                   help="Host prepare (POA draft) threads: each batch "
                        "is dealt over all of them, in reading order, "
                        "so drafts run ahead of the device polishes "
                        "in flight (0 = auto: --numThreads, a tuned "
                        "profile, else 2 to 4 by core count). "
                        "Default = %(default)s")
    p.add_argument("--schedPolicy", choices=("sticky", "least", "roundrobin"),
                   default="sticky",
                   help="Device-fleet routing: sticky keeps a compiled-"
                        "shape bucket on the device that already compiled "
                        "it (least-loaded otherwise). "
                        "Default = %(default)s")
    p.add_argument("--logFile", default=None, help="Log to a file vs stderr.")
    p.add_argument("--logLevel", default="INFO",
                   help="TRACE..FATAL. Default = %(default)s")
    p.add_argument("--trace-out", dest="trace_out", default=None,
                   metavar="FILE",
                   help="Write a Chrome-trace/Perfetto JSON of per-ZMW "
                        "spans (filter/draft/polish/emit, wall vs "
                        "device-wait) to FILE.")
    p.add_argument("--profile-dir", dest="profile_dir", default=None,
                   metavar="DIR",
                   help="Capture a jax.profiler trace of the run into DIR "
                        "(TensorBoard/XProf format).")
    p.add_argument("--reportFile", default="ccs_report.csv",
                   help="Where to write the yield report. Default = %(default)s")
    p.add_argument("--perfLedger", default=None, metavar="PATH",
                   help="Append one schema-versioned NDJSON performance "
                        "record for this run (obs/ledger.py) to PATH: "
                        "compile/refine/padding counters, wall time, "
                        "peak RSS, governor interventions -- the record "
                        "tools/perf_gate.py defends baselines against. "
                        "Default: off.")
    p.add_argument("--tuneProfile", default=None, metavar="PATH|auto",
                   help="Apply a `ccs tune` host profile: tuned knob "
                        "defaults (band width, prepare workers, memory "
                        "budget, ...) resolved as explicit flag/env > "
                        "profile > hand-tuned constants.  `auto` scans "
                        "the committed profiles/ directory for this "
                        "host's fingerprint; a mismatched or corrupt "
                        "profile degrades to defaults with a note "
                        "(PBCCS_TUNE_PROFILE is the env equivalent). "
                        "Default: off.")
    p.add_argument("--checkpoint", default=None, metavar="FILE",
                   help="Journal completed chunks to FILE (NDJSON) so a "
                        "killed run can restart with --resume. Default: "
                        "off (--resume implies OUTPUT.ckpt).")
    p.add_argument("--resume", action="store_true",
                   help="Restore completed chunks from the checkpoint "
                        "journal and compute only the rest; the final "
                        "tally and output are identical to an "
                        "uninterrupted run.")
    p.add_argument("--memBudget", default=None, metavar="SIZE",
                   help="Host-memory budget for the prepared-batch "
                        "backlog, e.g. 8G or "
                        "512M: the prepare pool throttles (visible as "
                        "ccs_resource_throttles_total, never a crash) "
                        "while prepared-batch bytes in flight would "
                        "exceed it.  Default: unbounded.")
    p.add_argument("--batchFallback", choices=("bisect", "serial"),
                   default="bisect",
                   help="Recovery when a lockstep polish batch fails: "
                        "bisect isolates the poison ZMW(s) in O(k log Z) "
                        "re-dispatches; serial re-runs the whole batch "
                        "per-ZMW (legacy). Default = %(default)s")
    add_resilience_args(p)
    p.add_argument("--decodePolicy", choices=("strict", "lenient", "salvage"),
                   default="strict",
                   help="BAM corruption handling: strict aborts on the "
                        "first corrupt byte (reference behavior); lenient "
                        "skips bad records and counts them; salvage "
                        "additionally resyncs past corrupt BGZF blocks so "
                        "one flipped bit costs <=64 KiB of input, not the "
                        "cell. Default = %(default)s")
    p.add_argument("--skipChemistryCheck", action="store_true",
                   help="Accept non-P6-C4 read groups (required for FASTA "
                        "input, which carries no chemistry metadata).")
    p.add_argument("output", help="Output BAM (or FASTA) path")
    p.add_argument("files", nargs="+", help="Input subread BAM/FASTA/FOFN files")
    return p


# ZMWs the reader turns away before any draft, by the gate that did it
# (they are tallied in the yield report like every other outcome)
_reg = default_registry()
_m_gated = {gate: _reg.counter(
    "ccs_reader_gated_zmws_total",
    "ZMWs the batch CLI's reader gates turned away before drafting "
    "(--minSnr, --minReadScore, --minPasses)", gate=gate)
    for gate in ("snr", "read_score", "passes")}


def _iter_fasta_chunks(path: str, log: Logger):
    """Group FASTA records named movie/zmw[/s_e] into per-ZMW chunks."""
    current: Chunk | None = None
    for name, seq in read_fasta(path):
        parts = name.split("/")
        try:
            movie, zmw = parts[0], int(parts[1])
        except (IndexError, ValueError):
            log.warn(f"skipping read {name}: name is not movie/zmw[/s_e]")
            continue
        zid = f"{movie}/{zmw}"
        if current is None or current.id != zid:
            if current is not None:
                yield current, None
            current = Chunk(zid, [], np.full(4, 8.0))
        current.reads.append(Subread.from_str(name, seq))
    if current is not None:
        yield current, None


def _iter_bam_chunks(path: str, log: Logger, policy: str = "strict"):
    """Group BAM subread records into per-ZMW chunks.

    Yields (chunk, read_group) so the caller can apply the chemistry gate."""
    reader = BamReader(path, policy=policy)
    rgs = {rg.id: rg for rg in reader.header.read_groups}
    current: Chunk | None = None
    current_rg: ReadGroupInfo | None = None
    for rec in reader:
        parts = rec.name.split("/")
        if len(parts) < 2:
            log.warn(f"skipping read {rec.name}: bad name")
            continue
        movie = parts[0]
        try:
            hole = int(rec.tags.get("zm", parts[1]))
        except (TypeError, ValueError):
            log.warn(f"skipping read {rec.name}: no usable ZMW number")
            continue
        zid = f"{movie}/{hole}"
        if current is None or current.id != zid:
            if current is not None:
                yield current, current_rg
            try:
                snr = np.asarray(rec.tags.get("sn", [8.0] * 4), np.float64)
            except (TypeError, ValueError):
                # validate_chunk downstream rejects the bad shape; here
                # only the crash matters (a string `sn` must not abort
                # a lenient run)
                snr = np.full(4, np.nan)
            current = Chunk(zid, [], snr)
            rg_id = rec.tags.get("RG", "")
            current_rg = rgs.get(rg_id)
        try:
            flags = int(rec.tags.get("cx", 3))
            accuracy = float(rec.tags.get("rq", 0.8))
        except (TypeError, ValueError) as e:
            # structurally valid record, semantically garbage tag values
            # (e.g. cx as a string): degrade the record, never the run
            if policy == "strict":
                raise BamDecodeError(
                    "bad_tag_value",
                    f"{rec.name}: cx/rq tag not numeric: {e}") from None
            # count through reader.stats so the end-of-file rejection
            # summary below includes these skips too
            reader.stats.count("bad_tag_value")
            log.warn(f"skipping read {rec.name}: cx/rq tag not numeric "
                     "[reason=bad_tag_value]")
            continue
        current.reads.append(Subread(rec.name, encode_bases(rec.seq),
                                     flags=flags, read_accuracy=accuracy))
    reader.close()
    stats = reader.stats
    if stats.total_invalid or stats.bytes_lost:
        by_reason = ", ".join(f"{k}={v}" for k, v
                              in sorted(stats.invalid_records.items()))
        log.warn(f"{path}: decode policy '{policy}' rejected "
                 f"{stats.total_invalid} record(s)/block(s) [{by_reason}], "
                 f"salvaged {stats.salvaged_blocks} block resync(s), "
                 f"{stats.bytes_lost} byte(s) lost"
                 + (" (input truncated mid-stream; pair with --resume "
                    "after re-fetching)" if stats.truncated else ""))
    if current is not None:
        yield current, current_rg


def _chunks_from_files(files, whitelist: Whitelist, args, log,
                       tally: ResultTally):
    """Apply CLI-level gates and yield batches of chunks."""
    from pbccs_tpu.io.validate import ChunkValidationError, validate_chunk

    batch: list[Chunk] = []
    for path in files:
        is_fasta = any(path.endswith(e) for e in FASTA_EXTS)
        it = (_iter_fasta_chunks(path, log) if is_fasta
              else _iter_bam_chunks(path, log, policy=args.decodePolicy))
        for chunk, rg in it:
            movie, hole_s = chunk.id.split("/")[:2]
            hole = int(hole_s)
            if not whitelist.contains(movie, hole):
                continue
            try:
                # the shared input contract (io.validate): the serve
                # front door rejects the same garbage with the same
                # reasons at `submit` (protocol.chunk_from_wire)
                validate_chunk(chunk)
            except ChunkValidationError as e:
                log.warn(f"rejecting ZMW {chunk.id}: {e} "
                         f"[reason={e.reason}]")
                continue
            if not args.skipChemistryCheck:
                if rg is None or not verify_chemistry(rg):
                    log.notice(f"Skipping ZMW {chunk.id}, invalid chemistry "
                               "(not P6/C4)")
                    continue
            if float(np.min(chunk.snr)) < args.minSnr:
                log.debug(f"Skipping ZMW {chunk.id}, fails SNR threshold")
                tally.tally(Failure.POOR_SNR)
                _m_gated["snr"].inc()
                continue
            n_reads = len(chunk.reads)
            chunk.reads = [r for r in chunk.reads
                           if r.read_accuracy >= args.minReadScore]
            if len(chunk.reads) < args.minPasses:
                log.debug(f"Skipping ZMW {chunk.id}, insufficient passes")
                tally.tally(Failure.TOO_FEW_PASSES)
                # the read-score gate's where it took the reads that
                # --minPasses then missed
                _m_gated["passes" if n_reads < args.minPasses
                         else "read_score"].inc()
                continue
            batch.append(chunk)
            if len(batch) >= args.chunkSize:
                yield batch
                batch = []
    if batch:
        yield batch


def run(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] == "serve":
        # `ccs serve`: the long-lived online engine (pbccs_tpu/serve/)
        from pbccs_tpu.serve.server import run_serve

        return run_serve(argv[1:])
    if argv and argv[0] == "router":
        # `ccs router`: multi-replica front door (pbccs_tpu/serve/router)
        from pbccs_tpu.serve.router import run_router

        return run_router(argv[1:])
    if argv and argv[0] == "fleet":
        # `ccs fleet`: self-healing supervised fleet (serve/supervisor)
        from pbccs_tpu.serve.supervisor import run_fleet

        return run_fleet(argv[1:])
    if argv and argv[0] == "warmup":
        # `ccs warmup`: precompile a declared bucket menu (pbccs_tpu/sched)
        from pbccs_tpu.sched.warmup import run_warmup

        return run_warmup(argv[1:])
    if argv and argv[0] == "tune":
        # `ccs tune`: ledger-driven autotuner (pbccs_tpu/tune)
        from pbccs_tpu.tune.cli import run_tune

        return run_tune(argv[1:])
    if argv and argv[0] == "analyze":
        # `ccs analyze`: project-native static analysis (pbccs_tpu/analysis)
        from pbccs_tpu.analysis.cli import run_analyze

        return run_analyze(argv[1:])
    if argv and argv[0] == "top":
        # `ccs top`: live fleet console over a router/serve endpoint
        from pbccs_tpu.obs.console import run_top

        return run_top(argv[1:])
    args = build_parser().parse_args(argv)
    apply_resilience_args(args)

    from pbccs_tpu.runtime.cache import enable_compilation_cache

    enable_compilation_cache()

    log = Logger.default(Logger(
        stream=open(args.logFile, "w") if args.logFile else sys.stderr,
        level=LogLevel.from_string(args.logLevel)))
    install_signal_handlers(log)
    dump_stacks_on_crash()

    from pbccs_tpu.runtime import tuning

    # opt-in tuned-knob resolution (runtime/tuning.py): explicit flag /
    # env still beats anything a profile carries
    tuning.configure(args.tuneProfile, logger=log)

    try:
        whitelist = Whitelist(args.zmws)
    except ValueError as e:
        print(f"option --zmws: invalid specification: {e}", file=sys.stderr)
        return 2

    if args.devices < 0:
        print(f"option --devices: must be >= 0, got {args.devices}",
              file=sys.stderr)
        return 2

    if args.memBudget is not None:
        from pbccs_tpu.resilience.resources import parse_size

        try:
            args.memBudget = parse_size(args.memBudget)
            if args.memBudget < 1:
                # '0' / '0.5' parse but HostBudget would reject them
                # mid-run; surface the usage error before reading input
                raise ValueError(
                    f"must be >= 1 byte, got {args.memBudget}")
        except ValueError as e:
            print(f"option --memBudget: {e}", file=sys.stderr)
            return 2
    else:
        # resolution ladder: no explicit --memBudget, so a tuned
        # profile's byte budget (already stored in bytes) applies
        args.memBudget = tuning.knob_int("mem_budget_bytes")

    settings = consensus_settings_from_args(args)

    files = flatten_fofn(args.files)
    for f in files:
        if not os.path.exists(f):
            print(f"input file does not exist: {f}", file=sys.stderr)
            return 2

    from pbccs_tpu.obs import profiling
    from pbccs_tpu.obs import trace as obs_trace
    from pbccs_tpu.runtime import timing

    # end-of-run observability: a measurement window over this run (the
    # summary table below reports its deltas) plus the opt-in capture
    # surfaces (--trace-out spans, --profile-dir jax profiler)
    run_window = timing.window()
    tracer = None
    if args.trace_out:
        tracer = obs_trace.Tracer()
        if not obs_trace.install_tracer(tracer):  # CAS: never hijack a
            # capture another owner (e.g. an in-process serve engine)
            # already has running
            log.warn("--trace-out ignored: another span capture is "
                     "already running in this process")
            tracer = None
    from pbccs_tpu.resilience.resources import OutputWriteError

    import time as time_mod

    t_run0 = time_mod.monotonic()
    tally = None
    try:
        with profiling.profile_capture(args.profile_dir), \
                obs_trace.span("run", threads=_prepare_workers(args),
                               cpus=os.cpu_count(),
                               chunk_size=args.chunkSize,
                               devices=args.devices) as run_span:
            tally = _run_pipeline(args, files, whitelist, settings, log)
            if run_span is not None:
                run_span.args["zmws"] = tally.total
    except OutputWriteError as e:
        # a full disk is an OPERATIONAL failure, not a bug: report what
        # was durably written and how to resume, exit nonzero without a
        # traceback.  The checkpoint journal (if any) keeps every
        # completed chunk, so a rerun with --resume after freeing space
        # completes byte-identically.
        log.error(f"output failure: {e}")
        print(f"ccs: {e}\n"
              "ccs: free disk space and re-run (add --resume to restore "
              "completed chunks from the checkpoint journal)",
              file=sys.stderr)
        log.flush()
        return 1
    finally:
        if tracer is not None:
            obs_trace.clear_tracer(tracer)
            tracer.write_json(args.trace_out)
            log.info(f"trace spans written to {args.trace_out}")

    summary = default_registry().summary_table(run_window)
    log.info("run metrics:\n" + summary)
    if args.perfLedger:
        # one perf-ledger record per run: the registry deltas over this
        # run's window + what only the driver knows (wall, yield)
        from pbccs_tpu.obs.ledger import PerfLedger, run_record

        ledger = PerfLedger(args.perfLedger, logger=log)
        ledger.append(run_record(
            run_window, kind="batch_run", source="ccs",
            workload={"files": [os.path.basename(f) for f in files],
                      "chunk_size": args.chunkSize,
                      "devices": args.devices,
                      "model": args.model},
            wall_s=time_mod.monotonic() - t_run0,
            zmws=tally.total if tally is not None else None,
            results=len(tally.results) if tally is not None else None))
        ledger.close()
        log.info(f"perf ledger record appended to {args.perfLedger}")
    log.flush()
    return 0


def _prepare_workers(args) -> int:
    """Threads of the host prepare pool, at every device count: the
    explicit flags, then a tuned profile (the runtime/tuning.py
    resolution ladder), then 2 to 4 by core count.  Never under two,
    even on a 1-core host: the device thread blocks on the device with
    the GIL released for most of a polish, and drafting the NEXT batch
    in that wait is the reference's reader/worker/writer overlap
    (ccs.cpp:388-499) re-expressed for a device-bound polish stage.
    Not over four: a draft is native POA under Python glue, and past
    about four threads the glue queues on the interpreter lock, which
    the device thread's own Python has to share."""
    from pbccs_tpu.runtime import tuning

    return (args.prepareWorkers or args.numThreads
            or tuning.knob_int("prepare_workers")
            or max(2, min(4, os.cpu_count() or 1)))


def _run_pipeline(args, files, whitelist, settings, log) -> ResultTally:
    """The reader -> scheduled pipeline -> writer body of a CLI run (split
    from run() so the observability capture scopes wrap it)."""
    tally = ResultTally()

    # collect movie names for the output header
    movies: dict[str, ReadGroupInfo] = {}

    def writer_record(result) -> BamRecord:
        movie = result.id.split("/")[0]
        hole = int(result.id.split("/")[1])
        return BamRecord(
            name=f"{result.id}/ccs",
            seq=result.sequence,
            qual=result.qualities,
            tags={
                "RG": make_read_group_id(movie, "CCS"),
                "zm": hole,
                "np": int(result.num_passes),
                "rq": int(1000 * result.predicted_accuracy),
                "sn": [float(s) for s in result.snr],
                "pq": float(result.predicted_accuracy),
                "za": float(result.avg_zscore),
                "zs": [float(z) if math.isfinite(z) else 0.0
                       for z in result.zscores],
                "rs": [int(c) for c in result.status_counts],
                # draft-only degradation marker (resilience.quarantine):
                # the sequence is the unpolished POA draft, QVs capped
                **({"df": 1} if result.draft_only else {}),
            })

    to_fasta = any(args.output.endswith(e) for e in (".fa", ".fasta", ".fsa"))

    from pbccs_tpu.obs import trace as obs_trace
    from pbccs_tpu.runtime import timing

    # checkpoint journal: restore completed chunks (--resume) and record
    # each chunk as its results are consumed, in submission order, so a
    # killed run loses at most the in-flight chunks
    journal = None
    restored: dict[int, ResultTally] = {}
    ckpt_path = args.checkpoint or (args.output + ".ckpt"
                                    if args.resume else None)
    if ckpt_path:
        from pbccs_tpu.resilience.checkpoint import (
            CheckpointJournal,
            run_fingerprint,
        )

        # every knob that changes chunk COMPOSITION must fingerprint:
        # minReadScore filters reads and skipChemistryCheck drops ZMWs
        # before batching (the rest ride in via settings/files)
        fp = run_fingerprint(
            files, args.chunkSize, settings,
            extra={"zmws": args.zmws,
                   "min_read_score": args.minReadScore,
                   "skip_chemistry_check": bool(args.skipChemistryCheck)})
        journal = CheckpointJournal(ckpt_path, logger=log)
        if args.resume:
            restored = journal.load(fp)
            # output order must match an uninterrupted run: restored
            # chunks splice ahead of recomputed ones, so only a
            # CONTIGUOUS prefix is usable (a dropped mid-journal record
            # invalidates everything after it -- recomputed, not stale)
            k = 0
            while k in restored:
                k += 1
            if len(restored) > k:
                log.warn(f"resume: journal has a gap at chunk {k}; "
                         f"recomputing {len(restored) - k} chunk(s) "
                         "after it to preserve output order")
            restored = {i: t for i, t in restored.items() if i < k}
        journal.start(fp, resume=args.resume and bool(restored))

    def _read_batches(gate_tally: ResultTally):
        """The reader loop: stream (idx, batch) with read-stage timing
        and output-header movie registration.  It runs on the pipeline's
        feeder thread, so CLI-gate skips tally into `gate_tally`, merged
        after the run, and never race the main thread's result merges."""
        it = iter(_chunks_from_files(files, whitelist, args, log,
                                     gate_tally))
        idx = -1
        while True:
            with obs_trace.span("read") as read_span, timing.stage("read"):
                batch = next(it, None)
                if read_span is not None:
                    read_span.args["zmws"] = len(batch or ())
                    if batch is not None:
                        read_span.args["batch"] = idx + 1
            if batch is None:
                return
            idx += 1
            for chunk in batch:
                movie = chunk.id.split("/")[0]
                movies.setdefault(movie, ReadGroupInfo(movie, "CCS"))
            yield idx, batch

    # The scheduled driver (pbccs_tpu/sched), at every device count: host
    # prepare workers draft ahead of the device polishes in flight, ZMW by
    # ZMW in reading order, and batches fan out across the pool with
    # sticky bucket routing.  Batch composition is that of
    # pipeline.process_chunks (same --chunkSize groups) and a batch's
    # shapes are its own bucket's or a padded neighbour's (the shape
    # menu), so the output is byte-identical at any count.
    from pbccs_tpu.sched import DevicePool, DevicePoolConfig, select_devices
    from pbccs_tpu.sched.executor import ScheduledPipeline

    # --memBudget: byte-bound the prepared-batch backlog (prep pool +
    # parked results) so a full-cell stream cannot outrun the devices
    # into the OOM killer (resilience.resources.HostBudget)
    budget = None
    if args.memBudget is not None:
        from pbccs_tpu.resilience.resources import HostBudget

        budget = HostBudget(args.memBudget, logger=log)
    pool = DevicePool(select_devices(args.devices),
                      DevicePoolConfig(policy=args.schedPolicy), logger=log)
    pipe = ScheduledPipeline(pool, settings,
                             prepare_workers=_prepare_workers(args),
                             on_error=args.batchFallback,
                             budget=budget, logger=log,
                             chunk_zmws=args.chunkSize)

    # journal-restored chunks ride through the scheduler as precomputed
    # tallies so they merge at their index slot
    gate_tally = ResultTally()
    items = ((idx, batch, restored.get(idx))
             for idx, batch in _read_batches(gate_tally))
    try:
        for idx, sub_tally in pipe.run(items):
            tally.merge(sub_tally)
            if journal is not None and idx not in restored:
                journal.record_chunk(idx, sub_tally)
    except BaseException:
        # the run is already doomed: fail queued batches fast
        # (PoolClosed) instead of polishing minutes of device work
        # whose results nothing will consume
        pool.close(wait=False)
        raise
    pool.close()
    tally.merge(gate_tally)
    log.info(f"processed {tally.total} ZMWs: "
             f"{tally.counts[Failure.SUCCESS]} successes")

    if to_fasta:
        from pbccs_tpu.io.fasta import write_fasta
        with obs_trace.span("emit", results=len(tally.results)), \
                timing.stage("write"):
            write_fasta(args.output,
                        ((f"{r.id}/ccs", r.sequence) for r in tally.results))
    else:
        header = BamHeader(read_groups=list(movies.values()),
                           program_lines=[
                               f"@PG\tID:ccs-{__version__}\tPN:ccs\t"
                               f"VN:{__version__}"])
        # companion .pbi, as the reference's PbiBuilder does alongside the
        # output BAM (reference src/main/ccs.cpp:120, 380)
        from pbccs_tpu.io.pbi import PbiBuilder, read_group_numeric_id
        from pbccs_tpu.resilience.resources import OutputWriteError
        uposs = []
        with obs_trace.span("emit", results=len(tally.results)), \
                timing.stage("write"):
            with BamWriter(args.output, header) as bw:
                for result in tally.results:
                    uposs.append(bw.write(writer_record(result)))
                bw_handle = bw
            # PbiBuilder publishes atomically itself (tmp+fsync+rename
            # inside close(), OutputWriteError on ENOSPC) -- the same
            # contract as the BamWriter beside it
            pbi_path = args.output + ".pbi"
            with PbiBuilder(pbi_path) as pbi:
                for result, upos in zip(tally.results, uposs):
                    movie = result.id.split("/")[0]
                    hole = int(result.id.split("/")[1])
                    pbi.add_record(
                        read_group_numeric_id(
                            make_read_group_id(movie, "CCS")),
                        -1, -1, hole, result.predicted_accuracy, 0,
                        bw_handle.voffset(upos))

    write_results_report_file(args.reportFile, tally)
    if journal is not None:
        # only a run whose OUTPUTS landed needs no resume point: a
        # disk-full failure writing the BAM/report above keeps the
        # journal, so --resume restores every completed chunk and
        # re-emits byte-identically once space is freed.  (A later
        # --resume against fresh inputs still cannot splice stale
        # results -- the fingerprint refuses it.)
        journal.remove()
    return tally


def main() -> None:
    sys.exit(run())


if __name__ == "__main__":
    main()
