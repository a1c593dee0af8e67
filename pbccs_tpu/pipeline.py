"""Per-ZMW consensus pipeline: filter -> POA draft -> Arrow polish -> QV.

TPU re-design of the reference's per-ZMW orchestration
(reference include/pacbio/ccs/Consensus.h:224-555): the same stage boundaries
and yield gates, but the polish stage is a batched device program and the
whole pipeline is structured so batches of ZMWs can be bucketed and vmapped
(see pbccs_tpu.parallel for the sharded batch driver).

Failure accounting matches the reference's eight result categories
(reference include/pacbio/ccs/Consensus.h:155-208, src/main/ccs.cpp:233-262).
"""

from __future__ import annotations

import dataclasses
import enum
import threading
import time
import traceback
from typing import Sequence

import numpy as np

from pbccs_tpu.obs import trace as obs_trace
from pbccs_tpu.obs.metrics import default_registry
from pbccs_tpu.runtime.logging import Logger
from pbccs_tpu.models.arrow.params import decode_bases, encode_bases
from pbccs_tpu.models.arrow.refine import (
    RefineOptions,
    predicted_accuracy,
    refine_consensus,
)
from pbccs_tpu.models.arrow.scorer import (ADD_ALPHABETAMISMATCH, ADD_SUCCESS,
                                           ArrowMultiReadScorer)
from pbccs_tpu.poa.sparse import PoaAlignmentSummary, SparsePoa

# Local-context adapter flags (reference pbbam LocalContextFlags; a subread is
# a full pass iff it is flanked by adapter hits on both sides).
ADAPTER_BEFORE = 1
ADAPTER_AFTER = 2

_reg = default_registry()

# One batch on the device at a time among concurrent process_chunks
# callers: N threads polishing at once hold N batches' fills in HBM, and
# eight 64-ZMW batches at once ran a 16 GB chip out of memory (PR 23).
# The batch CLI's scheduled driver (one executor thread per device) and
# `ccs serve` (one polish worker) own the device by construction and do
# not come through process_chunks.
_polish_turn = threading.Lock()

# every entry into the shared batch-polish core (offline driver, sched
# executor, serve flush, quarantine/OOM sub-dispatches re-enter): the
# kernel-invocation count the perf ledger records and the regression
# sentinel gates as a CPU-deterministic counter
_m_polish_dispatches = _reg.counter(
    "ccs_polish_dispatches_total",
    "polish_prepared_batch dispatches (incl. sub-dispatch re-entries)")
# how much the wide-band retry holds: a retry polishes four ZMWs at a time
# (BatchPolisher.wide_band_subs), so more than four a batch run in turn
_m_band_retry = {kind: _reg.counter(
    "ccs_band_retry_total",
    "The 2x-band mating retry: batches that built one (batches), the ZMWs "
    "it held (zmws), the sub-batches it built for them (sub_batches), and "
    "the ZMWs that adopted the wide band (adopted)", kind=kind)
    for kind in ("batches", "zmws", "sub_batches", "adopted")}


def record_zmw_failure(stage: str, exc: BaseException,
                       zmw: str | None = None) -> None:
    """Account one swallowed per-ZMW/per-batch exception: the class +
    traceback go to the debug log and ccs_zmw_failures_total{stage,exc}
    increments -- a fault-isolation boundary must never also be an
    information sink (the pre-resilience handlers discarded both)."""
    _reg.counter("ccs_zmw_failures_total",
                 "Exceptions absorbed by per-ZMW fault isolation",
                 stage=stage, exc=type(exc).__name__).inc()
    where = f"{stage}[{zmw}]" if zmw else stage
    tb = "".join(traceback.format_exception(type(exc), exc,
                                            exc.__traceback__))
    Logger.default().debug(
        f"{where}: absorbed {type(exc).__name__}: {exc}\n{tb}")


@dataclasses.dataclass(frozen=True)
class ConsensusSettings:
    """Pipeline knobs, reference defaults
    (reference include/pacbio/ccs/Consensus.h:86-111)."""

    max_poa_coverage: int = 1024
    min_length: int = 10
    min_passes: int = 3
    min_snr: float = 4.0  # CLI-level gate in the reference (ccs.cpp:441)
    min_predicted_accuracy: float = 0.90
    min_zscore: float = -5.0
    max_drop_fraction: float = 0.34
    refine: RefineOptions = dataclasses.field(default_factory=RefineOptions)
    # polish model family: "arrow" (the ccs default) or "quiver" (the
    # QV-feature model; reference ConsensusCore carries both behind one
    # templated refine/QV implementation, Consensus.hpp:64-79).  Subreads
    # without QV tracks polish with flat default tracks.
    model: str = "arrow"
    # quarantined poison ZMWs (batch AND serial polish failed) emit a
    # draft-only consensus (capped QVs, `df` tag) instead of dropping as
    # Failure.OTHER (resilience.quarantine; off = reference parity)
    degrade_quarantined: bool = False


@dataclasses.dataclass
class Subread:
    """One subread of a ZMW (reference ReadType, Consensus.h:115-124)."""

    id: str
    seq: np.ndarray  # int8 base codes
    flags: int = ADAPTER_BEFORE | ADAPTER_AFTER
    read_accuracy: float = 0.8

    @classmethod
    def from_str(cls, id: str, seq: str, **kw) -> "Subread":
        return cls(id, encode_bases(seq), **kw)

    @property
    def is_full_pass(self) -> bool:
        return bool(self.flags & ADAPTER_BEFORE) and bool(self.flags & ADAPTER_AFTER)


@dataclasses.dataclass
class Chunk:
    """All subreads of one ZMW (reference ChunkType, Consensus.h:126-133)."""

    id: str
    reads: list[Subread]
    snr: np.ndarray  # (4,) per-channel SNR, ACGT order


class Failure(enum.Enum):
    """Yield categories (reference ResultType, Consensus.h:155-208)."""

    SUCCESS = "Success"
    POOR_SNR = "PoorSNR"
    NO_SUBREADS = "NoSubreads"
    TOO_SHORT = "TooShort"
    TOO_MANY_UNUSABLE = "TooManyUnusable"
    TOO_FEW_PASSES = "TooFewPasses"
    NON_CONVERGENT = "NonConvergent"
    POOR_QUALITY = "PoorQuality"
    OTHER = "Other"


@dataclasses.dataclass
class ConsensusResult:
    """One CCS read (reference ConsensusType, Consensus.h:135-153)."""

    id: str
    sequence: str
    qvs: np.ndarray
    num_passes: int
    predicted_accuracy: float
    global_zscore: float
    avg_zscore: float
    zscores: np.ndarray
    status_counts: list[int]
    mutations_tested: int
    mutations_applied: int
    snr: np.ndarray
    elapsed_ms: float
    # set by resilience.quarantine.degrade_to_draft: the sequence is the
    # unpolished POA draft with capped QVs (emitted with a `df` BAM tag)
    draft_only: bool = False

    @property
    def qualities(self) -> str:
        """Phred+33 ASCII, clamped to [0, 93]
        (reference QVsToASCII, Consensus.h:328-339)."""
        # one array pass: a Python loop over 2 kb of QVs is 0.7 ms a
        # result, and a served reply or a BAM record asks for this string
        # on the thread that completes a flush or writes a file
        return (np.clip(np.asarray(self.qvs), 0, 93).astype(np.uint8)
                + np.uint8(33)).tobytes().decode("ascii")


@dataclasses.dataclass
class ResultTally:
    """Mutable per-batch yield counters + results."""

    results: list[ConsensusResult] = dataclasses.field(default_factory=list)
    counts: dict[Failure, int] = dataclasses.field(
        default_factory=lambda: {f: 0 for f in Failure})

    def tally(self, failure: Failure) -> None:
        self.counts[failure] += 1

    @property
    def total(self) -> int:
        return sum(self.counts.values())

    def merge(self, other: "ResultTally") -> None:
        self.results.extend(other.results)
        for f, c in other.counts.items():
            self.counts[f] += c


def filter_reads(reads: Sequence[Subread], min_length: int
                 ) -> list[Subread | None]:
    """Median-length window filter + full-pass-first priority sort.

    Returns the reads (or None for dropped ones) sorted so that full-pass
    reads closest to the median length come first.  Parity: reference
    FilterReads (Consensus.h:224-292): median over full-pass lengths (else
    the longest read), drop reads >= 2*median, return nothing when the median
    itself is < min_length.
    """
    if not reads:
        return []

    lengths = [len(r.seq) for r in reads if r.is_full_pass]
    longest = max(len(r.seq) for r in reads)
    median = float(np.median(lengths)) if lengths else float(longest)
    max_len = 2.0 * median

    if median < float(min_length):
        return []

    def lex_key(r: Subread | None):
        if r is None:
            return (-1.0, -1.0)  # sorts last
        l = float(len(r.seq))
        v = min(l / median, median / l)
        return (v, 0.0) if r.is_full_pass else (0.0, v)

    # non-ACGT codes (N / pad) never match in the POA or the HMM and would
    # desync sequence vs QV lengths downstream; empty reads divide-by-zero
    # in the sort key; both are unusable
    kept: list[Subread | None] = [
        r if 0 < len(r.seq) < max_len and bool((r.seq < 4).all()) else None
        for r in reads]
    kept.sort(key=lex_key, reverse=True)
    return kept


def poa_consensus(reads: Sequence[Subread | None], max_poa_coverage: int
                  ) -> tuple[np.ndarray, list[int], list[PoaAlignmentSummary]]:
    """Draft consensus via sparse POA.

    Returns (consensus codes, per-read keys (-1 = unadded), summaries).
    Parity: reference PoaConsensus (Consensus.h:352-390) including the
    min-coverage equation minCov = 1 if cov < 5 else (cov+1)/2 - 1.
    """
    poa = SparsePoa()
    keys: list[int] = []
    cov = 0
    for r in reads:
        if r is None:
            keys.append(-1)
            continue
        key = poa.orient_and_add_read(r.seq)
        keys.append(key)
        if key >= 0:
            cov += 1
            if cov >= max_poa_coverage:
                break
    min_cov = 1 if cov < 5 else (cov + 1) // 2 - 1
    css, summaries = poa.find_consensus(min_cov)
    return css, keys, summaries


@dataclasses.dataclass
class MappedRead:
    """A subread clipped to its POA extents, oriented onto the draft
    (reference ExtractMappedRead, Consensus.h:296-325)."""

    id: str
    seq: np.ndarray
    strand: int  # 0 = forward, 1 = reverse-complemented
    tpl_start: int
    tpl_end: int
    is_full_pass: bool


def extract_mapped_read(read: Subread, summary: PoaAlignmentSummary,
                        min_length: int) -> MappedRead | None:
    rs, re_ = summary.extent_on_read
    ts, te = summary.extent_on_consensus
    if rs > re_ or re_ - rs < min_length:
        return None
    if summary.reverse_complemented:
        # extents are in oriented-read (revcomp) coordinates; the scorer
        # aligns the NATIVE read against the reverse-complement template
        # window tpl_r[L-te : L-ts], whose native-frame slice is below
        n = len(read.seq)
        seq = read.seq[n - re_: n - rs]
        strand = 1
    else:
        seq = read.seq[rs:re_]
        strand = 0
    return MappedRead(read.id, seq, strand, ts, te, read.is_full_pass)


@dataclasses.dataclass
class PreparedZmw:
    """One ZMW past the filter/draft/mapping stages, ready to polish."""

    chunk: Chunk
    css: np.ndarray
    mapped: list[MappedRead]
    n_candidates: int
    n_unmappable: int
    prep_ms: float


def prepare_chunk(chunk: Chunk, settings: ConsensusSettings
                  ) -> tuple[Failure | None, PreparedZmw | None]:
    """Filter -> POA draft -> read mapping (the host stages of the per-ZMW
    pipeline, reference Consensus.h:396-434)."""
    t0 = time.monotonic()

    if float(np.min(chunk.snr)) < settings.min_snr:
        return Failure.POOR_SNR, None

    from pbccs_tpu.runtime import timing

    with obs_trace.span("filter", zmw=chunk.id):
        reads = filter_reads(chunk.reads, settings.min_length)
    if not reads or all(r is None for r in reads):
        return Failure.NO_SUBREADS, None

    with obs_trace.span("draft", zmw=chunk.id):
        with obs_trace.span("draft.poa"), timing.stage("draft.poa"):
            css, keys, summaries = poa_consensus(reads,
                                                 settings.max_poa_coverage)
        if len(css) < settings.min_length:
            return Failure.TOO_SHORT, None

        # map reads onto the draft
        mapped: list[MappedRead] = []
        n_unmappable = 0
        with obs_trace.span("draft.map"), timing.stage("draft.map"):
            for r, k in zip(reads, keys):
                if r is None or k < 0:
                    continue
                mr = extract_mapped_read(r, summaries[k],
                                         settings.min_length)
                if mr is None:
                    n_unmappable += 1
                    continue
                mapped.append(mr)

    n_candidates = sum(1 for k in keys if k >= 0)
    if not mapped:
        return Failure.NO_SUBREADS, None

    prep_ms = (time.monotonic() - t0) * 1e3
    return None, PreparedZmw(chunk, css, mapped, n_candidates,
                             n_unmappable, prep_ms)


def _read_gates(prep: PreparedZmw, statuses: np.ndarray,
                settings: ConsensusSettings
                ) -> tuple[Failure | None, list[int], int]:
    """Post-AddRead yield gates (reference Consensus.h:437-471): returns
    (failure or None, per-status counts, usable full passes)."""
    status_counts = [0] * 5
    n_passes = 0
    n_dropped = prep.n_unmappable
    for i, m in enumerate(prep.mapped):
        st = int(statuses[i])
        status_counts[st] += 1
        if st == ADD_SUCCESS and m.is_full_pass:
            n_passes += 1
        elif st != ADD_SUCCESS:
            n_dropped += 1

    if n_passes < settings.min_passes:
        return Failure.TOO_FEW_PASSES, status_counts, n_passes
    if prep.n_candidates > 0 and \
            n_dropped / prep.n_candidates > settings.max_drop_fraction:
        return Failure.TOO_MANY_UNUSABLE, status_counts, n_passes
    return None, status_counts, n_passes


def _finish_zmw(prep: PreparedZmw, settings: ConsensusSettings,
                tpl: np.ndarray, qvs: np.ndarray, refine,
                zscores: np.ndarray, global_z: float,
                status_counts: list[int], n_passes: int,
                elapsed_ms: float) -> tuple[Failure, ConsensusResult | None]:
    """Post-polish yield gates + result assembly
    (reference Consensus.h:497-553)."""
    if not refine.converged:
        return Failure.NON_CONVERGENT, None

    pred_acc = predicted_accuracy(qvs)
    if pred_acc < settings.min_predicted_accuracy:
        return Failure.POOR_QUALITY, None

    sequence = decode_bases(tpl)
    if len(sequence) != len(qvs):  # invalid bases reached the template
        return Failure.OTHER, None

    zs = zscores[np.isfinite(zscores)]
    avg_z = float(zs.mean()) if len(zs) else float("nan")
    return Failure.SUCCESS, ConsensusResult(
        id=prep.chunk.id,
        sequence=sequence,
        qvs=qvs,
        num_passes=n_passes,
        predicted_accuracy=pred_acc,
        global_zscore=global_z,
        avg_zscore=avg_z,
        zscores=zscores.copy(),
        status_counts=status_counts,
        mutations_tested=refine.n_tested,
        mutations_applied=refine.n_applied,
        snr=np.asarray(prep.chunk.snr),
        elapsed_ms=elapsed_ms)


def polish_prepared_quiver(prep: PreparedZmw, settings: ConsensusSettings
                           ) -> tuple[Failure, ConsensusResult | None]:
    """Quiver-model polish of a prepared ZMW: same stage structure as the
    Arrow path (gates -> refine -> QVs -> finish), driven through the
    generic refine/QV implementations over QuiverMultiReadScorer
    (reference Quiver/MultiReadMutationScorer.cpp behind the templated
    RefineConsensus/ConsensusQVs, Consensus-inl.hpp:160-297).  Subreads
    carry no QV tracks here, so the features use flat default tracks
    (param-only move scores); Quiver has no closed-form Z-score moments
    (an Arrow-specific construct, Arrow/Expectations.hpp), so z-score
    fields report NaN and the z-score gate is vacuous."""
    from pbccs_tpu.models.arrow.refine import consensus_qvs
    from pbccs_tpu.models.quiver.features import flat_default_features
    from pbccs_tpu.models.quiver.scorer import QuiverMultiReadScorer

    t0 = time.monotonic()
    scorer = QuiverMultiReadScorer(
        prep.css,
        [flat_default_features(m.seq) for m in prep.mapped],
        [m.strand for m in prep.mapped],
        [m.tpl_start for m in prep.mapped],
        [m.tpl_end for m in prep.mapped])

    failure, status_counts, n_passes = _read_gates(prep, scorer.statuses,
                                                   settings)
    if failure is not None:
        return failure, None

    refine = refine_consensus(scorer, settings.refine)
    if not refine.converged:
        return Failure.NON_CONVERGENT, None
    qvs = consensus_qvs(scorer)
    elapsed_ms = prep.prep_ms + (time.monotonic() - t0) * 1e3
    nan_zs = np.full(scorer.n_reads, np.nan)
    return _finish_zmw(prep, settings, scorer.tpl, qvs, refine,
                       nan_zs, float("nan"), status_counts, n_passes,
                       elapsed_ms)


def polish_prepared(prep: PreparedZmw, settings: ConsensusSettings
                    ) -> tuple[Failure, ConsensusResult | None]:
    """The serial polish half of the per-ZMW pipeline, given an already
    prepared (filtered + drafted + mapped) ZMW.  The serial scorer owns the
    wider-band AddRead retry."""
    if settings.model == "quiver":
        return polish_prepared_quiver(prep, settings)
    t0 = time.monotonic()
    scorer = ArrowMultiReadScorer(
        prep.css, prep.chunk.snr,
        [m.seq for m in prep.mapped],
        [m.strand for m in prep.mapped],
        [m.tpl_start for m in prep.mapped],
        [m.tpl_end for m in prep.mapped],
        min_zscore=settings.min_zscore)

    failure, status_counts, n_passes = _read_gates(prep, scorer.statuses,
                                                   settings)
    if failure is not None:
        return failure, None

    global_z = scorer.global_zscore()
    refine = refine_consensus(scorer, settings.refine)
    if not refine.converged:
        return Failure.NON_CONVERGENT, None
    qvs = scorer.consensus_qvs()
    elapsed_ms = prep.prep_ms + (time.monotonic() - t0) * 1e3
    return _finish_zmw(prep, settings, scorer.tpl, qvs, refine,
                       scorer.zscores, global_z, status_counts, n_passes,
                       elapsed_ms)


def process_chunk(chunk: Chunk, settings: ConsensusSettings | None = None
                  ) -> tuple[Failure, ConsensusResult | None]:
    """The per-ZMW pipeline (reference Consensus, Consensus.h:396-553)."""
    settings = settings or ConsensusSettings()
    failure, prep = prepare_chunk(chunk, settings)
    if failure is not None:
        return failure, None
    return polish_prepared(prep, settings)


def _polish_tasks(preps: Sequence[PreparedZmw]) -> list:
    """The ZmwTask batch of a prepared ZMW sequence (ONE construction
    shared by the inline dispatch and the prepare-side prebake)."""
    from pbccs_tpu.parallel.batch import ZmwTask

    return [ZmwTask(p.chunk.id, p.css, np.asarray(p.chunk.snr),
                    [m.seq for m in p.mapped],
                    [m.strand for m in p.mapped],
                    [m.tpl_start for m in p.mapped],
                    [m.tpl_end for m in p.mapped]) for p in preps]


def prebake_polish(preps: Sequence[PreparedZmw], *,
                   buckets: tuple[int, int, int] | None = None,
                   min_z: int = 1):
    """Pre-bake a prepared batch's device inputs on the PREPARE side:
    build the ZmwTask batch and its bucket-shaped numpy marshalling
    (parallel.batch.premarshal -- padded planes + f64 SNR transition
    tables).  The sched/ prepare workers run this so the device executor
    thread's BatchPolisher adopts arrays instead of re-deriving them;
    pass the result to polish_prepared_batch(prebaked=...)."""
    from pbccs_tpu.parallel.batch import premarshal

    return premarshal(_polish_tasks(preps), buckets=buckets, min_z=min_z)


def _polish_batch_arrow(preps: Sequence[PreparedZmw],
                        settings: ConsensusSettings, *,
                        buckets: tuple[int, int, int] | None = None,
                        min_z: int = 1, fixed_z: bool = False,
                        prebaked=None
                        ) -> list[tuple[Failure, ConsensusResult | None]]:
    """One lockstep BatchPolisher dispatch over `preps`: the raw Arrow
    device path, outcomes ALIGNED with `preps`.  Raises on any batch-path
    failure -- fault handling (hang watchdog, transient-error retry,
    poison-ZMW quarantine) lives in polish_prepared_batch."""
    from pbccs_tpu.runtime import timing

    t0 = time.monotonic()
    from pbccs_tpu.parallel.batch import BatchPolisher

    tasks = prebaked.tasks if prebaked is not None else _polish_tasks(preps)
    with obs_trace.span("polish.setup", zmws=len(preps)):
        polisher = BatchPolisher(tasks, min_zscore=settings.min_zscore,
                                 buckets=buckets, min_z=min_z,
                                 fixed_z=fixed_z, prebaked=prebaked)
    with obs_trace.span("polish.gates", zmws=len(preps)):
        gate_info = []
        for z, p in enumerate(preps):
            gate_info.append(_read_gates(p, polisher.statuses[z], settings))
        # ZMWs that shed reads to the alpha/beta mating gate retry in ONE
        # wider-band (2x) sub-batch -- the batched analogue of the serial
        # scorer's whole-scorer escalation (the reference rebands a
        # mismatched pair up to 5 times before dropping,
        # SimpleRecursor.cpp:642-691).  Keep-better-width per ZMW: a ZMW
        # polishes at the wide band iff it MATES more reads there
        # (status != ALPHABETAMISMATCH -- deliberately counting reads the
        # wide band mates but the z-score gate then drops: the reference
        # rebands to achieve alpha/beta agreement FIRST and applies the
        # z-score gate to whatever mated, so reband-to-mate-then-gate is
        # the parity semantics, not mates-that-survive-gating).  Otherwise
        # it stays in the narrow batch with its drops (the serial retry's
        # revert).  Either way the ZMW stays on the batched device path.
        reband = sorted(z for z, p in enumerate(preps)
                        if (polisher.statuses[z, : len(p.mapped)]
                            == ADD_ALPHABETAMISMATCH).any())
        # the wide sub-batches hold the rebanding ZMWs a few at a time, at
        # one Z: wide_pick maps a ZMW that adopts the wide band to its
        # (sub-batch, row)
        wide_pick: dict[int, tuple] = {}
        wides: list = []
        if reband:
            try:  # speculative build: any failure keeps the narrow batch
                wides = polisher.wide_band_subs([tasks[z] for z in reband])
            except Exception as e:  # noqa: BLE001 -- keep the narrow batch
                record_zmw_failure("polish.wide_build", e,
                                   zmw=f"reband[{len(reband)}]")
            for k, z in enumerate(reband if wides else ()):
                sub_k, i = divmod(k, wides[0]._Z)
                wide = wides[sub_k]
                nr = len(preps[z].mapped)
                n_narrow = int((polisher.statuses[z, :nr]
                                != ADD_ALPHABETAMISMATCH).sum())
                n_wide = int((wide.statuses[i, :nr]
                              != ADD_ALPHABETAMISMATCH).sum())
                if n_wide > n_narrow:
                    wide_pick[z] = (wide, i)
                    gate_info[z] = _read_gates(
                        preps[z], wide.statuses[i], settings)
            # banding observability: retry outcomes per batch (the
            # reference's NumFlipFlops analogue at batch granularity)
            for kind, n in (("batches", 1), ("zmws", len(reband)),
                            ("sub_batches", len(wides)),
                            ("adopted", len(wide_pick))):
                _m_band_retry[kind].inc(n)
            Logger.default().debug(
                f"band retry: {len(reband)} ZMW(s) had mating failures at "
                f"W={polisher._W}; "
                f"{len(wide_pick)} adopted the 2x band, "
                f"{len(reband) - len(wide_pick)} reverted")
        # gate-failed ZMWs are excluded from refinement/QV (the serial path
        # returns before polishing them); their batch slots stay idle
        gate_failed = {z for z, g in enumerate(gate_info) if g[0] is not None}
        skip = gate_failed | set(wide_pick)
        # z-score statistics are reported for the draft template, before
        # refinement (parity with the serial path)
        global_zs = polisher.global_zscores()
    with obs_trace.span("polish.refine", zmws=len(preps) - len(skip)):
        refine_results = polisher.refine(settings.refine, skip=skip)
    # z -> (template, QVs, refine result, z-scores, global z-score) of the
    # ZMWs that polished at the wide band
    wide_out: dict[int, tuple] = {}
    if wide_pick:
        with obs_trace.span("polish.wide", zmws=len(wide_pick)):
            try:  # the whole wide retry is speculative: any failure in its
                # polish falls back to the narrow batch's completed results
                # (with the narrow gates) instead of discarding the batch
                for wide in wides:
                    rows = {i: z for z, (w, i) in wide_pick.items()
                            if w is wide}
                    if not rows:
                        continue
                    wide_skip = {i for i in range(wide.n_zmws)
                                 if rows.get(i, -1) in gate_failed
                                 or i not in rows}
                    wide_gz = wide.global_zscores()
                    wide_refine = wide.refine(settings.refine,
                                              skip=wide_skip)
                    wide_qvs = wide.consensus_qvs(
                        skip=wide_skip | {i for i, r in
                                          enumerate(wide_refine)
                                          if not r.converged})
                    for i, z in rows.items():
                        nr = len(preps[z].mapped)
                        wide_out[z] = (wide.tpls[i], wide_qvs[i],
                                       wide_refine[i],
                                       wide.zscores[i, :nr], wide_gz[i])
            except Exception as e:  # noqa: BLE001 -- revert to narrow batch
                record_zmw_failure("polish.wide", e,
                                   zmw=f"reband[{len(wide_pick)}]")
                retry = set(wide_pick)
                for z in list(wide_pick):
                    gate_info[z] = _read_gates(
                        preps[z], polisher.statuses[z], settings)
                wide_pick.clear()
                wide_out.clear()
                gate_failed = {z for z, g in enumerate(gate_info)
                               if g[0] is not None}
                skip = gate_failed
                # refine ONLY the formerly wide-routed ZMWs: the rest of
                # the narrow batch already refined in the first pass, and
                # re-running them would hand non-convergent ZMWs a second
                # full iteration budget and rebuild their refine stats
                todo = retry - gate_failed
                if todo:
                    retry_results = polisher.refine(
                        settings.refine,
                        skip=set(range(polisher.n_zmws)) - todo)
                    for z in todo:
                        refine_results[z] = retry_results[z]
    # non-converged ZMWs are discarded by _finish_zmw; don't pay the QV
    # sweep (the most expensive single pass) for them
    skip = skip | {z for z, r in enumerate(refine_results)
                   if not r.converged}
    with obs_trace.span("polish.qv", zmws=len(preps) - len(skip)):
        qvs = polisher.consensus_qvs(skip=skip)
    polish_s = time.monotonic() - t0
    timing.add_stage("polish", polish_s)
    polish_ms = polish_s * 1e3 / max(len(preps), 1)

    # outcomes accumulate into a local list so a mid-loop fault cannot
    # double-count ZMWs when the serial fallback reruns them
    outcomes: list[tuple[Failure, ConsensusResult | None]] = []
    with obs_trace.span("polish.finish", zmws=len(preps)):
        for z, p in enumerate(preps):
            failure, status_counts, n_passes = gate_info[z]
            if failure is not None:
                outcomes.append((failure, None))
                continue
            nr = len(p.mapped)
            if z in wide_pick:
                w_tpl, w_qvs, w_refine, w_zscores, w_gz = wide_out[z]
                failure, result = _finish_zmw(
                    p, settings, w_tpl, w_qvs, w_refine, w_zscores, w_gz,
                    status_counts, n_passes, p.prep_ms + polish_ms)
            else:
                failure, result = _finish_zmw(
                    p, settings, polisher.tpls[z], qvs[z],
                    refine_results[z], polisher.zscores[z, :nr],
                    global_zs[z], status_counts, n_passes,
                    p.prep_ms + polish_ms)
            outcomes.append((failure, result))
    if polisher.first_of_shape_set:
        # the continuation's and the wide retry's programs belong to the
        # batch that brings the shape set: whether THIS batch left a
        # straggler or failed a mating is chance, and a later one that
        # does would stop the run to trace and load them
        try:
            polisher.warm_shape_set(settings.refine)
        except Exception as e:  # noqa: BLE001 -- the batch's results stand
            record_zmw_failure("polish.warm", e, zmw=f"batch[{len(preps)}]")
    return outcomes


def _batch_extents(preps: Sequence[PreparedZmw]) -> tuple[int, int, int, int]:
    """(ZMWs, most reads, longest read, longest draft) of a prepared
    batch: what its bucket is derived from."""
    return (len(preps),
            max(len(p.mapped) for p in preps),
            max((len(m.seq) for p in preps for m in p.mapped), default=8),
            max(len(p.css) for p in preps))


def menu_pin(preps: Sequence[PreparedZmw]) -> tuple[int, int, int]:
    """The (Imax, Jmax, R) a batch of the scheduled driver or a flush of
    `ccs serve` polishes at: its length class's pin in the process's
    shape menu (parallel.batch.ShapeMenu), so a file's batches share one
    family of programs.  Pass the pin on as `buckets` to prebake_polish
    and polish_prepared_batch."""
    from pbccs_tpu.parallel.batch import shape_menu

    return shape_menu.shapes(*_batch_extents(preps))[:3]


def menu_batch_shapes(preps: Sequence[PreparedZmw], full_zmws: int
                      ) -> tuple[tuple[int, int, int], int | None]:
    """A batch's pin (menu_pin) and the one Z every dispatch of the
    scheduled driver at that pin runs at, to pass on as `min_z` with
    `fixed_z`: the governor's ceiling for the pin, or the power of two of
    a whole work item (`full_zmws`: the driver's --chunkSize) where that
    is less.  A chunk over it is split into parts of Z ZMWs, and the
    last part of a chunk and the last chunk of a file, however few ZMWs
    they hold, polish at that Z too: a Z of their own would be a family
    of programs of their own, loaded when they come.  None where the
    governor gives the pin no ceiling (a backend that reports no
    memory): a batch then keeps the Z of its own size."""
    from pbccs_tpu.resilience import resources
    from pbccs_tpu.utils import next_pow2

    pin = menu_pin(preps)
    cap = resources.default_governor().cap(resources.shape_bucket(*pin))
    return pin, None if cap is None else min(cap, next_pow2(full_zmws, 1))


def _pinned_batch_shapes(preps: Sequence[PreparedZmw],
                         buckets: tuple[int, int, int] | None,
                         min_z: int) -> tuple[tuple[int, int, int], int]:
    """The effective (Imax, Jmax, R)/Z shapes the full batch polishes at:
    quarantine sub-dispatches pin to these so they replay the parent's
    compiled programs -- and, because band width W is a function of the
    Jmax bucket, produce byte-identical results for surviving ZMWs.

    zq/rq stay at their defaults (1): _polish_batch_arrow builds its
    BatchPolisher without a mesh, so the parent's shapes were derived
    with the same quanta.  A meshed dispatch path would need the mesh's
    axis sizes threaded through here."""
    from pbccs_tpu.parallel.batch import effective_shapes

    imax, jmax, r, z = effective_shapes(*_batch_extents(preps),
                                        buckets=buckets, min_z=min_z)
    return (imax, jmax, r), z


def _guarded_dispatch(preps: Sequence[PreparedZmw],
                      settings: ConsensusSettings, *,
                      buckets: tuple[int, int, int] | None,
                      min_z: int, fixed_z: bool = False, prebaked=None
                      ) -> list[tuple[Failure, ConsensusResult | None]]:
    """One fault-domain batch dispatch: the chaos fault site
    ("polish.dispatch", keyed by ZMW ids so poison specs can target one
    ZMW), the hang watchdog (ambient deadline: --polishTimeout /
    PBCCS_WATCHDOG_S; disabled by default), and a bounded retry on
    transient device errors.  A watchdog timeout is never retried -- a
    hang is not transient; the quarantine path isolates it instead."""
    from pbccs_tpu.resilience import faults, retry, watchdog

    ids = [p.chunk.id for p in preps]

    def dispatch():
        # the fault site sits INSIDE the watchdog scope: an injected
        # delay exercises exactly the hung-dispatch recovery path
        faults.maybe_fail("polish.dispatch", keys=ids)
        return _polish_batch_arrow(preps, settings, buckets=buckets,
                                   min_z=min_z, fixed_z=fixed_z,
                                   prebaked=prebaked)

    def attempt():
        return watchdog.run_with_deadline(dispatch, site="polish.dispatch")

    return retry.DEVICE_RETRY.run(
        attempt,
        retry_on=lambda e: not isinstance(e, watchdog.WatchdogTimeout)
        and retry.is_transient_device_error(e),
        site="polish.dispatch")


def polish_prepared_batch(preps: Sequence[PreparedZmw],
                          settings: ConsensusSettings | None = None, *,
                          buckets: tuple[int, int, int] | None = None,
                          min_z: int = 1, fixed_z: bool = False,
                          on_error: str = "bisect",
                          raise_device_shaped: bool = False,
                          prebaked=None
                          ) -> list[tuple[Failure, ConsensusResult | None]]:
    """Polish a batch of prepared ZMWs in one lockstep BatchPolisher and
    return per-ZMW outcomes ALIGNED with `preps` -- the polish core shared
    by the offline driver (process_chunks) and the serving engine
    (pbccs_tpu.serve.engine.CcsEngine), which needs to route each outcome
    back to the client that submitted it.

    `buckets`/`min_z` pin the BatchPolisher's (Imax, Jmax, R)/Z shapes to
    caller-chosen lower bounds: the serving engine pins them to its length
    class's pin in the shape menu and to its --maxBatch (`fixed_z`: every
    flush at that one Z, its wide-band retry too, and the family loaded
    with the shape set's first polish), so variable-size online flushes
    are one program family instead of a fresh device loop per (batch
    size, read count) draw.

    A batch-path error no longer re-runs everything serially with the
    exception discarded: the dispatch is guarded (hang watchdog,
    transient-XLA retry) and a persistent failure routes to
    resilience.quarantine -- with on_error="bisect" (default) the batch
    is bisected in O(k log Z) pinned-shape re-dispatches to isolate the
    k poison ZMW(s); on_error="serial" keeps the legacy whole-batch
    serial fallback.  Either way a ZMW that fails even its serial rescue
    is quarantined (logged + counted, optionally degraded to draft-only
    consensus) instead of silently reporting Failure.OTHER.

    `raise_device_shaped=True` (the device-fleet drivers' FIRST attempt
    at a batch) re-raises hardware-shaped failures -- a WatchdogTimeout,
    a persistent XLA runtime error, a RetriesExhausted -- instead of
    quarantining: bisecting on the device that just hung would burn
    O(Z log Z) timeouts on the same sick hardware, while re-raising lets
    the DevicePool strike/bench it and requeue the WHOLE batch to a
    healthy device.  Injected poison-ZMW faults (resilience.faults
    InjectedFault at polish.dispatch) are task-shaped and always stay on
    the quarantine path.

    `prebaked`: a PrebakedBatch from prebake_polish (built on a prepare
    worker) adopted by the full-batch dispatch only -- quarantine and
    OOM-split sub-dispatches and serial rescues always re-marshal their
    own subsets, so fault recovery is unchanged.

    Capacity governance (resilience.resources): a capacity-shaped
    failure (device OOM / RESOURCE_EXHAUSTED) is NEVER retried at the
    same shape and NEVER quarantined -- the batch splits Z -> Z/2
    through the same bucket-pinned sub-dispatch machinery quarantine
    uses (shapes pinned, so survivors stay byte-identical) and the
    MemoryGovernor records a shape ceiling, so later batches for the
    bucket are pre-split at admission instead of re-discovering the
    OOM."""
    settings = settings or ConsensusSettings()
    _m_polish_dispatches.inc()
    if settings.model == "quiver":
        # Quiver has no lockstep batch driver: it polishes per ZMW (its
        # scorer batches fills internally), with the same fault isolation
        out: list[tuple[Failure, ConsensusResult | None]] = []
        for p in preps:
            try:
                out.append(polish_prepared(p, settings))
            except Exception as e:  # noqa: BLE001 -- per-ZMW isolation
                record_zmw_failure("polish.quiver", e, zmw=p.chunk.id)
                out.append((Failure.OTHER, None))
        return out
    from pbccs_tpu.resilience import resources

    pin, z_pin = _pinned_batch_shapes(preps, buckets, min_z)
    cap = resources.default_governor().cap(
        resources.shape_bucket(*pin), device=resources.current_device())
    if cap is not None and len(preps) > cap:
        # admission pre-split: the governor already learned this bucket
        # OOMs past `cap` ZMWs on this device -- dispatch ceiling-sized
        # parts (pinned to the parent shapes, so results match the
        # unsplit batch byte for byte) instead of paying the OOM again
        resources.note_presplit()
        Logger.default().info(
            f"memory governor: pre-splitting batch of {len(preps)} "
            f"ZMW(s) at ceiling {cap} (bucket {pin})")
        out = []
        start = 0
        for size in resources.split_sizes(len(preps), cap):
            out.extend(_polish_split_part(
                preps[start:start + size], settings, pin, cap,
                on_error=on_error,
                raise_device_shaped=raise_device_shaped))
            start += size
        return out
    return _polish_guarded(preps, settings, buckets=buckets, min_z=min_z,
                           fixed_z=fixed_z,
                           pin=pin, z_pin=z_pin, on_error=on_error,
                           raise_device_shaped=raise_device_shaped,
                           prebaked=prebaked)


def _polish_split_part(preps: Sequence[PreparedZmw],
                       settings: ConsensusSettings, pin, z: int, *,
                       on_error: str, raise_device_shaped: bool
                       ) -> list[tuple[Failure, ConsensusResult | None]]:
    """One part of a split batch: pinned to the parent's (Imax, Jmax, R)
    bucket (byte-identity) and to the split's Z (the ceiling: the smaller
    Z IS the memory relief), the remainder of few ZMWs too -- it polishes
    in the program the whole parts loaded, not at a Z of its own.  Full
    recovery semantics (further capacity splits, quarantine, serial
    rescue) intact."""
    return _polish_guarded(preps, settings, buckets=pin, min_z=z,
                           fixed_z=True, pin=pin, z_pin=z,
                           on_error=on_error,
                           raise_device_shaped=raise_device_shaped,
                           prebaked=None)


def _capacity_split(preps: Sequence[PreparedZmw],
                    settings: ConsensusSettings, pin, *,
                    on_error: str, raise_device_shaped: bool,
                    exc: BaseException
                    ) -> list[tuple[Failure, ConsensusResult | None]]:
    """Recovery from a capacity-shaped dispatch failure at batch size Z:
    record the governor ceiling (Z // 2 for this device + bucket) and
    re-dispatch the two halves at the pinned bucket shapes.  A singleton
    that alone exceeds the device gets the serial host-path rescue (its
    last chance to fit), then quarantines -- never a same-shape retry
    loop, never a bisection tour over healthy ZMWs."""
    from pbccs_tpu.resilience import quarantine, resources

    record_zmw_failure("polish.capacity", exc,
                       zmw=f"batch[{len(preps)}]")
    resources.default_governor().record_oom(
        resources.shape_bucket(*pin), len(preps))
    if len(preps) == 1:
        return [quarantine.serial_rescue(preps[0], settings, exc)]
    resources.note_oom_split()
    from pbccs_tpu.utils import next_pow2

    mid = len(preps) // 2
    out: list[tuple[Failure, ConsensusResult | None]] = []
    for sub in (preps[:mid], preps[mid:]):
        out.extend(_polish_split_part(
            sub, settings, pin, next_pow2(len(preps) - mid, 1),
            on_error=on_error, raise_device_shaped=raise_device_shaped))
    return out


def _polish_guarded(preps: Sequence[PreparedZmw],
                    settings: ConsensusSettings, *,
                    buckets: tuple[int, int, int] | None, min_z: int,
                    fixed_z: bool = False,
                    pin, z_pin: int, on_error: str,
                    raise_device_shaped: bool, prebaked
                    ) -> list[tuple[Failure, ConsensusResult | None]]:
    """One guarded dispatch with the full failure-taxonomy recovery:
    capacity-shaped -> adaptive split (checked FIRST -- an OOM must
    never reach the device-shaped re-raise or the quarantine tour),
    device-shaped -> optional re-raise for the fleet scheduler,
    task-shaped -> quarantine bisection / legacy serial fallback."""
    try:
        return _guarded_dispatch(preps, settings, buckets=buckets,
                                 min_z=min_z, fixed_z=fixed_z,
                                 prebaked=prebaked)
    except Exception as e:  # noqa: BLE001 -- classified below
        from pbccs_tpu.resilience import quarantine, resources, retry, \
            watchdog

        if resources.is_capacity_error(e):
            return _capacity_split(preps, settings, pin,
                                   on_error=on_error,
                                   raise_device_shaped=raise_device_shaped,
                                   exc=e)
        if raise_device_shaped and (
                isinstance(e, (watchdog.WatchdogTimeout,
                               retry.RetriesExhausted))
                or type(e).__name__ == "XlaRuntimeError"):
            raise
        if on_error == "serial":
            # legacy fault isolation (reference Consensus.h:543-548):
            # re-run every ZMW through the serial pipeline, each with
            # the same rescue semantics bisection's singletons get
            record_zmw_failure("polish.batch", e,
                               zmw=f"batch[{len(preps)}]")
            return [quarantine.serial_rescue(p, settings, e)
                    for p in preps]
        return quarantine.isolate(
            preps,
            lambda sub: _guarded_dispatch(sub, settings, buckets=pin,
                                          min_z=z_pin, fixed_z=fixed_z),
            settings, e)


def prepare_batch(chunks: Sequence[Chunk],
                  settings: ConsensusSettings | None = None,
                  **span_args) -> tuple[ResultTally, list[PreparedZmw]]:
    """The host half of a batch: run every chunk through the prep stages
    (filter -> POA draft -> mapping) with per-ZMW fault isolation,
    returning (tally of prep-stage outcomes, survivors ready to polish).
    Shared by process_chunks (a whole batch) and the scheduled driver's
    prepare workers (pbccs_tpu.sched.executor: one contiguous slice of a
    batch each, joined in chunk order), so the two cannot drift.
    `span_args` go on the `prepare` trace span (the scheduled driver's
    `batch=idx`: a batch's slices and its polish run on several threads,
    under no common span)."""
    from pbccs_tpu.resilience import faults
    from pbccs_tpu.runtime import timing

    settings = settings or ConsensusSettings()
    tally = ResultTally()
    preps: list[PreparedZmw] = []
    with obs_trace.span("prepare", zmws=len(chunks), **span_args), \
            timing.stage("draft"):
        for chunk in chunks:
            try:
                faults.maybe_fail("prep.zmw", keys=[chunk.id])
                failure, prep = prepare_chunk(chunk, settings)
            except Exception as e:  # noqa: BLE001 -- per-ZMW isolation
                record_zmw_failure("prepare", e, zmw=chunk.id)
                tally.tally(Failure.OTHER)
                continue
            if failure is not None:
                tally.tally(failure)
            else:
                preps.append(prep)
    return tally, preps


def process_chunks(chunks: Sequence[Chunk],
                   settings: ConsensusSettings | None = None,
                   batch_polish: bool = True,
                   on_error: str = "bisect") -> ResultTally:
    """Process a batch of ZMWs; exceptions become Other tallies (logged +
    counted, record_zmw_failure) and the batch continues (reference
    Consensus.h:543-548).

    With batch_polish (the default), all ZMWs that survive the host stages
    polish together in one lockstep BatchPolisher (polish_prepared_batch) --
    the TPU execution model (one batched device program per refinement
    round) instead of the reference's one-thread-per-ZMW loop.  `on_error`
    selects the batch-failure recovery (see polish_prepared_batch)."""
    settings = settings or ConsensusSettings()
    tally = ResultTally()
    # the lockstep BatchPolisher is the Arrow device path; Quiver polishes
    # through the per-ZMW pipeline (its scorer batches fills internally)
    if not batch_polish or settings.model == "quiver":
        for chunk in chunks:
            try:
                failure, result = process_chunk(chunk, settings)
            except Exception as e:  # noqa: BLE001 -- per-ZMW isolation
                record_zmw_failure("zmw", e, zmw=chunk.id)
                tally.tally(Failure.OTHER)
                continue
            tally.tally(failure)
            if result is not None:
                tally.results.append(result)
        return tally

    prep_tally, preps = prepare_batch(chunks, settings)
    tally.merge(prep_tally)
    if not preps:
        return tally

    # the wait for the device turn is a span of its own, closed before
    # `polish` opens: long waits say the device is the bottleneck, none
    # (with an idle device) says the host's drafts are
    with obs_trace.span("dispatch.turn_wait", zmws=len(preps)):
        _polish_turn.acquire()
    try:
        with obs_trace.span("polish", zmws=len(preps)):
            outcomes = polish_prepared_batch(preps, settings,
                                             on_error=on_error)
    finally:
        _polish_turn.release()
    for failure, result in outcomes:
        tally.tally(failure)
        if result is not None:
            tally.results.append(result)
    return tally
