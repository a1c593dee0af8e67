"""End-to-end pipeline tests on simulated ZMWs.

Pattern: reference tests validate consensus recovery from synthetic read sets
(reference ConsensusCore/src/Tests/TestPoaConsensus.cpp and
tests/TestSparsePoa.cpp); here we run the full filter->draft->polish->QV
pipeline and assert template recovery + yield accounting.
"""

import numpy as np
import pytest

from pbccs_tpu.models.arrow.params import decode_bases, revcomp
from pbccs_tpu.pipeline import (
    ADAPTER_AFTER,
    ADAPTER_BEFORE,
    Chunk,
    ConsensusResult,
    ConsensusSettings,
    Failure,
    Subread,
    filter_reads,
    process_chunk,
    process_chunks,
)
from pbccs_tpu.simulate import simulate_zmw


def make_chunk(rng, zmw_id="movie/1", tpl_len=160, n_passes=8):
    tpl, reads, strands, snr = simulate_zmw(rng, tpl_len, n_passes)
    subreads = [Subread(f"{zmw_id}/{i}", r) for i, r in enumerate(reads)]
    return tpl, Chunk(zmw_id, subreads, snr)


def test_filter_reads_median_window():
    mk = lambda i, n, flags: Subread(str(i), np.zeros(n, np.int8), flags=flags)
    full = ADAPTER_BEFORE | ADAPTER_AFTER
    reads = [mk(0, 100, full), mk(1, 102, full), mk(2, 98, full),
             mk(3, 250, full),      # >= 2x median: dropped (None)
             mk(4, 100, 0)]         # partial pass: sorts after full passes
    out = filter_reads(reads, min_length=10)
    assert len(out) == 5
    assert out[-1] is None          # dropped read sorts last
    kept = [r for r in out if r is not None]
    # full-pass reads first, closest-to-median (101) first
    assert [r.id for r in kept[:3]] == ["1", "0", "2"]
    assert kept[3].id == "4"


def test_filter_reads_median_too_short():
    full = ADAPTER_BEFORE | ADAPTER_AFTER
    reads = [Subread("0", np.zeros(5, np.int8), flags=full)]
    assert filter_reads(reads, min_length=10) == []


def test_pipeline_recovers_template(rng):
    tpl, chunk = make_chunk(rng)
    failure, result = process_chunk(chunk)
    assert failure == Failure.SUCCESS
    assert result is not None
    # consensus orientation follows the first read threaded into the POA,
    # so either strand of the template is a correct recovery
    assert result.sequence in (decode_bases(tpl), decode_bases(revcomp(tpl)))
    assert result.predicted_accuracy > 0.99
    assert result.num_passes >= 3
    assert len(result.qualities) == len(result.sequence)
    assert np.isfinite(result.global_zscore)
    assert np.isfinite(result.avg_zscore)


@pytest.mark.parametrize("qvs", [
    np.arange(-5, 130, dtype=np.int32),            # both clamps
    np.arange(0, 94, dtype=np.int64),
    np.array([0, 93, 94, 255], dtype=np.uint8),
    np.array([-0.5, 0.4, 41.9, 92.7, 93.5, 1e9], dtype=np.float32),
    np.zeros(0, dtype=np.int32),
    [3, 40, 93, 200],                              # a list, as a test builds one
], ids=["int32", "int64", "uint8", "float32", "empty", "list"])
def test_qualities_is_the_per_base_clamp_in_one_pass(qvs):
    """`qualities` is one array pass; the reference's per-base loop
    (QVsToASCII: clamp to [0, 93], Phred+33) stays here as what it must
    equal, character for character."""
    result = ConsensusResult(
        id="m/1", sequence="A" * len(qvs), qvs=qvs, num_passes=3,
        predicted_accuracy=0.99, global_zscore=0.0, avg_zscore=0.0,
        zscores=np.zeros(0), status_counts=[0] * 5, mutations_tested=0,
        mutations_applied=0, snr=np.full(4, 8.0), elapsed_ms=0.0)
    assert result.qualities == "".join(
        chr(min(max(0, int(q)), 93) + 33) for q in qvs)


def test_pipeline_too_few_passes(rng):
    tpl, chunk = make_chunk(rng, n_passes=2)
    failure, result = process_chunk(chunk)
    assert failure == Failure.TOO_FEW_PASSES
    assert result is None


def test_pipeline_no_subreads():
    chunk = Chunk("movie/9", [], np.array([8.0] * 4))
    failure, result = process_chunk(chunk)
    assert failure == Failure.NO_SUBREADS


def test_pipeline_too_short(rng):
    tpl, chunk = make_chunk(rng, tpl_len=30, n_passes=4)
    settings = ConsensusSettings(min_length=100)
    failure, _ = process_chunk(chunk, settings)
    # reads are ~30bp, median < min_length -> filtered to nothing
    assert failure in (Failure.NO_SUBREADS, Failure.TOO_SHORT)


def test_extract_mapped_read_rc_coordinates():
    # extents are in oriented-read coordinates; for an RC read the native
    # slice must be flipped: read[n-re : n-rs]
    from pbccs_tpu.pipeline import extract_mapped_read
    from pbccs_tpu.poa.sparse import PoaAlignmentSummary

    seq = np.arange(30, dtype=np.int8) % 4
    read = Subread("r", seq)
    summary = PoaAlignmentSummary(reverse_complemented=True,
                                  extent_on_read=(5, 20),
                                  extent_on_consensus=(40, 55))
    mr = extract_mapped_read(read, summary, min_length=10)
    assert mr is not None
    assert mr.strand == 1
    assert np.array_equal(mr.seq, seq[10:25])
    # forward read: straight slice
    summary_f = PoaAlignmentSummary(reverse_complemented=False,
                                    extent_on_read=(5, 20),
                                    extent_on_consensus=(40, 55))
    mr_f = extract_mapped_read(read, summary_f, min_length=10)
    assert np.array_equal(mr_f.seq, seq[5:20])


def test_pipeline_poor_snr(rng):
    tpl, chunk = make_chunk(rng, tpl_len=100, n_passes=4)
    chunk.snr = np.array([3.0, 8.0, 8.0, 8.0])
    failure, result = process_chunk(chunk)
    assert failure == Failure.POOR_SNR
    assert result is None


def test_filter_reads_drops_empty_read():
    full = ADAPTER_BEFORE | ADAPTER_AFTER
    reads = [Subread("0", np.zeros(100, np.int8), flags=full),
             Subread("1", np.zeros(0, np.int8), flags=0)]
    out = filter_reads(reads, min_length=10)
    assert out[0] is not None and out[0].id == "0"
    assert out[1] is None


def test_pipeline_rejects_invalid_bases():
    # all-N reads must not yield a SUCCESS with desynced sequence/QV lengths
    r = np.full(120, 4, np.int8)
    chunk = Chunk("z/1", [Subread(f"z/1/{i}", r.copy()) for i in range(4)],
                  np.full(4, 8.0))
    failure, result = process_chunk(chunk)
    assert failure == Failure.NO_SUBREADS
    assert result is None


@pytest.mark.slow
def test_process_chunks_tally(rng):
    chunks = []
    for i in range(3):
        _, chunk = make_chunk(rng, zmw_id=f"movie/{i}", tpl_len=120,
                              n_passes=6 if i else 2)
        chunks.append(chunk)
    tally = process_chunks(chunks)
    assert tally.total == 3
    assert tally.counts[Failure.SUCCESS] == 2
    assert tally.counts[Failure.TOO_FEW_PASSES] == 1
    assert len(tally.results) == 2
    ids = {r.id for r in tally.results}
    assert ids == {"movie/1", "movie/2"}


@pytest.mark.slow
def test_batch_polish_matches_serial(rng):
    """The lockstep batched polish path produces the same consensus,
    QVs, gates, and yield counts as the serial per-ZMW path."""
    chunks = []
    for i in range(4):
        _, chunk = make_chunk(rng, zmw_id=f"bp/{i}", tpl_len=100,
                              n_passes=6 if i != 1 else 2)
        chunks.append(chunk)
    serial = process_chunks(chunks, batch_polish=False)
    # guard against a vacuous pass: if the batched path raised and fell back
    # to the serial loop, this patched process_chunk turns every ZMW into an
    # Other tally and the count comparison below fails
    import pbccs_tpu.pipeline as _pl

    def _boom(*a, **k):
        raise AssertionError("batched path fell back to serial")

    orig = _pl.process_chunk
    _pl.process_chunk = _boom
    try:
        batched = process_chunks(chunks, batch_polish=True)
    finally:
        _pl.process_chunk = orig
    assert {f: c for f, c in serial.counts.items()} == \
        {f: c for f, c in batched.counts.items()}
    assert len(serial.results) == len(batched.results)
    for rs, rb in zip(serial.results, batched.results):
        assert rs.id == rb.id
        assert rs.sequence == rb.sequence
        np.testing.assert_array_equal(rs.qvs, rb.qvs)
        assert rs.num_passes == rb.num_passes
        assert rs.status_counts == rb.status_counts
        assert abs(rs.predicted_accuracy - rb.predicted_accuracy) < 1e-9
        assert abs(rs.global_zscore - rb.global_zscore) < 1e-6
