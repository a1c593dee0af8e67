"""A ragged slice of a cell through the batch CLI: 1 to 30 passes a ZMW in
one file, SNR on both sides of the gate, under a governor's ceiling as
the chip gives one (ISSUE 46: the configuration `rs2-p6c4-2kb-cell-slice`
at a small size).

One closed set of programs: the file's first chunk opens one 32-lane pin
(the lane ladder, parallel/batch.py `lane_step`), every dispatch runs at
the ceiling's Z -- the parts of a chunk, its remainder, the file's last
chunk of one ZMW -- and the wide-band retry at its own one Z.  CPU only,
120 bp inserts, seeded: the tests assert bytes, counts and which counters
move, never how long anything takes.
"""

import io
import statistics

import numpy as np
import pytest

from pbccs_tpu import cli, pipeline
from pbccs_tpu.io.report import write_results_report
from pbccs_tpu.models.arrow.scorer import ADD_ALPHABETAMISMATCH
from pbccs_tpu.obs.metrics import default_registry
from pbccs_tpu.parallel import batch as pbatch
from pbccs_tpu.pipeline import Failure, ResultTally, process_chunks
from pbccs_tpu.resilience import resources
from pbccs_tpu.runtime.logging import Logger
from pbccs_tpu.runtime.whitelist import Whitelist
from pbccs_tpu.simulate import simulate_zmw
from test_ragged_file import (records_of, report_counts, run_cli,
                              write_subread_bam)

INSERT = 120
CHUNK = 4          # --chunkSize
CEILING = 2        # the Z the governor allows the 32-lane pin
SHAPE_SETS = "ccs_polish_shape_sets_total"
SNR_GATE, PASS_GATE = 4.0, 3
SEED = 20261004


def dealt_passes(n: int) -> list[int]:
    """The deck of `benchmark/drivers/batch_cli_ragged.py`: ZMW i of n at
    the (i + 0.5) / n quantile of a lognormal pass count, median 4.9,
    sigma 0.7, cut to 1..30."""
    nd = statistics.NormalDist()
    return [int(np.clip(np.exp(np.log(4.9) + 0.7 * nd.inv_cdf((i + 0.5) / n)),
                        1, 30)) for i in range(n)]


def plain_yield(zmws: dict) -> dict:
    """The yield of the reader's gates as a plain function of what was
    generated: a channel under the SNR gate, then fewer reads than
    --minPasses."""
    out = {"snr": 0, "passes": 0, "rest": 0}
    for z in zmws.values():
        out["snr" if min(z["snr"]) < SNR_GATE
            else "passes" if len(z["reads"]) < PASS_GATE else "rest"] += 1
    return out


def _limit_for_ceiling(pin, z: int) -> int:
    """Device memory under which the governor's model allows the pin
    exactly Z = z."""
    _imax, jmax, r = pin
    return resources.BYTES_PER_READ_COLUMN * r * jmax * z + 1


_SINCE_IMPORT = default_registry().scope()


def moved(name: str, **labels) -> float:
    want = set(labels.items())
    return sum(v for k, v in _SINCE_IMPORT.counters(name).items()
               if want <= set(k))


@pytest.fixture(scope="module")
def ragged_run(tmp_path_factory):
    """8 ZMWs x 120 bp, passes dealt 1..30 and shuffled from a seed, SNR
    lognormal a channel with two ZMWs under the gate, through `ccs
    --chunkSize 4` with the 32-lane pin's ceiling at Z = 2; then one
    3-pass ZMW at the file's end: its last chunk.  (Small on purpose:
    a round of 32 lanes takes the CPU a second a ZMW slot.)"""
    tmp = tmp_path_factory.mktemp("cell_slice")
    rng = np.random.default_rng(SEED)
    deck = dealt_passes(8)
    deck[-1] = 30                         # a cell's file holds one or two
    rng.shuffle(deck)
    deck += [3]
    snrs = {h: np.clip(rng.lognormal(np.log(8.0), 0.3, 4), 2.5, 15.0)
            for h in range(1, len(deck) + 1)}
    # two of the ZMWs with passes enough are under the SNR gate: those the
    # draw put there, and the first of 4 to 9 passes to make them two
    low = [h for h, n in enumerate(deck, start=1)
           if n >= PASS_GATE and min(snrs[h]) < SNR_GATE]
    for h in [h for h, n in enumerate(deck, start=1)
              if 4 <= n <= 9 and h not in low][:2 - len(low)]:
        snrs[h][h % 4] = 3.1
    zmws = {}
    for hole, n_passes in enumerate(deck, start=1):
        _tpl, reads, _strands, snr = simulate_zmw(rng, INSERT, n_passes,
                                                  snrs[hole])
        zmws[hole] = {"reads": reads, "snr": snr}
    in_bam = str(tmp / "subreads.bam")
    write_subread_bam(in_bam, [(h, z["reads"], z["snr"])
                               for h, z in zmws.items()])

    # what the reader lets through, in file order: the chunks of the run
    kept = [h for h, z in zmws.items()
            if min(z["snr"]) >= SNR_GATE and len(z["reads"]) >= PASS_GATE]
    chunks = [kept[i: i + CHUNK] for i in range(0, len(kept), CHUNK)]
    assert [len(c) for c in chunks] == [CHUNK, 1]
    assert max(len(zmws[h]["reads"]) for h in chunks[0]) == 30
    assert len(chunks[-1]) < CEILING
    assert all(len(zmws[h]["reads"]) <= 4 for h in chunks[-1])

    after_polish = []
    polish = pipeline.polish_prepared_batch

    def noting_polish(preps, settings, **kw):
        try:
            return polish(preps, settings, **kw)
        finally:
            after_polish.append((kw["buckets"], kw["min_z"], len(preps),
                                 moved(SHAPE_SETS), list(pbatch.shape_menu._pins),
                                 sorted(pbatch.shape_sets_seen())))

    pbatch.shape_menu.reset_for_tests()
    pin = pbatch.ShapeMenu().shapes(CHUNK, 30, INSERT + 30, INSERT + 12)[:3]
    mp = pytest.MonkeyPatch()
    mp.setattr(resources, "device_bytes_limit",
               lambda: _limit_for_ceiling(pin, CEILING))
    mp.setattr(pipeline, "polish_prepared_batch", noting_polish)
    before = {k: moved(k, **kw) for k, kw in COUNTERS.items()}
    try:
        bam, _pbi, csv = run_cli(tmp, "ragged", in_bam,
                                 "--chunkSize", str(CHUNK))
    finally:
        mp.undo()
    delta = {k: moved(k, **kw) - before[k] for k, kw in COUNTERS.items()}
    return (tmp, in_bam, zmws, chunks, records_of(tmp, bam),
            report_counts(csv), after_polish, delta, pin)


COUNTERS = {
    "ccs_menu_pins_total": {},
    "ccs_reader_gated_zmws_total": {},
    "ccs_resource_presplit_batches_total": {},
}


def test_ragged_file_equals_each_zmw_polished_alone(ragged_run):
    """Every Success consensus and QV string of the file equals the serial
    per-ZMW path's (models/arrow/refine.py under pipeline.process_chunk)
    on that ZMW alone, whatever part of a split it fell in and however
    many of its 32 lanes stayed empty, and each ZMW's status is the serial
    path's: the categories of the report are."""
    tmp, in_bam, zmws, _chunks, got, report, *_ = ragged_run
    args = cli.build_parser().parse_args([str(tmp / "unused.bam"), in_bam])
    settings = cli.consensus_settings_from_args(args)
    gated, serial, want = ResultTally(), ResultTally(), {}
    for batch in cli._chunks_from_files([in_bam], Whitelist("all"), args,
                                        Logger.default(), gated):
        for chunk in batch:
            one = process_chunks([chunk], settings, batch_polish=False)
            serial.merge(one)
            want.update({f"{r.id}/ccs": (r.sequence, r.qualities)
                         for r in one.results})
    serial.merge(gated)
    assert got == want and len(got) >= 4
    assert sum(report.values()) == len(zmws) == serial.total
    text = io.StringIO()
    write_results_report(text, serial)
    assert report == report_counts(text.getvalue().encode())


def test_the_yield_report_is_the_plain_function_of_the_truth(ragged_run):
    """Category by category: a channel under 4.0 is `Below SNR threshold`,
    fewer than 3 reads `Not enough full passes` (to which a ZMW of 3 or 4
    reads may fall when polish drops up to two at the mating and z-score
    gates), every other ZMW Success; none lost or `Other`."""
    _tmp, _in, zmws, _chunks, got, report, _after, delta, _pin = ragged_run
    want = plain_yield(zmws)
    assert want["snr"] >= 2 and want["passes"] == 2
    three_reads = sum(len(z["reads"]) in (3, 4) and min(z["snr"]) >= SNR_GATE
                      for z in zmws.values())
    assert report["Failed -- Below SNR threshold"] == want["snr"]
    few = report["Failed -- Not enough full passes"]
    assert want["passes"] <= few <= want["passes"] + three_reads
    assert report["Success -- CCS generated"] == len(got) \
        == len(zmws) - want["snr"] - few
    assert sum(report.values()) == len(zmws)
    # and the reader's counter says which gate turned each away
    assert delta["ccs_reader_gated_zmws_total"] == want["snr"] + want["passes"]


def test_one_closed_set_from_the_first_batch_to_the_last(ragged_run):
    """The first chunk opens the file's one pin, 32 lanes, and its parts
    load the Z = 2 set and the wide-band retry's: after the last chunk,
    one 3-pass ZMW that would pick 4 lanes and Z = 1 for itself,
    the shape sets and the menu's pins are what they were after the
    first, and every dispatch ran at the ceiling's Z."""
    *_, chunks, _got, _report, after_polish, delta, pin = ragged_run
    assert [n for _b, _z, n, *_ in after_polish] == [len(c) for c in chunks]
    assert {(b, z) for b, z, *_ in after_polish} == {(pin, CEILING)}
    first, last = after_polish[0], after_polish[-1]
    assert first[3:] == last[3:]              # sets, pins, the sets' keys
    assert last[4] == [pin] and pin[2] == 32
    assert {key[3] for key in last[5] if key[:3] == pin} == {
        CEILING, pbatch.WIDE_BAND_Z}
    assert delta["ccs_menu_pins_total"] == 1
    # whole chunks are split by the ceiling, the last one is not
    assert delta["ccs_resource_presplit_batches_total"] == len(chunks) - 1


@pytest.mark.parametrize("n_reads, lanes", [
    (1, 4), (4, 4), (5, 8), (9, 12), (12, 12), (13, 32), (30, 32),
    (32, 32), (33, 64), (100, 128)])
def test_the_lane_ladder(n_reads, lanes):
    assert pbatch.lane_step(n_reads) == lanes
    assert pbatch.effective_shapes(8, n_reads, 130, 120)[2] == lanes


# ------------------------------------------------ parts and retries at one Z


@pytest.fixture(scope="module")
def fourteen_preps():
    rng = np.random.default_rng(20261005)
    chunks = []
    for z in range(14):
        _tpl, reads, _strands, snr = simulate_zmw(rng, 60, 4)
        chunks.append(pipeline.Chunk(
            f"sp/{z}", [pipeline.Subread(f"sp/{z}/{i}", r)
                        for i, r in enumerate(reads)], snr))
    _tally, preps = pipeline.prepare_batch(chunks)
    assert len(preps) == 14
    pin = pipeline._pinned_batch_shapes(preps, None, 1)[0]
    unsplit = pipeline.polish_prepared_batch(preps, buckets=pin, min_z=16)
    assert sum(f is Failure.SUCCESS for f, _r in unsplit) >= 12
    return preps, pin, unsplit


def answers(outcomes) -> list:
    return [(f, r and (r.sequence, r.qualities, r.predicted_accuracy))
            for f, r in outcomes]


@pytest.mark.parametrize("remainder", [1, 3, 6])
def test_a_splits_remainder_polishes_at_the_splits_z(
        fourteen_preps, remainder, monkeypatch):
    """A batch of 8 + remainder ZMWs under a ceiling of 8: the remainder
    polishes at Z = 8 like the whole part before it, not at a Z of its
    own, and both give the bytes of the unsplit batch."""
    preps, pin, unsplit = fourteen_preps
    built = []
    init = pbatch.BatchPolisher.__init__

    def noting_init(self, tasks, *a, **kw):
        init(self, tasks, *a, **kw)
        built.append((self._Z, self.n_zmws))

    monkeypatch.setattr(pbatch.BatchPolisher, "__init__", noting_init)
    monkeypatch.setattr(resources, "device_bytes_limit",
                        lambda: _limit_for_ceiling(pin, 8))
    n = 8 + remainder
    got = pipeline.polish_prepared_batch(preps[:n], buckets=pin)
    assert [b for b in built if b[0] == 8] == [(8, 8), (8, remainder)]
    assert all(z in (8, pbatch.WIDE_BAND_Z) for z, _n in built)
    assert answers(got) == answers(unsplit[:n])


def test_a_wide_band_retry_of_six_zmws_polishes_four_at_a_time(
        fourteen_preps, monkeypatch):
    """Six ZMWs of a batch shed a read at the narrow band and mate it at
    twice the band: they polish in two sub-batches at Z = 4, the Z the
    shape set's first polish loads, and each ZMW's answer is what it is
    when three of them retry at a time."""
    preps, pin, _unsplit = fourteen_preps
    built = []

    class DropAtTheNarrowBand(pbatch.BatchPolisher):
        def __init__(self, tasks, **kw):
            super().__init__(tasks, **kw)
            built.append((self._W, self._Z, self.n_zmws))
            if self._W == built[0][0]:
                for z, t in enumerate(tasks):
                    self.statuses[z, len(t.reads) - 1] = \
                        ADD_ALPHABETAMISMATCH
                    self.active[z, len(t.reads) - 1] = False

    monkeypatch.setattr(pbatch, "BatchPolisher", DropAtTheNarrowBand)
    six = pipeline.polish_prepared_batch(preps[:6], buckets=pin, min_z=8)
    narrow_w = built[0][0]
    assert [b[1:] for b in built if b[0] == 2 * narrow_w] == [(4, 4), (4, 2)]
    threes = (pipeline.polish_prepared_batch(preps[:3], buckets=pin, min_z=8)
              + pipeline.polish_prepared_batch(preps[3:6], buckets=pin,
                                               min_z=8))
    assert sum(f is Failure.SUCCESS for f, _r in six) >= 5
    assert answers(six) == answers(threes)
