"""A ragged file through the batch CLI: 3-10 passes a ZMW in one batch, and
batches of one file either side of a bucket edge (the shape menu,
parallel/batch.py ShapeMenu).

CPU only, small sizes, seeded: the tests assert bytes, counts and which
counters move -- never how long anything takes on a device.
"""

import io

import numpy as np
import pytest

from pbccs_tpu import cli, pipeline
from pbccs_tpu.io.bam import (BamHeader, BamReader, BamRecord, BamWriter,
                              ReadGroupInfo, make_read_group_id)
from pbccs_tpu.io.report import write_results_report
from pbccs_tpu.models.arrow.params import decode_bases, revcomp
from pbccs_tpu.obs.metrics import default_registry
from pbccs_tpu.parallel import batch as pbatch
from pbccs_tpu.pipeline import ResultTally, process_chunks
from pbccs_tpu.runtime.logging import Logger
from pbccs_tpu.runtime.whitelist import Whitelist
from pbccs_tpu.simulate import simulate_zmw

MOVIE = "m140905_042212_sidney_c100564852550000001823085912221377_s1_X0"
LOAD_SECONDS = "ccs_program_load_seconds_total"
SHAPE_SETS = "ccs_polish_shape_sets_total"


def write_subread_bam(path: str, zmws) -> None:
    """`zmws`: (hole, reads, snr) in file order, every pass full length."""
    header = BamHeader(read_groups=[
        ReadGroupInfo(MOVIE, "SUBREAD", binding_kit="100356300",
                      sequencing_kit="100356200",
                      basecaller_version="2.3.0")])
    rg_id = make_read_group_id(MOVIE, "SUBREAD")
    with BamWriter(path, header) as bw:
        for hole, reads, snr in zmws:
            for i, r in enumerate(reads):
                bw.write(BamRecord(
                    name=f"{MOVIE}/{hole}/{i * 1000}_{i * 1000 + len(r)}",
                    seq=decode_bases(r), tags={
                        "RG": rg_id, "zm": hole, "cx": 3, "rq": 0.85,
                        "sn": [float(s) for s in snr]}))


def run_cli(tmp_path, tag: str, in_bam: str, *flags) -> list[bytes]:
    out = str(tmp_path / f"{tag}.bam")
    assert cli.run([out, in_bam, "--reportFile", out + ".csv",
                    "--numThreads", "2", "--logLevel", "WARN", *flags]) == 0
    return [open(out + ext, "rb").read() for ext in ("", ".pbi", ".csv")]


def records_of(tmp_path, bam_bytes: bytes) -> dict:
    path = tmp_path / "read_back.bam"
    path.write_bytes(bam_bytes)
    with BamReader(str(path)) as br:
        return {rec.name: (rec.seq, rec.qual) for rec in br}


def report_counts(csv_bytes: bytes) -> dict:
    rows = (ln.split(",") for ln in csv_bytes.decode().splitlines())
    return {r[0]: int(r[1]) for r in rows if len(r) == 3}


_SINCE_IMPORT = default_registry().scope()


def counter_total(name: str) -> float:
    """Every series of a counter, summed, as it moved since import."""
    return sum(_SINCE_IMPORT.counters(name).values())


# ------------------------------------------------ 3-10 passes in one batch


@pytest.fixture(scope="module")
def dealt_run(tmp_path_factory):
    """16 ZMWs x 120 bp, two at each pass count 3..10 in a seeded order,
    through `ccs` as one batch."""
    tmp = tmp_path_factory.mktemp("dealt")
    rng = np.random.default_rng(20260928)
    deck = np.repeat(np.arange(3, 11), 2)
    rng.shuffle(deck)
    zmws = {}
    for hole, n_passes in enumerate(deck, start=1):
        tpl, reads, strands, snr = simulate_zmw(rng, 120, int(n_passes))
        zmws[hole] = {"tpl": tpl, "reads": reads, "strands": strands,
                      "snr": snr}
    in_bam = str(tmp / "subreads.bam")
    write_subread_bam(in_bam, [(h, z["reads"], z["snr"])
                               for h, z in zmws.items()])
    pbatch.shape_menu.reset_for_tests()
    bam, _pbi, csv = run_cli(tmp, "dealt", in_bam)
    return tmp, in_bam, zmws, records_of(tmp, bam), report_counts(csv)


def test_ragged_batch_equals_each_zmw_polished_alone(dealt_run):
    """Every Success consensus and QV string of the batch equals the serial
    per-ZMW path's (models/arrow/refine.py under pipeline.process_chunk) on
    that ZMW alone, and the yield report's categories are the serial
    path's: 3-pass ZMWs that drop a read fail there, none is lost."""
    tmp, in_bam, zmws, got, report = dealt_run
    args = cli.build_parser().parse_args([str(tmp / "unused.bam"), in_bam])
    settings = cli.consensus_settings_from_args(args)
    gated = ResultTally()
    want, serial = {}, ResultTally()
    for batch in cli._chunks_from_files([in_bam], Whitelist("all"), args,
                                        Logger.default(), gated):
        for chunk in batch:
            one = process_chunks([chunk], settings, batch_polish=False)
            serial.merge(one)
            want.update({f"{r.id}/ccs": (r.sequence, r.qualities)
                         for r in one.results})
    serial.merge(gated)
    assert got == want and len(got) >= 12
    assert sum(report.values()) == len(zmws) == serial.total
    assert report["Success -- CCS generated"] == len(got)
    text = io.StringIO()
    write_results_report(text, serial)
    assert report == report_counts(text.getvalue().encode())


def test_ragged_lanes_score_as_the_float64_oracle(dealt_run):
    """The batched fills over ragged read lanes (3-10 live of 12): each
    read's log-likelihood under its ZMW's served consensus agrees with the
    dense float64 recursion of ops/fwdbwd_ref.py."""
    from pbccs_tpu.models.arrow.params import encode_bases
    from pbccs_tpu.ops.fwdbwd_ref import loglik_dense
    from pbccs_tpu.simulate import make_transition_track

    _tmp, _in_bam, zmws, got, _report = dealt_run
    tasks, oracle = [], []
    for name, (seq, _qual) in sorted(got.items()):
        z = zmws[int(name.split("/")[1])]
        cons = encode_bases(seq)
        # the consensus follows the first POA read: either strand
        flip = int(_closer(seq, revcomp(z["tpl"]), z["tpl"]))
        strands = [s ^ flip for s in z["strands"]]
        tasks.append(pbatch.ZmwTask(
            name, cons, z["snr"], z["reads"], strands,
            [0] * len(strands), [len(cons)] * len(strands)))
        tracks = (make_transition_track(cons, z["snr"]),
                  make_transition_track(revcomp(cons), z["snr"]))
        oracle.append([loglik_dense(r, (cons, revcomp(cons))[s], tracks[s])
                       for r, s in zip(z["reads"], strands)])
    polisher = pbatch.BatchPolisher(tasks)
    assert polisher._R == 12
    assert sorted({len(t.reads) for t in tasks}) == list(range(3, 11))
    for z, want in enumerate(oracle):
        have = polisher.baselines[z, : len(want)]
        np.testing.assert_allclose(have, want, rtol=2e-3)
        assert not polisher._real_rows[z, len(want):].any()


def _closer(seq: str, a: np.ndarray, b: np.ndarray) -> bool:
    """Is `seq` nearer `a` than `b`, by matching prefix bases?"""
    def score(t):
        s = decode_bases(t)
        return sum(x == y for x, y in zip(seq, s))
    return score(a) > score(b)


# --------------------------------------- batches either side of a bucket edge


def edge_file(tmp_path, rng) -> str:
    """Eight ZMWs in two batches of four: the first four 118 bp (own
    bucket Imax 192, Jmax 192), the last four 90 bp (128, 128)."""
    zmws = []
    for hole in range(1, 9):
        _tpl, reads, _strands, snr = simulate_zmw(
            rng, 118 if hole <= 4 else 90, 4)
        zmws.append((hole, reads, snr))
    path = str(tmp_path / "edge.bam")
    write_subread_bam(path, zmws)
    return path


def test_a_files_batches_share_the_first_batchs_shape_set(
        rng, tmp_path, monkeypatch):
    """A file whose batches would pick two buckets: every batch polishes
    at the first one's pin, nothing is loaded after the first batch's
    polish has closed, and BAM, index and report are byte for byte those
    of a run that polishes each batch at its own bucket."""
    in_bam = edge_file(tmp_path, rng)
    own_buckets, after_polish = [], []
    menu_shapes = pipeline.menu_batch_shapes
    polish = pipeline.polish_prepared_batch

    def noting_shapes(preps, full_zmws):
        own_buckets.append(pipeline._pinned_batch_shapes(preps, None, 1)[0])
        return menu_shapes(preps, full_zmws)

    def noting_polish(preps, settings, **kw):
        try:
            return polish(preps, settings, **kw)
        finally:
            after_polish.append((kw["buckets"], counter_total(LOAD_SECONDS),
                                 counter_total(SHAPE_SETS)))

    monkeypatch.setattr(pipeline, "menu_batch_shapes", noting_shapes)
    monkeypatch.setattr(pipeline, "polish_prepared_batch", noting_polish)
    pbatch.shape_menu.reset_for_tests()
    # one prepare thread: batches of four 100 bp ZMWs draft in less time
    # than the one before takes to submit, and two threads can swap them
    flags = ("--chunkSize", "4", "--prepareWorkers", "1")
    pinned = run_cli(tmp_path, "pinned", in_bam, *flags)
    assert own_buckets == [(192, 192, 4), (128, 128, 4)]
    assert [p[0] for p in after_polish] == [(192, 192, 4)] * 2
    assert after_polish[1][1:] == after_polish[0][1:]    # nothing loaded
    assert report_counts(pinned[2])["Success -- CCS generated"] >= 6

    # the same file with every batch at its own bucket
    monkeypatch.setattr(
        pipeline, "menu_batch_shapes",
        lambda preps, full_zmws: (
            pipeline._pinned_batch_shapes(preps, None, 1)[0], None))
    del after_polish[:]
    own = run_cli(tmp_path, "own", in_bam, *flags)
    assert [p[0] for p in after_polish] == [(192, 192, 4), (128, 128, 4)]
    assert after_polish[1][2] == after_polish[0][2] + 1   # a second set
    assert own == pinned


@pytest.mark.parametrize("pin, own, want, pin_after", [
    # a neighbour across one bucket edge adopts the pin
    ((2560, 2304, 12), (64, 10, 2140, 2170), (2560, 2304, 12, 64), None),
    # a draft past the pin's own headroom still fits its columns
    ((2560, 2304, 12), (64, 10, 2150, 2250), (2560, 2304, 12, 64), None),
    # a batch that does not fit grows the pin
    ((2048, 2304, 12), (64, 10, 2150, 2190), (2560, 2304, 12, 64),
     (2560, 2304, 12)),
    # 500 bp and 600 bp differ in band width: no shared pin
    ((640, 576, 32), (64, 30, 640, 600), (768, 640, 32, 64), None),
    # two steps apart: another class; more reads than the pin's lanes
    # hold: a pin of its own, at its step of the lane ladder
    ((2560, 2304, 12), (64, 10, 100, 100), (128, 128, 12, 64), None),
    ((2560, 2304, 12), (64, 30, 2150, 2190), (2560, 2304, 32, 64), None),
    ((2560, 2304, 12), (64, 14, 2150, 2190), (2560, 2304, 32, 64), None),
    # fewer reads than the pin's lanes hold: it joins, and the lanes stay
    ((2560, 2304, 32), (2, 3, 2150, 2190), (2560, 2304, 32, 2), None),
])
def test_shape_menu_pins_a_length_class(pin, own, want, pin_after):
    menu = pbatch.ShapeMenu()
    menu._pins = [pin]
    assert menu.shapes(*own) == want
    assert menu.shapes(*own) == want           # and stays there
    assert menu._pins[0] == (pin_after or pin)


def test_500bp_cell_shapes_are_what_they_were():
    """`500bp-30x.batch` polishes at Imax 640, Jmax 576, R 32."""
    assert pbatch.effective_shapes(64, 30, 545, 530) == (640, 576, 32, 64)
    assert pbatch.ShapeMenu().shapes(64, 30, 545, 530) == (640, 576, 32, 64)
