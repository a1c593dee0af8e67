"""`ccs serve` polishes every flush of a length class at one pin and one Z,
loads that family before it is ready, and answers a ZMW the same whatever
flush it shares with whom (ISSUE 31; docs/DESIGN.md, "Serving").

Sizes are small (100 bp, 3..10 passes, --maxBatch 16) and seeded; the
programs compile once a module.
"""

import json
import re
import socket
import threading
import time

import numpy as np
import pytest

from pbccs_tpu import pipeline
from pbccs_tpu.obs import trace as obs_trace
from pbccs_tpu.obs.metrics import default_registry
from pbccs_tpu.parallel import batch as pbatch
from pbccs_tpu.pipeline import (Chunk, ConsensusSettings, PreparedZmw, Subread,
                                prepare_chunk, process_chunks)
from pbccs_tpu.serve import batcher as serve_batcher
from pbccs_tpu.serve import engine as serve_engine
from pbccs_tpu.serve.client import CcsClient
from pbccs_tpu.serve.engine import CcsEngine, ServeConfig
from pbccs_tpu.serve.server import CcsServer, run_serve
from pbccs_tpu.simulate import simulate_zmw

SHAPE_SETS = "ccs_polish_shape_sets_total"
LOAD_SECONDS = "ccs_program_load_seconds_total"
COMPILES = "ccs_compiles_total"
FLUSH_SLOTS = "ccs_serve_flush_slots_total"
MAX_BATCH = 16
TPL_LEN = 100


def moved(scope, name: str, **labels) -> float:
    want = set(labels.items())
    return sum(v for k, v in scope.counters(name).items() if want <= set(k))


def make_chunks(seed: int, n: int, prefix: str) -> list[Chunk]:
    """n ZMWs of TPL_LEN bases: 10, 3, 9, 4, .. passes and round again."""
    rng = np.random.default_rng(seed)
    chunks = []
    for i in range(n):
        passes = (10 - i // 2 % 4) if i % 2 == 0 else (3 + i // 2 % 4)
        _tpl, reads, _strands, snr = simulate_zmw(rng, TPL_LEN, passes)
        chunks.append(Chunk(f"{prefix}/{i}", [Subread(f"{prefix}/{i}/{k}", r)
                                              for k, r in enumerate(reads)], snr))
    return chunks


def answer(failure, result):
    """What a caller of `ccs serve` reads of a ZMW."""
    if result is None:
        return (failure.value,)
    return (failure.value, result.sequence, result.qualities,
            round(float(result.predicted_accuracy), 6), int(result.num_passes))


# ------------------------------------------- one shape set, whatever the flush

FLUSH_SIZES = (1, 3, 16)


@pytest.fixture(scope="module")
def flushes():
    """The same 16 prepared ZMWs flushed as 1, as 3 and as 16 through the
    engine's polish function at --maxBatch 16; what each flush built."""
    pbatch.shape_menu.reset_for_tests()
    settings = ConsensusSettings()
    chunks = make_chunks(20260931, MAX_BATCH, "flush")
    preps = []
    for chunk in chunks:
        failure, prep = prepare_chunk(chunk, settings)
        assert failure is None
        preps.append(prep)
    out = {"chunks": chunks, "settings": settings}
    for n in FLUSH_SIZES:
        scope = default_registry().scope()
        before = pbatch.shape_sets_seen()
        outcomes = serve_engine._polish_shape_pinned(preps[:n], settings,
                                                     min_z=MAX_BATCH)
        out[n] = {"outcomes": outcomes, "sets_moved": moved(scope, SHAPE_SETS),
                  "built": pbatch.shape_sets_seen() - before}
    return out


@pytest.mark.parametrize("n", FLUSH_SIZES)
def test_every_flush_size_polishes_in_the_first_ones_shape_set(flushes, n):
    """The flush of one ZMW builds (Imax, Jmax, R, Z = 16) and its
    wide-band retry's set at the retry's own one Z; the flushes of 3 and
    of 16 build nothing, though their own buckets differ in R and in Z."""
    first = flushes[FLUSH_SIZES[0]]
    assert first["sets_moved"] == 2
    wide, narrow = sorted(first["built"], key=lambda key: key[3])
    assert narrow[:3] == wide[:3] and wide[4] == 2 * narrow[4]
    assert wide[3] == pbatch.WIDE_BAND_Z
    assert narrow[2] == 12 and narrow[3] == MAX_BATCH
    if n != FLUSH_SIZES[0]:
        assert flushes[n]["sets_moved"] == 0 and not flushes[n]["built"]
    assert len(flushes[n]["outcomes"]) == n


@pytest.fixture(scope="module")
def alone(flushes):
    """ZMWs 0 (10 passes) and 1 (3 passes) each through `process_chunks` on
    it alone, batched and by the serial per-ZMW reference
    (models/arrow/refine.py under ArrowMultiReadScorer)."""
    out = {}
    for z in (0, 1):
        chunk = flushes["chunks"][z]
        for name, kw in (("batched", {}), ("serial", {"batch_polish": False})):
            tally = process_chunks([chunk], flushes["settings"], **kw)
            (failure,) = [f for f, c in tally.counts.items() if c]
            out[z, name] = answer(failure, (tally.results or [None])[0])
    return out


@pytest.mark.parametrize("n, z", [(1, 0), (3, 0), (3, 1), (16, 0), (16, 1)])
def test_a_zmws_answer_is_its_own_whatever_the_flush(flushes, alone, n, z):
    """Sequence, QV string, predicted accuracy and passes of a ZMW in a
    flush of 1, 3 and 16 equal `process_chunks` on it alone and the serial
    reference: no answer depends on a flush-mate."""
    served = answer(*flushes[n]["outcomes"][z])
    assert served == alone[z, "batched"]
    assert served == alone[z, "serial"]
    assert served[0] == "Success"


# -------------------------------------------------------- the batcher's key


def stub_prep(css_len: int, n_reads: int) -> PreparedZmw:
    read = pipeline.MappedRead("r", np.zeros(css_len - 20, np.int8), 0, 0,
                               css_len, True)
    return PreparedZmw(Chunk("stub", [], np.full(4, 8.0)),
                       np.zeros(css_len, np.int8), [read] * n_reads, n_reads,
                       0, 0.0)


@pytest.mark.parametrize("first, then", [
    # a long 5-pass draft pins Jmax 2,304; a 10-pass ZMW of 2,010 joins it
    # (R grows to 12: up to the ladder's 12 a pin grows by a factor two)
    ((2150, 5), (2010, 10)),
    # the other way round the pin grows once (a draft of 2,200 does not fit
    # 2,176 columns), and both sides then share it
    ((2010, 10), (2200, 3)),
])
def test_both_sides_of_a_jmax_edge_share_a_batcher_key(first, then):
    pbatch.shape_menu.reset_for_tests()
    own = [pbatch.effective_shapes(1, n, css - 20, css)[:3] for css, n in (first, then)]
    assert own[0][1] != own[1][1] and own[0][2] != own[1][2]   # an edge apart
    serve_engine._flush_shapes([stub_prep(*first)])
    key = serve_engine._flush_shapes([stub_prep(*then)])
    assert key == (2560, 2304, 12)
    assert serve_engine._flush_shapes([stub_prep(*first)]) == key
    assert serve_engine._flush_shapes([stub_prep(*then)]) == key
    # and the flush of both polishes at that pin
    assert serve_engine._flush_shapes(
        [stub_prep(*first), stub_prep(*then)]) == key
    pbatch.shape_menu.reset_for_tests()


@pytest.mark.parametrize("n_zmws, n_reads, r, pins", [
    # a flush of `ccs serve`, one 3-pass ZMW: it fits the pin's lanes and
    # joins it
    (1, 3, 12, [(2560, 2304, 12)]),
    # a chunk of the batch driver does so too (since PR 46: a file's last
    # chunk of few passes opens no pin of its own)
    (64, 3, 12, [(2560, 2304, 12)]),
    # more reads than the pin's lanes hold: a pin of its own, the first
    # one's lanes as they were
    (1, 13, 32, [(2560, 2304, 12), (2560, 2304, 32)]),
])
def test_a_batch_joins_lanes_it_leaves_empty_and_grows_none(
        n_zmws, n_reads, r, pins):
    menu = pbatch.ShapeMenu()
    menu._pins = [(2560, 2304, 12)]
    assert menu.shapes(n_zmws, n_reads, 2180, 2200)[:3] == (2560, 2304, r)
    assert menu._pins == pins


def test_an_undeclared_servers_lanes_do_not_open_a_class_twice():
    """No --bucket, and the first ZMW has 3 passes: it pins 4 lanes, a
    flush with a 10-pass ZMW opens the 12-lane class, and what comes
    after joins the pin it fits as it stands -- the 4-lane pin is not
    grown into a second 12-lane family."""
    menu = pbatch.ShapeMenu()
    got = [menu.shapes(1, n, 2180, 2200)[2] for n in (3, 10, 7, 10, 3)]
    assert got == [4, 12, 12, 12, 4]
    assert sorted(pin[2] for pin in menu._pins) == [4, 12]


# ------------------------------------------------ warm before ready (--bucket)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_ready_comes_after_warm_and_the_first_request_loads_nothing(capsys):
    """`ccs serve --bucket`: the socket opens and the ready line is
    printed once `serve.warm` has closed; `status` and the ready line
    name the shape sets; the first flush of real ZMWs of that geometry
    then traces, lowers, compiles and loads nothing."""
    pbatch.shape_menu.reset_for_tests()
    capture = obs_trace.Tracer()
    assert obs_trace.install_tracer(capture)
    port, stop = free_port(), threading.Event()
    server = threading.Thread(
        target=run_serve, daemon=True, args=(
            ["--port", str(port), "--maxBatch", "4", "--maxWaitMs", "100",
             "--bucket", "4x10x140", "--logLevel", "WARN"], stop))
    server.start()
    try:
        give_up = time.monotonic() + 600
        while True:
            assert server.is_alive() and time.monotonic() < give_up
            try:
                socket.create_connection(("127.0.0.1", port), timeout=1).close()
                break
            except OSError:
                time.sleep(0.05)
        (warm,) = [s for s in capture.finished_spans() if s.name == "serve.warm"]
        assert not warm.open            # closed before the socket opened
        obs_trace.clear_tracer(capture)
        with CcsClient("127.0.0.1", port) as cli:
            status = cli.status()
            (warmed,) = status["warmed"]
            assert warmed["bucket"] == "4x10x140"
            sets = [(s["imax"], s["jmax"], s["r"], s["z"]) for s in warmed["shape_sets"]]
            assert len(sets) == 2 and sets[0] == sets[1] and sets[0][2:] == (12, 4)
            assert warm.args["shape_sets"] == [list(s.values())
                                               for s in warmed["shape_sets"]]
            scope = default_registry().scope()
            rng = np.random.default_rng(31)
            handles = []
            for i, passes in enumerate((10, 3, 7, 5)):
                _tpl, reads, _strands, snr = simulate_zmw(rng, 140, passes)
                handles.append(cli.submit_chunk(Chunk(
                    f"first/{i}", [Subread(f"first/{i}/{k}", r)
                                   for k, r in enumerate(reads)], snr)))
            replies = [h.reply(timeout=600.0) for h in handles]
            assert [r["type"] for r in replies] == ["result"] * 4
            assert moved(scope, COMPILES) == 0
            assert moved(scope, LOAD_SECONDS) == 0
            assert moved(scope, SHAPE_SETS) == 0
    finally:
        obs_trace.clear_tracer(capture)
        stop.set()
        server.join(timeout=60)
    assert not server.is_alive()
    ready = re.search(r"CCS-SERVE-READY \S+ (\d+) warmed=4x10x140 shape_sets=2",
                      capsys.readouterr().out)
    assert ready and int(ready.group(1)) == port


def test_without_a_bucket_the_server_is_ready_at_once_and_names_nothing():
    with CcsEngine(config=ServeConfig(max_batch=4), prep_fn=lambda c, s: (None, None),
                   polish_fn=lambda preps, s: []) as eng:
        assert eng.status()["warmed"] == []


# ------------------------------------------------------- a closed loop, traced


def test_closed_loop_of_8_sessions_answers_each_zmw_once_and_traces_it():
    """64 ZMWs through 8 sessions with one in flight each: every ZMW is
    answered exactly once; a capture holds one `serve.request` a request,
    whose duration covers its stages, and one `serve.flush` a flush; the
    slot counters agree with the batch-size histogram.  The engine is
    warmed as `--bucket 16x10x100` would: whatever a flush holds, it
    polishes at the pin (R = 12) and at Z = 16."""
    chunks = make_chunks(20260932, 64, "loop")
    todo = iter(chunks)
    lock = threading.Lock()
    replies: dict[str, list] = {}
    with CcsEngine(config=ServeConfig(max_batch=MAX_BATCH,
                                      max_wait_ms=150.0)) as eng, \
            CcsServer(eng, port=0) as srv:
        eng.warm([f"{MAX_BATCH}x10x{TPL_LEN}"])
        scope = default_registry().scope()
        sizes0 = serve_batcher._m_batch_zmws.snapshot()

        def session():
            with CcsClient(srv.host, srv.port) as cli:
                while True:
                    with lock:
                        chunk = next(todo, None)
                    if chunk is None:
                        return
                    reply = cli.submit_chunk(chunk).reply(timeout=900.0)
                    with lock:
                        replies.setdefault(chunk.id, []).append(reply)

        with CcsClient(srv.host, srv.port) as ctl:
            assert ctl.trace("start")["state"] == "started"
            threads = [threading.Thread(target=session) for _ in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            events = ctl.trace("stop")["trace"]["traceEvents"]
        sizes1 = serve_batcher._m_batch_zmws.snapshot()

    assert sorted(replies) == sorted(c.id for c in chunks)
    assert all(len(r) == 1 and r[0]["type"] == "result" for r in replies.values())
    requests = [e for e in events if e["name"] == "serve.request"]
    assert sorted(e["args"]["zmw"] for e in requests) == sorted(replies)
    stages = ("admission", "prepare", "queue", "dispatch", "polish", "emit")
    for e in requests:
        parts = [e["args"][f"{s}_ms"] for s in stages]
        assert all(p >= 0 for p in parts)
        assert abs(sum(parts) - e["dur"] / 1e3) <= 1.0        # ms
    flushes = [e for e in events if e["name"] == "serve.flush"]
    n_flushes = sizes1[2] - sizes0[2]
    assert len(flushes) == n_flushes >= 64 // MAX_BATCH
    assert sorted(e["args"]["flush"] for e in flushes) == \
        sorted({e["args"]["flush"] for e in requests})
    assert {(e["args"]["z"], e["args"]["r"]) for e in flushes} == {(MAX_BATCH, 12)}
    assert sum(e["args"]["zmws"] for e in flushes) == 64
    assert moved(scope, FLUSH_SLOTS, kind="used") == sizes1[1] - sizes0[1] == 64
    assert moved(scope, FLUSH_SLOTS, kind="capacity") == MAX_BATCH * n_flushes
    # the spans of the batch path are there under each flush's polish
    for name in ("serve.prep", "draft", "polish", "polish.setup",
                 "polish.refine", "polish.qv"):
        assert any(e["name"] == name for e in events), name
    assert json.dumps(events)      # a capture goes over the wire as JSON
