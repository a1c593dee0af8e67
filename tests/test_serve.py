"""Serving subsystem tests: dynamic batcher, protocol, engine, TCP server.

The batcher tests drive the scheduling core with a fake clock and no
sockets (the tentpole contract: fill-triggered flush, deadline-triggered
flush, bucket selection).  Engine and server tests inject stub
prep/polish functions so scheduling, backpressure, error containment,
and the wire protocol are exercised without device work; one slow test
runs the real pipeline end to end through the engine and pins equality
with the offline driver.
"""

import socket
import threading
import time

import numpy as np
import pytest

from pbccs_tpu.pipeline import (
    Chunk,
    ConsensusResult,
    Failure,
    PreparedZmw,
    Subread,
)
from pbccs_tpu.serve import protocol
from pbccs_tpu.serve.batcher import Batch, DynamicBatcher, PendingItem
from pbccs_tpu.serve.client import CcsClient, ServeError
from pbccs_tpu.serve.engine import (
    CcsEngine,
    EngineClosed,
    EngineOverloaded,
    ServeConfig,
)
from pbccs_tpu.serve.server import CcsServer

# ---------------------------------------------------------------- helpers


def item(key, t, wait=1.0, payload=None):
    return PendingItem(key=key, payload=payload, admit_t=t,
                       flush_by=t + wait)


def make_chunk(zmw_id="m/1", n_reads=4, length=20):
    seq = np.arange(length, dtype=np.int8) % 4
    return Chunk(zmw_id,
                 [Subread(f"{zmw_id}/{i}", seq.copy())
                  for i in range(n_reads)],
                 np.full(4, 8.0))


def stub_prep(tpl_len=64):
    """Prep stub: a PreparedZmw whose draft length selects the bucket."""
    def prep(chunk, settings):
        return None, PreparedZmw(chunk, np.zeros(tpl_len, np.int8),
                                 [], len(chunk.reads), 0, 0.0)
    return prep


def fake_result(zmw_id, sequence="ACGT"):
    return ConsensusResult(
        id=zmw_id, sequence=sequence,
        qvs=np.full(len(sequence), 40), num_passes=4,
        predicted_accuracy=0.999, global_zscore=0.0, avg_zscore=0.0,
        zscores=np.zeros(0), status_counts=[0] * 5, mutations_tested=0,
        mutations_applied=0, snr=np.full(4, 8.0), elapsed_ms=1.0)


def stub_polish(preps, settings):
    return [(Failure.SUCCESS, fake_result(p.chunk.id)) for p in preps]


def stub_engine(max_batch=4, max_wait_ms=50.0, max_pending=64,
                tpl_len=64, polish=stub_polish, **kw):
    return CcsEngine(
        config=ServeConfig(max_batch=max_batch, max_wait_ms=max_wait_ms,
                           max_pending=max_pending, **kw),
        prep_fn=stub_prep(tpl_len), polish_fn=polish)


# ---------------------------------------------------------------- batcher


class TestDynamicBatcher:
    def test_fill_triggered_flush(self):
        b = DynamicBatcher(max_batch=3)
        assert b.add(item("k", 0.0)) is None
        assert b.add(item("k", 0.1)) is None
        batch = b.add(item("k", 0.2))
        assert isinstance(batch, Batch)
        assert batch.reason == "fill"
        assert batch.key == "k"
        assert [i.admit_t for i in batch.items] == [0.0, 0.1, 0.2]
        assert b.pending_count() == 0

    def test_bucket_selection_keeps_keys_apart(self):
        """Items only co-batch within their length bucket."""
        b = DynamicBatcher(max_batch=2)
        assert b.add(item((64, 128), 0.0)) is None
        assert b.add(item((256, 128), 0.0)) is None
        assert b.pending_count() == 2  # two singleton buckets, no flush
        batch = b.add(item((64, 128), 0.1))
        assert batch is not None and batch.key == (64, 128)
        assert len(batch.items) == 2
        # the other bucket is untouched
        assert b.pending_count() == 1
        assert b.depth_by_bucket() == {str((256, 128)): 1}

    def test_deadline_triggered_flush(self):
        b = DynamicBatcher(max_batch=10)
        b.add(item("a", 0.0, wait=1.0))
        b.add(item("a", 0.5, wait=1.0))   # younger: flush_by 1.5
        b.add(item("b", 0.9, wait=1.0))
        assert b.due(0.99) == []          # nothing expired yet
        batches = b.due(1.0)              # bucket a's OLDEST expires at 1.0
        assert [bt.key for bt in batches] == ["a"]
        assert batches[0].reason == "deadline"
        # the whole bucket ships, including the younger item
        assert len(batches[0].items) == 2
        assert b.pending_count() == 1     # bucket b still waiting
        assert b.due(1.89) == []
        assert [bt.key for bt in b.due(1.9)] == ["b"]

    def test_next_deadline_tracks_oldest(self):
        b = DynamicBatcher(max_batch=10)
        assert b.next_deadline() is None
        b.add(item("a", 1.0, wait=2.0))
        b.add(item("b", 0.5, wait=1.0))
        assert b.next_deadline() == 1.5
        assert [bt.key for bt in b.due(1.6)] == ["b"]
        assert b.next_deadline() == 3.0

    def test_drain(self):
        b = DynamicBatcher(max_batch=10)
        b.add(item("a", 0.0))
        b.add(item("b", 0.0))
        batches = b.drain()
        assert {bt.key for bt in batches} == {"a", "b"}
        assert all(bt.reason == "drain" for bt in batches)
        assert b.pending_count() == 0 and b.next_deadline() is None

    def test_length_bucket_key(self):
        """The bucket key is the compiled-shape bucket of parallel.batch:
        nearby lengths share it, far lengths split."""
        from pbccs_tpu.parallel.batch import length_bucket

        assert length_bucket(100, 110) == length_bucket(105, 112)
        j_small, _ = length_bucket(100, 110)
        j_large, _ = length_bucket(1000, 110)
        assert j_small != j_large
        _, i_small = length_bucket(100, 110)
        _, i_large = length_bucket(100, 1100)
        assert i_small != i_large


# --------------------------------------------------------------- protocol


class TestProtocol:
    def test_chunk_round_trip(self):
        chunk = make_chunk("movie/7", n_reads=3, length=12)
        wire = protocol.chunk_to_wire(chunk)
        back = protocol.chunk_from_wire(wire)
        assert back.id == chunk.id
        np.testing.assert_allclose(back.snr, chunk.snr)
        assert len(back.reads) == 3
        for a, b in zip(chunk.reads, back.reads):
            assert a.id == b.id and a.flags == b.flags
            np.testing.assert_array_equal(a.seq, b.seq)

    def test_decode_line_errors(self):
        with pytest.raises(protocol.ProtocolError):
            protocol.decode_line(b"not json")
        with pytest.raises(protocol.ProtocolError):
            protocol.decode_line(b"[1, 2]")
        msg = protocol.decode_line(protocol.encode_msg({"verb": "ping"}))
        assert msg == {"verb": "ping"}

    @pytest.mark.parametrize("zmw", [
        None, "str", {}, {"id": "m/1"},
        {"id": "m/1", "reads": []},
        {"id": "m/1", "snr": [1, 2, 3], "reads": [{"seq": "ACGT"}]},
        {"id": "m/1", "reads": [{"seq": 5}]},
    ])
    def test_chunk_from_wire_rejects(self, zmw):
        with pytest.raises(protocol.ProtocolError):
            protocol.chunk_from_wire(zmw)

    def test_result_to_wire(self):
        ok = protocol.result_to_wire("r1", "m/1", Failure.SUCCESS,
                                     fake_result("m/1", "ACGT"), 12.5)
        assert ok["type"] == "result" and ok["status"] == "Success"
        assert ok["sequence"] == "ACGT" and len(ok["qual"]) == 4
        gate = protocol.result_to_wire("r2", "m/2", Failure.TOO_FEW_PASSES,
                                       None, 3.0)
        assert gate["status"] == "TooFewPasses"
        assert "sequence" not in gate


# ----------------------------------------------------------------- engine


class TestEngine:
    def test_fill_flush_completes_requests(self):
        with stub_engine(max_batch=2, max_wait_ms=60_000.0) as eng:
            r1 = eng.submit(make_chunk("m/1"))
            r2 = eng.submit(make_chunk("m/2"))  # tops off the bucket
            assert r1.wait(10.0) and r2.wait(10.0)
            assert r1.failure == Failure.SUCCESS
            assert r1.result.id == "m/1" and r2.result.id == "m/2"
            assert r1.latency_ms > 0

    def test_deadline_flush_completes_a_lone_request(self):
        # bucket can never fill (max_batch huge): only the max-wait flush
        # can complete this request
        with stub_engine(max_batch=1000, max_wait_ms=50.0) as eng:
            t0 = time.monotonic()
            req = eng.submit(make_chunk("m/1"))
            assert req.wait(10.0)
            assert req.failure == Failure.SUCCESS
            assert time.monotonic() - t0 >= 0.045  # waited for the flush

    def test_deadline_slack_beats_max_wait(self):
        # a tight per-request deadline flushes BEFORE the engine max-wait
        with stub_engine(max_batch=1000, max_wait_ms=60_000.0) as eng:
            req = eng.submit(make_chunk("m/1"), deadline_ms=80.0)
            assert req.wait(10.0)
            assert req.failure == Failure.SUCCESS

    def test_a_due_bucket_waits_while_its_class_keeps_the_executor(self):
        """While the one polish executor is taken by a batch of the same
        key, a bucket past its flush-by keeps collecting: what arrived
        meanwhile leaves as ONE flush when that batch completes, not as
        a flush a request."""
        gate, sizes = threading.Event(), []

        def polish(preps, settings):
            sizes.append(len(preps))
            if len(sizes) == 1:
                gate.wait(10.0)
            return stub_polish(preps, settings)

        with stub_engine(max_batch=8, max_wait_ms=20.0, polish=polish) as eng:
            first = eng.submit(make_chunk("m/0"))
            deadline = time.monotonic() + 5.0
            while not sizes and time.monotonic() < deadline:
                time.sleep(0.005)      # the lone request left at its flush-by
            late = []
            for i in (1, 2, 3):
                late.append(eng.submit(make_chunk(f"m/{i}")))
                time.sleep(0.05)       # each past the one before's flush-by
            assert eng.status()["bucketed"] == 3 and sizes == [1]
            gate.set()
            assert first.wait(10.0) and all(r.wait(10.0) for r in late)
        assert sizes == [1, 3]

    def test_a_saturating_class_does_not_starve_a_lone_zmw(self):
        """The hold is the class's own.  A closed loop of a majority
        class keeps the one executor taken with fill flushes (a batch
        always running or queued); a lone ZMW of another length class
        still leaves at its flush-by (--maxWaitMs holds for mixed
        lengths), and a second one, due while the first's batch is in
        flight, leaves when that batch has completed."""
        turns, lock, stop = [], threading.Lock(), threading.Event()

        def prep(chunk, settings):
            L = 512 if chunk.id.startswith("lone") else 64
            return None, PreparedZmw(chunk, np.zeros(L, np.int8), [],
                                     len(chunk.reads), 0, 0.0)

        def polish(preps, settings):
            time.sleep(0.03)
            with lock:
                turns.append(time.monotonic())     # a completion
            return stub_polish(preps, settings)

        def session(eng, k):
            n = 0
            while not stop.is_set():
                req = eng.submit(make_chunk(f"busy{k}/{n}"))
                assert req.wait(10.0)
                n += 1

        cfg = ServeConfig(max_batch=4, max_wait_ms=20.0, max_pending=64)
        with CcsEngine(config=cfg, prep_fn=prep, polish_fn=polish) as eng:
            loop = [threading.Thread(target=session, args=(eng, k),
                                     daemon=True) for k in range(8)]
            for t in loop:
                t.start()
            try:
                deadline = time.monotonic() + 5.0
                while len(turns) < 4 and time.monotonic() < deadline:
                    time.sleep(0.005)              # the loop is in its stride
                lone = eng.submit(make_chunk("lone/0"))
                while not lone.t_dispatch and time.monotonic() < deadline:
                    time.sleep(0.002)
                second = eng.submit(make_chunk("lone/1"))
                answered = lone.wait(5.0) and second.wait(5.0)
                still_saturated = eng.status()["in_flight_batches"] >= 1
            finally:
                stop.set()
                for t in loop:
                    t.join(10.0)
        assert answered and still_saturated
        assert lone.failure == second.failure == Failure.SUCCESS
        due = lone.submit_t + cfg.max_wait_ms / 1e3
        with lock:
            waited = [t for t in turns if due < t <= lone.t_dispatch]
        # it left at its flush-by (a completion may land while the
        # batcher's thread wakes), not when the load dipped
        assert len(waited) <= 2, (len(waited), lone.t_dispatch - due)
        # the second one's bucket was held by the first one's batch alone
        assert second.t_dispatch <= lone.t_polish1 + 0.5

    def test_out_of_order_completion_across_buckets(self):
        """A later-submitted small-bucket request completes while an
        earlier request still waits on its (slower) bucket."""
        order = []
        gate = threading.Event()

        def polish(preps, settings):
            if len(preps[0].css) == 512:  # the slow bucket
                gate.wait(10.0)
            return stub_polish(preps, settings)

        # two buckets: tpl_len differs enough to split the Jmax bucket
        cfg = ServeConfig(max_batch=1, max_wait_ms=60_000.0,
                          polish_workers=2)

        def prep(chunk, settings):
            L = 512 if chunk.id.startswith("slow") else 64
            return None, PreparedZmw(chunk, np.zeros(L, np.int8), [],
                                     1, 0, 0.0)

        with CcsEngine(config=cfg, prep_fn=prep, polish_fn=polish) as eng:
            slow = eng.submit(make_chunk("slow/1"),
                              callback=lambda r: order.append(r.chunk.id))
            fast = eng.submit(make_chunk("fast/1"),
                              callback=lambda r: order.append(r.chunk.id))
            assert fast.wait(10.0)       # completes while slow is blocked
            assert not slow.done.is_set()
            gate.set()
            assert slow.wait(10.0)
            assert order == ["fast/1", "slow/1"]

    def test_backpressure_overloaded(self):
        gate = threading.Event()

        def polish(preps, settings):
            gate.wait(10.0)
            return stub_polish(preps, settings)

        eng = stub_engine(max_batch=1, max_wait_ms=60_000.0,
                          max_pending=2, polish=polish).start()
        try:
            eng.submit(make_chunk("m/1"))
            eng.submit(make_chunk("m/2"))
            with pytest.raises(EngineOverloaded):
                eng.submit(make_chunk("m/3"))
            assert eng.status()["rejected"] == 1
            gate.set()  # drain; slots free as requests complete
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                try:
                    req = eng.submit(make_chunk("m/4"))
                    break
                except EngineOverloaded:
                    time.sleep(0.01)
            else:
                pytest.fail("admission never recovered after drain")
            assert req.wait(10.0)
        finally:
            gate.set()
            eng.close()

    def test_raising_polish_fails_batch_not_engine(self):
        calls = []

        def polish(preps, settings):
            calls.append(len(preps))
            if len(calls) == 1:
                raise RuntimeError("device on fire")
            return stub_polish(preps, settings)

        with stub_engine(max_batch=1, max_wait_ms=60_000.0,
                         polish=polish) as eng:
            bad = eng.submit(make_chunk("m/1"))
            assert bad.wait(10.0)
            assert bad.error is not None and "device on fire" in bad.error
            assert bad.result is None
            # the engine keeps serving after the failed batch
            ok = eng.submit(make_chunk("m/2"))
            assert ok.wait(10.0)
            assert ok.failure == Failure.SUCCESS
            assert eng.status()["errors"] == 1

    def test_raising_prep_fails_request_not_engine(self):
        def prep(chunk, settings):
            if chunk.id == "m/boom":
                raise ValueError("bad zmw")
            return stub_prep()(chunk, settings)

        with CcsEngine(config=ServeConfig(max_batch=1,
                                          max_wait_ms=60_000.0),
                       prep_fn=prep, polish_fn=stub_polish) as eng:
            bad = eng.submit(make_chunk("m/boom"))
            ok = eng.submit(make_chunk("m/2"))
            assert bad.wait(10.0) and ok.wait(10.0)
            assert bad.error is not None and ok.failure == Failure.SUCCESS

    def test_prep_gate_failure_skips_polish(self):
        def prep(chunk, settings):
            return Failure.TOO_FEW_PASSES, None

        polished = []

        def polish(preps, settings):
            polished.append(1)
            return stub_polish(preps, settings)

        with CcsEngine(config=ServeConfig(max_batch=1,
                                          max_wait_ms=60_000.0),
                       prep_fn=prep, polish_fn=polish) as eng:
            req = eng.submit(make_chunk("m/1"))
            assert req.wait(10.0)
            assert req.failure == Failure.TOO_FEW_PASSES
            assert req.result is None and not polished

    def test_min_read_score_gate_matches_offline(self):
        """The offline CLI's --minReadScore input gate applies at
        admission: low-accuracy reads never reach prep."""
        seen = []

        def prep(chunk, settings):
            seen.append([r.id for r in chunk.reads])
            return Failure.NO_SUBREADS, None

        with CcsEngine(config=ServeConfig(max_batch=1,
                                          max_wait_ms=60_000.0,
                                          min_read_score=0.75),
                       prep_fn=prep, polish_fn=stub_polish) as eng:
            chunk = make_chunk("m/1", n_reads=3)
            chunk.reads[1].read_accuracy = 0.5   # below the gate
            req = eng.submit(chunk)
            assert req.wait(10.0)
        assert seen == [["m/1/0", "m/1/2"]]

    def test_closed_engine_rejects(self):
        eng = stub_engine()
        with pytest.raises(EngineClosed):
            eng.submit(make_chunk("m/1"))  # never started
        eng.start()
        eng.close()
        with pytest.raises(EngineClosed):
            eng.submit(make_chunk("m/1"))

    @pytest.mark.parametrize("drain", [True, False])
    def test_close_after_a_start_that_failed(self, drain):
        """start() refuses a negative device count before it has made a
        thread: close() must still come back."""
        eng = stub_engine(devices=-1)
        with pytest.raises(ValueError, match="ServeConfig.devices"):
            eng.start()
        assert eng.close(drain=drain) is True

    def test_close_drains_pending(self):
        with stub_engine(max_batch=1000, max_wait_ms=60_000.0) as eng:
            # neither fill nor max-wait can flush this before close();
            # the shutdown drain must ship it
            req = eng.submit(make_chunk("m/1"))
        assert req.done.is_set()
        assert req.failure == Failure.SUCCESS

    @pytest.mark.parametrize("devices", [1, 2])
    def test_a_drain_returns_only_after_every_callback_ran(self, devices):
        """Replies leave on the completion thread, at one device as with
        a pool: close(drain=True) still returns only once every admitted
        request's callback has run, the slow ones too."""
        ran, names = [], set()

        def on_done(req):
            time.sleep(0.1)                 # a reply on a slow socket
            names.add(threading.current_thread().name)
            ran.append(req.chunk.id)

        eng = stub_engine(max_batch=2, max_wait_ms=60_000.0,
                          devices=devices).start()
        ids = [f"m/{i}" for i in range(5)]  # two fills and a parked one
        for zmw in ids:
            eng.submit(make_chunk(zmw), callback=on_done)
        assert eng.close(drain=True) is True
        assert sorted(ran) == ids           # at the return, not later
        assert names == {"ccs-serve-complete"}
        st = eng.status()
        assert st["pending"] == st["in_flight_batches"] == 0
        assert st["completed"] == 5

    @pytest.mark.parametrize("devices", [1, 2])
    def test_an_abort_with_a_flush_mid_polish_finishes_each_request_once(
            self, devices):
        """close(drain=False) with one flush on the device, one queued
        behind it and a ZMW parked in the batcher: every callback runs
        exactly once, and the flush that was polishing is handed off with
        its results, not left behind the completer's sentinel."""
        started, gate = threading.Event(), threading.Event()
        lock, calls = threading.Lock(), {}

        def polish(preps, settings):
            started.set()
            gate.wait(10.0)
            return stub_polish(preps, settings)

        def on_done(req):
            with lock:
                calls[req.chunk.id] = calls.get(req.chunk.id, 0) + 1

        eng = stub_engine(max_batch=2, max_wait_ms=60_000.0, polish=polish,
                          devices=devices).start()
        mid = [eng.submit(make_chunk(f"mid/{i}"), callback=on_done)
               for i in range(2)]
        assert started.wait(5.0)
        rest = [eng.submit(make_chunk(f"queued/{i}"), callback=on_done)
                for i in range(2)]
        rest.append(eng.submit(make_chunk("parked/0"), callback=on_done))
        deadline = time.monotonic() + 5.0
        while eng.status()["bucketed"] != 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        closer = threading.Thread(target=eng.close,
                                  kwargs={"drain": False}, daemon=True)
        closer.start()
        time.sleep(0.1)                     # the abort is under way
        gate.set()
        closer.join(30.0)
        assert not closer.is_alive()
        assert all(r.done.is_set() for r in mid + rest)
        assert calls == {r.chunk.id: 1 for r in mid + rest}
        assert all(r.failure == Failure.SUCCESS and r.error is None
                   for r in mid)
        assert all((r.error is None) == (r.failure == Failure.SUCCESS)
                   for r in rest)
        st = eng.status()
        assert st["pending"] == st["in_flight_batches"] == 0
        assert st["completed"] == 5

    @pytest.mark.parametrize("devices", [1, 2])
    def test_a_raising_callback_does_not_stop_the_completer(self, devices):
        seen = []

        def boom(req):
            raise RuntimeError("the client went away")

        with stub_engine(max_batch=1, max_wait_ms=60_000.0,
                         devices=devices) as eng:
            bad = eng.submit(make_chunk("m/1"), callback=boom)
            assert bad.wait(10.0) and bad.failure == Failure.SUCCESS
            ok = eng.submit(make_chunk("m/2"),
                            callback=lambda r: seen.append(r.chunk.id))
            assert ok.wait(10.0)
        assert seen == ["m/2"]
        assert eng.status()["completed"] == 2

    def test_a_flush_that_outlives_close_is_completed_where_it_ended(
            self, monkeypatch):
        """A device program that hangs past close()'s bounded joins ends
        after the completer has gone: its requests still finish, once,
        on the thread the flush ended on."""
        started, gate = threading.Event(), threading.Event()
        calls = []

        def polish(preps, settings):
            started.set()
            gate.wait(10.0)
            return stub_polish(preps, settings)

        join = threading.Thread.join
        monkeypatch.setattr(          # close() gives a hung polish 10 s
            threading.Thread, "join",
            lambda self, timeout=None: join(
                self, 0.05 if timeout == 10.0 else timeout))
        eng = stub_engine(max_batch=1, max_wait_ms=60_000.0,
                          polish=polish).start()
        req = eng.submit(make_chunk("m/1"), callback=lambda r: calls.append(
            threading.current_thread().name))
        assert started.wait(5.0)
        assert eng.close(drain=False) is False
        assert not req.done.is_set()
        gate.set()
        assert req.wait(10.0) and req.failure == Failure.SUCCESS
        deadline = time.monotonic() + 5.0
        while not calls and time.monotonic() < deadline:
            time.sleep(0.005)
        assert calls == ["ccs-serve-polish-0"]
        assert eng.status()["pending"] == 0

    @pytest.mark.parametrize("devices", [1, 2])
    def test_an_abort_racing_the_hand_off_finishes_every_request_once(
            self, devices):
        """More submitters than cores, a short switch interval, and
        close(drain=False) in the middle of it: whichever stage an
        admitted request had reached (undrafted, parked, queued for the
        executor, polishing, handed off), its callback
        runs exactly once and the accounts close."""
        import sys

        lock, calls, admitted = threading.Lock(), {}, []
        stop = threading.Event()

        def polish(preps, settings):
            time.sleep(0.002)
            return stub_polish(preps, settings)

        def on_done(req):
            with lock:
                calls[req.chunk.id] = calls.get(req.chunk.id, 0) + 1

        def submitter(eng, k):
            n = 0
            while not stop.is_set():
                try:
                    req = eng.submit(make_chunk(f"s{k}/{n}"), callback=on_done)
                except EngineClosed:
                    return
                except EngineOverloaded:
                    time.sleep(0.001)
                    continue
                with lock:
                    admitted.append(req)
                n += 1

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            eng = stub_engine(max_batch=3, max_wait_ms=5.0, max_pending=48,
                              polish=polish, devices=devices).start()
            threads = [threading.Thread(target=submitter, args=(eng, k),
                                        daemon=True) for k in range(16)]
            for t in threads:
                t.start()
            deadline = time.monotonic() + 10.0
            while (eng.status()["completed"] < 96
                   and time.monotonic() < deadline):
                time.sleep(0.01)            # the loop is in its stride
            eng.close(drain=False)
            stop.set()
            for t in threads:
                t.join(10.0)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(interval)
        assert len(admitted) >= 96          # the loop went round
        assert all(r.wait(5.0) for r in admitted)
        time.sleep(0.05)                    # `done` is set before the callback
        with lock:
            assert calls == {r.chunk.id: 1 for r in admitted}
        st = eng.status()
        assert st["pending"] == st["in_flight_batches"] == 0
        assert st["completed"] == len(admitted)

    def test_status_shape(self):
        with stub_engine() as eng:
            req = eng.submit(make_chunk("m/1"))
            req.wait(10.0)
            st = eng.status()
            for key in ("queue_depth", "bucketed", "in_flight_batches",
                        "stage_seconds", "device_fetches", "pending",
                        "admitted", "completed", "uptime_s"):
                assert key in st
            assert st["admitted"] == st["completed"] == 1


# ----------------------------------------------------------------- server


@pytest.fixture
def serve_stack():
    """Engine (stubbed pipeline) + TCP server on an ephemeral port."""
    eng = stub_engine(max_batch=2, max_wait_ms=50.0, max_pending=8).start()
    srv = CcsServer(eng, port=0).start()
    yield srv
    srv.shutdown()
    eng.close()


class TestServer:
    def test_submit_streams_results(self, serve_stack):
        with CcsClient(serve_stack.host, serve_stack.port) as cli:
            handles = [cli.submit(f"m/{i}", ["ACGTACGT"] * 4)
                       for i in range(5)]
            for i, h in enumerate(handles):
                msg = h.reply(timeout=10.0)
                assert msg["status"] == "Success"
                assert msg["zmw"] == f"m/{i}"
                assert msg["sequence"] == "ACGT"
                assert msg["latency_ms"] > 0

    def test_status_and_ping(self, serve_stack):
        with CcsClient(serve_stack.host, serve_stack.port) as cli:
            cli.ping()
            st = cli.status()
            assert st["engine"] == "ccs-serve"
            assert st["sessions"] == 1
            assert "stage_seconds" in st and "in_flight_batches" in st

    def test_malformed_frame_keeps_session(self, serve_stack):
        raw = socket.create_connection(
            (serve_stack.host, serve_stack.port), timeout=10.0)
        rf = raw.makefile("rb")
        raw.sendall(b"{broken\n")
        err = protocol.decode_line(rf.readline())
        assert err["type"] == "error" and err["code"] == "bad_request"
        # same session still answers
        raw.sendall(protocol.encode_msg({"verb": "ping", "id": "p"}))
        assert protocol.decode_line(rf.readline())["type"] == "pong"
        raw.close()

    def test_invalid_zmw_is_structured_error(self, serve_stack):
        with CcsClient(serve_stack.host, serve_stack.port) as cli:
            handle = cli.submit_wire({"id": "m/1", "reads": []})
            with pytest.raises(ServeError) as ei:
                handle.reply(timeout=10.0)
            assert ei.value.code == "bad_request"

    def test_unknown_verb(self, serve_stack):
        raw = socket.create_connection(
            (serve_stack.host, serve_stack.port), timeout=10.0)
        rf = raw.makefile("rb")
        raw.sendall(protocol.encode_msg({"verb": "frobnicate", "id": "x"}))
        err = protocol.decode_line(rf.readline())
        assert err["code"] == "bad_request" and "frobnicate" in err["error"]
        raw.close()

    def test_disconnect_mid_stream_server_survives(self, serve_stack):
        cli = CcsClient(serve_stack.host, serve_stack.port)
        cli.submit("gone/1", ["ACGTACGT"] * 4)
        cli.close()  # vanish with a request in flight
        # the server keeps serving other sessions
        with CcsClient(serve_stack.host, serve_stack.port) as cli2:
            msg = cli2.submit("m/2", ["ACGTACGT"] * 4).reply(timeout=10.0)
            assert msg["status"] == "Success"
            # the orphaned request still completed engine-side
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if cli2.status()["pending"] == 0:
                    break
                time.sleep(0.02)
            assert cli2.status()["pending"] == 0

    def test_overloaded_reply(self):
        gate = threading.Event()

        def polish(preps, settings):
            gate.wait(10.0)
            return stub_polish(preps, settings)

        eng = stub_engine(max_batch=1, max_wait_ms=60_000.0, max_pending=1,
                          polish=polish).start()
        srv = CcsServer(eng, port=0).start()
        try:
            with CcsClient(srv.host, srv.port) as cli:
                first = cli.submit("m/1", ["ACGTACGT"] * 4)
                # second submit exceeds max_pending -> structured reply
                deadline = time.monotonic() + 10.0
                code = None
                while time.monotonic() < deadline:
                    try:
                        cli.submit("m/2", ["ACGTACGT"] * 4).reply(10.0)
                    except ServeError as e:
                        code = e.code
                        break
                    time.sleep(0.01)
                assert code == "overloaded"
                gate.set()
                assert first.reply(timeout=10.0)["status"] == "Success"
        finally:
            gate.set()
            srv.shutdown()
            eng.close()

    def test_concurrent_sessions(self, serve_stack):
        results = {}
        lock = threading.Lock()

        def one(i):
            with CcsClient(serve_stack.host, serve_stack.port) as cli:
                msg = cli.submit(f"c{i}/1",
                                 ["ACGTACGT"] * 4).reply(timeout=10.0)
                with lock:
                    results[i] = msg["status"]

        threads = [threading.Thread(target=one, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=15.0)
        assert results == {i: "Success" for i in range(4)}


# ----------------------------------------------------- real-pipeline e2e


@pytest.mark.slow
def test_engine_matches_offline_pipeline(rng):
    """Real prep + real polish through the serving engine: results equal
    the offline driver's on the same chunks (same polish core)."""
    from pbccs_tpu.pipeline import process_chunks
    from pbccs_tpu.simulate import simulate_zmw

    chunks = []
    for i in range(4):
        _, reads, _, snr = simulate_zmw(rng, 100, 6 if i != 1 else 2)
        chunks.append(Chunk(
            f"serve/{i}",
            [Subread(f"serve/{i}/{k}", r) for k, r in enumerate(reads)],
            snr))
    offline = process_chunks(list(chunks))
    off_by_id = {r.id: r for r in offline.results}

    with CcsEngine(config=ServeConfig(max_batch=4,
                                      max_wait_ms=60_000.0)) as eng:
        reqs = [eng.submit(c) for c in chunks]
        for req in reqs:
            assert req.wait(600.0)
    statuses = {r.chunk.id: r.failure for r in reqs}
    assert statuses["serve/1"] == Failure.TOO_FEW_PASSES
    for req in reqs:
        assert req.error is None
        if req.failure == Failure.SUCCESS:
            off = off_by_id[req.chunk.id]
            assert req.result.sequence == off.sequence
            np.testing.assert_array_equal(req.result.qvs, off.qvs)
    assert sum(1 for r in reqs if r.failure == Failure.SUCCESS) == \
        len(off_by_id) == 3
