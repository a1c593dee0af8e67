"""Resilience subsystem tests: fault injection, retry, quarantine
bisection, watchdog, checkpoint journal, and the serve-side retry/
watchdog integrations.

The unit layers (faults/retry/watchdog/checkpoint/bisection control
flow) run with stubs and no device work; two pipeline-level tests pin
the batch-fallback parity contract -- a poisoned batch must yield
byte-identical results for every surviving ZMW, on both the bisection
path and the legacy serial path -- against the real polish core.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from pbccs_tpu.obs.metrics import default_registry
from pbccs_tpu.pipeline import (
    Chunk,
    ConsensusResult,
    ConsensusSettings,
    Failure,
    MappedRead,
    PreparedZmw,
    Subread,
)
from pbccs_tpu.resilience import (checkpoint, faults, quarantine, resources,
                                  retry, watchdog)
from pbccs_tpu.resilience.faults import FaultSpecError, InjectedFault
from pbccs_tpu.resilience.resources import (HostBudget, MemoryGovernor,
                                            OutputWriteError, parse_size,
                                            shape_bucket, split_sizes)

# ----------------------------------------------------------------- helpers


def make_chunk(zmw_id="m/1", n_reads=4, length=20):
    seq = np.arange(length, dtype=np.int8) % 4
    return Chunk(zmw_id,
                 [Subread(f"{zmw_id}/{i}", seq.copy())
                  for i in range(n_reads)],
                 np.full(4, 8.0))


def make_prep(zmw_id="m/1", tpl_len=24, n_reads=3):
    chunk = make_chunk(zmw_id, n_reads=n_reads, length=tpl_len)
    css = np.arange(tpl_len, dtype=np.int8) % 4
    mapped = [MappedRead(r.id, r.seq, 0, 0, tpl_len, True)
              for r in chunk.reads]
    return PreparedZmw(chunk, css, mapped, n_reads, 0, 1.5)


def fake_result(zmw_id, sequence="ACGT"):
    return ConsensusResult(
        id=zmw_id, sequence=sequence,
        qvs=np.full(len(sequence), 40.0), num_passes=4,
        predicted_accuracy=0.999, global_zscore=0.1, avg_zscore=0.2,
        zscores=np.array([0.5, np.nan]), status_counts=[2, 0, 1, 0, 0],
        mutations_tested=7, mutations_applied=3, snr=np.full(4, 8.0),
        elapsed_ms=1.25)


# ------------------------------------------------------------------- faults


class TestFaults:
    def test_parse_grammar(self):
        specs = faults.parse_faults(
            "polish.dispatch:error~m/3,prep.zmw:delay=0.5@2*1,"
            "checkpoint.record:corrupt%0.25")
        assert [s.site for s in specs] == ["polish.dispatch", "prep.zmw",
                                           "checkpoint.record"]
        assert specs[0].kind == "error" and specs[0].key == "m/3"
        assert specs[1].kind == "delay" and specs[1].delay_s == 0.5
        assert specs[1].at == 2 and specs[1].times == 1
        assert specs[2].kind == "corrupt" and specs[2].prob == 0.25

    @pytest.mark.parametrize("bad", ["nosite", "site:frobnicate",
                                     "site:error@x"])
    def test_parse_rejects(self, bad):
        with pytest.raises(FaultSpecError):
            faults.parse_faults(bad)

    def test_key_selects_poison(self):
        inj = faults.FaultInjector("polish.dispatch:error~m/2")
        inj.maybe_fail("polish.dispatch", keys=["m/1", "m/3"])  # no match
        inj.maybe_fail("other.site", keys=["m/2"])              # other site
        with pytest.raises(InjectedFault):
            inj.maybe_fail("polish.dispatch", keys=["m/1", "m/2"])
        assert inj.fired("polish.dispatch") == 1

    def test_at_and_times_modifiers(self):
        inj = faults.FaultInjector("s:error@2*1")
        inj.maybe_fail("s")                    # call 1: not yet
        with pytest.raises(InjectedFault):
            inj.maybe_fail("s")                # call 2: fires
        inj.maybe_fail("s")                    # call 3: exhausted
        assert inj.fired() == 1

    def test_probability_is_seed_deterministic(self):
        def fire_pattern(seed):
            inj = faults.FaultInjector("s:error%0.5", seed=seed)
            pat = []
            for _ in range(20):
                try:
                    inj.maybe_fail("s")
                    pat.append(0)
                except InjectedFault:
                    pat.append(1)
            return pat

        assert fire_pattern(7) == fire_pattern(7)
        assert fire_pattern(7) != fire_pattern(8)
        assert 0 < sum(fire_pattern(7)) < 20

    def test_corrupt_bytes_and_array(self):
        inj = faults.FaultInjector("c:corrupt")
        data = b"0123456789"
        bad = inj.corrupt("c", data)
        assert bad != data and len(bad) == len(data)
        arr = np.zeros(8, np.int8)
        bad_arr = inj.corrupt("c", arr)
        assert (bad_arr != arr).any()
        assert (arr == 0).all()  # input untouched
        # unarmed site is identity
        assert inj.corrupt("other", data) is data

    def test_module_level_noop_and_active_scope(self):
        faults.maybe_fail("anywhere", keys=["x"])  # no injector: no-op
        with faults.active("s:error"):
            with pytest.raises(InjectedFault):
                faults.maybe_fail("s")
        faults.maybe_fail("s")  # restored

    def test_injected_fault_metric(self):
        scope = default_registry().scope()
        with faults.active("s:error*2"):
            for _ in range(3):
                try:
                    faults.maybe_fail("s")
                except InjectedFault:
                    pass
        assert scope.counter_value("ccs_faults_injected_total",
                                   site="s", kind="error") == 2


# -------------------------------------------------------------------- retry


class TestRetry:
    def test_delays_backoff_and_cap(self):
        pol = retry.RetryPolicy(max_attempts=5, base_delay_s=0.1,
                                max_delay_s=0.3, multiplier=2.0,
                                jitter=0.0)
        assert list(pol.delays()) == [0.1, 0.2, 0.3, 0.3]

    def test_jitter_is_rng_deterministic(self):
        pol = retry.RetryPolicy(max_attempts=4, jitter=0.5)
        a = list(pol.delays(np.random.default_rng(3)))
        b = list(pol.delays(np.random.default_rng(3)))
        assert a == b
        assert a != list(pol.delays(np.random.default_rng(4)))

    def test_run_retries_then_succeeds(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise RuntimeError("transient blip")
            return "ok"

        scope = default_registry().scope()
        pol = retry.RetryPolicy(max_attempts=4, base_delay_s=0.0)
        assert pol.run(flaky, retry_on=lambda e: "transient" in str(e),
                       site="test.retry") == "ok"
        assert len(calls) == 3
        assert scope.counter_value("ccs_retries_total",
                                   site="test.retry") == 2

    def test_run_propagates_non_retryable(self):
        pol = retry.RetryPolicy(max_attempts=5, base_delay_s=0.0)
        with pytest.raises(ValueError):
            pol.run(lambda: (_ for _ in ()).throw(ValueError("poison")),
                    retry_on=lambda e: False)

    def test_run_exhausts_with_cause(self):
        pol = retry.RetryPolicy(max_attempts=2, base_delay_s=0.0)
        with pytest.raises(retry.RetriesExhausted) as ei:
            pol.run(lambda: (_ for _ in ()).throw(RuntimeError("always")),
                    retry_on=lambda e: True)
        assert isinstance(ei.value.__cause__, RuntimeError)

    def test_deadline_bounds_total_wall(self):
        slept = []
        pol = retry.RetryPolicy(max_attempts=10, base_delay_s=5.0,
                                jitter=0.0, deadline_s=1.0)
        with pytest.raises(retry.RetriesExhausted):
            pol.run(lambda: (_ for _ in ()).throw(RuntimeError("x")),
                    retry_on=lambda e: True, sleep=slept.append)
        assert slept == []  # first 5 s backoff already busts the deadline

    def test_transient_classifier(self):
        # RESOURCE_EXHAUSTED is CAPACITY-shaped, never transient: a
        # same-shape retry of an OOM cannot succeed, so the adaptive
        # split path owns it (resilience.resources)
        assert not retry.is_transient_device_error(
            RuntimeError("RESOURCE_EXHAUSTED: out of memory"))
        assert resources.is_capacity_error(
            RuntimeError("RESOURCE_EXHAUSTED: out of memory"))
        assert retry.is_transient_device_error(
            RuntimeError("UNAVAILABLE: device preempted"))
        assert retry.is_transient_device_error(
            InjectedFault("polish.dispatch", "transient"))
        assert not retry.is_transient_device_error(
            ValueError("bad template"))
        assert not retry.is_transient_device_error(
            watchdog.WatchdogTimeout("polish.dispatch", 3.0))


# ----------------------------------------------------------------- watchdog


class TestWatchdog:
    def test_disabled_runs_inline(self):
        tid = threading.get_ident()
        assert watchdog.run_with_deadline(
            threading.get_ident, 0) == tid

    def test_result_and_exception_pass_through(self):
        assert watchdog.run_with_deadline(lambda: 42, 5.0) == 42
        with pytest.raises(ValueError):
            watchdog.run_with_deadline(
                lambda: (_ for _ in ()).throw(ValueError("boom")), 5.0)

    def test_timeout_raises_structured(self):
        scope = default_registry().scope()
        release = threading.Event()
        with pytest.raises(watchdog.WatchdogTimeout) as ei:
            watchdog.run_with_deadline(lambda: release.wait(30.0), 0.1,
                                       site="test.hang")
        release.set()  # unblock the abandoned thread
        assert ei.value.site == "test.hang"
        assert scope.counter_value("ccs_watchdog_timeouts_total",
                                   site="test.hang") == 1

    def test_configure_overrides_env(self):
        watchdog.configure(1.5)
        try:
            assert watchdog.default_deadline_s() == 1.5
        finally:
            watchdog.configure(None)
        assert os.environ.get("PBCCS_WATCHDOG_S") is None \
            or watchdog.default_deadline_s() >= 0


# --------------------------------------------------------------- quarantine


class TestQuarantineBisection:
    def run_isolate(self, n, poison_ids, settings=None):
        preps = [make_prep(f"m/{i}") for i in range(n)]
        dispatched = []

        def dispatch(sub):
            dispatched.append(len(sub))
            if any(p.chunk.id in poison_ids for p in sub):
                raise RuntimeError("poisoned sub-batch")
            return [(Failure.SUCCESS, fake_result(p.chunk.id))
                    for p in sub]

        def serial(prep, s, exc):
            if prep.chunk.id in poison_ids:
                return quarantine.quarantine_outcome(
                    prep, s or ConsensusSettings(), exc)
            return (Failure.SUCCESS, fake_result(prep.chunk.id))

        out = quarantine.isolate(
            preps, dispatch, settings or ConsensusSettings(),
            RuntimeError("batch failed"), serial_fn=serial)
        return out, dispatched

    def test_single_poison_isolated(self):
        out, dispatched = self.run_isolate(8, {"m/5"})
        assert [o[0] for o in out] == [Failure.SUCCESS] * 5 + \
            [Failure.OTHER] + [Failure.SUCCESS] * 2
        assert all(o[1].id == f"m/{i}" for i, o in enumerate(out)
                   if o[1] is not None)
        # log2 isolation: far fewer sub-dispatches than the serial O(n)
        assert len(dispatched) <= 2 * 3  # 2 halves per level, 3 levels

    def test_multiple_poisons(self):
        out, _ = self.run_isolate(8, {"m/0", "m/7"})
        statuses = [o[0] for o in out]
        assert statuses[0] == statuses[7] == Failure.OTHER
        assert statuses[1:7] == [Failure.SUCCESS] * 6

    def test_all_poison(self):
        out, _ = self.run_isolate(4, {f"m/{i}" for i in range(4)})
        assert all(o == (Failure.OTHER, None) for o in out)

    def test_degrade_emits_draft(self):
        out, _ = self.run_isolate(
            4, {"m/2"}, ConsensusSettings(degrade_quarantined=True))
        failure, result = out[2]
        assert failure == Failure.SUCCESS
        assert result.draft_only and result.id == "m/2"

    def test_quarantine_metrics(self):
        scope = default_registry().scope()
        self.run_isolate(8, {"m/3"})
        assert scope.counter_value("ccs_quarantined_zmws_total") == 1
        self.run_isolate(4, {"m/1"},
                         ConsensusSettings(degrade_quarantined=True))
        assert scope.counter_value("ccs_degraded_zmws_total") == 1


class TestSerialRescue:
    def test_persistent_hang_quarantines_not_stalls(self):
        """A ZMW whose polish hangs EVERY time (not just once) must end
        quarantined: the serial rescue runs under the same ambient
        watchdog deadline as the batch dispatch, so the run's last
        re-polish cannot stall forever."""
        prep = make_prep("m/0")
        # low SNR: the abandoned (hung) thread's eventual process_chunk
        # exits instantly at the SNR gate instead of polishing
        prep.chunk.snr = np.full(4, 1.0)
        watchdog.configure(0.2)
        try:
            with faults.active("polish.dispatch:delay=5~m/0"):
                t0 = time.monotonic()
                failure, result = quarantine.serial_rescue(
                    prep, ConsensusSettings(), RuntimeError("batch"))
                assert time.monotonic() - t0 < 2.0  # did not wait out 5 s
        finally:
            watchdog.configure(None)
        assert failure == Failure.OTHER and result is None


class TestDegradeToDraft:
    def test_draft_consensus_shape(self):
        prep = make_prep("m/9", tpl_len=16, n_reads=3)
        failure, result = quarantine.degrade_to_draft(
            prep, ConsensusSettings())
        assert failure == Failure.SUCCESS
        assert result.draft_only
        assert len(result.sequence) == 16
        assert (np.asarray(result.qvs) == quarantine.DRAFT_QV_CAP).all()
        assert result.num_passes == 3
        assert 0.89 < result.predicted_accuracy < 0.91
        assert np.isnan(result.global_zscore)


# --------------------------------------------------------------- checkpoint


class TestCheckpoint:
    def test_result_round_trip(self):
        r = fake_result("m/1", "ACGTA")
        back = checkpoint.result_from_json(
            json.loads(json.dumps(checkpoint.result_to_json(r))))
        assert back.id == r.id and back.sequence == r.sequence
        assert back.qualities == r.qualities
        np.testing.assert_array_equal(back.qvs, r.qvs)
        np.testing.assert_array_equal(back.status_counts, r.status_counts)
        # NaN z-scores survive
        assert np.isnan(back.zscores[1]) and back.zscores[0] == 0.5
        assert back.draft_only == r.draft_only

    def make_tally(self, ids):
        from pbccs_tpu.pipeline import ResultTally

        tally = ResultTally()
        for zid in ids:
            tally.tally(Failure.SUCCESS)
            tally.results.append(fake_result(zid))
        tally.tally(Failure.POOR_SNR)
        return tally

    def test_journal_round_trip(self, tmp_path):
        path = str(tmp_path / "j.ckpt")
        fp = {"version": 1, "inputs": [["a", 10]], "chunk_size": 2}
        j = checkpoint.CheckpointJournal(path)
        j.start(fp, resume=False)
        j.record_chunk(0, self.make_tally(["m/0", "m/1"]))
        j.record_chunk(1, self.make_tally(["m/2"]))
        j.close()

        restored = checkpoint.CheckpointJournal(path).load(fp)
        assert sorted(restored) == [0, 1]
        assert [r.id for r in restored[0].results] == ["m/0", "m/1"]
        assert restored[1].counts[Failure.SUCCESS] == 1
        assert restored[1].counts[Failure.POOR_SNR] == 1

    def test_fingerprint_mismatch_refuses(self, tmp_path):
        path = str(tmp_path / "j.ckpt")
        j = checkpoint.CheckpointJournal(path)
        j.start({"chunk_size": 2}, resume=False)
        j.record_chunk(0, self.make_tally(["m/0"]))
        j.close()
        assert checkpoint.CheckpointJournal(path).load(
            {"chunk_size": 4}) == {}

    def test_torn_and_corrupt_records_dropped(self, tmp_path):
        path = str(tmp_path / "j.ckpt")
        fp = {"chunk_size": 2}
        j = checkpoint.CheckpointJournal(path)
        j.start(fp, resume=False)
        j.record_chunk(0, self.make_tally(["m/0"]))
        j.record_chunk(1, self.make_tally(["m/1"]))
        j.close()
        # tear the LAST record mid-line (kill -9 mid-write)
        data = open(path, "rb").read()
        open(path, "wb").write(data[: data.rindex(b'{"type": "chunk"') + 40])
        scope = default_registry().scope()
        restored = checkpoint.CheckpointJournal(path).load(fp)
        assert sorted(restored) == [0]
        assert scope.counter_value("ccs_checkpoint_records_total",
                                   kind="corrupt") == 1

    def test_corrupt_fault_site(self, tmp_path):
        path = str(tmp_path / "j.ckpt")
        fp = {"chunk_size": 2}
        with faults.active("checkpoint.record:corrupt@2"):
            j = checkpoint.CheckpointJournal(path)
            j.start(fp, resume=False)              # record 1: header
            j.record_chunk(0, self.make_tally(["m/0"]))  # record 2: corrupt
            j.record_chunk(1, self.make_tally(["m/1"]))
            j.close()
        restored = checkpoint.CheckpointJournal(path).load(fp)
        assert sorted(restored) == [1]  # chunk 0 dropped, recomputable

    def test_fingerprint_tracks_same_size_content_change(self, tmp_path):
        """A regenerated same-size input must refuse the resume (mtime
        is part of the fingerprint): a refused resume only recomputes,
        a wrong splice silently mixes two datasets."""
        f = tmp_path / "in.fasta"
        f.write_text(">a\nACGT\n")
        fp1 = checkpoint.run_fingerprint([str(f)], 2, ConsensusSettings())
        os.utime(f, ns=(1, 1))  # same path + size, different mtime
        fp2 = checkpoint.run_fingerprint([str(f)], 2, ConsensusSettings())
        assert fp1 != fp2

    def test_resume_appends_and_last_record_wins(self, tmp_path):
        path = str(tmp_path / "j.ckpt")
        fp = {"chunk_size": 2}
        j = checkpoint.CheckpointJournal(path)
        j.start(fp, resume=False)
        j.record_chunk(0, self.make_tally(["m/0"]))
        j.close()
        j2 = checkpoint.CheckpointJournal(path)
        assert sorted(j2.load(fp)) == [0]
        j2.start(fp, resume=True)
        j2.record_chunk(0, self.make_tally(["m/0x"]))  # re-journal
        j2.record_chunk(1, self.make_tally(["m/1"]))
        j2.close()
        restored = checkpoint.CheckpointJournal(path).load(fp)
        assert sorted(restored) == [0, 1]
        assert [r.id for r in restored[0].results] == ["m/0x"]


# ---------------------------------------- resource-exhaustion governance


class TestCapacityClassification:
    def test_capacity_markers(self):
        assert resources.is_capacity_error(
            RuntimeError("RESOURCE_EXHAUSTED: Attempting to allocate"))
        assert resources.is_capacity_error(MemoryError())
        assert resources.is_capacity_error(
            RuntimeError("Out of memory allocating 2.1G in HBM"))
        assert resources.is_capacity_error(
            InjectedFault("sched.dispatch", "RESOURCE_EXHAUSTED"))
        assert not resources.is_capacity_error(ValueError("bad template"))
        assert not resources.is_capacity_error(
            RuntimeError("UNAVAILABLE: preempted"))

    def test_oom_fault_kind_is_capacity_not_transient(self):
        with faults.active("sched.dispatch:oom@1"):
            with pytest.raises(InjectedFault) as ei:
                faults.maybe_fail("sched.dispatch", keys=["cpu:0"])
        assert resources.is_capacity_error(ei.value)
        assert not retry.is_transient_device_error(ei.value)

    def test_enospc_fault_kind_raises_real_oserror(self):
        with faults.active("checkpoint.record:enospc@1"):
            with pytest.raises(OSError) as ei:
                faults.maybe_fail("checkpoint.record", keys=["chunk"])
        import errno

        assert ei.value.errno == errno.ENOSPC

    def test_grammar_accepts_new_kinds(self):
        specs = faults.parse_faults(
            "sched.dispatch:oom@1*1,output.write:enospc~bam@2")
        assert [s.kind for s in specs] == ["oom", "enospc"]
        with pytest.raises(FaultSpecError):
            faults.parse_faults("site:eNoSpC")


class TestMemoryGovernor:
    def test_ceiling_learn_and_apply(self):
        gov = MemoryGovernor()
        b = shape_bucket(128, 256, 8)
        assert gov.cap(b) is None
        assert gov.record_oom(b, 64, device="tpu:0") == 32
        assert gov.cap(b, device="tpu:0") == 32
        # a device with no own record inherits the fleet minimum
        # (pessimistic warm start, no per-device re-discovery)
        assert gov.cap(b, device="tpu:1") == 32
        assert gov.cap(b) == 32
        # ceilings only ever lower: a later SMALLER OOM tightens, a
        # later larger one cannot loosen
        assert gov.record_oom(b, 16, device="tpu:0") == 8
        assert gov.record_oom(b, 100, device="tpu:0") == 8
        assert gov.cap(b, device="tpu:0") == 8
        # an unrelated bucket is unaffected
        assert gov.cap(shape_bucket(64, 128, 4)) is None

    @pytest.mark.parametrize("limit,bucket,want", [
        # one v5e chip (15.75 GiB): the CLI's default 64-ZMW batch at
        # 2 kb x 12 reads does not fit, half of it does
        (16911433728, (2560, 2240, 12), 32),
        (16911433728, (640, 576, 8), 128),
        (16911433728, (15360, 15104, 4), 16),
        # a bucket one ZMW of which outgrows the device still dispatches
        (1 << 20, (2560, 2240, 12), 1),
        # no reported limit (the CPU backend): no modelled ceiling
        (None, (2560, 2240, 12), None),
    ])
    def test_modelled_cap_from_device_memory(self, monkeypatch, limit,
                                             bucket, want):
        from pbccs_tpu.resilience import resources

        monkeypatch.setattr(resources, "device_bytes_limit", lambda: limit)
        b = shape_bucket(*bucket)
        assert resources.modelled_cap(b) == want
        gov = MemoryGovernor()
        assert gov.cap(b) == want
        # keys of other shapes (the pool's opaque task keys) get none
        assert resources.modelled_cap(("other", 1)) is None
        if want and want > 1:
            # a learned ceiling only ever lowers the modelled one
            assert gov.record_oom(b, want, device="tpu:0") == want // 2
            assert gov.cap(b, device="tpu:0") == want // 2
            assert gov.record_oom(b, 8 * want, device="tpu:1") == 4 * want
            assert gov.cap(b, device="tpu:1") == want

    def test_ceiling_reset_on_device_readmit(self):
        gov = MemoryGovernor()
        b = shape_bucket(128, 256, 8)
        gov.record_oom(b, 64, device="tpu:0")
        gov.record_oom(b, 32, device="tpu:1")
        assert gov.reset_device("tpu:0") == 1
        # the re-admitted device re-learns; until then it inherits the
        # surviving fleet minimum
        assert gov.cap(b, device="tpu:0") == 16
        assert gov.reset_device("tpu:1") == 1
        assert gov.cap(b) is None
        assert gov.reset_device("tpu:1") == 0

    def test_split_sizes_greedy_minimizes_pow2_padding(self):
        # cap-sized parts are pow2 (a ceiling is Z//2 of a pow2
        # dispatch) and pad nothing; only the remainder is ragged
        assert split_sizes(10, 4) == [4, 4, 2]
        assert split_sizes(4, 4) == [4]
        assert split_sizes(5, 4) == [4, 1]
        assert split_sizes(12, 8) == [8, 4]
        assert split_sizes(1, 3) == [1]
        assert sum(split_sizes(1023, 64)) == 1023
        assert max(split_sizes(1023, 64)) == 64
        with pytest.raises(ValueError):
            split_sizes(4, 0)

    def test_device_scope_thread_local(self):
        assert resources.current_device() == "host"
        with resources.device_scope("tpu:3"):
            assert resources.current_device() == "tpu:3"
            seen = []
            t = threading.Thread(
                target=lambda: seen.append(resources.current_device()))
            t.start()
            t.join()
            assert seen == ["host"]   # scope never leaks across threads
        assert resources.current_device() == "host"


class TestHostBudget:
    def test_parse_size(self):
        assert parse_size("8G") == 8 << 30
        assert parse_size("512M") == 512 << 20
        assert parse_size("1.5K") == 1536
        assert parse_size("12345") == 12345
        assert parse_size("2GiB") == 2 << 30
        with pytest.raises(ValueError):
            parse_size("eight gigs")

    def test_gate_blocks_until_release(self):
        b = HostBudget(100)
        first = b.admit(80, site="t")
        got = []
        t = threading.Thread(
            target=lambda: got.append(b.admit(50, site="t")))
        t.start()
        time.sleep(0.15)
        assert not got                      # parked: 80 + 50 > 100
        first.release()
        t.join(timeout=5.0)
        assert got and got[0] is not None
        assert b.in_use() == 50
        assert b.throttle_count() == 1
        got[0].release()
        assert b.in_use() == 0

    def test_oversize_charge_admits_alone(self):
        b = HostBudget(10)
        lease = b.admit(500, site="t")
        assert lease is not None and b.in_use() == 500
        lease.release()

    def test_abort_unblocks_waiter(self):
        b = HostBudget(10)
        hold = b.admit(10, site="t")
        flag = threading.Event()
        got = []
        t = threading.Thread(
            target=lambda: got.append(
                b.admit(5, site="t", abort=flag.is_set)))
        t.start()
        time.sleep(0.1)
        flag.set()
        t.join(timeout=5.0)
        assert got == [None]                # aborted, nothing charged
        assert b.in_use() == 10
        hold.release()

    def test_release_idempotent(self):
        b = HostBudget(100)
        lease = b.admit(60, site="t")
        lease.release()
        lease.release()
        assert b.in_use() == 0

    def test_rejects_nonpositive_budget(self):
        with pytest.raises(ValueError):
            HostBudget(0)


class TestDiskFullWriters:
    def _records(self):
        from pbccs_tpu.io.bam import BamRecord

        return [BamRecord(name=f"m/{i}/ccs", seq="ACGTACGT",
                          qual="IIIIIIII", tags={"zm": i})
                for i in range(3)]

    def _write_all(self, path):
        from pbccs_tpu.io.bam import BamHeader, BamWriter, ReadGroupInfo

        header = BamHeader(read_groups=[ReadGroupInfo("m", "CCS")])
        with BamWriter(str(path), header) as bw:
            for rec in self._records():
                bw.write(rec)

    def test_bam_enospc_structured_and_rewrite_identical(self, tmp_path):
        control = tmp_path / "control.bam"
        self._write_all(control)
        out = tmp_path / "out.bam"
        scope = default_registry().scope()
        # header write is eligible call 1; fail on the 3rd write
        with faults.active("output.write:enospc@3*1"):
            with pytest.raises(OutputWriteError) as ei:
                self._write_all(out)
        assert ei.value.sink == "bam"
        import errno

        assert ei.value.errno == errno.ENOSPC
        # atomic: neither a torn output nor a leftover temp is published
        assert not out.exists()
        assert not (tmp_path / "out.bam.tmp").exists()
        assert scope.counter_value("ccs_output_write_errors_total",
                                   sink="bam") == 1
        # disk "freed": the rewrite is byte-identical to the control
        self._write_all(out)
        assert out.read_bytes() == control.read_bytes()

    def test_bam_body_exception_discards_tmp(self, tmp_path):
        from pbccs_tpu.io.bam import BamHeader, BamWriter, ReadGroupInfo

        out = tmp_path / "out.bam"
        with pytest.raises(RuntimeError, match="boom"):
            with BamWriter(str(out),
                           BamHeader(read_groups=[
                               ReadGroupInfo("m", "CCS")])) as bw:
                bw.write(self._records()[0])
                raise RuntimeError("boom")
        assert not out.exists()
        assert not (tmp_path / "out.bam.tmp").exists()

    def test_report_enospc_atomic(self, tmp_path):
        from pbccs_tpu.io.report import write_report_file
        from pbccs_tpu.pipeline import ResultTally

        tally = ResultTally()
        tally.tally(Failure.SUCCESS)
        path = tmp_path / "report.csv"
        with faults.active("output.write:enospc~report@1*1"):
            with pytest.raises(OutputWriteError) as ei:
                write_report_file(str(path), tally)
        assert ei.value.sink == "report"
        assert not path.exists()
        assert not (tmp_path / "report.csv.tmp").exists()
        write_report_file(str(path), tally)
        assert "Success -- CCS generated,1" in path.read_text()


class TestCheckpointDiskFull:
    def _tallies(self):
        from pbccs_tpu.pipeline import ResultTally

        out = []
        for i in range(3):
            t = ResultTally()
            t.tally(Failure.SUCCESS)
            t.results.append(fake_result(f"m/{i}"))
            out.append(t)
        return out

    def _restore_map(self, path, fp):
        restored = checkpoint.CheckpointJournal(str(path)).load(fp)
        return {i: [r.id for r in t.results] for i, t in restored.items()}

    def test_enospc_mid_record_then_resume_byte_identity(self, tmp_path):
        fp = {"v": 1}
        tallies = self._tallies()
        control = tmp_path / "control.ndjson"
        j = checkpoint.CheckpointJournal(str(control))
        j.start(fp, resume=False)
        for i, t in enumerate(tallies):
            j.record_chunk(i, t)
        j.close()
        want = self._restore_map(control, fp)

        path = tmp_path / "run.ndjson"
        j = checkpoint.CheckpointJournal(str(path))
        j.start(fp, resume=False)
        j.record_chunk(0, tallies[0])
        # disk fills while appending chunk 1: structured error with
        # bytes-written accounting, journal keeps its complete prefix
        with faults.active("checkpoint.record:enospc@1*1"):
            with pytest.raises(OutputWriteError) as ei:
                j.record_chunk(1, tallies[1])
        assert ei.value.sink == "checkpoint"
        # bytes-written accounting: exactly the durable prefix on disk
        assert ei.value.bytes_written == path.stat().st_size
        # emulate the short write a real ENOSPC leaves: a torn partial
        # line at the tail (no newline)
        with open(path, "ab") as fh:
            fh.write(b'{"type":"chunk","index":1,"cou')

        # space freed -> resume: the torn tail is dropped AND trimmed,
        # the rerun journals the missing chunks, and the final restore
        # set equals the uninterrupted run's
        j2 = checkpoint.CheckpointJournal(str(path))
        restored = j2.load(fp)
        assert sorted(restored) == [0]
        j2.start(fp, resume=True)
        for i in (1, 2):
            j2.record_chunk(i, tallies[i])
        j2.close()
        assert self._restore_map(path, fp) == want
        # every journal line parses (the torn tail did not concatenate
        # into the resumed records)
        for line in path.read_bytes().splitlines():
            json.loads(line)

    def test_close_reraise_does_not_clobber_structured_error(
            self, tmp_path):
        """A REAL full disk raises from flush() with bytes parked in
        the BufferedWriter; the teardown close() re-flushes and raises
        the same ENOSPC -- which must not replace the structured
        OutputWriteError with a raw OSError traceback."""
        import errno

        class FullDiskFile:
            def __init__(self, fh):
                self._fh = fh

            def write(self, data):       # buffers fine, like a real fd
                return len(data)

            def tell(self):
                return 0

            def flush(self):
                raise OSError(errno.ENOSPC, "No space left on device")

            def close(self):             # close re-flushes -> re-raises
                raise OSError(errno.ENOSPC, "No space left on device")

        path = tmp_path / "full.ndjson"
        j = checkpoint.CheckpointJournal(str(path))
        j.start({"v": 1}, resume=False)
        real_fh = j._fh
        j._fh = FullDiskFile(real_fh)
        try:
            with pytest.raises(OutputWriteError) as ei:
                j.record_chunk(0, self._tallies()[0])
        finally:
            real_fh.close()
        assert ei.value.sink == "checkpoint"
        assert j._fh is None             # handle dropped, journal kept

    def test_trim_noop_on_clean_journal(self, tmp_path):
        fp = {"v": 1}
        path = tmp_path / "clean.ndjson"
        j = checkpoint.CheckpointJournal(str(path))
        j.start(fp, resume=False)
        j.record_chunk(0, self._tallies()[0])
        j.close()
        before = path.read_bytes()
        j2 = checkpoint.CheckpointJournal(str(path))
        j2.load(fp)
        j2.start(fp, resume=True)
        j2.close()
        assert path.read_bytes() == before


class TestOomAdaptiveDispatch:
    """polish_prepared_batch's capacity governance, with the device
    dispatch stubbed: a RESOURCE_EXHAUSTED at batch size Z must split
    (pinned shapes, outcomes aligned), record a governor ceiling, and
    pre-split the NEXT batch for the bucket at admission -- never a
    same-shape retry loop, never quarantine of healthy ZMWs."""

    @pytest.fixture(autouse=True)
    def fresh_governor(self, monkeypatch):
        monkeypatch.setattr(resources, "_default_governor",
                            MemoryGovernor())

    def _preps(self, n):
        return [make_prep(f"m/{i}") for i in range(n)]

    def test_oom_splits_and_records_ceiling(self, monkeypatch):
        from pbccs_tpu import pipeline

        sizes = []

        def stub_dispatch(preps, settings, *, buckets=None, min_z=1,
                          fixed_z=False, prebaked=None):
            sizes.append(len(preps))
            if len(preps) > 2:
                raise RuntimeError("RESOURCE_EXHAUSTED: out of memory "
                                   "allocating scratch")
            return [(Failure.SUCCESS, None) for _ in preps]

        monkeypatch.setattr(pipeline, "_guarded_dispatch", stub_dispatch)
        scope = default_registry().scope()
        out = pipeline.polish_prepared_batch(self._preps(6))
        assert len(out) == 6
        assert all(f == Failure.SUCCESS for f, _ in out)
        # 6 OOMs -> 3+3, each OOMs -> 1+2, 2+1 -- no same-shape retry
        assert sizes[0] == 6 and max(sizes[1:]) <= 3
        assert scope.counter_value("ccs_resource_oom_splits_total") >= 1
        assert scope.counter_value("ccs_resource_oom_ceilings_total") >= 1
        gov = resources.default_governor()
        assert gov.snapshot()        # a ceiling was recorded
        # the NEXT batch for this bucket pre-splits at admission: no
        # dispatch bigger than the learned ceiling, no new OOM
        sizes.clear()
        out2 = pipeline.polish_prepared_batch(self._preps(6))
        assert len(out2) == 6
        assert max(sizes) <= 2
        assert scope.counter_value(
            "ccs_resource_presplit_batches_total") >= 1

    def test_oom_singleton_serial_rescue_not_retry(self, monkeypatch):
        from pbccs_tpu import pipeline

        rescued = []

        def stub_dispatch(preps, settings, **kw):
            raise RuntimeError("RESOURCE_EXHAUSTED: always")

        def stub_rescue(prep, settings, exc):
            rescued.append(prep.chunk.id)
            return (Failure.OTHER, None)

        monkeypatch.setattr(pipeline, "_guarded_dispatch", stub_dispatch)
        monkeypatch.setattr(quarantine, "serial_rescue", stub_rescue)
        out = pipeline.polish_prepared_batch(self._preps(4))
        assert len(out) == 4
        assert all(f == Failure.OTHER for f, _ in out)
        assert sorted(rescued) == [f"m/{i}" for i in range(4)]

    def test_injected_oom_at_polish_dispatch_splits(self, monkeypatch):
        """The fault grammar's oom kind at polish.dispatch drives the
        same path as a real device OOM: one split, zero quarantined."""
        from pbccs_tpu import pipeline

        sizes = []

        def spy(preps, settings, **kw):
            sizes.append(len(preps))
            return [(Failure.SUCCESS, None) for _ in preps]

        monkeypatch.setattr(pipeline, "_polish_batch_arrow", spy)
        scope = default_registry().scope()
        with faults.active("polish.dispatch:oom@1*1"):
            out = pipeline.polish_prepared_batch(self._preps(4))
        assert len(out) == 4
        assert all(f == Failure.SUCCESS for f, _ in out)
        assert sizes == [2, 2]      # split halves, no same-shape retry
        assert scope.counter_value("ccs_quarantined_zmws_total") == 0
        assert scope.counter_value("ccs_resource_oom_splits_total") == 1
        assert scope.counter_value(
            "ccs_retries_total", site="polish.dispatch") == 0


class TestPoolCapacityHandling:
    @pytest.fixture(autouse=True)
    def fresh_governor(self, monkeypatch):
        monkeypatch.setattr(resources, "_default_governor",
                            MemoryGovernor())

    def test_capacity_failure_requeues_same_device_no_strike(self):
        from pbccs_tpu.sched.pool import DevicePool

        bucket = shape_bucket(64, 128, 4)
        calls = []

        def flaky(device):
            calls.append(resources.current_device())
            if len(calls) == 1:
                raise RuntimeError("RESOURCE_EXHAUSTED: HBM full")
            return "ok"

        with DevicePool() as pool:
            fut = pool.submit("k", flaky, zmws=8, capacity_bucket=bucket)
            assert fut.result(timeout=30.0) == "ok"
            st = pool.status()
        # requeued to the SAME device, which was neither struck nor
        # benched (capacity != sick hardware)
        assert len(set(calls)) == 1 and len(calls) == 2
        assert st["devices"][0]["strikes"] == 0
        assert not st["devices"][0]["benched"]
        gov = resources.default_governor()
        assert gov.cap(bucket, device=calls[0]) == 4

    def test_injected_sched_oom_records_ceiling(self):
        from pbccs_tpu.sched.pool import DevicePool

        bucket = shape_bucket(64, 128, 4)
        scope = default_registry().scope()
        with faults.active("sched.dispatch:oom@1*1"):
            with DevicePool() as pool:
                fut = pool.submit("k", lambda device: "ok", zmws=6,
                                  capacity_bucket=bucket)
                assert fut.result(timeout=30.0) == "ok"
                st = pool.status()
        assert st["devices"][0]["strikes"] == 0
        assert scope.counter_value("ccs_resource_oom_splits_total") == 1
        assert scope.counter_value(
            "ccs_sched_device_benched_total",
            device=st["devices"][0]["device"]) == 0
        assert resources.default_governor().cap(bucket) == 3

    def test_capacity_without_bucket_stays_legacy(self):
        from pbccs_tpu.sched.pool import DevicePool

        def always_oom(device):
            raise RuntimeError("RESOURCE_EXHAUSTED: HBM full")

        with DevicePool() as pool:
            fut = pool.submit("k", always_oom, zmws=4)
            with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
                fut.result(timeout=30.0)
        assert resources.default_governor().snapshot() == {}


class TestBudgetedPipeline:
    def test_tight_budget_never_deadlocks(self, monkeypatch):
        """Regression: with prepare workers admitting out of sequence
        order and a budget that fits ~one batch, a release tied to
        ORDERED emission deadlocks (batch N+1's charge fills the budget
        while batch N's prep blocks in admit).  Leases release at
        polish completion, so the run must finish."""
        from pbccs_tpu import pipeline
        from pbccs_tpu.sched.executor import ScheduledPipeline
        from pbccs_tpu.sched.pool import DevicePool

        def stub_prepare(chunks, settings, **span_args):
            from pbccs_tpu.pipeline import ResultTally

            time.sleep(0.01)
            return ResultTally(), [make_prep(c.id) for c in chunks]

        def stub_polish(preps, settings, **kw):
            time.sleep(0.02)
            return [(Failure.SUCCESS, fake_result(p.chunk.id))
                    for p in preps]

        monkeypatch.setattr(pipeline, "prepare_batch", stub_prepare)
        monkeypatch.setattr(pipeline, "polish_prepared_batch",
                            stub_polish)
        monkeypatch.setattr(pipeline, "prebake_polish",
                            lambda preps, **kw: None)
        # budget fits ONE batch's estimate (the deadlock-shaped config)
        from pbccs_tpu.parallel.batch import premarshal_nbytes

        (imax, jmax, r), z = pipeline._pinned_batch_shapes(
            [make_prep("m/0"), make_prep("m/1")], None, 1)
        budget = HostBudget(premarshal_nbytes((imax, jmax, r, z)) + 1)
        items = [(i, [make_chunk(f"m/{2 * i + k}") for k in range(2)],
                  None) for i in range(8)]
        with DevicePool() as pool:
            pipe = ScheduledPipeline(pool, ConsensusSettings(), chunk_zmws=64,
                                     prepare_workers=2, budget=budget)
            got = {}
            done = threading.Event()

            def consume():
                for idx, tally in pipe.run(iter(items)):
                    got[idx] = tally
                done.set()

            t = threading.Thread(target=consume, daemon=True)
            t.start()
            assert done.wait(timeout=60.0), \
                f"pipeline wedged with {len(got)}/8 batches emitted"
            t.join(timeout=5.0)
        assert sorted(got) == list(range(8))
        assert all(t.counts[Failure.SUCCESS] == 2 for t in got.values())
        assert budget.in_use() == 0   # every lease released


class TestEngineGovernedFlush:
    @pytest.fixture(autouse=True)
    def fresh_governor(self, monkeypatch):
        monkeypatch.setattr(resources, "_default_governor",
                            MemoryGovernor())

    def test_flush_pre_splits_at_learned_ceiling(self):
        """A serve flush for a bucket with a learned ceiling dispatches
        as ceiling-sized sub-batches (the fleet-wide conservative cap),
        before any device is picked."""
        from pbccs_tpu.serve.engine import CcsEngine, ServeConfig

        sizes = []

        def spy_polish(preps, settings):
            sizes.append(len(preps))
            return stub_polish(preps, settings)

        # the stub prep geometry: css 64 bases, no mapped reads
        bucket = shape_bucket(64, 128, 4)
        resources.default_governor().record_oom(bucket, 8, device="tpu:9")
        cfg = ServeConfig(max_batch=6, max_wait_ms=10.0)
        with CcsEngine(config=cfg, prep_fn=stub_prep,
                       polish_fn=spy_polish) as eng:
            reqs = [eng.submit(make_chunk(f"m/{i}")) for i in range(6)]
            for r in reqs:
                assert r.wait(10.0)
                assert r.failure == Failure.SUCCESS
        assert sizes and max(sizes) <= 4
        assert sum(sizes) == 6


# ------------------------------------------- serve: retry + watchdog wiring


def stub_prep(chunk, settings):
    return None, PreparedZmw(chunk, np.zeros(64, np.int8), [],
                             len(chunk.reads), 0, 0.0)


def stub_polish(preps, settings):
    return [(Failure.SUCCESS, fake_result(p.chunk.id)) for p in preps]


class TestServeResilience:
    def serve_stack(self, polish=stub_polish, **cfg):
        from pbccs_tpu.serve.engine import CcsEngine, ServeConfig
        from pbccs_tpu.serve.server import CcsServer

        eng = CcsEngine(config=ServeConfig(**cfg), prep_fn=stub_prep,
                        polish_fn=polish).start()
        srv = CcsServer(eng, port=0).start()
        return eng, srv

    def test_submit_with_retry_rides_out_overloaded(self):
        """Satellite contract: against a max_pending=1 engine, every
        submit_with_retry eventually succeeds -- the overloaded
        rejections are absorbed by the backoff policy."""
        from pbccs_tpu.serve.client import CcsClient

        def slow_polish(preps, settings):
            time.sleep(0.15)
            return stub_polish(preps, settings)

        eng, srv = self.serve_stack(polish=slow_polish, max_batch=1,
                                    max_wait_ms=10.0, max_pending=1)
        scope = default_registry().scope()
        try:
            with CcsClient(srv.host, srv.port) as cli:
                results = {}
                errs = []

                def one(i):
                    try:
                        msg = cli.submit_with_retry(
                            {"id": f"m/{i}",
                             "reads": [{"seq": "ACGTACGT"}] * 4},
                            policy=retry.RetryPolicy(
                                max_attempts=40, base_delay_s=0.05,
                                max_delay_s=0.2, deadline_s=30.0))
                        results[i] = msg["status"]
                    except Exception as e:  # noqa: BLE001
                        errs.append(repr(e))

                threads = [threading.Thread(target=one, args=(i,))
                           for i in range(4)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=60.0)
                assert not errs, errs
                assert results == {i: "Success" for i in range(4)}
                # max_pending=1 forces real rejections along the way
                assert scope.counter_value("ccs_retries_total",
                                           site="client.submit") >= 1
        finally:
            srv.shutdown()
            eng.close()

    def test_engine_watchdog_fails_batch_keeps_serving(self):
        from pbccs_tpu.serve.engine import CcsEngine, ServeConfig

        hang = threading.Event()

        def hung_once(preps, settings):
            if not hang.is_set():
                hang.set()
                time.sleep(5.0)
            return stub_polish(preps, settings)

        cfg = ServeConfig(max_batch=1, max_wait_ms=60_000.0,
                          polish_timeout_ms=200.0)
        with CcsEngine(config=cfg, prep_fn=stub_prep,
                       polish_fn=hung_once) as eng:
            bad = eng.submit(make_chunk("m/hang"))
            assert bad.wait(10.0)
            assert bad.error is not None and "watchdog" in bad.error
            ok = eng.submit(make_chunk("m/2"))
            assert ok.wait(10.0)
            assert ok.failure == Failure.SUCCESS
            assert eng.status()["errors"] == 1


# ------------------------------------- pipeline: batch-fallback parity (e2e)


@pytest.mark.slow
@pytest.mark.parametrize("on_error", ["bisect", "serial"])
def test_poisoned_batch_survivor_parity(rng, on_error):
    """A poisoned batch yields byte-identical results for all surviving
    ZMWs vs an unpoisoned run -- for the bisection path AND the legacy
    serial path (the satellite contract; chaos_smoke re-checks this in
    tier-1 CI)."""
    from pbccs_tpu.pipeline import process_chunks
    from pbccs_tpu.simulate import simulate_zmw

    chunks = []
    for i in range(5):
        _, reads, _, snr = simulate_zmw(rng, 60, 4)
        chunks.append(Chunk(
            f"par/{i}",
            [Subread(f"par/{i}/{k}", r) for k, r in enumerate(reads)],
            snr))
    base = process_chunks(list(chunks))
    base_out = {r.id: (r.sequence, r.qualities) for r in base.results}

    with faults.active("polish.dispatch:error~par/1"):
        pois = process_chunks(list(chunks), on_error=on_error)
    pois_out = {r.id: (r.sequence, r.qualities) for r in pois.results}
    assert pois_out == {k: v for k, v in base_out.items() if k != "par/1"}
    assert pois.counts[Failure.OTHER] == 1
    assert pois.total == base.total
