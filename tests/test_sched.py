"""Device-fleet scheduler (pbccs_tpu/sched): routing, health, pipelining.

Runs on the conftest-forced 8-virtual-CPU-device platform, so the pool
tests exercise REAL multi-device dispatch (distinct jax.Device objects,
per-device executable caches) without hardware.  Polish-heavy parity
legs use tiny simulated ZMWs; pure scheduling legs use stub task fns.
"""

import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from pbccs_tpu.obs.metrics import default_registry  # noqa: E402
from pbccs_tpu.pipeline import (  # noqa: E402
    Chunk,
    ConsensusSettings,
    Failure,
    ResultTally,
    Subread,
    process_chunks,
)
from pbccs_tpu.resilience import faults  # noqa: E402
from pbccs_tpu.sched import (  # noqa: E402
    DevicePool,
    DevicePoolConfig,
    PoolClosed,
    ScheduledPipeline,
)
from pbccs_tpu.simulate import simulate_zmw  # noqa: E402

reg = default_registry()


def make_pool(n=4, **cfg) -> DevicePool:
    return DevicePool(jax.devices()[:n], DevicePoolConfig(**cfg))


def worker_name(pool, i):
    return pool._workers[i].name


# ------------------------------------------------------------------ routing

def test_sticky_keeps_bucket_on_home_device():
    with make_pool(4) as pool:
        seen = []
        for _ in range(5):
            # sequential waits: the home is idle at every submit, so a
            # sticky bucket must stay put
            pool.submit("bucket-a", lambda d: seen.append(d) or d).result(30)
        assert len({d.id for d in seen}) == 1


def test_sticky_spreads_distinct_buckets():
    with make_pool(4) as pool:
        homes = {}
        for key in ("a", "b", "c", "d"):
            dev = pool.submit(key, lambda d: d).result(30)
            homes[key] = dev.id
        # the least-loaded tie-break prefers devices with fewer resident
        # buckets, so four idle devices take four distinct buckets
        assert len(set(homes.values())) == 4


def test_sticky_spills_when_home_busy():
    with make_pool(2) as pool:
        release = threading.Event()
        started = threading.Event()

        def slow(d):
            started.set()
            assert release.wait(30)
            return d

        f1 = pool.submit("k", slow)
        assert started.wait(30)
        # home busy and spill_depth=0: the second task must go elsewhere
        f2 = pool.submit("k", lambda d: d)
        d2 = f2.result(30)
        release.set()
        d1 = f1.result(30)
        assert d1.id != d2.id
        # the spill target became an additional home
        assert len(pool._sticky.homes("k")) == 2


def test_roundrobin_policy_cycles():
    with make_pool(3, policy="roundrobin") as pool:
        devs = [pool.submit("k", lambda d: d).result(30).id
                for _ in range(6)]
        assert devs[:3] == devs[3:]
        assert len(set(devs[:3])) == 3


def test_worker_index_pins(rng):
    with make_pool(4) as pool:
        for i in range(4):
            dev = pool.submit("k", lambda d: d, worker_index=i).result(30)
            assert dev.id == pool._workers[i].device.id


# ------------------------------------------------------------------- health

def test_device_failure_requeues_and_benches():
    scope = reg.scope()
    with make_pool(3, bench_after=2) as pool:
        bad = worker_name(pool, 0)
        with faults.active(f"sched.dispatch:error~{bad}"):
            futs = [pool.submit("k", lambda d: d, worker_index=0)
                    for _ in range(2)]
            # every task completes despite device 0 failing every attempt
            out = [f.result(60) for f in futs]
        assert all(d.id != pool._workers[0].device.id for d in out)
        assert pool._workers[0].benched
        st = pool.status()
        assert [d["benched"] for d in st["devices"]] == [True, False, False]
    assert scope.counter_value("ccs_sched_device_benched_total",
                               device=bad) == 1
    assert scope.counter_value("ccs_sched_requeues_total") >= 2
    assert scope.counter_value("ccs_sched_task_failures_total",
                               device=bad) >= 2


def test_benched_device_queue_drains_to_healthy():
    with make_pool(2, bench_after=1) as pool:
        bad = worker_name(pool, 0)
        release = threading.Event()
        started = threading.Event()

        def slow_ok(d):
            started.set()
            assert release.wait(30)
            return "ok"

        with faults.active(f"sched.dispatch:error~{bad}*1"):
            # park worker 1 so queued work stacks on worker 0
            f_slow = pool.submit("other", slow_ok, worker_index=1)
            assert started.wait(30)
            f1 = pool.submit("k", lambda d: "a", worker_index=0)  # fails once
            f2 = pool.submit("k", lambda d: "b", worker_index=0)  # stranded
            release.set()
            assert f_slow.result(30) == "ok"
            assert f1.result(60) == "a"
            assert f2.result(60) == "b"
        assert pool._workers[0].benched


def test_last_healthy_device_never_benched():
    with make_pool(1, bench_after=1) as pool:
        bad = worker_name(pool, 0)
        with faults.active(f"sched.dispatch:error~{bad}"):
            f = pool.submit("k", lambda d: d)
            exc = f.exception(30)
        assert exc is not None           # no other device to requeue to
        assert not pool._workers[0].benched
        # the pool still serves once the fault clears
        assert pool.submit("k", lambda d: "fine").result(30) == "fine"


def test_task_exception_propagates_when_all_devices_fail():
    with make_pool(3) as pool:
        def boom(d):
            raise ValueError("poison task")

        exc = pool.submit("k", boom).exception(60)
        assert isinstance(exc, ValueError)


def test_submit_after_close_raises():
    pool = make_pool(2)
    pool.close()
    with pytest.raises(PoolClosed):
        pool.submit("k", lambda d: d)


def test_close_without_wait_fails_queued_tasks():
    pool = make_pool(1)
    release = threading.Event()
    started = threading.Event()

    def slow(d):
        started.set()
        assert release.wait(30)
        return "done"

    f_running = pool.submit("k", slow)
    assert started.wait(30)
    f_queued = pool.submit("k", lambda d: "late")
    release.set()
    pool.close(wait=False)
    assert f_running.result(30) == "done"  # running tasks finish
    assert isinstance(f_queued.exception(30), PoolClosed) or \
        f_queued.result(0) == "late"  # raced the worker loop: either is fine


def test_watchdog_carries_thread_local_device():
    """An armed watchdog deadline moves the guarded callable to a fresh
    thread; it must carry the caller's thread-local jax.default_device
    (else every fleet polish with --polishTimeout lands on device 0)."""
    import jax.numpy as jnp

    from pbccs_tpu.resilience.watchdog import run_with_deadline

    target = jax.devices()[3]

    def placed_device():
        return next(iter(jnp.asarray([1.0]).devices()))

    with jax.default_device(target):
        assert run_with_deadline(placed_device, 30.0,
                                 site="test") == target
    # and with no override, behavior is unchanged
    assert run_with_deadline(placed_device, 30.0,
                             site="test") == jax.devices()[0]


def test_plain_exception_requeues_without_strike():
    """A non-device-shaped failure (poison input escaping quarantine)
    never benches healthy devices."""
    with make_pool(3, bench_after=1) as pool:
        def boom(d):
            raise ValueError("poison input")

        exc = pool.submit("k", boom).exception(60)
        assert isinstance(exc, ValueError)
        assert all(not w.benched for w in pool._workers)
        assert all(w.strikes == 0 for w in pool._workers)


def test_task_shaped_failure_retries_once_not_fleet_tour():
    """A deterministic task-shaped failure gets exactly ONE healthy-device
    retry before surfacing -- touring all N devices would cost N polish
    durations just to return the same error."""
    attempts = [0]
    with make_pool(4) as pool:
        def boom(d):
            attempts[0] += 1
            raise ValueError("deterministic bug")

        exc = pool.submit("k", boom).exception(60)
        assert isinstance(exc, ValueError)
    assert attempts[0] == 2


def test_pinned_task_fails_loudly_instead_of_requeueing():
    """A pin=True task that fails must surface its exception, not
    silently succeed on another device (a requeued warmup would leave
    the pinned device cold while reporting success).  Bare worker_index
    keeps initial-placement semantics: failures requeue normally."""
    ran_on = []
    with make_pool(3) as pool:
        def boom(d):
            ran_on.append(d)
            raise ValueError("pinned failure")

        exc = pool.submit("k", boom, worker_index=1, pin=True).exception(60)
        assert isinstance(exc, ValueError)
        assert len(ran_on) == 1 and ran_on[0].id == 1
        # unpinned placement on the same failing fn requeues off device 1
        ran_on.clear()
        exc = pool.submit("k2", boom, worker_index=1).exception(60)
        assert isinstance(exc, ValueError)
        assert len(ran_on) == 2          # one retry elsewhere, then surfaced
        assert ran_on[0].id == 1 and ran_on[1].id != 1


def test_submit_rejects_bad_placement():
    """worker_index must not wrap pythonically (an off-by-one pinning the
    LAST device would 'succeed' while the intended device stays cold) and
    pin=True without a target is a caller bug, not a no-op."""
    with make_pool(3) as pool:
        with pytest.raises(ValueError):
            pool.submit("k", lambda d: d, worker_index=-1)
        with pytest.raises(ValueError):
            pool.submit("k", lambda d: d, worker_index=3)
        with pytest.raises(ValueError):
            pool.submit("k", lambda d: d, pin=True)
        # in-range placement still works
        assert pool.submit("k", lambda d: d, worker_index=2).result(30).id == 2


def test_post_close_failure_completes_future():
    """A task that fails after close() gave up joining its worker must
    still complete its future (a post-close requeue would park it on a
    dead worker's deque and strand it forever)."""
    pool = make_pool(3)
    started, release = threading.Event(), threading.Event()

    def slow_fail(d):
        started.set()
        assert release.wait(30)
        raise RuntimeError("late failure")

    fut = pool.submit("k", slow_fail)
    assert started.wait(30)
    closer = threading.Thread(
        target=lambda: pool.close(join_timeout_s=0.1))
    closer.start()
    closer.join(30)            # close returns while the task still runs
    release.set()
    assert fut.wait(30), "future stranded after post-close failure"
    assert isinstance(fut.exception(), RuntimeError)


# -------------------------------------------------------- scheduled pipeline

def make_chunks(n, seed=20260803):
    rng = np.random.default_rng(seed)
    chunks = []
    for i in range(n):
        _, reads, _, snr = simulate_zmw(rng, 60, 5)
        chunks.append(Chunk(
            f"sched/{i}",
            [Subread(f"sched/{i}/{k}", r) for k, r in enumerate(reads)],
            snr))
    return chunks


def outputs(tally):
    return {r.id: (r.sequence, r.qualities) for r in tally.results}


@pytest.mark.slow
def test_scheduled_pipeline_matches_process_chunks():
    chunks = make_chunks(12)
    batches = [chunks[i: i + 4] for i in range(0, 12, 4)]
    settings = ConsensusSettings()

    want = {}
    want_counts = {f: 0 for f in Failure}
    for b in batches:
        t = process_chunks(list(b), settings)
        want.update(outputs(t))
        for f, c in t.counts.items():
            want_counts[f] += c

    with make_pool(4) as pool:
        pipe = ScheduledPipeline(pool, settings, chunk_zmws=64, prepare_workers=2)
        got, got_counts = {}, {f: 0 for f in Failure}
        order = []
        for idx, tally in pipe.run(
                (i, list(b), None) for i, b in enumerate(batches)):
            order.append(idx)
            got.update(outputs(tally))
            for f, c in tally.counts.items():
                got_counts[f] += c
        st = pool.status()
    assert order == [0, 1, 2]            # emission in submission order
    assert got == want                   # byte-identical to single-device
    assert got_counts == want_counts
    assert sum(d["tasks_done"] for d in st["devices"]) == 3


@pytest.mark.slow
def test_scheduled_pipeline_precomputed_and_chaos():
    """Journal-restored tallies pass through untouched, and a benched
    device mid-run loses zero ZMWs (the chaos acceptance leg in unit
    form; tools/sched_smoke.py runs the full-size version)."""
    chunks = make_chunks(8)
    batches = [chunks[:4], chunks[4:]]
    settings = ConsensusSettings()
    base = [process_chunks(list(b), settings) for b in batches]

    scope = reg.scope()
    with make_pool(3, bench_after=1) as pool:
        bad = worker_name(pool, 0)
        pipe = ScheduledPipeline(pool, settings, chunk_zmws=64, prepare_workers=1)
        with faults.active(f"sched.dispatch:error~{bad}"):
            items = [(0, None, base[0]),      # precomputed (restored)
                     (1, list(batches[1]), None)]
            emitted = dict(pipe.run(iter(items)))
    assert emitted[0] is base[0]
    assert outputs(emitted[1]) == outputs(base[1])
    assert emitted[1].total == base[1].total   # zero lost ZMWs
    assert scope.counter_value("ccs_sched_device_benched_total",
                               device=bad) == 1


def test_executor_first_attempt_device_failure_reaches_pool(monkeypatch):
    """A device-shaped polish failure on a fleet's FIRST attempt escapes
    the quarantine layer (raise_device_shaped=True) so the pool strikes
    the device and requeues the WHOLE batch; the requeued attempt runs
    with raise_device_shaped=False (local quarantine as usual)."""
    import types

    from pbccs_tpu import pipeline as pl
    from pbccs_tpu.pipeline import PreparedZmw, ResultTally

    FakeXla = type("XlaRuntimeError", (RuntimeError,), {})
    chunks = [Chunk(f"m/{i}", [Subread(f"m/{i}/0", np.zeros(8, np.int8))],
                    np.ones(4, np.float32)) for i in range(3)]

    def stub_prepare(cs, settings, **span_args):
        read = types.SimpleNamespace(seq="ACGTACGT")
        return ResultTally(), [
            PreparedZmw(c, np.zeros(12, np.int8), [read], 0, 0, 0.0)
            for c in cs]

    flags = []

    def fake_polish(preps, settings, *, buckets=None, min_z=1,
                    fixed_z=False, on_error="bisect",
                    raise_device_shaped=False, prebaked=None):
        flags.append(raise_device_shaped)
        if len(flags) == 1:
            raise FakeXla("device fell over")
        return [(Failure.SUCCESS, None) for _ in preps]

    monkeypatch.setattr(pl, "prepare_batch", stub_prepare)
    monkeypatch.setattr(pl, "polish_prepared_batch", fake_polish)
    monkeypatch.setattr(pl, "menu_batch_shapes",
                        lambda preps, full_zmws: ((8, 8, 4), None))

    scope = reg.scope()
    with make_pool(3) as pool:
        pipe = ScheduledPipeline(pool, ConsensusSettings(), chunk_zmws=64,
                                 prepare_workers=1)
        emitted = dict(pipe.run([(0, chunks, None)]))
        assert any(w.strikes == 1 for w in pool._workers)
    assert flags == [True, False]
    assert emitted[0].counts[Failure.SUCCESS] == 3   # zero lost ZMWs
    assert scope.counter_value("ccs_sched_requeues_total") == 1


# ------------------------------------------- drafts dealt ZMW by ZMW, in order

def _stub_host_and_device(monkeypatch, prep_seconds, polished=None,
                          polish_seconds=lambda preps: 0.05):
    """Stub the host stages below `prepare_batch` and the whole polish:
    `prepare_chunk` sleeps `prep_seconds(chunk)` (or raises what it
    returns), the polish sleeps `polish_seconds(preps)` and notes the ZMW
    ids it was handed."""
    from pbccs_tpu import pipeline as pl

    def stub_prepare_chunk(chunk, settings):
        wait = prep_seconds(chunk)
        if isinstance(wait, BaseException):
            raise wait
        time.sleep(wait)
        return _stub_prep(chunk, settings)

    def stub_polish(preps, settings, **kw):
        if polished is not None:
            polished.append([p.chunk.id for p in preps])
        time.sleep(polish_seconds(preps))
        return [(Failure.SUCCESS, None) for _ in preps]

    monkeypatch.setattr(pl, "prepare_chunk", stub_prepare_chunk)
    monkeypatch.setattr(pl, "polish_prepared_batch", stub_polish)
    monkeypatch.setattr(pl, "menu_batch_shapes",
                        lambda preps, full_zmws: ((8, 8, 4), None))
    monkeypatch.setattr(pl, "prebake_polish", lambda preps, **kw: None)


def _stub_batches(n_batches, size):
    return [[Chunk(f"m/{b * size + i}", [], np.ones(4, np.float32))
             for i in range(size)] for b in range(n_batches)]


def test_first_polish_opens_after_its_own_slices_alone(monkeypatch):
    """Three batches of four on two prepare workers: a batch is dealt as
    two slices of two, the pool is FIFO, so batch 0 is whole after one
    slice's time and its polish opens then -- not after every batch's
    drafts, as when each worker takes a whole batch.  The next batch is
    read when a batch's drafts have closed, not before."""
    from pbccs_tpu.obs import trace as obs_trace

    _stub_host_and_device(monkeypatch, lambda chunk: 0.06)

    def read_batches():
        for i, batch in enumerate(_stub_batches(3, 4)):
            with obs_trace.span("read", batch=i):
                pass
            yield i, batch, None

    tracer = obs_trace.Tracer()
    prev = obs_trace.set_tracer(tracer)
    try:
        with make_pool(1) as pool:
            pipe = ScheduledPipeline(pool, ConsensusSettings(), chunk_zmws=64,
                                     prepare_workers=2)
            order = [idx for idx, _t in pipe.run(read_batches())]
    finally:
        obs_trace.set_tracer(prev)
    assert order == [0, 1, 2]
    events = tracer.to_chrome()["traceEvents"]
    prepares = [e for e in events if e["name"] == "prepare"]
    # the reader and the drafts take turns: a batch is asked for once the
    # drafts of the one before it have closed
    reads = {e["args"]["batch"]: e["ts"] for e in events
             if e["name"] == "read"}
    for k in (1, 2):
        assert reads[k] >= max(e["ts"] + e["dur"] for e in prepares
                               if e["args"]["batch"] == k - 1)
    assert sorted((e["args"]["batch"], e["args"]["zmws"])
                  for e in prepares) == [(b, 2) for b in (0, 0, 1, 1, 2, 2)]
    (first_polish,) = [e for e in events if e["name"] == "polish"
                       and e["args"]["batch"] == 0]
    closed_before = [e["args"]["batch"] for e in prepares
                     if e["ts"] + e["dur"] <= first_polish["ts"]]
    assert closed_before == [0, 0]              # its own slices, no other
    last_draft_closes = max(e["ts"] + e["dur"] for e in prepares
                            if e["args"]["batch"] == 2)
    assert first_polish["ts"] < last_draft_closes - 100_000


def test_slices_out_of_order_keep_chunk_and_emission_order(monkeypatch):
    """The first slice of every batch is the slow one, so a batch's slices
    close out of order, and on two devices batch 0 polishes longest, so
    the polishes close out of order too: yet each polish sees its ZMWs in
    chunk order and the outcomes come out in submission order."""
    batches = _stub_batches(3, 6)
    slow = {b[0].id for b in batches}
    polished: list[list[str]] = []
    _stub_host_and_device(
        monkeypatch, lambda chunk: 0.1 if chunk.id in slow else 0.005,
        polished,
        lambda preps: 0.5 if preps[0].chunk.id == batches[0][0].id else 0.01)
    with make_pool(2) as pool:
        pipe = ScheduledPipeline(pool, ConsensusSettings(), chunk_zmws=64,
                                 prepare_workers=3)
        emitted = list(pipe.run(
            (i, b, None) for i, b in enumerate(batches)))
    assert [idx for idx, _t in emitted] == [0, 1, 2]
    assert all(t.counts[Failure.SUCCESS] == 6 for _idx, t in emitted)
    assert polished == [[c.id for c in b] for b in batches]


def test_a_chunk_whose_prepare_raises_tallies_other_alone(monkeypatch):
    batches = _stub_batches(2, 4)
    bad = batches[1][2].id
    polished: list[list[str]] = []
    _stub_host_and_device(
        monkeypatch,
        lambda chunk: ValueError("boom") if chunk.id == bad else 0.0,
        polished)
    with make_pool(1) as pool:
        pipe = ScheduledPipeline(pool, ConsensusSettings(), chunk_zmws=64,
                                 prepare_workers=2)
        emitted = dict(pipe.run(
            (i, b, None) for i, b in enumerate(batches)))
    assert emitted[0].counts[Failure.SUCCESS] == 4
    assert emitted[0].counts[Failure.OTHER] == 0
    assert emitted[1].counts[Failure.SUCCESS] == 3
    assert emitted[1].counts[Failure.OTHER] == 1
    assert [c.id for c in batches[1] if c.id != bad] in polished


@pytest.mark.parametrize("max_inflight", [1, 3])
def test_reader_stalls_at_max_inflight_until_results_are_taken(
        monkeypatch, max_inflight):
    """No more than `max_inflight` batches are past the reader (drafting,
    queued, polished and not yet taken) however far behind the consumer
    falls: the reader holds one more in its hand and waits."""
    polished: list[list[str]] = []
    _stub_host_and_device(monkeypatch, lambda chunk: 0.0, polished,
                          lambda preps: 0.0)
    read: list[int] = []

    def read_batches():
        for i, batch in enumerate(_stub_batches(8, 2)):
            read.append(i)
            yield i, batch, None

    with make_pool(1) as pool:
        pipe = ScheduledPipeline(pool, ConsensusSettings(), chunk_zmws=64,
                                 prepare_workers=2,
                                 max_inflight=max_inflight)
        out = pipe.run(read_batches())
        order = [next(out)[0]]
        deadline = time.monotonic() + 20.0   # the consumer falls behind
        while (len(polished) < max_inflight
               or len(read) < max_inflight + 1) \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        time.sleep(0.3)                      # and nothing more moves
        assert len(polished) == max_inflight
        assert read == list(range(max_inflight + 1))
        order += [idx for idx, _t in out]
    assert order == list(range(8)) and len(polished) == 8


def test_device_starved_seconds_count_an_empty_queue_after_first_submit():
    scope = reg.scope()
    with make_pool(1) as pool:
        name = worker_name(pool, 0)
        time.sleep(0.2)                          # before the first submit
        pool.submit("k", lambda d: time.sleep(0.2)).result(10)
        time.sleep(0.3)                          # starved
        pool.submit("k", lambda d: None).result(10)
    starved = scope.counter_value("ccs_sched_device_starved_seconds_total",
                                  device=name)
    assert 0.25 <= starved < 0.45


# ------------------------------------------------------------- serve engine

def _stub_prep(chunk, settings):
    from pbccs_tpu.pipeline import PreparedZmw
    return None, PreparedZmw(chunk, np.zeros(12, np.int8), [], 0, 0, 0.0)


def _stub_polish_ok(preps, settings):
    return [(Failure.SUCCESS, None) for _ in preps]


def test_engine_pool_mode_completes_and_reports():
    from pbccs_tpu.serve.engine import CcsEngine, ServeConfig

    cfg = ServeConfig(max_batch=4, max_wait_ms=20.0, devices=4)
    with CcsEngine(config=cfg, prep_fn=_stub_prep,
                   polish_fn=_stub_polish_ok) as eng:
        chunks = make_chunks(10)
        reqs = [eng.submit(c) for c in chunks]
        for r in reqs:
            assert r.wait(60.0)
            assert r.error is None
        st = eng.status()
        assert st["sched"]["policy"] == "sticky"
        assert len(st["sched"]["devices"]) == 4
        assert sum(d["tasks_done"] for d in st["sched"]["devices"]) >= 1
    # pool is torn down with the engine
    assert eng._pool is None


def test_engine_warm_loads_every_device_of_the_pool():
    """`ccs serve --bucket --devices N`: the synthetic flush runs on each
    device's own thread, so a flush routed to any of them meets its
    programs loaded."""
    from pbccs_tpu.serve.engine import CcsEngine, ServeConfig

    ran = []

    def polish(preps, settings):
        ran.append((threading.current_thread().name, len(preps)))
        return _stub_polish_ok(preps, settings)

    cfg = ServeConfig(max_batch=4, devices=3)
    with CcsEngine(config=cfg, prep_fn=_stub_prep, polish_fn=polish) as eng:
        (entry,) = eng.warm(["4x3x60"])
        assert entry["bucket"] == "4x3x60"
        assert eng.status()["warmed"] == [entry]
    assert len({name for name, _n in ran}) == 3
    assert sorted(n for _name, n in ran) == [4, 4, 4]


def test_engine_pool_mode_survives_benched_device():
    from pbccs_tpu.serve.engine import CcsEngine, ServeConfig

    scope = reg.scope()
    cfg = ServeConfig(max_batch=2, max_wait_ms=20.0, devices=3)
    eng = CcsEngine(config=cfg, prep_fn=_stub_prep,
                    polish_fn=_stub_polish_ok)
    eng.start()
    try:
        bad = eng._pool._workers[0].name
        with faults.active(f"sched.dispatch:error~{bad}"):
            reqs = [eng.submit(c) for c in make_chunks(8)]
            for r in reqs:
                assert r.wait(60.0)
                # requeue to a healthy device: every request SUCCEEDS
                assert r.error is None, r.error
        assert scope.counter_value("ccs_sched_requeues_total") >= 1
        assert len(eng.status()["sched"]["devices"]) == 3
    finally:
        eng.close()


def test_engine_fleet_timeout_fails_after_two_devices_not_a_tour():
    """A polish that outlives the serve watchdog on TWO different devices
    is workload-shaped (e.g. a cold compile slower than the deadline):
    the batch must fail after the second expiry, not tour every device at
    one full timeout per hop while striking healthy hardware."""
    from pbccs_tpu.serve.engine import CcsEngine, ServeConfig

    attempts = []

    def slow_polish(preps, settings):
        attempts.append(1)
        time.sleep(1.0)
        return [(Failure.SUCCESS, None) for _ in preps]

    cfg = ServeConfig(max_batch=4, max_wait_ms=10.0, devices=4,
                      polish_timeout_ms=150.0)
    eng = CcsEngine(config=cfg, prep_fn=_stub_prep, polish_fn=slow_polish)
    eng.start()
    try:
        reqs = [eng.submit(c) for c in make_chunks(2)]
        for r in reqs:
            assert r.wait(60.0)
            assert r.error is not None
        assert len(attempts) == 2        # one requeue, then surfaced
        benched = [d for d in eng.status()["sched"]["devices"]
                   if d["benched"]]
        assert not benched               # no healthy device benched
    finally:
        eng.close()


def test_engine_single_device_default_unchanged():
    from pbccs_tpu.serve.engine import CcsEngine, ServeConfig

    with CcsEngine(config=ServeConfig(max_batch=2, max_wait_ms=20.0),
                   prep_fn=_stub_prep, polish_fn=_stub_polish_ok) as eng:
        reqs = [eng.submit(c) for c in make_chunks(4)]
        for r in reqs:
            assert r.wait(30.0)
        assert eng._pool is None
        assert "sched" not in eng.status()


# ------------------------------------------------------------------- warmup

def test_warmup_bucket_parsing():
    from pbccs_tpu.sched.warmup import parse_bucket

    assert parse_bucket("64x8x300") == (64, 8, 300)
    with pytest.raises(SystemExit):
        parse_bucket("64x8")
    with pytest.raises(SystemExit):
        parse_bucket("0x8x300")


@pytest.mark.slow
def test_warmup_runs_tiny_bucket(capsys):
    from pbccs_tpu.sched.warmup import run_warmup

    rc = run_warmup(["--bucket", "2x3x40", "--devices", "1"])
    assert rc == 0
    import json

    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["warmed"][0]["bucket"] == "2x3x40"
    assert out["warmed"][0]["shapes"]["Z"] >= 2


# ---------------------------------------------------------- CLI integration

@pytest.fixture(scope="module")
def one_device_cli_run(tmp_path_factory):
    """Six small ZMWs through `ccs --devices 1` in chunks of three."""
    from pbccs_tpu import cli
    from pbccs_tpu.io.fasta import write_fasta
    from tests.test_cli import make_zmw_records

    tmp = tmp_path_factory.mktemp("one_driver")
    rng = np.random.default_rng(20260926)
    fasta = str(tmp / "subreads.fasta")
    write_fasta(fasta, [rec for hole in range(1, 7) for rec in
                        make_zmw_records(rng, "m", hole)[1]])

    def run(tag, *flags):
        out = str(tmp / f"{tag}.bam")
        argv = [out, fasta, "--skipChemistryCheck", "--chunkSize", "3",
                "--reportFile", out + ".csv", "--logLevel", "WARN", *flags]
        assert cli.run(argv) == 0
        return [open(out + ext, "rb").read() for ext in ("", ".pbi", ".csv")]

    return fasta, run, run("dev1", "--devices", "1")


@pytest.mark.parametrize("other", ["process_chunks", "devices2"])
def test_cli_one_driver_output_matches(one_device_cli_run, other, tmp_path):
    """The one driver at `--devices 1` writes what the library's one-call
    form computes batch by batch, and the bytes it writes at two (forced
    host) devices."""
    from pbccs_tpu import cli
    from pbccs_tpu.io.bam import BamReader
    from pbccs_tpu.runtime.logging import Logger
    from pbccs_tpu.runtime.whitelist import Whitelist

    fasta, run, dev1 = one_device_cli_run
    if other == "devices2":
        assert run("dev2", "--devices", "2", "--prepareWorkers", "3") == dev1
        return
    args = cli.build_parser().parse_args(
        [str(tmp_path / "unused.bam"), fasta, "--skipChemistryCheck",
         "--chunkSize", "3"])
    settings = cli.consensus_settings_from_args(args)
    want = []
    for batch in cli._chunks_from_files([fasta], Whitelist("all"), args,
                                        Logger.default(), ResultTally()):
        want += [(f"{r.id}/ccs", r.sequence, r.qualities)
                 for r in process_chunks(batch, settings).results]
    bam = tmp_path / "dev1.bam"
    bam.write_bytes(dev1[0])
    with BamReader(str(bam)) as br:
        got = [(rec.name, rec.seq, rec.qual) for rec in br]
    assert len(got) == 6 and got == want


@pytest.mark.slow
def test_cli_multi_device_output_byte_identical(tmp_path):
    """--devices 4 produces the identical FASTA output (and yield report)
    as the default single-device driver."""
    from pbccs_tpu import cli
    from pbccs_tpu.models.arrow.params import decode_bases

    rng = np.random.default_rng(20260803)
    fasta = tmp_path / "subreads.fasta"
    with open(fasta, "w") as f:
        for z in range(8):
            tpl, reads, _, _ = simulate_zmw(rng, 60, 5)
            start = 0
            for r in reads:
                seq = decode_bases(r)
                f.write(f">m/{z}/{start}_{start + len(seq)}\n{seq}\n")
                start += len(seq) + 20

    def run(devices):
        out = tmp_path / f"out_{devices}.fasta"
        rep = tmp_path / f"rep_{devices}.csv"
        rc = cli.run([str(out), str(fasta), "--skipChemistryCheck",
                      "--chunkSize", "3", "--reportFile", str(rep),
                      "--devices", str(devices)])
        assert rc == 0
        return out.read_bytes(), rep.read_bytes()

    single = run(1)
    multi = run(4)
    assert multi == single
