"""Intervals, whitelist, work queue, logging: host runtime components.

Patterns: reference tests/TestInterval.cpp, TestWhitelist.cpp (incl.
invalid-spec throws) and the WorkQueue ordering contract (WorkQueue.h).
"""

import io
import time

import pytest

from pbccs_tpu.runtime.logging import Logger, LogLevel
from pbccs_tpu.runtime.whitelist import Whitelist
from pbccs_tpu.runtime.workqueue import WorkQueue
from pbccs_tpu.utils.intervals import Interval, IntervalTree


class TestInterval:
    def test_from_string_single(self):
        assert Interval.from_string("5") == Interval(5, 6)

    def test_from_string_range(self):
        assert Interval.from_string("3-7") == Interval(3, 8)

    @pytest.mark.parametrize("bad", ["", "a", "7-3", "1-2-3", "-1"])
    def test_from_string_invalid(self, bad):
        with pytest.raises(ValueError):
            Interval.from_string(bad)

    def test_contains_overlaps(self):
        i = Interval(2, 5)
        assert i.contains(2) and i.contains(4) and not i.contains(5)
        assert i.overlaps(Interval(4, 9))
        assert not i.overlaps(Interval(5, 9))
        assert i.touches(Interval(5, 9))


class TestIntervalTree:
    def test_merging(self):
        t = IntervalTree()
        t.insert(Interval(1, 3))
        t.insert(Interval(5, 7))
        assert len(t) == 2
        t.insert(Interval(3, 5))  # bridges both
        assert list(t) == [Interval(1, 7)]

    def test_from_string_and_contains(self):
        t = IntervalTree.from_string("1-3,5")
        assert t.contains(1) and t.contains(3) and t.contains(5)
        assert not t.contains(4) and not t.contains(0)

    def test_gaps(self):
        t = IntervalTree.from_string("1-3,7-9")
        assert list(t.gaps()) == [Interval(4, 7)]


class TestWhitelist:
    def test_all(self):
        for spec in ("all", "*:*"):
            wl = Whitelist(spec)
            assert wl.contains("anyMovie", 123)

    def test_global_ranges(self):
        for spec in ("1-3,5", "*:1-3,5"):
            wl = Whitelist(spec)
            assert wl.contains("m1", 2) and wl.contains("m2", 5)
            assert not wl.contains("m1", 4)

    def test_movie_scoped(self):
        wl = Whitelist("movie1:1-3;movie2:*")
        assert wl.contains("movie1", 2)
        assert not wl.contains("movie1", 4)
        assert wl.contains("movie2", 999)
        assert not wl.contains("movie3", 1)

    @pytest.mark.parametrize("bad", [
        "all;1-3",            # all mixed with ranges
        "1-3;movie:4",        # global then per-movie
        "movie:1;movie:2",    # movie repeated
        "a:b:c",              # too many parts
    ])
    def test_invalid_specs(self, bad):
        with pytest.raises(ValueError):
            Whitelist(bad)


class TestWorkQueue:
    def test_preserves_order(self):
        def work(i):
            time.sleep(0.01 * ((7 * i) % 5))  # jittered finish order
            return i * i

        # max_pending bounds UNCONSUMED results, so a produce-all-then-
        # consume loop needs the pipeline sized for the whole workload
        # (concurrent consumers are exercised below and in cli.py)
        with WorkQueue(4, max_pending=20) as wq:
            for i in range(20):
                wq.produce(work, i)
            wq.finalize()
            assert list(wq.results()) == [i * i for i in range(20)]

    def test_exception_propagates_to_consumer(self):
        def work(i):
            if i == 3:
                raise RuntimeError("boom")
            return i

        with WorkQueue(2) as wq:
            for i in range(6):
                wq.produce(work, i)
            wq.finalize()
            with pytest.raises(RuntimeError, match="boom"):
                list(wq.results())

    def test_ordered_consumption_out_of_order_completion(self):
        """Earlier tasks finishing LAST must not reorder consumption."""
        import threading

        gate = threading.Event()

        def work(i):
            if i == 0:
                gate.wait(timeout=5.0)  # task 0 completes after the rest
            return i

        with WorkQueue(4) as wq:
            for i in range(8):
                wq.produce(work, i)
            wq.finalize()
            it = wq.results()
            gate_setter = threading.Timer(0.05, gate.set)
            gate_setter.start()
            try:
                assert list(it) == list(range(8))
            finally:
                gate_setter.cancel()

    def test_producer_backpressure_at_max_pending(self):
        """produce() blocks once max_pending results are unconsumed --
        including COMPLETED ones -- and unblocks as results are consumed."""
        import threading

        max_pending = 3
        wq = WorkQueue(2, max_pending=max_pending)
        produced = []
        done = threading.Event()

        def producer():
            for i in range(max_pending + 2):
                wq.produce(lambda i=i: i, i)
                produced.append(i)
            done.set()

        t = threading.Thread(target=producer)
        t.start()
        # tasks are trivial and complete immediately; the producer must
        # still stall at max_pending because nothing has been consumed
        done.wait(timeout=0.5)
        assert not done.is_set()
        assert len(produced) == max_pending
        # consuming results frees slots and unblocks the producer
        it = wq.results()
        assert next(it) == 0
        assert next(it) == 1
        assert done.wait(timeout=5.0)
        wq.finalize()
        assert list(it) == [2, 3, 4]
        t.join()
        wq.shutdown()

    def test_exception_propagates_to_blocked_producer(self):
        """A producer stalled on a full pipeline wakes and raises when a
        worker fails while it waits."""
        import threading

        release = threading.Event()

        def work(i):
            if i == 0:
                release.wait(timeout=5.0)
                raise RuntimeError("boom")
            return i

        wq = WorkQueue(1, max_pending=2)
        wq.produce(work, 0)
        wq.produce(work, 1)  # fills the pipeline (nothing consumed)
        threading.Timer(0.05, release.set).start()
        with pytest.raises(RuntimeError, match="no new tasks accepted"):
            # blocks on the full pipeline, then task 0 fails
            for i in range(2, 50):
                wq.produce(work, i)
        wq.finalize()
        with pytest.raises(RuntimeError, match="boom"):
            list(wq.results())
        wq.shutdown()


class TestLogger:
    def test_levels_and_format(self):
        buf = io.StringIO()
        log = Logger(stream=buf, level=LogLevel.INFO)
        log.debug("hidden")
        log.info("shown")
        log.flush()
        out = buf.getvalue()
        assert "hidden" not in out
        assert "shown" in out and "INFO" in out

    def test_from_string(self):
        assert LogLevel.from_string("warn") == LogLevel.WARN
        with pytest.raises(ValueError):
            LogLevel.from_string("nope")


class TestCompilationCacheDir:
    """runtime/cache.py: the machine places the cache through
    JAX_COMPILATION_CACHE_DIR; unset, it is the checkout's .jax_cache."""

    @pytest.fixture(autouse=True)
    def _restore_cache_dir(self):
        import jax

        prev = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", prev)

    def test_env_beats_argument(self, monkeypatch, tmp_path):
        import jax

        from pbccs_tpu.runtime.cache import enable_compilation_cache

        placed = str(tmp_path / "placed")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        got = enable_compilation_cache(str(tmp_path / "argument"))
        assert got == placed
        assert jax.config.jax_compilation_cache_dir == placed

    def test_argument_when_env_unset(self, monkeypatch, tmp_path):
        from pbccs_tpu.runtime.cache import enable_compilation_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        arg = str(tmp_path / "argument")
        assert enable_compilation_cache(arg) == arg

    def test_checkout_local_without_git(self, monkeypatch, tmp_path):
        """A checkout that is not a git repository (the copy a chip run
        is made from) still caches inside itself, never under $HOME."""
        import os

        from pbccs_tpu.runtime import cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("HOME", str(tmp_path / "home"))
        checkout = tmp_path / "copy"
        checkout.mkdir()
        assert not (checkout / ".git").exists()
        monkeypatch.setattr(cache, "_CHECKOUT", str(checkout))
        assert cache.enable_compilation_cache() == \
            os.path.join(str(checkout), ".jax_cache")

    def test_checkout_is_this_repo(self):
        import os

        from pbccs_tpu.runtime import cache

        assert os.path.isfile(os.path.join(cache._CHECKOUT, "chip_smoke.py"))


@pytest.mark.parametrize("gate", ["fills_use_pallas", "dense_score_enabled"])
def test_kernel_gate_lets_a_backend_error_out(gate, monkeypatch):
    """A backend that failed to initialise must not become a quiet choice
    of the pure-JAX path."""
    import jax

    from pbccs_tpu.ops import dense_score_pallas, fwdbwd_pallas

    fn = {"fills_use_pallas": fwdbwd_pallas.fills_use_pallas,
          "dense_score_enabled": dense_score_pallas.dense_score_enabled}[gate]
    monkeypatch.delenv("PBCCS_PALLAS", raising=False)
    monkeypatch.delenv("PBCCS_DENSE", raising=False)

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        fn()
