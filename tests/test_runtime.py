"""Intervals, whitelist, logging: host runtime components.

Patterns: reference tests/TestInterval.cpp, TestWhitelist.cpp (incl.
invalid-spec throws).
"""

import io

import pytest

from pbccs_tpu.runtime.logging import Logger, LogLevel
from pbccs_tpu.runtime.whitelist import Whitelist
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
