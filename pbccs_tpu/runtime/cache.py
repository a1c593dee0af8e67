"""Persistent JAX compilation-cache setup shared by the CLI and bench.

The polish programs take minutes to compile at batch shapes; cached
executables make reruns start fast.  JAX_COMPILATION_CACHE_DIR, where
set, decides the directory and nothing in code sets another; unset, the
cache is the checkout's own .jax_cache (a fixed path: the path is part of
the cache key, so a directory that moves never hits)."""

from __future__ import annotations

import contextlib
import os
import threading

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_monitoring_installed = False
_suppress_events = threading.local()


@contextlib.contextmanager
def suppress_cache_metrics():
    """Hide compile/cache-event counts from the ledger counters for the
    duration.  Used by the roofline CostCard extraction: its AOT compile
    of the canonical bucket program races the workload's own jit on the
    shared persistent cache, so counting its hit/miss would make the
    deterministic compile-class ledger counters timing-dependent."""
    prev = getattr(_suppress_events, "v", False)
    _suppress_events.v = True
    try:
        yield
    finally:
        _suppress_events.v = prev


def _install_cache_metrics() -> None:
    """Route jax's compilation-cache monitoring events into the metrics
    registry: ccs_compile_cache_events_total{kind="hit"|"miss"} plus
    ccs_compiles_total for backend compiles.  Best-effort -- event names
    are jax-internal and version-dependent, so unknown events are ignored
    and a jax without jax.monitoring leaves the counters at zero."""
    global _monitoring_installed
    if _monitoring_installed:
        return
    _monitoring_installed = True
    from pbccs_tpu.obs.metrics import default_registry

    reg = default_registry()
    hits = reg.counter("ccs_compile_cache_events_total",
                       "Persistent compilation cache hits/misses",
                       kind="hit")
    misses = reg.counter("ccs_compile_cache_events_total", kind="miss")
    compiles = reg.counter("ccs_compiles_total",
                           "Backend compile events observed via "
                           "jax.monitoring")

    def on_event(event: str, **kw) -> None:
        if getattr(_suppress_events, "v", False):
            return
        if "compilation_cache" in event:
            if "hit" in event:
                hits.inc()
            elif "miss" in event:
                misses.inc()
        elif "backend_compile" in event or event.endswith("/compile"):
            compiles.inc()

    try:
        import jax.monitoring

        jax.monitoring.register_event_listener(on_event)
    except Exception:  # noqa: BLE001 -- observability must not block setup
        pass


def enable_compilation_cache(cache_dir: str | None = None) -> str:
    """Point jax's persistent compilation cache at one directory and
    return it.  JAX_COMPILATION_CACHE_DIR wins wherever it is set: the
    machine that runs the program places the cache, and no argument
    (`--compileCache`) overrides that.  Unset, `cache_dir` applies, else
    `<checkout>/.jax_cache`, whether or not the checkout is a git
    repository (the copy a chip run is made from is not).  A
    shared directory is the fleet-restart contract: every `ccs serve`
    replica and `ccs warmup` pointed at it shares one executable store,
    so a rolling replica restart pays a disk load instead of the
    first-run XLA compile."""
    import jax

    _install_cache_metrics()

    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR") or cache_dir
                 or os.path.join(_CHECKOUT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # respect a user-provided min-compile-time; default to caching anything
    # that took >= 1 s to compile
    if os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS") is None \
            and jax.config.jax_persistent_cache_min_compile_time_secs <= 0:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return cache_dir
