"""Persistent JAX compilation-cache setup shared by the CLI and bench.

The polish programs take minutes to compile at batch shapes; cached
executables make reruns start fast.  JAX_COMPILATION_CACHE_DIR, where
set, decides the directory and nothing in code sets another; unset, the
cache is the checkout's own .jax_cache (a fixed path: the path is part of
the cache key, so a directory that moves never hits)."""

from __future__ import annotations

import os
import threading

from pbccs_tpu.obs import trace as _trace

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# jax's event names (jax/_src/dispatch.py, jax/_src/compiler.py): internal
# and version-dependent, like the count events below -- a jax that renames
# them leaves the program-load counters and spans at nothing
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_PHASE_OF_EVENT = {_TRACE_EVENT: "trace", _LOWER_EVENT: "lower",
                   _COMPILE_EVENT: "compile"}

_monitoring_installed = False


def _install_cache_metrics() -> None:
    """Route jax's compilation-cache monitoring events into the metrics
    registry: ccs_compile_cache_events_total{kind="hit"|"miss"},
    ccs_compiles_total for backend compiles, and the seconds each phase
    of bringing a program up took (ccs_program_load_seconds_total{phase},
    with a program.trace/lower/compile span for each while a tracer is
    installed).  Best-effort -- event names
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
    load_help = (
        "Wall seconds this process spent bringing programs up, by phase: "
        "trace (Python to jaxpr), lower (jaxpr to MLIR), compile (jax's "
        "backend_compile event: in jax 0.9 it wraps compile_or_get_cached, "
        "so it CONTAINS cache_read), cache_read (reading and loading "
        "executables the persistent cache held; hits only, jax reports no "
        "time for a miss).  An event nested in another on its thread (a "
        "jnp op traced while its caller is traced or lowered) counts in "
        "the outer one only, so trace + lower + compile never exceeds wall")
    load_seconds = {
        phase: reg.counter("ccs_program_load_seconds_total", load_help,
                           phase=phase)
        for phase in ("trace", "lower", "compile", "cache_read")}

    def on_event(event: str, **kw) -> None:
        if "compilation_cache" in event:
            if "hit" in event:
                hits.inc()
            elif "miss" in event:
                misses.inc()
        elif "backend_compile" in event or event.endswith("/compile"):
            compiles.inc()

    # jax brackets each phase with a scalar event at its start and a
    # time span at its end, both on the compiling thread.  Phases nest
    # (every jnp op traced inside a jit fires a trace event of its own),
    # so only the outermost one on a thread is booked.
    open_phases = threading.local()

    def on_phase_start(event: str, _value, **kw) -> None:
        if event in _PHASE_OF_EVENT:
            open_phases.n = getattr(open_phases, "n", 0) + 1

    def on_phase_end(event: str, start_time: float, end_time: float,
                     **kw) -> None:
        phase = _PHASE_OF_EVENT.get(event)
        if phase is None:
            return
        open_phases.n = n = max(getattr(open_phases, "n", 1) - 1, 0)
        if n:
            return
        dur = end_time - start_time
        load_seconds[phase].inc(dur)
        tracer = _trace.get_tracer()
        if tracer is None:
            return
        fun = str(kw.get("fun_name", ""))
        # literal names: the REG010 span inventory reads them from here
        if event == _TRACE_EVENT:
            tracer.add_span("program.trace", dur, start_unix=start_time,
                            fun=fun)
        elif event == _LOWER_EVENT:
            tracer.add_span("program.lower", dur, start_unix=start_time,
                            fun=fun)
        else:
            tracer.add_span("program.compile", dur, start_unix=start_time,
                            fun=fun)

    def on_duration(event: str, duration_secs: float, **kw) -> None:
        # the one phase jax reports as a duration only
        if event == _CACHE_READ_EVENT:
            load_seconds["cache_read"].inc(duration_secs)

    try:
        import jax.monitoring

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_scalar_listener(on_phase_start)
        jax.monitoring.register_event_time_span_listener(on_phase_end)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
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
