"""Device-wait accounting -- back-compat shim over pbccs_tpu.obs.metrics.

The historical module-level API (stage timers, device_fetch, reset) now
records into the process-wide MetricsRegistry (obs/metrics.py):

  ccs_stage_seconds_total{stage=...}   thread-seconds per pipeline stage
  ccs_device_wait_seconds_total        blocking time inside device fetches
  ccs_device_fetches_total             fetch count
  ccs_device_fetch_seconds             per-fetch latency histogram

Registry values are monotone; a *measurement window* (window(), a
MeasurementScope over the default registry) reports deltas.  reset()
keeps its historical meaning -- start a new window -- but now only
replaces the MODULE-DEFAULT window that the module-level getters read
from: a live serving engine holds its own window (engine status), so a
reset elsewhere in the process cannot clobber the engine's counters
(and vice versa).

device_fetch() additionally attributes its blocking time to the
innermost open trace span (obs/trace.py) so exported span trees carry
wall vs device-wait decomposition.
"""

from __future__ import annotations

import contextlib
import threading
import time

import numpy as np

from pbccs_tpu.obs import metrics as _metrics
from pbccs_tpu.obs import trace as _trace

STAGE_SECONDS = "ccs_stage_seconds_total"
DEVICE_WAIT_SECONDS = "ccs_device_wait_seconds_total"
DEVICE_FETCHES = "ccs_device_fetches_total"
DEVICE_FETCH_SECONDS = "ccs_device_fetch_seconds"

_registry = _metrics.default_registry()
_device_wait = _registry.counter(
    DEVICE_WAIT_SECONDS, "Blocking seconds inside device-to-host fetches")
_fetches = _registry.counter(DEVICE_FETCHES, "Device-to-host fetch count")
_fetch_hist = _registry.histogram(
    DEVICE_FETCH_SECONDS, "Per-fetch blocking latency (s)",
    buckets=_metrics.log_buckets(1e-5, 30.0))

# per-stage Counter handles, cached so the hot path is one dict hit + one
# locked add (the old defaultdict had the same cost profile)
_stage_counters: dict[str, _metrics.Counter] = {}
_stage_lock = threading.Lock()

_window = _registry.scope()   # module-default measurement window
_window_lock = threading.Lock()


def _stage_counter(name: str) -> _metrics.Counter:
    c = _stage_counters.get(name)
    if c is None:
        with _stage_lock:
            c = _stage_counters.get(name)
            if c is None:
                c = _registry.counter(
                    STAGE_SECONDS,
                    "Accumulated thread-seconds per pipeline stage",
                    stage=name)
                _stage_counters[name] = c
    return c


@contextlib.contextmanager
def stage(name: str):
    """Attribute the enclosed wall time to a named pipeline stage
    (summed across threads; see stage_seconds).  Cheap enough to leave on:
    two perf_counter calls + one locked add per use."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        _stage_counter(name).inc(time.perf_counter() - t0)


def add_stage(name: str, dt: float) -> None:
    """Attribute dt seconds to a stage (for callers that already timed)."""
    _stage_counter(name).inc(dt)


def device_fetch(arr, dtype=None) -> np.ndarray:
    """np.asarray(arr) with the blocking time attributed to device wait
    (registry counters + the innermost open trace span)."""
    t0 = time.perf_counter()
    out = np.asarray(arr, dtype) if dtype is not None else np.asarray(arr)
    dt = time.perf_counter() - t0
    _device_wait.inc(dt)
    _fetches.inc()
    _fetch_hist.observe(dt)
    _trace.add_device_wait(dt)
    return out


# ------------------------------------------------------- measurement windows

def window() -> _metrics.MeasurementScope:
    """Open an independent measurement window over the default registry.
    Any number may be live at once; none interferes with another."""
    return _registry.scope()


def reset() -> None:
    """Back-compat: start a new MODULE-DEFAULT window (what the
    module-level getters below report from).  Does not zero anything and
    does not touch windows other callers hold."""
    global _window
    with _window_lock:
        _window = _registry.scope()


def _module_window() -> _metrics.MeasurementScope:
    """The module-default window, read under the same lock reset() swaps
    it under: a getter racing a reset() must see one coherent scope, not
    whatever the interpreter happened to publish (the Logger.default()
    race of PR 2, in sibling form)."""
    with _window_lock:
        return _window


def stage_seconds(win: _metrics.MeasurementScope | None = None
                  ) -> dict[str, float]:
    """Per-stage accumulated THREAD time over the given window (default:
    the module window, i.e. since the last reset()).  With overlapped
    workers the stages can sum past wall time; the e2e attribution
    compares each stage against wall to find what binds the 1-core host."""
    win = win or _module_window()
    # stages untouched inside the window are dropped (zero delta), which
    # matches the old cleared-dict-on-reset surface
    return {dict(labels)["stage"]: v
            for labels, v in win.counters(STAGE_SECONDS).items() if v != 0}


def device_wait_seconds(win: _metrics.MeasurementScope | None = None
                        ) -> float:
    return (win or _module_window()).counter_value(DEVICE_WAIT_SECONDS)


def fetch_count(win: _metrics.MeasurementScope | None = None) -> int:
    return int((win or _module_window()).counter_value(DEVICE_FETCHES))
