"""Unified observability layer: metrics, traces, and profiling hooks.

Seven cooperating pieces, all host-side and dependency-free (no jax
import at module load, so the CLI's argument errors stay fast):

  * obs.metrics -- a thread-safe MetricsRegistry (counters, gauges,
    histograms with fixed log-scale buckets) with Prometheus text
    exposition, per-registry MeasurementScope windows (concurrent
    measurement windows instead of one global reset), a per-name series
    cap (label-cardinality armor), and the text-level federation
    helpers the router's fleet scrape is built from;
  * obs.trace -- per-ZMW span trees (filter -> draft -> polish rounds ->
    emit) with wall vs device-wait attribution AND cross-process trace
    context (trace_id / span_id / remote_parent riding the serve
    protocol's `trace` submit field), exported as Chrome-trace/Perfetto
    JSON (`--trace-out`, serve `trace` verb; tools/trace_merge.py
    assembles the fleet-wide timeline);
  * obs.flight -- the refine-loop flight recorder: per-round
    convergence/occupancy/padding gauges plus a bounded ring buffer
    dumped on quarantine / capacity splits;
  * obs.httpexp -- the stdlib-HTTP `/metrics` + `/healthz` scrape
    endpoint (`--metricsPort` on `ccs serve` and `ccs router`; healthz
    tracks the engine/router `accepting` flag through a drain);
  * obs.ledger -- the performance ledger: schema-versioned NDJSON
    per-run perf records with per-field tolerance classes
    (`--perfLedger`; tools/perf_gate.py is the regression sentinel
    defending PERF_BASELINE.json, REG011 drift-checks the schema);
  * obs.console -- `ccs top`, the live plain-terminal fleet console
    over the status verb + the federated exposition;
  * obs.profiling -- the opt-in jax.profiler capture hook
    (`--profile-dir`).

`runtime/timing.py` keeps its historical module-level API as a
back-compat shim over the default registry, so existing callers
(engine status) see identical semantics.
"""

from pbccs_tpu.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MeasurementScope,
    MetricsRegistry,
    default_registry,
    log_buckets,
)
from pbccs_tpu.obs.profiling import profile_capture  # noqa: F401
from pbccs_tpu.obs.trace import (  # noqa: F401
    Tracer,
    get_tracer,
    set_tracer,
    span,
)
