"""Performance ledger: schema-versioned NDJSON per-run perf records.

The trace/metrics planes are write-only: span rollups and the
federated exposition are produced and never *watched*, so a
padding-waste slide or a compile-count blowup survives until a human
re-reads JSON.  The ledger is the machine-readable record the
regression sentinel (tools/perf_gate.py) defends baselines against and
the substrate ROADMAP's continuous-batching and autopilot items key on:

  * one NDJSON record per run/snapshot, appended to ``--perfLedger
    PATH`` by the batch CLI and periodically by the serve engine (plus
    per-replica records merged fleet-wide by `ccs router --perfLedger`);
  * every field carries a TOLERANCE CLASS (``LEDGER_FIELDS``) the gate
    keys enforcement on -- wall-clock metrics are noisy and
    accelerator-only, CPU-deterministic counters are exact everywhere
    (the full class vocabulary is documented on ``LEDGER_CLASSES``);
  * the schema is drift-checked: the analyzer's REG011 pass fails the
    build when ``LEDGER_FIELDS`` and the DESIGN.md ledger-schema table
    disagree (regenerate with `python -m pbccs_tpu.analysis.cli
    --emit-tables`), so the gate, the docs, and the writers cannot
    desynchronize;
  * appends are journal-shaped exactly like the checkpoint journal:
    one line per record, flushed, torn tails tolerated by the reader
    (``read_ledger`` skips an unparseable final line with a note) --
    the `atomic_output` family's contract applied to an append-only
    sink.  A failing filesystem degrades the ledger to absence
    (counted under ``ccs_output_write_errors_total{sink=perf_ledger}``),
    never to a crashed run.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any

from pbccs_tpu.obs.metrics import MeasurementScope, default_registry

LEDGER_SCHEMA_VERSION = 2

# Tolerance classes (what tools/perf_gate.py enforces per class):
#   meta     identity/environment fields -- recorded, never gated
#   live     point-in-time serving state -- recorded, never gated
#   wall     wall-clock measurements: median-of-N vs a relative band,
#            enforced only on accelerator hosts (CPU wall time is noise)
#   resource host-memory figures: relative band, accelerator hosts only
#   counter  CPU-deterministic counts: exact match, enforced everywhere
#   ratio    CPU-deterministic ratios (fill, padding): absolute band,
#            enforced everywhere
#   compile  compile/cache counts: exact match everywhere, but only
#            when the ledger's jax_version matches the baseline's (a
#            jax upgrade legitimately changes compile behavior)
LEDGER_CLASSES = ("meta", "live", "wall", "resource", "counter", "ratio",
                  "compile")

# The canonical field -> tolerance-class schema.  REG011 drift-checks
# this mapping against the DESIGN.md ledger-schema table both ways, and
# PerfLedger.append refuses fields outside it -- a writer cannot mint
# an undocumented field.
LEDGER_FIELDS = {
    # ---- identity / environment (meta) ----
    "schema_version": "meta",
    "kind": "meta",            # batch_run | serve_snapshot |
    #                            router_snapshot | replica_snapshot |
    #                            fleet_event | tenant_snapshot
    "t_unix": "meta",
    "source": "meta",          # emitting process/row identity
    "workload": "meta",        # free-form workload descriptor (dict)
    "platform": "meta",        # jax backend platform ("cpu", "tpu", ...)
    "jax_version": "meta",
    "devices": "meta",
    "tuned_profile": "meta",   # active ccs-tune profile id, or "none"
    # ---- wall-clock (wall: accelerator-only, median-of-N) ----
    "wall_s": "wall",
    "zmws_per_sec": "wall",
    "device_wait_s": "wall",
    "device_step_ms": "wall",  # mean device fetch-to-fetch step
    "compile_s": "wall",       # warmup/compile seconds where measured
    # ---- host memory (resource) ----
    "peak_rss_bytes": "resource",
    # ---- CPU-deterministic counters (exact everywhere) ----
    "zmws": "counter",
    "results": "counter",
    "polish_dispatches": "counter",
    "batch_polishes": "counter",
    "sched_batches": "counter",
    "refine_rounds_host": "counter",
    "refine_rounds_device": "counter",
    "zmw_slots": "counter",
    "zmw_slots_used": "counter",
    "read_slots": "counter",
    "read_slots_used": "counter",
    "device_fetches": "counter",
    "quarantined_zmws": "counter",
    "degraded_zmws": "counter",
    "watchdog_timeouts": "counter",
    "oom_splits": "counter",
    "oom_ceilings": "counter",
    "admission_presplits": "counter",
    "budget_throttles": "counter",
    # ---- CPU-deterministic ratios (absolute band everywhere) ----
    "fill_ratio_zmw": "ratio",
    "fill_ratio_read": "ratio",
    "padding_waste": "ratio",
    "slot_occupancy": "ratio",
    "converged_fraction": "ratio",
    # ---- compile/cache counts (exact iff jax_version matches) ----
    "compiles": "compile",
    "compile_cache_hits": "compile",
    "compile_cache_misses": "compile",
    # ---- fleet-autopilot events (meta: audit trail, never gated) ----
    # one record per supervisor decision (kind == "fleet_event"):
    # respawn | quarantine | readmit | scale_up | scale_down | add |
    # remove | drain_kill | rolling_restart_begin / _step / _done
    "fleet_event": "meta",
    "slot": "meta",            # supervisor slot index the event is about
    "reason": "meta",          # structured cause (quarantine/bench text)
    "attempt": "meta",         # respawn attempt number within the window
    "backoff_s": "meta",       # backoff applied before the next respawn
    # ---- live serving state (recorded, never gated) ----
    "uptime_s": "live",
    "pending": "live",
    "in_flight_zmws": "live",
    "completed": "live",
    "errors": "live",
    "slo_requests": "live",
    "slo_violations": "live",
    "queue_depth": "live",
    "replica": "live",
    # ---- multi-tenant edge (kind == "tenant_snapshot" accounting rows
    # from the router's fair queue) ----
    "tenant": "meta",            # tenant name the record is about
    "tenant_priority": "meta",   # shed class (0 = never shed)
    "tenant_inflight": "live",
    "tenant_queued": "live",
    "tenant_completed": "live",
    "tenant_sheds": "live",
    "tenant_rejects": "live",
}

_reg = default_registry()


def _m_records(kind: str):
    return _reg.counter("ccs_ledger_records_total",
                        "Perf-ledger records appended, by record kind",
                        kind=kind)


def _m_write_errors():
    # the shared output-failure counter (resilience.resources registers
    # the name); the ledger is one more sink under it
    return _reg.counter("ccs_output_write_errors_total", sink="perf_ledger")


class LedgerSchemaError(ValueError):
    """A record carries a field outside LEDGER_FIELDS (the REG011
    contract applied at write time)."""


class PerfLedger:
    """Append-only NDJSON perf journal (thread-safe).

    One ``append(record)`` per run/row/snapshot; each line is flushed so
    a crash loses at most the in-flight record and the reader's
    torn-tail tolerance absorbs a half-written one.  A filesystem
    failure (ENOSPC, quota) disables the ledger with a warning and a
    ``ccs_output_write_errors_total{sink=perf_ledger}`` count --
    observability must degrade to absence, never crash the run."""

    def __init__(self, path: str, logger=None):
        self.path = path
        self._lock = threading.Lock()
        self._fh = None
        self._dead = False
        self._records = 0
        self._last: dict[str, Any] | None = None
        self._log = logger

    def _warn(self, msg: str) -> None:
        if self._log is not None:
            self._log.warn(msg)

    def append(self, record: dict[str, Any]) -> bool:
        """Validate + append one record; returns False when the ledger
        is disabled (a prior write failure).  Unknown fields raise
        LedgerSchemaError -- the schema table is the contract."""
        unknown = sorted(set(record) - set(LEDGER_FIELDS))
        if unknown:
            raise LedgerSchemaError(
                f"fields not in LEDGER_FIELDS: {', '.join(unknown)} "
                "(extend the schema + regenerate the DESIGN.md "
                "ledger-schema table)")
        rec = {"schema_version": LEDGER_SCHEMA_VERSION,
               "t_unix": round(time.time(), 3), **record}
        line = json.dumps(rec, separators=(",", ":"), sort_keys=True,
                          default=str) + "\n"
        with self._lock:
            if self._dead:
                return False
            try:
                if self._fh is None:
                    self._fh = open(self.path, "a")
                self._fh.write(line)
                self._fh.flush()
            except OSError as e:
                self._dead = True
                _m_write_errors().inc()
                self._warn(f"perf ledger {self.path} disabled after "
                           f"write failure: {e}")
                return False
            self._records += 1
            self._last = rec
        _m_records(str(rec.get("kind", "unknown"))).inc()
        return True

    def records_written(self) -> int:
        with self._lock:
            return self._records

    def last_record(self) -> dict[str, Any] | None:
        with self._lock:
            return dict(self._last) if self._last is not None else None

    def perf_block(self) -> dict[str, Any]:
        """The status verb's `perf` block (protocol.FIELD_PERF): the
        schema version, how many records this process appended, and the
        most recent record -- what the router federates fleet-wide."""
        return {"schema_version": LEDGER_SCHEMA_VERSION,
                "records": self.records_written(),
                "last_record": self.last_record()}

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                try:
                    self._fh.close()
                except OSError:
                    pass
                self._fh = None


def read_ledger(path: str) -> tuple[list[dict[str, Any]], int]:
    """Parse an NDJSON ledger; returns (records, skipped_lines).  A torn
    tail (crash mid-append) or an alien line is skipped and counted,
    never a raise -- the checkpoint journal's loader contract."""
    records: list[dict[str, Any]] = []
    skipped = 0
    try:
        fh = open(path)
    except OSError:
        return [], 0
    with fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                skipped += 1
                continue
            if isinstance(obj, dict):
                records.append(obj)
            else:
                skipped += 1
    return records, skipped


# --------------------------------------------------- record construction

def _label_key(labels: dict[str, str]) -> tuple[tuple[str, str], ...]:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


def _counter(delta: dict, name: str, **labels) -> int:
    v = delta.get((name, _label_key(labels)), 0.0)
    return int(round(v)) if isinstance(v, (int, float)) else 0


def _counter_sum(delta: dict, name: str) -> int:
    """Sum a labeled counter family's deltas (site/cause labels)."""
    return int(round(sum(
        v for (n, _), v in delta.items()
        if n == name and isinstance(v, (int, float)))))


def environment_fields() -> dict[str, Any]:
    """The meta fields every record shares: platform + jax version
    (best-effort -- a ledger write must NEVER initialize a backend:
    router processes are host-side, backend discovery can block for
    minutes and contend an exclusive accelerator)."""
    out: dict[str, Any] = {}
    try:
        import jax

        out["jax_version"] = jax.__version__
        platform = os.environ.get("JAX_PLATFORMS") or None
        if platform is None:
            # consult only an ALREADY-initialized backend (private
            # registry read, guarded): jax.devices() here would trigger
            # full backend discovery from a ledger append
            bridge = getattr(getattr(jax, "_src", None), "xla_bridge",
                             None)
            if bridge is not None and getattr(bridge, "_backends", None):
                platform = jax.devices()[0].platform
        if platform:
            out["platform"] = platform.split(",")[0].strip()
    except Exception:  # noqa: BLE001 -- environment capture is best-effort
        pass
    try:
        from pbccs_tpu.runtime import tuning

        out["tuned_profile"] = tuning.ledger_tag()
    except Exception:  # noqa: BLE001 -- environment capture is best-effort
        pass
    return out


def run_record(scope: MeasurementScope, *, kind: str, source: str,
               workload: dict | None = None,
               wall_s: float | None = None,
               zmws: int | None = None,
               results: int | None = None,
               extra: dict | None = None) -> dict[str, Any]:
    """Build one ledger record from a MeasurementScope's registry deltas
    plus caller-known figures.  The scope supplies every counter the
    registry already tracks (compiles, refine rounds, slot fills,
    governor interventions); the caller supplies what only it knows
    (wall time, workload identity)."""
    from pbccs_tpu.resilience.resources import peak_rss_bytes

    # ONE registry snapshot for the whole record (scope.counter_value
    # would re-snapshot per field)
    delta = scope.delta()
    zslots = _counter(delta, "ccs_batch_slots_total", axis="zmw")
    zused = _counter(delta, "ccs_batch_slots_used_total", axis="zmw")
    rslots = _counter(delta, "ccs_batch_slots_total", axis="read")
    rused = _counter(delta, "ccs_batch_slots_used_total", axis="read")
    fetches = _counter(delta, "ccs_device_fetches_total")
    wait_s = float(delta.get(("ccs_device_wait_seconds_total", ()), 0.0))
    rec: dict[str, Any] = {
        "kind": kind,
        "source": source,
        **environment_fields(),
        "polish_dispatches": _counter(delta, "ccs_polish_dispatches_total"),
        "batch_polishes": _counter(delta, "ccs_batch_polishes_total"),
        "sched_batches": _counter(delta, "ccs_sched_batches_total"),
        "refine_rounds_host": _counter(delta, "ccs_refine_rounds_total",
                                       source="host"),
        "refine_rounds_device": _counter(delta, "ccs_refine_rounds_total",
                                         source="device"),
        "zmw_slots": zslots,
        "zmw_slots_used": zused,
        "read_slots": rslots,
        "read_slots_used": rused,
        "device_fetches": fetches,
        "device_wait_s": round(wait_s, 4),
        "quarantined_zmws": _counter(delta, "ccs_quarantined_zmws_total"),
        "degraded_zmws": _counter(delta, "ccs_degraded_zmws_total"),
        "oom_splits": _counter(delta, "ccs_resource_oom_splits_total"),
        "oom_ceilings": _counter(delta, "ccs_resource_oom_ceilings_total"),
        "admission_presplits": _counter(
            delta, "ccs_resource_presplit_batches_total"),
        "compiles": _counter(delta, "ccs_compiles_total"),
        "compile_cache_hits": _counter(delta,
                                       "ccs_compile_cache_events_total",
                                       kind="hit"),
        "compile_cache_misses": _counter(
            delta, "ccs_compile_cache_events_total", kind="miss"),
        "peak_rss_bytes": peak_rss_bytes(),
        # watchdog + throttles carry site/cause labels; sum across them
        "watchdog_timeouts": _counter_sum(delta,
                                          "ccs_watchdog_timeouts_total"),
        "budget_throttles": _counter_sum(delta,
                                         "ccs_resource_throttles_total"),
    }
    if zslots:
        rec["fill_ratio_zmw"] = round(zused / zslots, 4)
        rec["padding_waste"] = round(1.0 - zused / zslots, 4)
    if rslots:
        rec["fill_ratio_read"] = round(rused / rslots, 4)
    if fetches and wait_s:
        rec["device_step_ms"] = round(wait_s * 1e3 / fetches, 4)
    if workload is not None:
        rec["workload"] = workload
    if wall_s is not None:
        rec["wall_s"] = round(float(wall_s), 4)
        if zmws:
            rec["zmws_per_sec"] = round(zmws / wall_s, 4)
    if zmws is not None:
        rec["zmws"] = int(zmws)
    if results is not None:
        rec["results"] = int(results)
    if extra:
        rec.update(extra)
    return rec
