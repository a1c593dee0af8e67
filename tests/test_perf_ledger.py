"""Perf ledger (obs/ledger.py) + regression sentinel (tools/perf_gate.py):
schema enforcement, journal-shaped durability, record construction from
registry windows, and the gate's per-class tolerance semantics."""

import json
import os
import sys

import pytest

from pbccs_tpu.obs.ledger import (
    LEDGER_CLASSES,
    LEDGER_FIELDS,
    LEDGER_SCHEMA_VERSION,
    LedgerSchemaError,
    PerfLedger,
    read_ledger,
    run_record,
)
from pbccs_tpu.obs.metrics import default_registry

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))

import perf_gate  # noqa: E402  (tools/ module, path-injected above)

_REG = default_registry()


def make_record(**over):
    rec = {"kind": "batch_run", "source": "ccs",
           "jax_version": "1.2.3", "platform": "cpu",
           "polish_dispatches": 3, "refine_rounds_host": 40,
           "padding_waste": 0.25, "compiles": 7, "wall_s": 2.0,
           "zmws": 8, "results": 8, "peak_rss_bytes": 1000}
    rec.update(over)
    return rec


class TestLedgerSchema:
    def test_every_class_is_declared(self):
        assert set(LEDGER_FIELDS.values()) <= set(LEDGER_CLASSES), \
            set(LEDGER_FIELDS.values()) - set(LEDGER_CLASSES)

    def test_append_stamps_version_and_time(self, tmp_path):
        path = str(tmp_path / "l.ndjson")
        led = PerfLedger(path)
        assert led.append({"kind": "batch_run", "source": "t"})
        led.close()
        records, skipped = read_ledger(path)
        assert skipped == 0 and len(records) == 1
        rec = records[0]
        assert rec["schema_version"] == LEDGER_SCHEMA_VERSION
        assert rec["t_unix"] > 0

    def test_unknown_field_is_refused(self, tmp_path):
        led = PerfLedger(str(tmp_path / "l.ndjson"))
        with pytest.raises(LedgerSchemaError, match="made_up_field"):
            led.append({"kind": "batch_run", "made_up_field": 1})

    def test_perf_block_carries_last_record(self, tmp_path):
        led = PerfLedger(str(tmp_path / "l.ndjson"))
        led.append({"kind": "serve_snapshot", "pending": 4})
        block = led.perf_block()
        assert block["schema_version"] == LEDGER_SCHEMA_VERSION
        assert block["records"] == 1
        assert block["last_record"]["pending"] == 4


class TestLedgerDurability:
    def test_torn_tail_skipped_not_raised(self, tmp_path):
        path = str(tmp_path / "l.ndjson")
        led = PerfLedger(path)
        led.append({"kind": "batch_run"})
        led.close()
        with open(path, "a") as f:
            f.write('{"kind": "batch_r')  # crash mid-append
        records, skipped = read_ledger(path)
        assert len(records) == 1 and skipped == 1

    def test_missing_file_is_empty_not_raise(self, tmp_path):
        assert read_ledger(str(tmp_path / "nope.ndjson")) == ([], 0)

    def test_write_failure_degrades_to_absence(self, tmp_path):
        # a directory in place of the ledger path: open() fails, the
        # ledger disables itself (False) instead of crashing the run
        path = str(tmp_path / "as_dir")
        os.mkdir(path)
        led = PerfLedger(path)
        assert led.append({"kind": "batch_run"}) is False
        assert led.append({"kind": "batch_run"}) is False  # stays dead
        assert led.records_written() == 0


class TestRunRecord:
    def test_counters_and_ratios_from_scope(self):
        scope = _REG.scope()
        _REG.counter("ccs_polish_dispatches_total").inc(2)
        _REG.counter("ccs_batch_slots_total", axis="zmw").inc(16)
        _REG.counter("ccs_batch_slots_used_total", axis="zmw").inc(12)
        rec = run_record(scope, kind="batch_run", source="t",
                         wall_s=2.0, zmws=12, results=11)
        assert rec["polish_dispatches"] == 2
        assert rec["fill_ratio_zmw"] == 0.75
        assert rec["padding_waste"] == 0.25
        assert rec["zmws_per_sec"] == 6.0
        assert rec["results"] == 11
        # every produced field is schema-declared (the append contract)
        assert set(rec) <= set(LEDGER_FIELDS)

    def test_what_the_caller_did_not_supply_is_absent(self):
        """The gate compares only fields both sides carry: a record
        holds no rate without a wall time and no ratio without slots."""
        rec = run_record(_REG.scope(), kind="serve_snapshot", source="t",
                         extra={"pending": 3})
        for field in ("wall_s", "zmws_per_sec", "zmws", "results",
                      "workload", "fill_ratio_zmw", "fill_ratio_read",
                      "padding_waste", "device_step_ms"):
            assert field not in rec, field
        assert rec["pending"] == 3 and rec["polish_dispatches"] == 0
        assert set(rec) <= set(LEDGER_FIELDS)

    def test_environment_fields_never_initialize_a_backend(self,
                                                           monkeypatch):
        """With no JAX_PLATFORMS and no backend yet initialized, the
        platform is simply ABSENT -- a ledger append must never be the
        thing that triggers backend discovery (router processes are
        host-side; discovery can block and contend the accelerator)."""
        import jax

        from pbccs_tpu.obs.ledger import environment_fields

        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(jax._src.xla_bridge, "_backends", {},
                            raising=False)
        called = []
        monkeypatch.setattr(jax, "devices",
                            lambda *a: called.append(1) or [])
        fields = environment_fields()
        assert "platform" not in fields
        assert fields["jax_version"] == jax.__version__
        assert not called, "environment_fields initialized a backend"


class TestPerfGate:
    def _baseline(self, **over):
        base = {"baseline_version": 1,
                "select": {"kind": "batch_run"},
                "jax_version": "1.2.3", "platform": "cpu",
                "tolerances": dict(perf_gate.DEFAULT_TOLERANCES),
                "metrics": perf_gate.observed_metrics([make_record()])}
        base.update(over)
        return base

    def test_clean_ledger_passes(self):
        violations, _ = perf_gate.compare(
            self._baseline(), [make_record()], counters_only=True)
        assert violations == []

    def test_counter_bump_fails_with_structured_diff(self):
        violations, _ = perf_gate.compare(
            self._baseline(), [make_record(refine_rounds_host=47)],
            counters_only=True)
        assert len(violations) == 1
        v = violations[0]
        assert v["metric"] == "refine_rounds_host"
        assert v["class"] == "counter"
        assert v["baseline"] == 40 and v["observed"] == 47

    def test_ratio_band_allows_small_drift_only(self):
        ok, _ = perf_gate.compare(
            self._baseline(), [make_record(padding_waste=0.26)],
            counters_only=True)
        assert ok == []
        bad, _ = perf_gate.compare(
            self._baseline(), [make_record(padding_waste=0.5)],
            counters_only=True)
        assert [v["metric"] for v in bad] == ["padding_waste"]

    def test_compile_class_skipped_on_jax_mismatch(self):
        violations, notes = perf_gate.compare(
            self._baseline(),
            [make_record(jax_version="9.9.9", compiles=99)],
            counters_only=True)
        assert violations == []
        assert any("compile-class" in n for n in notes)

    def test_wall_not_enforced_on_cpu(self):
        violations, notes = perf_gate.compare(
            self._baseline(), [make_record(wall_s=100.0)])
        assert violations == []
        assert any("wall/resource" in n for n in notes)

    def test_wall_median_and_band_on_accelerator(self):
        base = self._baseline(platform="tpu")
        recs = [make_record(platform="tpu", wall_s=w)
                for w in (2.0, 2.1, 50.0)]  # median 2.1: one spike is noise
        assert perf_gate.compare(base, recs)[0] == []
        slow = [make_record(platform="tpu", wall_s=w)
                for w in (3.0, 3.1, 3.2)]
        bad, _ = perf_gate.compare(base, slow)
        assert [v["metric"] for v in bad] == ["wall_s"]

    def test_wall_improvement_never_fails(self):
        base = self._baseline(platform="tpu")
        fast = [make_record(platform="tpu", wall_s=0.5)]
        assert perf_gate.compare(base, fast)[0] == []

    def test_missing_enforced_metric_is_violation(self):
        rec = make_record()
        del rec["refine_rounds_host"]
        bad, _ = perf_gate.compare(self._baseline(), [rec],
                                   counters_only=True)
        assert any(v["metric"] == "refine_rounds_host"
                   and v["observed"] is None for v in bad)

    def test_ignored_metric_is_exempt_and_noted(self):
        rec = make_record(compiles=99, refine_rounds_host=47)
        bad, notes = perf_gate.compare(self._baseline(), [rec],
                                       counters_only=True,
                                       ignore={"compiles"})
        assert [v["metric"] for v in bad] == ["refine_rounds_host"]
        assert any("exempted" in n and "compiles" in n for n in notes)

    @pytest.mark.parametrize("section,value,reason", [
        ("metrics", [1, 2], "metrics must be an object"),
        ("metrics", {"zmws": {"a": 1}}, "metrics.zmws must be a number"),
        ("tolerances", {"wall": "wide"}, "tolerances.wall must be a number"),
        ("select", ["batch_run"], "select must be an object"),
    ])
    def test_bad_baseline_names_its_fault(self, section, value, reason):
        assert perf_gate.bad_baseline_reason(self._baseline()) is None
        doc = self._baseline(**{section: value})
        assert reason in perf_gate.bad_baseline_reason(doc)

    def test_update_baseline_prints_accepted_deltas(self, tmp_path,
                                                    capsys):
        path = str(tmp_path / "base.json")
        old = self._baseline()
        perf_gate.update_baseline(
            path, old, [make_record(refine_rounds_host=47)],
            {"kind": "batch_run"})
        out = capsys.readouterr().out
        assert "accepting refine_rounds_host: 40 -> 47" in out
        with open(path) as f:
            fresh = json.load(f)
        assert fresh["metrics"]["refine_rounds_host"] == 47

    def test_cli_end_to_end(self, tmp_path):
        ledger = tmp_path / "l.ndjson"
        ledger.write_text(json.dumps(make_record()) + "\n")
        base = tmp_path / "b.json"
        assert perf_gate.main([str(ledger), "--baseline", str(base),
                               "--update-baseline"]) == 0
        assert perf_gate.main([str(ledger), "--baseline", str(base),
                               "--counters-only"]) == 0
        ledger.write_text(json.dumps(
            make_record(polish_dispatches=9)) + "\n")
        assert perf_gate.main([str(ledger), "--baseline", str(base),
                               "--counters-only"]) == 1

    def test_corrupt_baseline_is_exit_2_not_traceback(self, tmp_path):
        ledger = tmp_path / "l.ndjson"
        ledger.write_text(json.dumps(make_record()) + "\n")
        base = tmp_path / "b.json"
        doc = self._baseline()
        doc["metrics"]["zmws"] = "8"   # hand-mangled string value
        base.write_text(json.dumps(doc))
        assert perf_gate.main([str(ledger), "--baseline", str(base),
                               "--counters-only"]) == 2
        # compare() itself (library path) skips with a note, no crash
        violations, notes = perf_gate.compare(doc, [make_record()],
                                              counters_only=True)
        assert not any(v["metric"] == "zmws" for v in violations)
        assert any("non-numeric" in n for n in notes)
        # --update-baseline may regenerate OVER a corrupt baseline
        assert perf_gate.main([str(ledger), "--baseline", str(base),
                               "--update-baseline"]) == 0
        assert perf_gate.main([str(ledger), "--baseline", str(base),
                               "--counters-only"]) == 0

    def test_no_matching_records_is_usage_error(self, tmp_path):
        ledger = tmp_path / "l.ndjson"
        ledger.write_text(json.dumps(make_record(kind="serve_snapshot"))
                          + "\n")
        base = tmp_path / "b.json"
        base.write_text(json.dumps(self._baseline()))
        assert perf_gate.main([str(ledger), "--baseline",
                               str(base)]) == 2


# ------------------------------------------------ serve/router emitters

def _stub_engine(tmp_path, interval_s=30.0):
    import numpy as np

    from pbccs_tpu.pipeline import Failure, PreparedZmw
    from pbccs_tpu.serve.engine import CcsEngine, ServeConfig

    path = str(tmp_path / "serve_ledger.ndjson")
    eng = CcsEngine(
        config=ServeConfig(max_batch=1, max_wait_ms=20.0,
                           perf_ledger_path=path,
                           perf_ledger_interval_s=interval_s),
        prep_fn=lambda c, s: (None, PreparedZmw(
            c, np.zeros(8, np.int8), [], 1, 0, 0.0)),
        polish_fn=lambda p, s: [(Failure.SUCCESS, None) for _ in p])
    return eng, path


class TestServeLedger:
    def test_engine_writes_snapshots_and_final_record(self, tmp_path):
        import time as time_mod

        from pbccs_tpu.pipeline import Chunk, Subread

        eng, path = _stub_engine(tmp_path, interval_s=0.1)
        eng.start()
        try:
            chunk = Chunk("m/1", [Subread("m/1/0", b"\x00\x01" * 4)
                                  for _ in range(3)], [8.0] * 4)
            req = eng.submit(chunk)
            assert req.wait(10.0)
            # status carries the federated perf block
            perf = eng.status()["perf"]
            assert perf["schema_version"] == LEDGER_SCHEMA_VERSION
            deadline = time_mod.monotonic() + 5.0
            while time_mod.monotonic() < deadline:
                if read_ledger(path)[0]:
                    break
                time_mod.sleep(0.05)
        finally:
            eng.close()
        records, skipped = read_ledger(path)
        assert skipped == 0 and records
        assert all(r["kind"] == "serve_snapshot" for r in records)
        final = records[-1]
        assert final["completed"] == 1
        assert final["pending"] == 0
        assert set(final) <= set(LEDGER_FIELDS)

    def test_router_merges_fleet_records(self, tmp_path):
        import time as time_mod

        import numpy as np

        from pbccs_tpu.pipeline import Failure, PreparedZmw
        from pbccs_tpu.serve.engine import CcsEngine, ServeConfig
        from pbccs_tpu.serve.router import CcsRouter, RouterConfig
        from pbccs_tpu.serve.server import CcsServer

        # one replica WITH its own ledger, one without: the router's
        # fleet tick must record both (newest-ledger-record vs
        # live-status flavors)
        eng1, _ = _stub_engine(tmp_path, interval_s=0.1)
        eng1.start()
        srv1 = CcsServer(eng1, port=0).start()
        eng2 = CcsEngine(
            config=ServeConfig(max_batch=1, max_wait_ms=20.0),
            prep_fn=lambda c, s: (None, PreparedZmw(
                c, np.zeros(8, np.int8), [], 1, 0, 0.0)),
            polish_fn=lambda p, s: [(Failure.SUCCESS, None)
                                    for _ in p]).start()
        srv2 = CcsServer(eng2, port=0).start()
        fleet_path = str(tmp_path / "fleet_ledger.ndjson")
        router = CcsRouter(
            [f"127.0.0.1:{srv1.port}", f"127.0.0.1:{srv2.port}"],
            RouterConfig(health_interval_s=0.2,
                         perf_ledger_path=fleet_path,
                         perf_ledger_interval_s=0.2)).start()
        try:
            deadline = time_mod.monotonic() + 10.0
            while time_mod.monotonic() < deadline:
                kinds = {r["kind"] for r in read_ledger(fleet_path)[0]}
                if {"router_snapshot", "replica_snapshot"} <= kinds:
                    break
                time_mod.sleep(0.05)
        finally:
            router.close(drain=False)
            for srv, eng in ((srv1, eng1), (srv2, eng2)):
                srv.shutdown()
                eng.close(drain=False)
        records, _ = read_ledger(fleet_path)
        kinds = {r["kind"] for r in records}
        assert {"router_snapshot", "replica_snapshot"} <= kinds
        replicas = {r.get("replica") for r in records
                    if r["kind"] == "replica_snapshot"}
        assert {f"127.0.0.1:{srv1.port}",
                f"127.0.0.1:{srv2.port}"} <= replicas
