"""Roofline attribution plane (obs/roofline.py): CostCard extraction
determinism across fresh processes, the degraded no-cost-analysis path,
ledger schema enforcement for the roofline fields, tracker
charge/measure surfaces, and the wire/console/report integrations."""

import json
import os
import subprocess
import sys

import pytest

from pbccs_tpu.obs import roofline
from pbccs_tpu.obs.ledger import (
    LEDGER_FIELDS,
    LedgerSchemaError,
    PerfLedger,
)
from pbccs_tpu.obs.metrics import MetricsRegistry

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# tiny extraction geometry: the smallest bucket the repo's own shape
# quantization produces (2 ZMWs, 2 passes, 40-base templates)
_GEOM = dict(imax=64, jmax=64, r=4, z=2, width=64,
             use_pallas=False, guided_passes=0)

_EXTRACT_SCRIPT = """\
import json, os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from dataclasses import asdict
from pbccs_tpu.obs import roofline
card = roofline.extract_card(imax=64, jmax=64, r=4, z=2, width=64,
                             use_pallas=False, guided_passes=0)
assert card is not None, "extraction returned no card on cpu"
print(json.dumps(asdict(card), sort_keys=True))
"""


def _extract_in_fresh_process(cache_dir: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache_dir)
    env.pop("PBCCS_ROOFLINE", None)
    proc = subprocess.run([sys.executable, "-c", _EXTRACT_SCRIPT],
                          capture_output=True, text=True, timeout=300,
                          cwd=_REPO, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_cost_card_deterministic_across_fresh_processes(tmp_path):
    """The tentpole determinism claim: two FRESH processes extracting
    the same bucket on the CPU backend produce identical cards (shared
    compile cache makes run 2 cheap; the VALUES must not depend on
    which process asked)."""
    cache = str(tmp_path / "cache")
    card1 = _extract_in_fresh_process(cache)
    card2 = _extract_in_fresh_process(cache)
    assert card1 == card2
    assert card1["flops"] > 0
    assert card1["label"] == "I64xJ64xR4"
    assert card1["platform"] == "cpu"


class _FakeCompiled:
    def __init__(self, ca=None, raise_ca=False):
        self._ca, self._raise = ca, raise_ca

    def cost_analysis(self):
        if self._raise:
            raise RuntimeError("backend has no cost analysis")
        return self._ca

    def memory_analysis(self):
        raise RuntimeError("no memory analysis either")


def test_degraded_no_cost_analysis_yields_absent_card():
    """A backend without cost analysis yields None, never a crash --
    every shape the real API can degrade into."""
    for compiled in (_FakeCompiled(raise_ca=True),
                     _FakeCompiled(ca=None),
                     _FakeCompiled(ca=[]),
                     _FakeCompiled(ca="nope"),
                     _FakeCompiled(ca={}),                  # no flops
                     _FakeCompiled(ca={"flops": -1.0}),     # absent sentinel
                     _FakeCompiled(ca={"flops": "many"})):
        card = roofline.card_from_compiled(
            compiled, label="I64xJ64xR4", imax=64, jmax=64, r=4, z=2,
            width=64)
        assert card is None


def test_card_from_compiled_list_and_dict_forms():
    """jax returns dict or list-of-dict depending on version; both must
    parse, and memory_analysis failures must not lose the card."""
    ca = {"flops": 1000.0, "bytes accessed": 4000.0,
          "optimal_seconds": 0.25}
    for form in (ca, [ca]):
        card = roofline.card_from_compiled(
            _FakeCompiled(ca=form), label="I64xJ64xR4", imax=64,
            jmax=64, r=4, z=2, width=64)
        assert card is not None
        assert card.flops == 1000
        assert card.bytes_accessed == 4000
        assert card.intensity == 0.25
        assert card.optimal_seconds == 0.25
        assert card.peak_hbm_bytes == 0   # memory_analysis raised


def test_card_charge_scaling_is_integer_exact():
    card = roofline.CostCard(
        label="I64xJ64xR4", imax=64, jmax=64, r=4, z=4, width=64,
        flops=1001, bytes_accessed=2003, peak_hbm_bytes=0,
        intensity=None, optimal_seconds=None, platform="cpu",
        jax_version="x")
    assert card.flops_for(8) == 2002
    assert card.flops_for(2) == 500    # floor division: deterministic
    assert card.bytes_for(4) == 2003


def test_ledger_rejects_undeclared_roofline_field(tmp_path):
    """REG011-style: the schema gate must reject a roofline field that
    is not declared in LEDGER_FIELDS (and accept the declared ones)."""
    led = PerfLedger(str(tmp_path / "ledger.ndjson"))
    with pytest.raises(LedgerSchemaError):
        led.append({"kind": "batch_run", "roofline_bogus": 1})
    assert {"roofline_flops", "roofline_bytes",
            "roofline_achieved_tflops",
            "roofline_efficiency"} <= set(LEDGER_FIELDS)
    assert LEDGER_FIELDS["roofline_flops"] == "counter"
    assert LEDGER_FIELDS["roofline_bytes"] == "counter"
    assert LEDGER_FIELDS["roofline_achieved_tflops"] == "wall"
    assert LEDGER_FIELDS["roofline_efficiency"] == "wall"
    led.append({"kind": "batch_run", "roofline_flops": 12,
                "roofline_bytes": 34, "roofline_achieved_tflops": 0.5,
                "roofline_efficiency": 0.01})


def _tracker_with_card(z: int = 2) -> roofline.RooflineTracker:
    tr = roofline.RooflineTracker(registry=MetricsRegistry())
    tr.register_card(roofline.CostCard(
        label="I64xJ64xR4", imax=64, jmax=64, r=4, z=z, width=64,
        flops=1_000_000, bytes_accessed=2_000_000, peak_hbm_bytes=0,
        intensity=0.5, optimal_seconds=None, platform="cpu",
        jax_version="x"), persist=False)
    return tr


def test_tracker_charges_and_status_block(monkeypatch):
    monkeypatch.delenv("PBCCS_ROOFLINE", raising=False)
    monkeypatch.setenv("PBCCS_ROOFLINE_PEAK_TFLOPS", "1.0")
    tr = _tracker_with_card(z=2)
    tr.charge_execution(imax=64, jmax=64, r=4, z=4)   # 2x the card z
    with tr.refine_scope(imax=64, jmax=64, r=4):
        pass
    with tr.dispatch_scope("I64xJ64xR4", zmws=4):
        pass
    block = tr.status_block()
    assert block is not None
    assert block["schema_version"] == roofline.ROOFLINE_SCHEMA_VERSION
    assert block["peak_tflops"] == 1.0
    entry = block["buckets"]["I64xJ64xR4"]
    assert entry["flops"] == 1_000_000          # card bound
    assert entry["flops_charged"] == 2_000_000  # scaled by Z=4 vs z=2
    assert entry["dispatches"] == 1
    assert entry["refine_s"] >= 0.0
    assert entry["achieved_tflops"] >= 0.0
    assert entry["efficiency"] == pytest.approx(
        entry["achieved_tflops"], rel=1e-6)   # peak pinned to 1.0

    # block keys match the wire contract (protocol.FIELD_ROOFLINE)
    from pbccs_tpu.serve import protocol
    assert protocol.KEY_ROOFLINE_SCHEMA in block
    assert protocol.KEY_ROOFLINE_PEAK in block
    assert protocol.KEY_ROOFLINE_BUCKETS in block


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("platform,kind,peak", [
    ("tpu", "TPU v5 lite", 197.0),      # the published v5e bf16 peak
    ("tpu", "TPU v9 imaginary", None),  # unknown TPU kind: no default
    ("cpu", "cpu", 0.1),
    ("quantum", "qpu", None),           # unknown platform: no default
])
def test_peak_is_keyed_by_device_kind(monkeypatch, platform, kind, peak):
    """On a TPU the efficiency denominator is the published peak of the
    device_kind that ran; a kind with no entry yields no efficiency
    figure at all (never another chip's peak, never 1.0)."""
    import jax

    monkeypatch.delenv("PBCCS_ROOFLINE", raising=False)
    monkeypatch.delenv("PBCCS_ROOFLINE_PEAK_TFLOPS", raising=False)
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice(platform, kind)])
    tr = _tracker_with_card(z=2)
    tr.charge_execution(imax=64, jmax=64, r=4, z=4)
    with tr.refine_scope(imax=64, jmax=64, r=4):
        pass
    assert tr.peak_tflops() == peak
    block = tr.status_block()
    assert block["peak_tflops"] == peak
    entry = block["buckets"]["I64xJ64xR4"]
    gauges = {k[0] for k in tr._registry.snapshot()}
    if peak is None:
        assert entry["efficiency"] is None
        assert roofline.EFFICIENCY not in gauges
        assert roofline.EFFICIENCY_OVERALL not in gauges
    else:
        assert entry["efficiency"] == pytest.approx(
            entry["achieved_tflops"] / peak, rel=1e-5)
        assert roofline.EFFICIENCY in gauges


def test_tracker_charge_without_card_is_noop():
    tr = roofline.RooflineTracker(registry=MetricsRegistry())
    tr.charge_execution(imax=64, jmax=64, r=4, z=4)
    assert tr.status_block() is None


def test_dispatch_scope_reentrancy_counts_outer_only(monkeypatch):
    """Fleet serve: _run_polish runs inside a pool task that already
    opened a dispatch scope -- the inner scope must not double count."""
    monkeypatch.delenv("PBCCS_ROOFLINE", raising=False)
    tr = _tracker_with_card()
    with tr.dispatch_scope("I64xJ64xR4", zmws=2):
        with tr.dispatch_scope("I64xJ64xR4", zmws=2):
            pass
    assert tr.status_block()["buckets"]["I64xJ64xR4"]["dispatches"] == 1


def test_disabled_plane_is_inert(monkeypatch):
    monkeypatch.setenv("PBCCS_ROOFLINE", "0")
    tr = _tracker_with_card()
    tr.charge_execution(imax=64, jmax=64, r=4, z=4)
    with tr.refine_scope(imax=64, jmax=64, r=4):
        pass
    entry = tr.status_block()["buckets"]["I64xJ64xR4"]
    assert entry["flops_charged"] == 0
    assert entry["refine_s"] == 0.0
    assert tr.ensure_card(**_GEOM) is None


def test_cards_roundtrip_and_byte_stable(tmp_path):
    path = str(tmp_path / "cards.json")
    card = roofline.CostCard(
        label="I64xJ64xR4", imax=64, jmax=64, r=4, z=2, width=64,
        flops=7, bytes_accessed=11, peak_hbm_bytes=13, intensity=0.6364,
        optimal_seconds=None, platform="cpu", jax_version="x")
    assert roofline.save_cards(path, {card.label: card})
    blob1 = open(path, "rb").read()
    assert roofline.load_cards(path) == {card.label: card}
    # a second save of the same cards must be byte-identical (no
    # timestamps, sorted keys) -- what the smoke asserts across runs
    assert roofline.save_cards(path, {card.label: card})
    assert open(path, "rb").read() == blob1


def test_load_cards_tolerates_garbage(tmp_path):
    p = tmp_path / "cards.json"
    p.write_text("{not json")
    assert roofline.load_cards(str(p)) == {}
    p.write_text(json.dumps({"schema_version": 999, "cards": {}}))
    assert roofline.load_cards(str(p)) == {}
    assert roofline.load_cards(str(tmp_path / "missing.json")) == {}


def test_label_from_capacity_bucket():
    assert roofline.label_from_capacity_bucket(
        ("shape", 64, 128, 4)) == "I64xJ128xR4"
    assert roofline.label_from_capacity_bucket(None) is None
    assert roofline.label_from_capacity_bucket(("other", 1)) is None
    assert roofline.label_from_capacity_bucket("bucket") is None


def test_protocol_declares_roofline_block():
    from pbccs_tpu.serve import protocol
    spec = protocol.WIRE_FIELDS[protocol.FIELD_ROOFLINE]
    assert protocol.VERB_STATUS in spec["verbs"]
    assert set(spec["keys"]) == {protocol.KEY_ROOFLINE_SCHEMA,
                                 protocol.KEY_ROOFLINE_PEAK,
                                 protocol.KEY_ROOFLINE_BUCKETS}


def test_console_row_carries_roofline_efficiency():
    from pbccs_tpu.obs.console import _replica_row, render_text
    metrics = {
        ("ccs_serve_completed_total", ()): 10.0,
        ("ccs_serve_pending", ()): 0.0,
        ("ccs_serve_in_flight_zmws", ()): 0.0,
        ("ccs_roofline_efficiency_overall", ()): 0.123456,
        ("ccs_roofline_achieved_tflops_overall", ()): 0.0123456,
    }
    row = _replica_row(None, metrics, None, None)
    assert row["roofline"]["efficiency"] == pytest.approx(0.123456)
    assert row["roofline"]["achieved_tflops"] == pytest.approx(0.0123456)
    view = {"target": "t", "engine": "ccs-serve", "fleet": {},
            "replicas": [row]}
    text = render_text(view)
    assert "EFF" in text.splitlines()[1]
    assert "0.123456" in text


def test_run_roofline_cards_report(tmp_path, capsys):
    path = str(tmp_path / "cards.json")
    card = roofline.CostCard(
        label="I64xJ64xR4", imax=64, jmax=64, r=4, z=2, width=64,
        flops=7, bytes_accessed=11, peak_hbm_bytes=13, intensity=0.6364,
        optimal_seconds=None, platform="cpu", jax_version="x")
    roofline.save_cards(path, {card.label: card})
    assert roofline.run_roofline(["--cards", path,
                                  "--format", "json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["source"] == "cards"
    assert doc["rows"][0]["bucket"] == "I64xJ64xR4"
    assert roofline.run_roofline(["--cards", path]) == 0
    assert "I64xJ64xR4" in capsys.readouterr().out


def test_run_roofline_ledger_report(tmp_path, capsys):
    ledger = tmp_path / "ledger.ndjson"
    rec = {"schema_version": 1, "kind": "batch_run",
           "roofline_flops": 1000, "roofline_bytes": 2000,
           "roofline_achieved_tflops": 0.001,
           "roofline_efficiency": 0.01, "polish_dispatches": 3}
    ledger.write_text(json.dumps(rec) + "\n")
    assert roofline.run_roofline(["--ledger", str(ledger),
                                  "--format", "json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["source"] == "ledger"
    [row] = doc["rows"]
    assert row["flops"] == 1000
    assert row["efficiency"] == 0.01


def test_run_record_includes_roofline_fields_from_scope():
    """run_record folds the roofline counter deltas in (and omits the
    fields entirely on the degraded/no-card path)."""
    from pbccs_tpu.obs.ledger import run_record
    from pbccs_tpu.obs.metrics import default_registry

    reg = default_registry()
    scope = reg.scope()
    rec0 = run_record(scope, kind="batch_run", source="test")
    # no roofline activity inside this scope window -> fields absent
    assert "roofline_flops" not in rec0

    scope2 = reg.scope()
    reg.counter(roofline.FLOPS_TOTAL, bucket="IxJxR").inc(5000)
    reg.counter(roofline.BYTES_TOTAL, bucket="IxJxR").inc(7000)
    reg.counter(roofline.REFINE_SECONDS, bucket="IxJxR").inc(2.0)
    rec = run_record(scope2, kind="batch_run", source="test")
    assert rec["roofline_flops"] == 5000
    assert rec["roofline_bytes"] == 7000
    assert rec["roofline_achieved_tflops"] == pytest.approx(
        5000 / 1e12 / 2.0, rel=1e-4)
    assert rec["roofline_efficiency"] > 0


def test_perf_gate_floor_enforcement(tmp_path):
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import perf_gate

    baseline = {
        "baseline_version": 1,
        "jax_version": "x", "platform": "tpu",
        "select": {"kind": "batch_run"},
        "metrics": {"zmws": 8},
        "floors": {"roofline_efficiency": 0.5},
    }
    assert perf_gate.bad_baseline_reason(baseline) is None
    rec = {"kind": "batch_run", "jax_version": "x", "platform": "tpu",
           "zmws": 8, "roofline_efficiency": 0.75}
    violations, _ = perf_gate.compare(baseline, [rec])
    assert violations == []
    rec_bad = dict(rec, roofline_efficiency=0.25)
    violations, _ = perf_gate.compare(baseline, [rec_bad])
    assert [v["metric"] for v in violations] == ["roofline_efficiency"]
    assert violations[0]["class"] == "floor"
    # a missing metric cannot satisfy a floor
    rec_none = {k: v for k, v in rec.items()
                if k != "roofline_efficiency"}
    violations, _ = perf_gate.compare(baseline, [rec_none])
    assert violations and violations[0]["class"] == "floor"
    # counters-only (tier-1 CI) skips floors with a note
    violations, notes = perf_gate.compare(baseline, [rec_bad],
                                          counters_only=True)
    assert violations == []
    assert any("floor" in n for n in notes)
    # malformed floors are an exit-2 diagnostic, not a crash
    assert perf_gate.bad_baseline_reason(
        dict(baseline, floors={"roofline_efficiency": "high"}))
    assert perf_gate.bad_baseline_reason(
        dict(baseline, floors={"not_a_field": 1.0}))
