"""The cell `2kb-3to10x.serve-c32` (PR 31): its traffic file and driver by
name, its five readers on hand-made spans and counters, the client's frame,
and a rehearsal of the cell, sound and under the `draft-only` control."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness import manifest, prom, simulate
from harness.reduce import ReaderInput

CELL = "2kb-3to10x.serve-c32"
CONFIG = "rs2-p6c4-2kb-amplicon-served"
NEW = ["serve_latency_p50_ms", "serve_latency_p95_ms", "serve_queue_ms_per_zmw",
       "serve_flush_fill_share", "setup_serve_warm_s"]
STAGES = "ccs_serve_stage_latency_seconds"
SLOTS = "ccs_serve_flush_slots_total"


def read(name: str, spans=(), before=None, after=None):
    inp = ReaderInput(prom.Counters(before or {}, after or {}), list(spans), None,
                      100, "TPU v5 lite", {}, None)
    return manifest.load_by_path("metrics", name).read(inp)


def span(name: str, ms: float) -> dict:
    return {"name": name, "ts": 0.0, "dur": ms * 1e3, "args": {}}


# ------------------------------------------------------- the cell by name


def test_the_cell_its_traffic_and_its_driver_load_by_name():
    doc = manifest.load()
    cell = manifest.Cell(doc, CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        CONFIG, "serve-closed-32", 1)
    assert doc["workloads"][-1]["name"] == CELL
    assert [c["name"] for c in doc["configs"]] == ["rs2-p6c4-500bp-30x",
                                                   "rs2-p6c4-2kb-amplicon", CONFIG]
    t = cell.traffic
    assert t["driver"] == "serve_closed" and t["sessions"] == 32
    assert t["serve_args"] == ["--devices", "1", "--bucket", "16x10x2000"]
    assert (t["deck_zmws"], t["wave_zmws"], t["waves_max"]) == (256, 64, 3)
    assert t["trace"] == {"start_s": 10.0, "seconds": 6.0}
    assert t["guarantees"] and t["assumed"]
    # a pool 1.5 times a 40 s window at up to 29.9 ZMWs/s, deck 0 apart
    assert (t["pool_decks"] - 1) * t["deck_zmws"] >= 1.5 * 40 * 29.8
    driver = manifest.load_by_path("drivers", t["driver"])
    assert all(hasattr(driver.Session, m) for m in (
        "setup", "window", "repeat_check", "memory_peak_bytes", "close"))
    assert [m["name"] for m in doc["per_layer"]][-5:] == NEW
    for m in doc["per_layer"][-5:]:
        assert (m["layer"], m["workloads"]) == ("serve", [CELL])
        assert m["moves"] == ("setup_s" if m["name"] == "setup_serve_warm_s"
                              else "zmws_per_s")
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= reported
    assert not reported & {"read_ms_per_zmw", "emit_ms_per_zmw", "turn_wait_ms_per_zmw",
                           "device_unowned_share", "straggler_ms_per_zmw"}


def test_the_served_configuration_is_the_batch_cells_run_behind_the_server():
    """A configuration of its own (another deployment: the entry point, the
    source, nothing cut), with the library, the gates and the `check` of
    `rs2-p6c4-2kb-amplicon` as they stand."""
    doc = manifest.load()
    entries = {c["name"]: c for c in doc["configs"]}
    served, batch = entries[CONFIG], entries["rs2-p6c4-2kb-amplicon"]
    assert served["file"] != batch["file"] and served["source"] != batch["source"]
    assert served["reduced"] == [] and batch["reduced"] == ["zmws"]
    files = {}
    for name, entry in (("served", served), ("batch", batch)):
        with open(os.path.join(manifest.ROOT, entry["file"])) as f:
            files[name] = json.load(f)
    for key in ("model", "library", "gates", "check", "rehearse_library", "rehearse_check"):
        assert files["served"][key] == files["batch"][key], key
    assert (files["served"]["name"], files["served"]["source"]) == (CONFIG, served["source"])
    assert files["served"]["reduced"] == [] and "zmws" not in files["served"]
    assert set(files["served"]["check_reasons"]) >= set(files["served"]["check"]["limits"])
    traffic = manifest.Cell(doc, CELL).traffic
    assert traffic["serve_args"][-1] == files["served"]["serve_bucket"]


def test_the_clients_frame_is_the_batch_cells_zmw():
    """A submit frame names and describes a ZMW as the subread BAM of the
    batch cells does: movie/hole/start_end, float32 SNR, accuracy 0.85."""
    driver = manifest.load_by_path("drivers", "serve_closed")
    lib = {"insert_length": {"dist": "fixed", "value": 60},
           "passes": {"dist": "fixed", "value": 3},
           "snr": {"dist": "uniform", "lo": 6.0, "hi": 12.0}}
    z = simulate.make_zmw(7, 0, 41, lib)
    msg = json.loads(driver._frame(z))
    assert (msg["verb"], msg["id"]) == ("submit", "z41")
    assert msg["zmw"]["id"] == f"{simulate.MOVIE}/41"
    assert msg["zmw"]["snr"] == [float(np.float32(s)) for s in z["snr"]]
    reads = msg["zmw"]["reads"]
    assert [r["seq"] for r in reads] == ["".join("ACGT"[b] for b in r) for r in z["reads"]]
    n0 = len(z["reads"][0])
    assert reads[0]["id"].endswith(f"/41/0_{n0}")
    assert reads[1]["id"].endswith(f"/41/{n0 + 50}_{n0 + 50 + len(z['reads'][1])}")
    assert {(r["flags"], r["accuracy"]) for r in reads} == {(3, float(np.float32(0.85)))}


def test_a_reply_becomes_what_the_check_reads():
    driver = manifest.load_by_path("drivers", "serve_closed")
    ok = {"type": "result", "status": "Success", "sequence": "ACGT", "qual": "IIII",
          "predicted_accuracy": 0.999, "num_passes": 5}
    assert driver._result({"hole": 3, "reply": ok}) == {
        "hole": 3, "status": "Success", "seq": "ACGT", "qual": "IIII", "pq": 0.999,
        "passes": 5, "degraded": False}
    assert driver._result({"hole": 3, "reply": dict(ok, draft_only=True)})["degraded"]
    assert driver._result({"hole": 4, "reply": {"type": "result",
                                                "status": "TooFewPasses"}}) == {
        "hole": 4, "status": "TooFewPasses"}
    for bad in ({"type": "error", "code": "overloaded"}, {"type": "closed"},
                {"type": "lost"}):
        assert driver._result({"hole": 5, "reply": bad}) == {"hole": 5, "status": "error"}


# ------------------------------------------------------------ the readers


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_and_counters_reads_nothing(name):
    parent = {("ccs_refine_rounds_total", (("source", "device"),)): 12.0}
    assert read(name, [span("polish.refine", 30.0)], parent, parent) is None
    assert read(name) is None


def test_the_latency_readers_take_quantiles_of_the_request_spans():
    spans = [span("serve.request", ms) for ms in (900, 1000, 1100, 1200, 5000)]
    spans += [span("serve.polish", 7000.0), span("serve.flush", 1.0)]
    assert read("serve_latency_p50_ms", spans) == pytest.approx(1100.0)
    assert read("serve_latency_p95_ms", spans) == pytest.approx(1200 + 0.8 * 3800)
    assert read("serve_latency_p95_ms", spans) == pytest.approx(
        float(np.percentile([900, 1000, 1100, 1200, 5000], 95)))


def test_the_queue_reader_is_sum_over_count_as_they_moved():
    def stages(q_sum, q_count):
        return {(STAGES + "_sum", (("stage", "queue"),)): q_sum,
                (STAGES + "_count", (("stage", "queue"),)): q_count,
                (STAGES + "_sum", (("stage", "polish"),)): 99.0,
                (STAGES + "_count", (("stage", "polish"),)): 9.0}

    assert read("serve_queue_ms_per_zmw", [], stages(10.0, 64.0),
                stages(10.0 + 120.0, 64.0 + 1000.0)) == pytest.approx(120.0)
    assert read("serve_queue_ms_per_zmw", [], stages(10.0, 64.0), stages(10.0, 64.0)) is None


def test_the_fill_reader_is_used_over_capacity_as_they_moved():
    def slots(used, capacity):
        return {(SLOTS, (("kind", "used"),)): used, (SLOTS, (("kind", "capacity"),)): capacity}

    assert read("serve_flush_fill_share", [], slots(64.0, 64.0),
                slots(64.0 + 900.0, 64.0 + 1024.0)) == pytest.approx(100 * 900 / 1024)
    assert read("serve_flush_fill_share", [], slots(64.0, 64.0), slots(64.0, 64.0)) is None


def test_the_warm_reader_sums_the_warm_spans():
    assert read("setup_serve_warm_s", [span("serve.warm", 151500.0),
                                       span("polish.warm", 40000.0)]) == pytest.approx(151.5)


# ------------------------------------------------------------ the cell


def rehearse(trace: int, *extra):
    env = dict(os.environ, PBCCS_DEVICE_REFINE="0")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "2147483671",
         "--seconds", "10", "--trace", str(trace), "--rehearse", *extra],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True, timeout=1500)
    return done, done.stdout.strip().splitlines()


def test_a_traced_rehearsal_ends_with_a_result_line_and_prints_no_value():
    done, lines = rehearse(1, "--check-seeds", "2147483671")
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["rehearsal"] is True
    assert last["metrics"] == {} and last["failed"] == 0
    (reported,) = [ln for ln in lines if ln.startswith("rehearsal: the cell reports")]
    listed = reported.split("reports ")[1].split(";")[0].split(", ")
    assert set(NEW) <= set(listed), set(NEW) - set(listed)
    assert {"refine_ms_per_zmw", "draft_ms_per_zmw", "polish_device_wait_share",
            "setup_shape_sets", "window_compiles"} <= set(listed)
    # the server named what it had loaded, and the last warm wave loaded nothing
    assert any("status names 2 warmed shape set(s)" in ln for ln in lines)
    waves = [ln for ln in lines if ln.startswith("setup: warm wave")]
    assert 1 <= len(waves) <= 3 and "shape sets 0, program load 0.000 s" in waves[-1]
    assert any(ln.startswith("window: ") and "`serve.request` spans" in ln for ln in lines)
    assert any("warmup_file_again_gives_the_same_bytes = 1" in ln for ln in lines)
    assert "inside the window" in "\n".join(lines)


def test_the_draft_only_control_comes_out_not_correct_in_rehearsal():
    # polish is skipped: the answers are drafts, and the check says so
    done, lines = rehearse(0, "--control", "draft-only")
    assert done.returncode == 1, done.stdout[-3000:] + done.stderr[-3000:]
    last = json.loads(lines[-1])
    assert last["correct"] is False and last["failed"] > 0
    assert any("(draft_only)" in ln for ln in lines if ln.startswith("yield:"))
