"""The eight readers of the program's trace sites (PR 25), on hand-made
inputs, and a rehearsal of the cell that has to list them all.

Hand-made: the spans a capture of the parent would hold (none of the new
ones, no `cpu_ms`) read nothing and raise nothing; the new ones read what
the arithmetic says."""

import json
import os
import subprocess
import sys

import pytest

from harness import manifest, prom
from harness.reduce import ReaderInput

NEW = ["turn_wait_ms_per_zmw", "device_unowned_share", "draft_cpu_share",
       "read_ms_per_zmw", "straggler_ms_per_zmw", "refine_slot_occupancy",
       "setup_trace_lower_s", "setup_compile_load_s"]
LOAD = "ccs_program_load_seconds_total"
SLOTS = "ccs_refine_slot_rounds_total"


def read(name: str, spans=(), before=None, after=None, zmws=100):
    counters = prom.Counters(before or {}, after or {})
    inp = ReaderInput(counters, list(spans), None, zmws, "TPU v5 lite", {}, None)
    return manifest.load_by_path("metrics", name).read(inp)


def span(name: str, start_s: float, dur_s: float, **args) -> dict:
    return {"name": name, "ts": 1.79e15 + start_s * 1e6, "dur": dur_s * 1e6,
            "args": dict(args, device_wait_ms=0.0)}


PARENT_SPANS = [span("filter", 0.0, 0.01), span("draft", 0.01, 0.07),
                span("polish", 1.0, 2.0), span("polish.setup", 1.0, 0.2),
                span("polish.refine", 1.2, 1.5), span("polish.qv", 2.7, 0.1),
                span("emit", 3.0, 0.1)]
PARENT_COUNTERS = {("ccs_refine_rounds_total", (("source", "device"),)): 12.0,
                   ("ccs_compiles_total", ()): 3.0}


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_sites_reads_nothing(name):
    assert read(name, PARENT_SPANS, PARENT_COUNTERS, PARENT_COUNTERS) is None
    assert read(name) is None


def test_every_new_reader_has_its_entry_for_the_cell():
    doc = manifest.load()
    entries = {m["name"]: m for m in doc["per_layer"]}
    assert [m["name"] for m in doc["per_layer"]][-len(NEW):] == NEW   # appended
    for name in NEW:
        assert entries[name]["workloads"] == ["500bp-30x.batch"]
    assert {entries[n]["moves"] for n in NEW[-2:]} == {"setup_s"}
    assert {entries[n]["moves"] for n in NEW[:-2]} == {"zmws_per_s"}


def test_device_unowned_share_is_one_less_the_union_of_polish_over_run():
    spans = [span("run", 0.0, 10.0), span("run", 20.0, 10.0),
             # two workers' turns overlap nowhere, a fleet's polishes may:
             # 1..4 and 3..6 hold the device for 5 s, not 6
             span("polish", 1.0, 3.0), span("polish", 3.0, 3.0),
             span("polish", 22.0, 5.0),
             span("polish.refine", 1.0, 3.0)]          # not a holder
    assert read("device_unowned_share", spans) == pytest.approx(50.0)
    assert read("device_unowned_share", spans[:2]) == pytest.approx(100.0)


def test_turn_wait_is_summed_over_the_windows_zmws():
    spans = [span("run", 0.0, 9.0), span("dispatch.turn_wait", 1.0, 1.5),
             span("dispatch.turn_wait", 2.0, 2.5)]
    assert read("turn_wait_ms_per_zmw", spans, zmws=100) == pytest.approx(40.0)
    # a turn that was always free is a reading, not a silence
    free = [span("dispatch.turn_wait", 1.0, 0.0)]
    assert read("turn_wait_ms_per_zmw", free) == 0.0


def test_draft_cpu_share_is_cpu_over_wall_of_draft_spans_only():
    spans = [span("draft", 0.0, 0.100, cpu_ms=40.0),
             span("draft", 0.0, 0.060, cpu_ms=40.0),
             span("draft.poa", 0.0, 0.050, cpu_ms=50.0),
             span("filter", 0.0, 0.020, cpu_ms=1.0)]
    assert read("draft_cpu_share", spans) == pytest.approx(50.0)


def test_read_ms_per_zmw():
    spans = [span("read", 0.0, 0.05, zmws=64), span("read", 1.0, 0.07, zmws=64)]
    assert read("read_ms_per_zmw", spans, zmws=128) == pytest.approx(0.9375)


def test_straggler_reads_zero_where_refines_left_none_behind():
    ran = [span("run", 0.0, 9.0), span("polish.refine", 1.0, 2.0)]
    assert read("straggler_ms_per_zmw", ran) == 0.0
    ran.append(span("polish.refine.straggler", 2.5, 0.5, zmws=1))
    assert read("straggler_ms_per_zmw", ran, zmws=100) == pytest.approx(5.0)
    # no refine in the window at all: nothing to say
    assert read("straggler_ms_per_zmw", [span("run", 0.0, 9.0)]) is None


def test_refine_slot_occupancy_is_live_over_capacity_as_they_moved():
    live, cap = (SLOTS, (("kind", "live"),)), (SLOTS, (("kind", "capacity"),))
    before = {live: 1000.0, cap: 1280.0}
    after = {live: 1000.0 + 150.0, cap: 1280.0 + 192.0}
    assert read("refine_slot_occupancy", before=before, after=after) \
        == pytest.approx(78.125)
    assert read("refine_slot_occupancy", before=before, after=before) is None


def test_the_setup_split_reads_the_phases_as_they_stood_before_the_window():
    def phases(trace, lower, compile_, cache_read):
        return {(LOAD, (("phase", "trace"),)): trace,
                (LOAD, (("phase", "lower"),)): lower,
                (LOAD, (("phase", "compile"),)): compile_,
                (LOAD, (("phase", "cache_read"),)): cache_read}

    before = phases(61.5, 20.25, 30.0, 12.0)
    after = phases(70.0, 25.0, 99.0, 50.0)       # a window that loaded more
    assert read("setup_trace_lower_s", before=before, after=after) == 81.75
    # jax's compile event wraps the cache read: not added again
    assert read("setup_compile_load_s", before=before, after=after) == 30.0
    # counters that first appear inside the window say nothing of set-up
    assert read("setup_trace_lower_s", before={}, after=after) is None


def test_a_traced_rehearsal_of_the_cell_lists_all_eight(tmp_path):
    env = dict(os.environ, PBCCS_DEVICE_REFINE="0")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "500bp-30x.batch",
         "--seed", "2147483659", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True, timeout=1500)
    lines = done.stdout.strip().splitlines()
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["rehearsal"] is True
    (reported,) = [ln for ln in lines if ln.startswith("rehearsal: the cell reports")]
    listed = reported.split("reports ")[1].split(";")[0].split(", ")
    assert set(NEW) <= set(listed), set(NEW) - set(listed)
    # and the shipped readers that a CPU can feed are still there
    assert {"draft_ms_per_zmw", "polish_device_wait_share",
            "refine_rounds_per_dispatch", "device_idle_share"} <= set(listed)
