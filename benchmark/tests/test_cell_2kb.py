"""The cell `2kb-3to10x.batch` (PR 27): its dealt traffic, its three
readers on hand-made counters, and a rehearsal of the cell, sound and under
the `draft-only` control."""

import collections
import json
import os
import subprocess
import sys

import pytest

from harness import common, manifest, prom, simulate
from harness.reduce import ReaderInput

CELL = "2kb-3to10x.batch"
NEW = ["read_lane_occupancy", "window_program_load_s", "setup_shape_sets"]
LOAD = "ccs_program_load_seconds_total"
SLOTS, USED = "ccs_batch_slots_total", "ccs_batch_slots_used_total"
SHAPE_SETS = "ccs_polish_shape_sets_total"


def read(name: str, before=None, after=None):
    inp = ReaderInput(prom.Counters(before or {}, after or {}), [], None, 100,
                      "TPU v5 lite", {}, None)
    return manifest.load_by_path("metrics", name).read(inp)


# ------------------------------------------------------------ the traffic


def test_a_dealt_file_holds_32_zmws_at_each_pass_count():
    dealt = manifest.load_by_path("drivers", "batch_cli_dealt")
    spec = {"dist": "uniform_int", "lo": 3, "hi": 10}
    deck = dealt.dealt_passes(2147483801, 2, 256, spec)
    assert collections.Counter(deck) == {k: 32 for k in range(3, 11)}
    assert deck == dealt.dealt_passes(2147483801, 2, 256, spec)
    assert deck != dealt.dealt_passes(2147483801, 3, 256, spec)
    assert deck != dealt.dealt_passes(2147483802, 2, 256, spec)
    # shuffled over the whole file, not dealt to the program's 64-ZMW chunks
    per_chunk = [collections.Counter(deck[c: c + 64]) for c in range(0, 256, 64)]
    assert any(set(c.values()) != {8} for c in per_chunk)
    with pytest.raises(common.BenchFailure):
        dealt.dealt_passes(1, 0, 100, spec)
    with pytest.raises(common.BenchFailure):
        dealt.dealt_passes(1, 0, 256, {"dist": "fixed", "value": 30})


def test_the_same_seed_makes_the_same_file(tmp_path, monkeypatch):
    dealt = manifest.load_by_path("drivers", "batch_cli_dealt")
    cell = manifest.Cell(manifest.load(), CELL)
    assert cell.traffic["driver"] == "batch_cli_dealt"
    assert (cell.traffic["zmws_per_file"], cell.traffic["window_files"],
            cell.traffic["warmup_files_max"], cell.traffic["cli_args"]) == (256, 3, 2, [])
    monkeypatch.setattr(common.Context, "work", str(tmp_path))
    ctx = common.Context(cell=cell, seed=2147483801, seconds=1.0, trace=False,
                         rehearse=True, control=None, t_process=0.0)
    session = dealt.Session(ctx)
    assert session.n == 16
    path, truth = session._make_file(2147483801, 1)
    with open(path, "rb") as f:
        first = f.read()
    again, truth_again = session._make_file(2147483801, 1)
    with open(again, "rb") as f:
        assert f.read() == first
    zmws = [truth[h] for h in sorted(truth)]
    assert sorted(truth) == list(range(16, 32))
    assert collections.Counter(len(z["reads"]) for z in zmws) == {
        k: 2 for k in range(3, 11)}
    assert simulate.digest(zmws) == simulate.digest(
        [truth_again[h] for h in sorted(truth_again)])
    _path, other = session._make_file(2147483802, 1)
    assert simulate.digest(zmws) != simulate.digest([other[h] for h in sorted(other)])


# ------------------------------------------------------------ the readers


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_reads_nothing(name):
    parent = {("ccs_refine_rounds_total", (("source", "device"),)): 12.0}
    assert read(name, parent, parent) is None
    assert read(name) is None


def test_the_entries_are_appended_and_both_cells_list_them():
    doc = manifest.load()
    assert [m["name"] for m in doc["per_layer"]][-3:] == NEW
    assert [w["name"] for w in doc["workloads"]][-1] == CELL
    assert [c["name"] for c in doc["configs"]][-1] == "rs2-p6c4-2kb-amplicon"
    assert doc["configs"][-1]["reduced"] == ["zmws"]
    for m in doc["per_layer"]:
        assert m["workloads"] == ["500bp-30x.batch", CELL], m["name"]
    moves = {m["name"]: (m["moves"], m["layer"]) for m in doc["per_layer"][-3:]}
    assert moves == {"read_lane_occupancy": ("zmws_per_s", "refine loop"),
                     "window_program_load_s": ("zmws_per_s", "compile cache"),
                     "setup_shape_sets": ("setup_s", "compile cache")}


def test_read_lane_occupancy_is_used_over_padded_as_they_moved():
    def slots(read_slots, read_used, zmw=64.0):
        return {(SLOTS, (("axis", "read"),)): read_slots,
                (USED, (("axis", "read"),)): read_used,
                (SLOTS, (("axis", "zmw"),)): zmw, (USED, (("axis", "zmw"),)): zmw}

    before, after = slots(768.0, 400.0), slots(768.0 + 3072.0, 400.0 + 1664.0, 320.0)
    assert read("read_lane_occupancy", before, after) == pytest.approx(100 * 1664 / 3072)
    assert read("read_lane_occupancy", before, before) is None


def test_window_program_load_s_sums_the_phases_that_moved_in_the_window():
    def phases(trace, lower, compile_, cache_read):
        return {(LOAD, (("phase", "trace"),)): trace, (LOAD, (("phase", "lower"),)): lower,
                (LOAD, (("phase", "compile"),)): compile_,
                (LOAD, (("phase", "cache_read"),)): cache_read}

    before = phases(61.5, 20.25, 30.0, 12.0)
    assert read("window_program_load_s", before, before) == 0.0
    # a family loaded from cache hits: no compile is counted, 47.5 s are
    # (cache_read lies inside compile and is not added again)
    after = phases(61.5 + 33.0, 20.25 + 4.0, 30.0 + 10.5, 12.0 + 9.0)
    assert read("window_program_load_s", before, after) == pytest.approx(47.5)


def test_setup_shape_sets_reads_the_count_as_it_stood_before_the_window():
    before, after = {(SHAPE_SETS, ()): 2.0}, {(SHAPE_SETS, ()): 4.0}
    assert read("setup_shape_sets", before, after) == 2.0
    assert read("setup_shape_sets", {}, after) is None


# ------------------------------------------------------------ the cell


def rehearse(trace: int, *extra):
    env = dict(os.environ, PBCCS_DEVICE_REFINE="0")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "2147483659",
         "--seconds", "1", "--trace", str(trace), "--rehearse", *extra],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True, timeout=1500)
    return done, done.stdout.strip().splitlines()


def test_a_traced_rehearsal_of_the_cell_lists_the_three_new_metrics():
    done, lines = rehearse(1)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["rehearsal"] is True
    assert last["attempted"] == 32 and last["metrics"] == {}
    (reported,) = [ln for ln in lines if ln.startswith("rehearsal: the cell reports")]
    listed = reported.split("reports ")[1].split(";")[0].split(", ")
    assert set(NEW) <= set(listed), set(NEW) - set(listed)
    assert {"refine_rounds_per_dispatch", "device_idle_share",
            "setup_trace_lower_s"} <= set(listed)
    # the warm-up stopped after an invocation that loaded nothing, and the
    # window loaded nothing either
    warm = [ln for ln in lines if ln.startswith("setup: warm-up invocation")]
    assert len(warm) == 2 and "misses 0, backend compiles 0" in warm[1]


def test_the_draft_only_control_comes_out_not_correct_in_rehearsal():
    # polish is skipped: a traced run would see no device operation
    done, lines = rehearse(0, "--control", "draft-only")
    assert done.returncode == 1, done.stdout[-3000:] + done.stderr[-3000:]
    last = json.loads(lines[-1])
    assert last["correct"] is False and last["failed"] == last["attempted"] == 32
