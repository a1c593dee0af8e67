"""The reader PR 30 adds: `fill_reads_share`, filled over capacity of
ccs_refine_fill_reads_total as they moved, nothing from a program that
lacks the counter."""

import pytest
from harness import manifest, prom
from harness.reduce import ReaderInput

FILLS = "ccs_refine_fill_reads_total"


def read(before, after):
    inp = ReaderInput(prom.Counters(before, after), [], None, 100,
                      "TPU v5 lite", {}, None)
    return manifest.load_by_path("metrics", "fill_reads_share").read(inp)


def fills(filled, capacity):
    return {(FILLS, (("kind", "filled"),)): filled,
            (FILLS, (("kind", "capacity"),)): capacity}


def test_fill_reads_share_is_filled_over_capacity_as_they_moved():
    before, after = fills(500.0, 1920.0), fills(500.0 + 1000.0, 1920.0 + 3840.0)
    assert read(before, after) == pytest.approx(100 * 1000 / 3840)
    assert read(before, before) is None


def test_a_program_without_the_counter_reads_nothing():
    assert read({}, {("ccs_refine_slot_rounds_total", (("kind", "live"),)): 7.0}) is None


def test_the_entry_is_the_last_of_per_layer_and_lists_both_cells():
    entry = manifest.load()["per_layer"][-1]
    assert entry == {"name": "fill_reads_share", "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "refine loop",
                     "moves": "zmws_per_s",
                     "workloads": ["500bp-30x.batch", "2kb-3to10x.batch"]}
