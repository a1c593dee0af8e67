"""The cell `cell-slice-ragged.batch` (PR 46): its dealt deck, the plain
function its yield is held to, its three readers on hand-made counters and
the entries it brings.  (A rehearsal of the cell on the CPU:
`PBCCS_DEVICE_REFINE=0 python3 benchmark/run.py --workload
cell-slice-ragged.batch --seed 1 --seconds 2 --trace 1 --rehearse`.)"""

import collections

import numpy as np
import pytest

from harness import common, manifest, prom
from harness.reduce import ReaderInput

CELL = "cell-slice-ragged.batch"
NEW = ["menu_pin_moves", "zmw_slot_occupancy", "gated_zmw_share"]
SLOTS, USED = "ccs_batch_slots_total", "ccs_batch_slots_used_total"


def read(name: str, before=None, after=None, zmws=100):
    inp = ReaderInput(prom.Counters(before or {}, after or {}), [], None, zmws,
                      "TPU v5 lite", {}, None)
    return manifest.load_by_path("metrics", name).read(inp)


def test_every_file_of_every_seed_holds_the_same_reads():
    ragged = manifest.load_by_path("drivers", "batch_cli_ragged")
    spec = manifest.Cell(manifest.load(), CELL).config["library"]["passes"]
    deck = ragged.dealt_passes(2147483801, 2, 256, spec)
    assert (sum(deck), sum(k < 3 for k in deck), sum(k > 12 for k in deck),
            deck.count(30), min(deck), max(deck)) == (1468, 62, 21, 1, 1, 30)
    assert deck == ragged.dealt_passes(2147483801, 2, 256, spec)
    for other in (ragged.dealt_passes(2147483801, 3, 256, spec),
                  ragged.dealt_passes(2147483802, 2, 256, spec)):
        assert other != deck
        assert collections.Counter(other) == collections.Counter(deck)
    with pytest.raises(common.BenchFailure):
        ragged.dealt_passes(1, 0, 256, {"dist": "uniform_int", "lo": 3, "hi": 10})


def test_the_plain_yield_takes_the_snr_gate_first():
    ragged = manifest.load_by_path("drivers", "batch_cli_ragged")
    gates = {"minSnr": 4.0, "minPasses": 3, "maxDropFraction": 0.34}
    zmws = [{"snr": np.array([8.0, 3.9, 8.0, 8.0]), "reads": [0] * 9},
            {"snr": np.array([3.0, 9.0, 8.0, 8.0]), "reads": [0]},
            {"snr": np.full(4, 4.0), "reads": [0] * 2},
            {"snr": np.full(4, 8.0), "reads": [0] * 3},
            {"snr": np.full(4, 8.0), "reads": [0] * 4},
            {"snr": np.full(4, 8.0), "reads": [0] * 5},
            {"snr": np.full(4, 8.0), "reads": [0] * 6},
            {"snr": np.full(4, 8.0), "reads": [0] * 30}]
    assert ragged.plain_yield(zmws, gates) == {
        "snr": 2, "few": 1, "few_after": 2}
    # the allowances are twice the most a file has read on the chip
    assert (ragged.MAX_FEW_AFTER, ragged.MAX_UNPOLISHED) == (18, 8)


def test_the_three_readers_on_hand_made_counters():
    pins, gated = "ccs_menu_pins_total", "ccs_reader_gated_zmws_total"
    before = {(pins, (("kind", "new"),)): 1.0, (pins, (("kind", "grown"),)): 0.0,
              (gated, (("gate", "snr"),)): 9.0, (gated, (("gate", "passes"),)): 60.0,
              (SLOTS, (("axis", "zmw"),)): 200.0, (USED, (("axis", "zmw"),)): 190.0,
              (SLOTS, (("axis", "read"),)): 6400.0}
    after = dict(before)
    after.update({(pins, (("kind", "grown"),)): 1.0,
                  (gated, (("gate", "snr"),)): 20.0, (gated, (("gate", "passes"),)): 119.0,
                  (SLOTS, (("axis", "zmw"),)): 392.0, (USED, (("axis", "zmw"),)): 377.0})
    assert read("menu_pin_moves", before, after) == 1.0
    assert read("menu_pin_moves", before, before) == 0.0
    assert read("gated_zmw_share", before, after, zmws=256) == pytest.approx(100 * 70 / 256)
    assert read("zmw_slot_occupancy", before, after) == pytest.approx(100 * 187 / 192)
    # a program of before PR 46 has neither counter: nothing is reported
    old = {k: v for k, v in after.items() if k[0] in (SLOTS, USED)}
    assert read("menu_pin_moves", {}, old) is None
    assert read("gated_zmw_share", {}, old) is None
    assert read("zmw_slot_occupancy", {}, {}) is None


def test_the_cell_and_its_entries():
    doc = manifest.load()
    cell = manifest.Cell(doc, CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "rs2-p6c4-2kb-cell-slice", "batch-256-ragged")
    assert cell.traffic["driver"] == "batch_cli_ragged"
    assert (cell.traffic["zmws_per_file"], cell.traffic["window_files"],
            cell.traffic["warmup_files_max"], cell.traffic["cli_args"]) == (256, 3, 2, [])
    assert cell.config["gates"] == manifest.Cell(doc, "2kb-3to10x.batch").config["gates"]
    names = [m["name"] for m in doc["per_layer"]]
    assert [n for n in names if n in NEW] == NEW        # present, in order
    for m in doc["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"] and m["moves"] == "zmws_per_s"
    listed = {m["name"] for m in cell.per_layer}
    batch_2kb = {m["name"] for m in manifest.Cell(doc, "2kb-3to10x.batch").per_layer}
    assert listed >= batch_2kb | set(NEW)
