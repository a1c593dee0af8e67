import copy
import json
import os

import pytest

from harness import manifest


@pytest.fixture(scope="module")
def doc():
    return manifest.load()


def test_benchmark_json_is_valid_and_its_files_exist(doc):
    for c in doc["configs"]:
        assert os.path.exists(os.path.join(manifest.ROOT, c["file"]))
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert {"library", "gates", "guarantees", "assumed", "check"} <= set(cfg)
    for w in doc["workloads"]:
        cell = manifest.Cell(doc, w["name"])
        manifest.load_by_path("drivers", cell.traffic["driver"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "zmws_per_s"}
        assert cell.per_layer
    for m in doc["per_layer"]:
        assert hasattr(manifest.load_by_path("metrics", m["name"]), "read")


def test_every_data_file_names_a_driver_that_exists():
    for f in os.listdir(os.path.join(manifest.HERE, "traffic")):
        with open(os.path.join(manifest.HERE, "traffic", f)) as fh:
            manifest.load_by_path("drivers", json.load(fh)["driver"])


@pytest.mark.parametrize("bad", ["has space", "comma,name", "slash/name", "", "x" * 65,
                                 "-leading", "grüß"])
def test_a_name_the_driver_refuses_is_refused(doc, bad):
    d = copy.deepcopy(doc)
    d["per_layer"][0]["name"] = bad
    with pytest.raises(manifest.ManifestError):
        manifest.validate(d)


@pytest.mark.parametrize("bad", ["tokens per second", "µs", "", "x" * 17, "a,b"])
def test_a_unit_the_driver_refuses_is_refused(doc, bad):
    d = copy.deepcopy(doc)
    d["end_to_end"][0]["unit"] = bad
    with pytest.raises(manifest.ManifestError):
        manifest.validate(d)


def test_a_metric_that_moves_nothing_known_is_refused(doc):
    d = copy.deepcopy(doc)
    d["per_layer"][0]["moves"] = "no_such_metric"
    with pytest.raises(manifest.ManifestError):
        manifest.validate(d)
