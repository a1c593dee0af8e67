"""The reducer against a recorded trace: 0.16 s of the device plane of the
2 kb batch cell on a TPU v5e (PR 24, chip call 1), cut out of the capture
with its HLO texts left whole (the capture's own start and stop kept)."""

import gzip
import json
import os

import numpy as np
import pytest

from harness import arith, manifest, xplane
from harness.reduce import ReaderInput

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    raw = tmp_path_factory.mktemp("trace") / "slice.xplane.pb"
    with gzip.open(os.path.join(HERE, "data", "v5e_2kb_batch_slice.xplane.pb.gz")) as f:
        raw.write_bytes(f.read())
    return xplane.load(str(raw))


@pytest.fixture(scope="module")
def inp(trace):
    with open(os.path.join(manifest.HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    return ReaderInput(None, [], trace, 512, "TPU v5 lite", peaks, None)


def test_planes_and_window(trace):
    assert len(trace.chips) == 1 and len(trace.chips[0].ops) == 7436
    assert trace.window_s == pytest.approx(6.281556657)
    assert trace.start_unix == pytest.approx(1790529407.4506857)


def test_busy_is_the_union_of_nested_operations(trace):
    ops = trace.chips[0].ops
    assert trace.busy_s() == pytest.approx(0.119792768, rel=1e-9)
    grid = np.zeros(400_001, bool)                  # the same on a 1 us raster
    for o in ops:
        grid[int(round(o.start_s * 1e6)): int(round((o.start_s + o.dur_s) * 1e6))] = True
    assert grid.sum() / 1e6 == pytest.approx(trace.busy_s(), rel=1e-3)
    assert sum(o.dur_s for o in ops) > 1.3 * trace.busy_s()    # they do nest
    assert all(o.self_s >= -1e-12 for o in ops)


def test_idle_share_and_gaps(trace, inp):
    idle = manifest.load_by_path("metrics", "device_idle_share").read(inp)
    assert idle == pytest.approx(100 * (1 - 0.119792768 / 6.281556657))
    gaps = trace.idle_gaps(0.09, 0.25)
    inside = [(max(o.start_s, 0.09), min(o.start_s + o.dur_s, 0.25))
              for o in trace.chips[0].ops if o.start_s < 0.25]
    assert sum(b - a for a, b in gaps) == pytest.approx(
        0.16 - arith.union_seconds(inside), abs=1e-9)
    assert len(gaps) == 1956


def test_kernel_sums_and_rooflines(trace, inp):
    kernels = {}
    for o in trace.matching(r'custom_call_target="tpu_custom_call"'):
        kernels[o.name] = kernels.get(o.name, 0.0) + o.dur_s
    assert kernels == pytest.approx({"_batch_setup custom-call": 0.033019125,
                                     "branch_1_fun custom-call": 0.00827548,
                                     "dense_interior_scores_batch custom-call": 0.004010765},
                                    rel=1e-4)
    read = lambda n: manifest.load_by_path("metrics", n).read(inp)  # noqa: E731
    assert read("kernel_share_of_busy") == pytest.approx(37.81945835)
    assert read("fill_roofline") == pytest.approx(6.91954974)
    assert read("dense_roofline") == pytest.approx(20.28402398)
    assert max(read("fill_roofline"), read("dense_roofline")) < 100


def test_top_operations_are_by_self_time(trace):
    top = trace.top_ops(3)
    assert [k for k, _ in top] == ["fusion", "_batch_setup custom-call", "copy"]
    assert top[0][1] == pytest.approx(0.034390655, rel=1e-6)
    assert sum(o.self_s for o in trace.chips[0].ops) == pytest.approx(trace.busy_s(), rel=0.01)


def test_short_names():
    assert xplane.short_name(
        "%fusion.1318 = f32[8,9]{0,1} fusion(f32[8]{0} %p), kind=kCustom") == "fusion"
    assert xplane.short_name("%while.119 = (s8[32,2304]{0,1}, s32[32]{0}) while((s8[32,2304]) %t), "
                             "condition=%c, body=%b") == "while"
    assert xplane.short_name("%dense_interior_scores_batch.12 = f32[384,2304,9]{2,1,0} "
                             "custom-call(f32[384,9,272,96]{3,2,1,0} %b)") == \
        "dense_interior_scores_batch custom-call"
    assert xplane.short_name("copy-start.5") == "copy-start"
