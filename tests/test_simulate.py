"""The polish workload generator of pbccs_tpu/simulate.py: pass-count
parsing and seeded task building (tools/perf_smoke.py,
native/refbench/dump_workload.py and tests/test_calibration.py draw their
ZMWs from it)."""

import numpy as np
import pytest

from pbccs_tpu.simulate import build_tasks, parse_passes


@pytest.mark.parametrize("spec,want", [("8", (8, 8)), ("3-10", (3, 10))])
def test_parse_passes(spec, want):
    assert parse_passes(spec) == want


@pytest.mark.parametrize("bad", ["", "3-", "three"])
def test_parse_passes_refuses(bad):
    with pytest.raises(ValueError):
        parse_passes(bad)


def _same(a, b) -> bool:
    (ta, tra), (tb, trb) = a, b
    return (all(np.array_equal(x, y) for x, y in zip(tra, trb))
            and all(x.id == y.id and np.array_equal(x.tpl, y.tpl)
                    and np.array_equal(x.snr, y.snr)
                    and x.strands == y.strands
                    and len(x.reads) == len(y.reads)
                    and all(np.array_equal(r, s)
                            for r, s in zip(x.reads, y.reads))
                    for x, y in zip(ta, tb)))


@pytest.mark.parametrize("passes", ["4", "3-6"])
def test_build_tasks_is_a_function_of_the_seed(passes):
    draw = lambda seed: build_tasks(  # noqa: E731
        np.random.default_rng(seed), 6, 60, passes, 2)
    assert _same(draw(11), draw(11))
    assert not _same(draw(11), draw(12))


def test_build_tasks_shapes_and_corruptions():
    tasks, truths = build_tasks(np.random.default_rng(5), 12, 80, "3-6", 2)
    assert len(tasks) == len(truths) == 12
    counts = {len(t.reads) for t in tasks}
    assert counts <= set(range(3, 7)) and len(counts) > 1
    for t, truth in zip(tasks, truths):
        assert len(t.tpl) == len(truth) == 80
        # a draft differs from its truth at no more than the two
        # corrupted positions, never within 5 of an end
        diff = np.nonzero(np.asarray(t.tpl) != np.asarray(truth))[0]
        assert 1 <= len(diff) <= 2
        assert diff.min() >= 5 and diff.max() < 75
