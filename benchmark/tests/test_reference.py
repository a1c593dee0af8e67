import numpy as np
import pytest

from harness import checks, p6c4, simulate
from reference import arrow_ref

SNR = np.array([7.0, 10.0, 8.0, 11.0])
TABLE = p6c4.transition_table(SNR)


def _pairs(rng, lengths):
    reads, tpls = [], []
    for n in lengths:
        tpl = rng.integers(0, 4, n).astype(np.int8)
        reads.append(simulate.sample_read(rng, tpl, p6c4.transition_track(tpl, TABLE)))
        tpls.append(tpl)
    return reads, tpls


def test_batched_recursion_is_the_scalar_one():
    rng = np.random.default_rng(5)
    reads, tpls = _pairs(rng, [5, 8, 30, 30, 61, 90, 300])     # uneven on purpose
    reads.append(reads[2])              # a read against another template
    tpls.append(tpls[3])
    want = [arrow_ref.loglik_scalar(r, t, TABLE) for r, t in zip(reads, tpls)]
    assert arrow_ref.loglik_batch(reads, tpls, TABLE, half=64) == pytest.approx(want, abs=1e-9)


def test_the_band_only_bounds_the_work():
    rng = np.random.default_rng(6)
    reads, tpls = _pairs(rng, [600, 600])
    wide = arrow_ref.loglik_batch(reads, tpls, TABLE, half=160)
    assert arrow_ref.loglik_batch(reads, tpls, TABLE, half=64) == pytest.approx(wide, abs=1e-7)


def test_transition_rows_are_distributions():
    assert TABLE.shape == (8, 4) and TABLE.sum(axis=1) == pytest.approx(np.ones(8))


def test_unique_mutations_and_qv():
    tpl = np.array([0, 0, 1, 2], np.int8)
    assert len(arrow_ref.unique_mutations(tpl, 0)) == 3 + 4 + 1
    assert len(arrow_ref.unique_mutations(tpl, 1)) == 3 + 3 + 0     # inside a homopolymer
    assert arrow_ref.qv_of(np.array([1.0, 2.0])) == arrow_ref.QV_MAX
    s = np.array([-3.0, -5.0, 0.5])
    total = np.exp(-3.0) + np.exp(-5.0)
    assert arrow_ref.qv_of(s) == pytest.approx(-10 * np.log10(total / (1 + total)))


def test_edits_between_are_single_steps_toward_the_target():
    rng = np.random.default_rng(2)
    for _ in range(40):
        a = rng.integers(0, 4, int(rng.integers(5, 60))).astype(np.int8)
        b = list(a)
        for _k in range(int(rng.integers(0, 6))):
            op, p = int(rng.integers(0, 3)), int(rng.integers(0, len(b)))
            if op == 0:
                b[p] = (b[p] + 1) % 4
            elif op == 1:
                b.insert(p, int(rng.integers(0, 4)))
            elif len(b) > 3:
                del b[p]
        b = np.array(b, np.int8)
        d0 = checks.edit_distance(p6c4.decode(a), p6c4.decode(b))
        steps = arrow_ref.edits_between(a, b)
        assert len(steps) == d0
        for m in steps:
            assert checks.edit_distance(p6c4.decode(arrow_ref.mutate(a, m)),
                                        p6c4.decode(b)) == d0 - 1


def test_the_true_template_is_a_consensus_and_a_damaged_one_is_not():
    z = simulate.make_zmw(4, 0, 0, {"insert_length": {"dist": "fixed", "value": 150},
                                    "passes": {"dist": "fixed", "value": 8},
                                    "snr": {"dist": "fixed", "value": 9.0}})
    strands = [k % 2 for k in range(8)]
    tpl = z["template"]
    good = arrow_ref.check_zmw(z["reads"], strands, z["snr"], tpl, tpl, [20, 75, 120])
    assert good["best_mutation"] < 0 and good["ll_deficit"] == 0
    assert all(q > 20 for q in good["qv"].values())
    bad = arrow_ref.mutate(tpl, ("sub", 75, (tpl[75] + 1) % 4))
    res = arrow_ref.check_zmw(z["reads"], strands, z["snr"], bad, tpl, [20, 75, 120],
                              toward_truth=arrow_ref.edits_between(bad, tpl))
    assert res["toward_truth"][0][1] > 10 and res["best_mutation"] > 10
    assert res["ll_deficit"] > 10


def test_allowed_edits_and_orientation():
    assert checks.allowed_edits(1.0, 2000) == 2
    assert checks.allowed_edits(0.99, 2000) == 42
    tpl = np.random.default_rng(1).integers(0, 4, 80).astype(np.int8)
    assert checks.orient(p6c4.decode(p6c4.revcomp(tpl)), tpl, 2) == (0, 1)
    assert checks.orient(p6c4.decode(tpl), tpl, 2) == (0, 0)
