"""The checks chip_smoke.py holds a chip run to, on inputs small enough to
verify by hand.  The script itself needs a TPU (`python chip_smoke.py`);
its control flow rehearses on the CPU with `--rehearse`."""

import numpy as np
import pytest

import chip_smoke
from pbccs_tpu.models.arrow.params import encode_bases


@pytest.mark.parametrize("a,b,want", [
    ("ACGT", "ACGT", 0),
    ("ACGT", "AGGT", 1),        # substitution
    ("ACGT", "ACT", 1),         # deletion
    ("ACGT", "ACGGT", 1),       # insertion
    ("AAAA", "", 4),
    ("", "ACG", 3),
    ("ACGTACGT", "TGCATGCA", 6),
    ("GATTACA", "GCATGCT", 4),
])
def test_edit_distance(a, b, want):
    assert chip_smoke.edit_distance(encode_bases(a), encode_bases(b)) == want
    assert chip_smoke.edit_distance(encode_bases(b), encode_bases(a)) == want


def test_allowed_edits_follow_predicted_accuracy():
    assert chip_smoke.allowed_edits(1.0, 2000) == 2
    assert chip_smoke.allowed_edits(0.999, 2000) == 6
    assert chip_smoke.allowed_edits(0.99, 2000) == 42


def test_consensus_check_takes_either_strand_and_enforces_the_bound():
    rng = np.random.default_rng(5)
    tpl = rng.integers(0, 4, 200).astype(np.int8)
    truth = {7: tpl}
    fwd = "".join("ACGT"[b] for b in tpl)
    rev = "".join("ACGT"[3 - b] for b in tpl[::-1])
    qual = "I" * 200
    assert chip_smoke.check_consensus("t", 7, fwd, qual, 0.999, truth) == 0
    assert chip_smoke.check_consensus("t", 7, rev, qual, 0.999, truth) == 0
    # three edits: inside the bound at pq 0.999 (2 + ceil(0.4) = 3) ...
    bad = "".join("ACGT"[(b + 1) % 4] if i in (10, 50, 90) else "ACGT"[b]
                  for i, b in enumerate(tpl))
    assert chip_smoke.check_consensus("t", 7, bad, qual, 0.999, truth) == 3
    # ... and outside it when the read claims to be perfect
    with pytest.raises(chip_smoke.SmokeFailure, match="3 edits"):
        chip_smoke.check_consensus("t", 7, bad, qual, 1.0, truth)
    with pytest.raises(chip_smoke.SmokeFailure, match="QVs"):
        chip_smoke.check_consensus("t", 7, fwd, qual[:-1], 0.999, truth)


@pytest.mark.parametrize("n,ok,other,passes", [
    (256, 256, 0, True),
    (256, 254, 0, True),     # two ZMWs turned away by a yield gate
    (256, 243, 0, False),    # below the 95 % floor
    (256, 255, 1, False),    # an exception is never a yield outcome
    (8, 8, 0, True),
    (8, 7, 0, False),
])
def test_yield_check(n, ok, other, passes):
    if passes:
        chip_smoke.check_yield("t", n, ok, other)
    else:
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.check_yield("t", n, ok, other)
