"""QV calibration: predicted per-position error probabilities must track
realized error rates (the reference's contract that minPredictedAccuracy
actually predicts accuracy, reference include/pacbio/ccs/Consensus.h:506-512).

Method: polish model-sampled ZMWs with corrupted drafts, align each final
template to its ground truth (positional comparison is wrong: one
compensating indel pair shifts a whole segment and miscounts dozens of
phantom "errors"), attribute substitution/extra-base errors to the QV of
the template position, and bin by predicted QV.

Measured at Z=64/L200/P8 (2026-07-30): every error fell in QV<30 bins,
zero errors at QV>=30 across 11.5k positions, each bin within ~3x of its
predicted rate -- i.e. the bench's sub-100% exact_recoveries coexist with
mean QV ~72 because the misses are low-QV indel sites (and the reference
C++ on identical ZMWs recovers exactly the same 83/128; see
native/refbench/README.md).
"""

import numpy as np
import pytest

from pbccs_tpu.align.pairwise import align as nw_align
from pbccs_tpu.models.arrow.params import decode_bases


def _polish_workload(n_zmws, tpl_len, n_passes, seed):
    from pbccs_tpu.parallel.batch import BatchPolisher
    from pbccs_tpu.simulate import build_tasks

    rng = np.random.default_rng(seed)
    tasks, truths = build_tasks(rng, n_zmws, tpl_len, n_passes, 2)
    p = BatchPolisher(tasks)
    p.refine()
    return p, truths, p.consensus_qvs()


@pytest.mark.slow
def test_qv_calibration_binned():
    Z, L = 16, 150
    p, truths, qvs = _polish_workload(Z, L, 8, 456)

    bins: dict[int, list[int]] = {}
    for z in range(Z):
        final = decode_bases(np.asarray(p.tpls[z]))
        truth = decode_bases(truths[z])
        q = qvs[z]
        aln = nw_align(final, truth)  # target=final: D=extra base, I=missing
        ti = 0
        for op in aln.transcript:
            if op in "MRD":
                b = min(int(q[ti]) // 10, 9)
                s = bins.setdefault(b, [0, 0])
                s[0] += 1
                s[1] += int(op != "M")
                ti += 1
        assert ti == len(final)

    total = sum(n for n, _ in bins.values())
    assert total >= Z * L * 0.9

    for b, (n, errors) in sorted(bins.items()):
        predicted_hi = 10 ** (-(b * 10) / 10)  # bin's loosest prediction
        realized = errors / max(n, 1)
        # within 3x of the bin's upper prediction, with a small-sample
        # allowance (binomial noise dominates sparse high-QV bins)
        assert realized <= 3 * predicted_hi + 3 / max(n, 1), (
            f"QV bin [{b*10},{b*10+10}): realized {realized:.3g} vs "
            f"predicted <= {predicted_hi:.3g} over {n} positions")

    # the strong end of the contract: confident positions are clean
    high = [(n, e) for b, (n, e) in bins.items() if b >= 4]
    n_high = sum(n for n, _ in high)
    e_high = sum(e for _, e in high)
    assert n_high > Z * L * 0.5            # most positions are confident
    assert e_high <= max(1, n_high // 2000)  # and essentially error-free


@pytest.mark.slow
def test_predicted_accuracy_tracks_realized():
    Z, L = 12, 150
    p, truths, qvs = _polish_workload(Z, L, 8, 789)

    pred_err, real_err, n_pos = 0.0, 0, 0
    for z in range(Z):
        final = decode_bases(np.asarray(p.tpls[z]))
        truth = decode_bases(truths[z])
        aln = nw_align(final, truth)
        real_err += aln.length - aln.matches
        pred_err += float(np.sum(10.0 ** (-qvs[z] / 10.0)))
        n_pos += len(final)

    realized = real_err / n_pos
    predicted = pred_err / n_pos
    # predicted mean error must not be over-confident by more than ~5x
    # (under-confidence is conservative and acceptable)
    assert realized <= 5 * predicted + 3 / n_pos, (realized, predicted)
