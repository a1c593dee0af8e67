"""Batched ZMW polishing: parity with the per-ZMW scorer + mesh sharding.

Pattern: the reference validates its fast kernels against a reference
implementation over random inputs (TestRecursors.cpp:291-440); here the
batched driver is validated against the per-ZMW ArrowMultiReadScorer, and
the sharded path against the unsharded one.
"""

import jax
import numpy as np
import pytest

from pbccs_tpu.models.arrow import mutations as mutlib
from pbccs_tpu.models.arrow.refine import RefineOptions
from pbccs_tpu.models.arrow.scorer import ArrowMultiReadScorer
from pbccs_tpu.parallel import BatchPolisher, make_zmw_mesh
from pbccs_tpu.parallel.batch import ZmwTask
from pbccs_tpu.simulate import simulate_zmw


def make_tasks(rng, n_zmws=3, tpl_len=80, n_passes=5):
    tasks, tpls = [], []
    for z in range(n_zmws):
        tpl, reads, strands, snr = simulate_zmw(rng, tpl_len, n_passes)
        tasks.append(ZmwTask(
            id=f"m/{z}", tpl=tpl, snr=snr, reads=reads, strands=strands,
            tstarts=[0] * len(reads), tends=[len(tpl)] * len(reads)))
        tpls.append(tpl)
    return tasks, tpls


def corrupt(rng, tpl):
    out = tpl.copy()
    pos = rng.integers(10, len(tpl) - 10)
    out[pos] = (out[pos] + 1 + rng.integers(0, 3)) % 4
    return out


@pytest.mark.slow
def test_batch_scores_match_per_zmw_scorer(rng):
    tasks, _ = make_tasks(rng, n_zmws=2, tpl_len=60, n_passes=4)
    batch = BatchPolisher(tasks)
    muts_per_zmw = [mutlib.enumerate_unique(t.tpl)[:40] for t in tasks]
    got = batch.score_mutations(muts_per_zmw)

    for z, t in enumerate(tasks):
        solo = ArrowMultiReadScorer(
            t.tpl, t.snr, list(t.reads), list(t.strands),
            list(t.tstarts), list(t.tends))
        want = solo.score_mutations(muts_per_zmw[z])
        # same active sets required for comparable sums
        assert np.array_equal(batch.active[z, : len(t.reads)],
                              solo.active[: solo.n_reads])
        np.testing.assert_allclose(got[z], want, rtol=1e-4, atol=1e-3)


def test_batch_refine_recovers_templates(rng):
    tasks, tpls = make_tasks(rng, n_zmws=3, tpl_len=70, n_passes=6)
    for t in tasks:  # polish must fix a corrupted draft
        t.tpl = corrupt(rng, t.tpl)
    batch = BatchPolisher(tasks)
    results = batch.refine(RefineOptions(max_iterations=10))
    assert all(r.converged for r in results)
    for z in range(3):
        assert np.array_equal(batch.tpls[z], tpls[z]), f"zmw {z} not recovered"
    qvs = batch.consensus_qvs()
    assert all(len(q) == len(batch.tpls[z]) for z, q in enumerate(qvs))
    assert all(q.mean() > 10 for q in qvs)


@pytest.mark.slow
def test_batch_sharded_matches_unsharded(rng):
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    tasks, _ = make_tasks(rng, n_zmws=4, tpl_len=60, n_passes=4)
    muts_per_zmw = [mutlib.enumerate_unique(t.tpl)[:30] for t in tasks]

    plain = BatchPolisher(tasks)
    want = plain.score_mutations(muts_per_zmw)

    mesh = make_zmw_mesh(n_zmw=4, n_read=2)
    sharded = BatchPolisher(tasks, mesh=mesh)
    got = sharded.score_mutations(muts_per_zmw)

    assert np.array_equal(sharded.active[:4, :4], plain.active[:4, :4])
    for z in range(4):
        np.testing.assert_allclose(got[z], want[z], rtol=1e-4, atol=1e-3)


def test_batch_sharded_pallas_fills(rng, monkeypatch):
    """Mesh runs keep the Pallas fill kernel: fills run inside
    jax.shard_map per device (interpret mode on CPU), and sharded scores
    match the unsharded JAX-path scores."""
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    tasks, _ = make_tasks(rng, n_zmws=4, tpl_len=60, n_passes=4)
    muts_per_zmw = [mutlib.enumerate_unique(t.tpl)[:20] for t in tasks]

    plain = BatchPolisher(tasks)
    want = plain.score_mutations(muts_per_zmw)

    from pbccs_tpu.ops.fwdbwd_pallas import fills_use_pallas

    monkeypatch.setenv("PBCCS_PALLAS", "1")
    assert fills_use_pallas()
    mesh = make_zmw_mesh(n_zmw=4, n_read=2)
    sharded = BatchPolisher(tasks, mesh=mesh)
    got = sharded.score_mutations(muts_per_zmw)

    assert np.array_equal(sharded.active[:4, :4], plain.active[:4, :4])
    for z in range(4):
        np.testing.assert_allclose(got[z], want[z], rtol=1e-4, atol=1e-3)


@pytest.mark.slow
@pytest.mark.parametrize("tpl_len,pallas", [(60, False), (300, False),
                                            (60, True)])
def test_batch_sharded_device_refine_matches_unsharded(rng, monkeypatch,
                                                       tpl_len, pallas):
    """The sharded device-resident refinement loop (shard_map over the
    ('zmw', 'read') mesh with read-axis psum) produces the same templates,
    refine stats, and QVs as the single-device device loop.  With
    `pallas` the fills are the kernel's (interpreted): each device's
    rebuild packs the needed reads of its own block (PR 30).

    tpl_len=300 runs a multi-block (NB=6) bucket so the mesh path covers
    the halo-block streaming, the W(L) schedule, and the live-mask einsum
    the 60 bp bucket doesn't reach — multi-chip long-insert runs take
    this same sharded dense path (dense_score_enabled up to
    DENSE_MAX_JMAX; the mesh bail at parallel/batch.py only triggers
    beyond it)."""
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    from pbccs_tpu.models.arrow.refine import RefineOptions

    tasks, _ = make_tasks(rng, n_zmws=4, tpl_len=tpl_len, n_passes=4)
    for t in tasks:  # corrupt drafts so refinement has real work
        t.tpl[30] = (t.tpl[30] + 1) % 4
    opts = RefineOptions(max_iterations=6)

    monkeypatch.setenv("PBCCS_DEVICE_REFINE", "1")
    monkeypatch.setenv("PBCCS_DENSE", "1")
    if pallas:
        monkeypatch.setenv("PBCCS_PALLAS", "1")
    plain = BatchPolisher(tasks)
    rp = plain.refine(opts)
    qp = plain.consensus_qvs()

    mesh = make_zmw_mesh(n_zmw=4, n_read=2)
    sharded = BatchPolisher(tasks, mesh=mesh)
    rs = sharded.refine_device(opts)
    assert rs is not None, "mesh refine fell back to the host loop"
    qs = sharded.consensus_qvs()

    for z in range(4):
        assert rp[z].converged == rs[z].converged
        np.testing.assert_array_equal(plain.tpls[z], sharded.tpls[z])
        np.testing.assert_array_equal(qp[z], qs[z])


def test_batch_global_zscores_finite(rng):
    tasks, _ = make_tasks(rng, n_zmws=2, tpl_len=60, n_passes=4)
    batch = BatchPolisher(tasks)
    gz = batch.global_zscores()
    assert gz.shape == (2,)
    assert np.isfinite(gz).all()


@pytest.mark.slow
def test_partial_refill_matches_full(rng):
    """Refilling only changed ZMWs after apply_mutations produces the same
    templates, QVs, and convergence as the always-full rebuild."""
    from pbccs_tpu.models.arrow.refine import RefineOptions

    def build(seed):
        r = np.random.default_rng(seed)
        tasks = []
        for z in range(6):
            tpl, reads, strands, snr = simulate_zmw(r, 120, 5)
            draft = tpl.copy()
            draft[30 + z] = (draft[30 + z] + 1) % 4
            tasks.append(ZmwTask(f"pr/{z}", draft, snr, reads, strands,
                                 [0] * len(reads), [len(draft)] * len(reads)))
        return tasks

    pol_full = BatchPolisher(build(7))
    orig = BatchPolisher._setup_partial
    BatchPolisher._setup_partial = \
        lambda self, ch: BatchPolisher._setup(self, first=False)
    try:
        res_full = pol_full.refine(RefineOptions(max_iterations=6))
        qv_full = pol_full.consensus_qvs()
    finally:
        BatchPolisher._setup_partial = orig

    pol_part = BatchPolisher(build(7))
    res_part = pol_part.refine(RefineOptions(max_iterations=6))
    qv_part = pol_part.consensus_qvs()

    for z in range(6):
        np.testing.assert_array_equal(pol_full.tpls[z], pol_part.tpls[z])
        np.testing.assert_array_equal(qv_full[z], qv_part[z])
        assert res_full[z].converged == res_part[z].converged


@pytest.mark.slow
def test_tiny_window_fallback_matches_per_zmw(rng):
    """Reads whose template window is shorter than MIN_FAST_EDGE_WLEN score
    boundary mutations by full refill (the fallback pair path); decisions
    must still match the per-ZMW scorer."""
    from pbccs_tpu.parallel.batch import MIN_FAST_EDGE_WLEN

    tpl, reads, strands, snr = simulate_zmw(rng, 60, 5)
    tstarts = [0] * len(reads)
    tends = [len(tpl)] * len(reads)
    # clip one read to a tiny window at the template start
    w = MIN_FAST_EDGE_WLEN - 2
    reads = list(reads)
    reads[1] = reads[1][:w]
    tends[1] = w

    task = ZmwTask("tiny/0", tpl, snr, reads, strands, tstarts, tends)
    pol = BatchPolisher([task])
    sc = ArrowMultiReadScorer(tpl, snr, reads, strands, tstarts, tends)

    muts = mutlib.enumerate_unique(tpl)
    batch_scores = pol.score_mutations([muts])[0]
    serial_scores = sc.score_mutations(muts)
    np.testing.assert_allclose(batch_scores, serial_scores, atol=2e-3)
