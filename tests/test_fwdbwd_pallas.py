"""Pallas banded-fill kernel vs the pure-JAX reference path.

Pattern from the reference suite: the same scores must come out of every
kernel implementation (reference ConsensusCore TestRecursors.cpp:63-69 runs
one test body over scalar/SSE and dense/sparse recursors; here the pair is
JAX lax.scan vs the Pallas column-scan kernel, run in interpret mode on
CPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from pbccs_tpu.models.arrow.params import (
    MISMATCH_PROBABILITY,
    snr_to_transition_table_host,
    template_transition_params,
)
from pbccs_tpu.ops import fwdbwd as fb
from pbccs_tpu.ops import fwdbwd_pallas as fp


def noisy_read(rng, tpl, sub=0.08, dele=0.06, ins=0.07):
    out = []
    for b in tpl:
        u = rng.random()
        if u < sub:
            out.append(int(rng.integers(0, 4)))
        elif u < sub + dele:
            continue
        else:
            out.append(int(b))
            if rng.random() < ins:
                out.append(int(rng.integers(0, 4)))
    return np.array(out, np.int8)


def _batch(rng, specs, Imax, Jmax, snr=8.0):
    """Build a padded read/template batch from (read_len_hint, tpl_len)."""
    R = len(specs)
    reads = np.full((R, Imax), 4, np.int8)
    rlens = np.zeros(R, np.int32)
    tpls = np.full((R, Jmax), 4, np.int8)
    tlens = np.zeros(R, np.int32)
    trans = np.zeros((R, Jmax, 4), np.float32)
    table = snr_to_transition_table_host(np.full(4, snr))
    for r, (_, J) in enumerate(specs):
        tpl = rng.integers(0, 4, J).astype(np.int8)
        read = noisy_read(rng, tpl)
        if len(read) == 0:
            read = np.array([0], np.int8)
        I = min(len(read), Imax)
        reads[r, :I] = read[:I]
        rlens[r] = I
        tpls[r, :J] = tpl
        tlens[r] = J
        padded = np.pad(tpl, (0, Jmax - J), constant_values=4)
        trans[r] = np.asarray(template_transition_params(
            jnp.asarray(padded), jnp.asarray(table, jnp.float32), jnp.int32(J)))
    return tuple(jnp.asarray(x) for x in (reads, rlens, tpls, trans, tlens))


WIDTH = 48


def _plain(fills):
    """fill_alpha_beta_batch's outputs with the bands by template column."""
    alpha, beta, *rest = fills
    return (fb.band_columns(alpha), fb.band_columns(beta), *rest)


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(20260730)
    specs = [(0, 2), (0, 1), (0, 5), (0, 90), (0, 64), (0, 80), (0, 33)]
    return _batch(rng, specs, Imax=160, Jmax=96)


def test_forward_matches_jax_path(batch):
    reads, rlens, tpls, trans, tlens = batch
    pa = fb.band_columns(
        fp.pallas_forward_batch(reads, rlens, tpls, trans, tlens, WIDTH))
    for r in range(reads.shape[0]):
        a = fb.banded_forward(reads[r], rlens[r], tpls[r], trans[r], tlens[r], WIDTH)
        np.testing.assert_allclose(np.asarray(pa.vals[r]), np.asarray(a.vals),
                                   atol=1e-5)
        np.testing.assert_array_equal(np.asarray(pa.offsets[r]),
                                      np.asarray(a.offsets))
        np.testing.assert_allclose(np.asarray(pa.log_scales[r]),
                                   np.asarray(a.log_scales), atol=1e-5)


def test_backward_matches_jax_path(batch):
    reads, rlens, tpls, trans, tlens = batch
    pb = fb.band_columns(
        fp.pallas_backward_batch(reads, rlens, tpls, trans, tlens, WIDTH))
    for r in range(reads.shape[0]):
        b = fb.banded_backward(reads[r], rlens[r], tpls[r], trans[r], tlens[r], WIDTH)
        np.testing.assert_allclose(np.asarray(pb.vals[r]), np.asarray(b.vals),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(pb.log_scales[r]),
                                   np.asarray(b.log_scales), atol=1e-5)


def test_logliks_match_and_mate(batch):
    """alpha/beta LLs agree with the JAX path and with each other (the
    reference's AlphaBetaMismatch mating check, SimpleRecursor.cpp:667-691)."""
    reads, rlens, tpls, trans, tlens = batch
    pa = fp.pallas_forward_batch(reads, rlens, tpls, trans, tlens, WIDTH)
    pb = fp.pallas_backward_batch(reads, rlens, tpls, trans, tlens, WIDTH)
    lla = np.asarray(fp.forward_loglik_batch(pa, rlens, tlens))
    llb = np.asarray(fp.backward_loglik_batch(pb, tlens))
    for r in range(reads.shape[0]):
        a = fb.banded_forward(reads[r], rlens[r], tpls[r], trans[r], tlens[r], WIDTH)
        ref = float(fb.forward_loglik(a, rlens[r], tlens[r]))
        assert abs(lla[r] - ref) < 2e-3, (r, lla[r], ref)
        assert abs(1.0 - lla[r] / llb[r]) < 1e-3, (r, lla[r], llb[r])


def test_fill_dispatch_forced_pallas(monkeypatch, batch):
    """fill_alpha_beta_batch with PBCCS_PALLAS=1 (interpret mode on CPU)
    agrees with the default JAX dispatch."""
    from pbccs_tpu.models.arrow.scorer import fill_alpha_beta_batch

    reads, rlens, tpls, trans, tlens = batch
    monkeypatch.delenv("PBCCS_PALLAS", raising=False)
    ref = fill_alpha_beta_batch(reads, rlens, tpls, trans, tlens, WIDTH)
    monkeypatch.setenv("PBCCS_PALLAS", "1")
    got = _plain(fill_alpha_beta_batch(reads, rlens, tpls, trans, tlens,
                                       WIDTH))
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-3)


def test_wide_band_half_column_blocks_match_jax_path(batch):
    """Bands wider than 128 (the 2x mating retry) take half the columns
    per grid step to stay inside the chip's per-kernel VMEM; the blocking
    must not change a value."""
    from pbccs_tpu.models.arrow.scorer import fill_alpha_beta_batch

    reads, rlens, tpls, trans, tlens = batch
    wide = 160
    ref = fill_alpha_beta_batch(reads, rlens, tpls, trans, tlens, wide,
                                use_pallas=False)
    got = _plain(fill_alpha_beta_batch(reads, rlens, tpls, trans, tlens,
                                       wide, use_pallas=True))
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-3)


def test_band_shift_clamp_drops_read_not_crashes():
    """A read/template length ratio beyond the kernel's max band shift must
    produce a (finite or -inf) score, never garbage; the scorer drops such
    reads via the mating gate."""
    rng = np.random.default_rng(7)
    tpl = rng.integers(0, 4, 16).astype(np.int8)
    read = np.concatenate([np.repeat(tpl, 12)])[:180].astype(np.int8)  # ~11x
    Imax, Jmax = 192, 96
    reads = np.full((1, Imax), 4, np.int8)
    reads[0, :len(read)] = read
    table = snr_to_transition_table_host(np.full(4, 8.0))
    padded = np.pad(tpl, (0, Jmax - len(tpl)), constant_values=4)
    trans = np.asarray(template_transition_params(
        jnp.asarray(padded), jnp.asarray(table, jnp.float32),
        jnp.int32(len(tpl))))[None]
    pa = fp.pallas_forward_batch(
        jnp.asarray(reads), jnp.asarray([len(read)], jnp.int32),
        jnp.asarray(padded[None]), jnp.asarray(trans),
        jnp.asarray([len(tpl)], jnp.int32), WIDTH)
    ll = np.asarray(fp.forward_loglik_batch(
        pa, jnp.asarray([len(read)], jnp.int32),
        jnp.asarray([len(tpl)], jnp.int32)))
    assert not np.isnan(ll).any()
    # the clamped band cannot represent this read: it must be deterministically
    # droppable (LL at the log-tiny floor), not silently mis-scored
    assert ll[0] < -60.0


# --------------------------------------------------------------------------
# PR 28: the fill writes the framed, read-major band itself
# --------------------------------------------------------------------------


def _unframed_fill(reads, rlens, tpls, trans, tlens, W, backward):
    """The fill as it ran before the frame: the kernel's columns are the
    template's (padded to a step), each coefficient builder makes its own
    read windows, and the band is cut out of the kernel's output by XLA."""
    R, Imax = reads.shape
    Jmax = tpls.shape[1]
    nc = fp._pad_cols(Jmax + 1)
    I, J = rlens.astype(jnp.int32), tlens.astype(jnp.int32)
    offs = jax.vmap(lambda i, j: fb.band_offsets(i, j, nc, W))(I, J)

    def one(r, i, t, tr, j, o):
        r, t = r.astype(jnp.int32), t.astype(jnp.int32)
        if backward:
            return fp._backward_coeffs(r, i, t, tr, j, o, W,
                                       MISMATCH_PROBABILITY, nc, top=Jmax)
        rb = fp.window_rows_circ(jnp.concatenate([r[0:1], r]), o, W)
        return fp._forward_coeffs(r, i, t, tr, j, o, rb, W,
                                  MISMATCH_PROBABILITY)

    cm, cd, cc, mask, seed, seedcol = jax.vmap(
        one, out_axes=(1, 1, 1, 1, 0, 0))(reads, I, tpls, trans, J, offs)
    Rp = fp._pad_reads(R)
    cm, cd, cc, mask = fp._pad_r([cm, cd, cc, mask], R, Rp, axis=1)
    seed, seedcol = fp._pad_r([seed, seedcol], R, Rp)
    vals, ls = fp._run_fill(cm, cd, cc, mask, seed, seedcol,
                            rev_store=backward)
    lo = nc - 1 - Jmax if backward else 0
    return (np.asarray(vals[:R, lo: lo + Jmax + 1]),
            np.asarray(ls[:R, lo: lo + Jmax + 1, 0]),
            np.asarray(offs[:, : Jmax + 1]))


FRAME_CASES = {
    "500bp-W64-R32": dict(R=32, Jmax=576, W=64, Imax=640),
    "2kb-W96-R12": dict(R=12, Jmax=2304, W=96, Imax=2560),
    "retry-W192-R8": dict(R=8, Jmax=1152, W=192, Imax=1280),
    "R40-not-a-block": dict(R=40, Jmax=128, W=48, Imax=192),
}


@pytest.fixture(scope="module", params=list(FRAME_CASES))
def frame_case(request):
    c = FRAME_CASES[request.param]
    rng = np.random.default_rng(28 + c["R"])
    specs = [(0, int(rng.integers(c["Jmax"] // 2, c["Jmax"] - 3)))
             for _ in range(c["R"])]
    specs[0] = (0, c["Jmax"] - 1)            # one window fills the bucket
    return c, _batch(rng, specs, Imax=c["Imax"], Jmax=c["Jmax"])


@pytest.mark.parametrize("backward", [False, True], ids=["alpha", "beta"])
def test_framed_fill_is_the_unframed_fill(frame_case, backward):
    """The band the kernel writes read-major in the dense kernel's row
    frame holds, column for column and bit for bit, what the kernel wrote
    columns-leading in the template's own frame; the rows around the
    columns are zero; and it is ops/fwdbwd's band to float32 rounding
    (the two scans associate differently, as they always have)."""
    c, (reads, rlens, tpls, trans, tlens) = frame_case
    fill = fp.pallas_backward_batch if backward else fp.pallas_forward_batch
    got = fill(reads, rlens, tpls, trans, tlens, c["W"])
    n = c["Jmax"] + 1
    assert got.vals.shape == (c["R"], fb.band_frame_rows(n), c["W"])
    assert fb.band_lead(got) == fb.BAND_LEAD
    vals, ls, offs = _unframed_fill(reads, rlens, tpls, trans, tlens,
                                    c["W"], backward)
    plain = fb.band_columns(got)
    np.testing.assert_array_equal(np.asarray(plain.vals), vals)
    np.testing.assert_array_equal(np.asarray(plain.log_scales), ls)
    np.testing.assert_array_equal(np.asarray(plain.offsets), offs)
    framed = np.asarray(got.vals)
    assert not framed[:, : fb.BAND_LEAD].any()
    assert not framed[:, fb.BAND_LEAD + n:].any()
    xla = fb.banded_backward if backward else fb.banded_forward
    for r in (0, c["R"] - 1):
        ref = xla(reads[r], rlens[r], tpls[r], trans[r], tlens[r], c["W"])
        np.testing.assert_allclose(vals[r], np.asarray(ref.vals), atol=1e-5)
        np.testing.assert_allclose(ls[r], np.asarray(ref.log_scales),
                                   atol=1e-5)


def test_band_frame_of_a_plain_band_round_trips(batch):
    """band_frame (what the Pallas fill makes unnecessary) puts an XLA
    fill's band where the Pallas fill writes its own, and band_columns
    takes either back."""
    reads, rlens, tpls, trans, tlens = batch
    plain = jax.vmap(lambda r, i, t, tr, j: fb.banded_forward(
        r, i, t, tr, j, WIDTH))(reads, rlens, tpls, trans, tlens)
    framed = fb.band_frame(plain)
    assert fb.band_lead(plain) == 0 and fb.band_lead(framed) == fb.BAND_LEAD
    pallas = fp.pallas_forward_batch(reads, rlens, tpls, trans, tlens, WIDTH)
    assert framed.vals.shape == pallas.vals.shape
    np.testing.assert_allclose(np.asarray(framed.vals),
                               np.asarray(pallas.vals), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(fb.band_columns(framed).vals),
                                  np.asarray(plain.vals))
    assert fb.band_frame(framed) is framed


# --------------------------------------------------------------------------
# PR 30: the fill skips read blocks that hold no live read
# --------------------------------------------------------------------------


def _live_counts(R):
    """Live-read counts that end inside a block, on a block's edge and,
    where the batch has one, inside its second block."""
    return sorted({1, min(R, 31), min(R, 32), min(R, 33), R})


@pytest.mark.parametrize("backward", [False, True], ids=["alpha", "beta"])
def test_gated_fill_is_the_ungated_fill_on_its_live_reads(frame_case,
                                                           backward):
    """A fill told that reads [0, live) alone need one writes them what
    the ungated fill writes, bit for bit, block by whole block; NaN in
    the inputs of every other read changes nothing in them (no read's
    scan looks at another's, and a dead block's never ran)."""
    c, (reads, rlens, tpls, trans, tlens) = frame_case
    fill = fp.pallas_backward_batch if backward else fp.pallas_forward_batch
    want = fill(reads, rlens, tpls, trans, tlens, c["W"])
    for live in _live_counts(c["R"]):
        dead = jnp.arange(c["R"]) >= live
        poisoned = jnp.where(dead[:, None, None], jnp.nan, trans)
        got = fill(reads, rlens, tpls, poisoned, tlens, c["W"],
                   live=jnp.int32(live))
        for g, w in ((got.vals, want.vals), (got.log_scales,
                                             want.log_scales)):
            np.testing.assert_array_equal(np.asarray(g)[:live],
                                          np.asarray(w)[:live])


def test_fill_given_no_liveness_is_the_program_it_was(frame_case):
    """With no `live` the call prefetches no scalar and keeps its read
    blocks independent, as before the gate (its output is pinned above,
    against the unframed fill); with one, it prefetches the block count."""
    c, batch = frame_case

    def call_params(**kw):
        jaxpr = jax.make_jaxpr(
            lambda *a: fp.pallas_forward_batch(*a, c["W"], **kw))(*batch)
        (eqn,) = [e for e in jaxpr.jaxpr.eqns
                  if e.primitive.name == "pallas_call"]
        return (eqn.params["grid_mapping"].num_index_operands,
                eqn.params["compiler_params"]["mosaic_tpu"]
                .dimension_semantics[0], len(eqn.outvars))

    assert call_params() == (0, "parallel", 2)
    assert call_params(live=jnp.int32(1)) == (1, "arbitrary", 2)


@pytest.mark.parametrize("n", [0, 1, 5, 40])
def test_place_reads_moves_the_placed_rows_alone(n):
    rng = np.random.default_rng(30)
    packed = jnp.asarray(rng.normal(size=(64, 4160, 48)).astype(np.float32))
    into = jnp.asarray(rng.normal(size=(40, 4160, 48)).astype(np.float32))
    dest = np.concatenate([rng.permutation(40), np.zeros(24)]).astype(np.int32)
    got = fp.place_reads(packed, jnp.asarray(dest), jnp.int32(n), into)
    want = np.asarray(into).copy()
    want[dest[:n]] = np.asarray(packed)[:n]
    np.testing.assert_array_equal(np.asarray(got), want)


def test_needed_reads_are_refilled_and_the_rest_kept(frame_case):
    """fill_alpha_beta_batch with `need`: the needed reads get the bands,
    log-scales and likelihoods a fill of every read gives them (to float32
    rounding here: the passes run inside a loop, which the CPU compiles
    with other fused multiply-adds than the eager full fill; on the chip
    the kernel is one Mosaic program either way) and every other read
    zero bands; passes over bands a caller carries (fill_pass under
    for_needed_reads, as the refine loop's rebuild runs them) leave every
    other read the bands and likelihoods it went in with, bit for bit."""
    from pbccs_tpu.models.arrow.scorer import (fill_alpha_beta_batch,
                                               fill_pass, for_needed_reads)

    c, batch = frame_case
    rng = np.random.default_rng(30 + c["R"])
    full = fill_alpha_beta_batch(*batch, c["W"], use_pallas=True)
    prev = jax.tree.map(lambda x: x + 1, (full[:2], full[2:4]))

    def one_pass(idx, live, carry):
        take = lambda a: jnp.take(a, idx, axis=0)
        return fill_pass(tuple(map(take, batch)), idx, live, *carry,
                         c["W"], True)[:2]

    for share in (0.0, 0.3, 1.0):
        need = rng.random(c["R"]) < share
        got = for_needed_reads(jnp.asarray(need), one_pass, prev)
        for g, f in zip(jax.tree.leaves(got), jax.tree.leaves(full[:4])):
            np.testing.assert_allclose(np.asarray(g)[need],
                                       np.asarray(f)[need], rtol=2e-5,
                                       atol=1e-6)
        for g, p in zip(jax.tree.leaves(got), jax.tree.leaves(prev)):
            np.testing.assert_array_equal(np.asarray(g)[~need],
                                          np.asarray(p)[~need])
    half = np.arange(c["R"]) < c["R"] // 2
    fresh = fill_alpha_beta_batch(*batch, c["W"], use_pallas=True,
                                  need=jnp.asarray(half))
    for g, f in zip(jax.tree.leaves(fresh), jax.tree.leaves(full)):
        np.testing.assert_allclose(np.asarray(g)[half], np.asarray(f)[half],
                                   rtol=2e-5, atol=1e-6)
    for band in fresh[:2]:
        assert not np.asarray(band.vals)[~half].any()
        assert np.asarray(band.vals)[half].any()


@pytest.mark.parametrize("cols", [1, 97, 577, 2305])
def test_a_reads_scale_total_is_the_same_bits_in_any_batch(cols):
    """The likelihoods' sum of log-scales is a pairwise sum written out in
    elementwise adds: a read's total is what the same tree gives in numpy
    float32, whether it is summed alone, in a pass of 64 reads or in a
    batch of 384, so a refill in passes gives the baselines of a refill
    in one call (on the chip a jnp.sum did not: PERF.md, PR 30)."""
    rng = np.random.default_rng(cols)
    x = rng.normal(-1.0, 0.5, size=(384, cols)).astype(np.float32)
    J = rng.integers(0, cols, size=(384, 1)).astype(np.int32)

    want = np.where(np.arange(cols)[None, :] <= J, x, np.float32(0))
    n = 1 << (cols - 1).bit_length()
    want = np.pad(want, ((0, 0), (0, n - cols)))
    while n > 1:
        n //= 2
        want = want[:, :n] + want[:, n:]
    total = jax.jit(fp._scale_total)
    for rows in (1, 64, 384):
        got = np.concatenate([
            np.asarray(total(jnp.asarray(x[k: k + rows]),
                             jnp.asarray(J[k: k + rows])))
            for k in range(0, 384, rows)])
        np.testing.assert_array_equal(got, want[:, 0])
    exact = np.where(np.arange(cols)[None, :] <= J, x, 0).astype(
        np.float64).sum(axis=1)
    np.testing.assert_allclose(want[:, 0], exact, rtol=1e-6, atol=1e-5)
