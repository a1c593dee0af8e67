"""Pallas TPU path for the banded Quiver fills.

Same two-stage design as the Arrow fill kernel (ops/fwdbwd_pallas): an XLA
coefficient precompute turns the Quiver recurrence
(reference ConsensusCore/src/C++/Quiver/SimpleRecursor.cpp:62-231, move
scores QvEvaluator.hpp:160-207) into per-column CIRCULAR-lane band
coefficients (fwdbwd.BandedMatrix: cell (i, j) at lane i mod W)

    col[L] = cm[L] * roll(prev, 1)[L]       (Incorporate)
           + cd[L] * prev[L]                (Delete)
           + cg[L] * roll(prev2, 1)[L] / scale_prev   (Merge, j-2)
           + cc[L] * col[L-1 circ]          (Extra, in-column)

with all band-membership masks folded into cm/cd/cg and the circular
scan's cut into cc, and the shared column-scan kernel
(fwdbwd_pallas._fill_kernel with merge=True) runs the sequential scan
with the band state -- including the two-column Merge carry -- resident
in VMEM.  (The circular layout replaced the Merge carry's 15-variant
dynamic shift-select chain, which made the kernel pathologically slow to
compile on Mosaic -- the round-4 Quiver compile wall.)  This is the device analogue of
the reference's SSE recursor (SseRecursor.cpp:66-130): the reference
vectorizes 4 rows per __m128, here the whole band rides the vector lanes.

Emission lookups per (column, band-lane) use the same one-hot-matmul
windowing as the Arrow precompute; QV feature tracks are general floats, so
their windows run at exact=True (f32 HIGHEST) rather than the bf16 base-code
fast path.

Parity: tests/test_quiver_pallas.py fuzzes these fills against the JAX
banded recursor (models/quiver/recursor.py) and the dense log-space oracle,
mirroring the reference's typed-recursor concordance tests
(ConsensusCore/src/Tests/TestRecursors.cpp:63-69).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from pbccs_tpu.models.quiver.params import MERGE, QuiverConfig
from pbccs_tpu.models.quiver.recursor import QuiverFeatureArrays, _move_params
from pbccs_tpu.ops.fwdbwd import BandedMatrix, band_offsets
from pbccs_tpu.ops.fwdbwd_pallas import (_circ_rows_cols, _edge_clip_rows,
                                         _in_band2, _pad_cols, _pad_r,
                                         _pad_reads, _rev_clip_rows,
                                         _run_fill, window_rows_circ)

_TINY = 1e-30


def _win(x, starts, W: int, exact: bool = True):
    """y[j, L] = x[row(L)] over the circular window (one back row pad)."""
    xp = jnp.concatenate([x, x[-1:]])
    return window_rows_circ(xp, starts, W, exact=exact)


def _win_m1(x, starts, W: int, exact: bool = True):
    """y[j, L] = x[row(L) - 1] (front-clipped, circular window)."""
    xp = jnp.concatenate([x[0:1], x])
    return window_rows_circ(xp, starts, W, exact=exact)


def _emissions(pp, feat: QuiverFeatureArrays, rows, seq_w, subs_w, ins_w,
               dtag_w, dqv_w, mqv_w, tb_inc, tb_extra, tb_mrg, tb_mrg2,
               I, in_tpl, mrg_ok, pin_s, pin_e):
    """exp-space Inc/Del/Extra/Merge planes over an (nc, W) window whose
    feature tracks were gathered at the per-plane row index (see callers).
    Mirrors recursor._inc/_del/_extra/_merge value for value."""
    inc = jnp.where(seq_w == tb_inc, pp["match"],
                    pp["mismatch"] + pp["mismatch_s"] * subs_w)

    tagged = (rows < I) & (dtag_w == tb_inc.astype(jnp.float32))
    dele = jnp.where(tagged,
                     pp["deletion_with_tag"]
                     + pp["deletion_with_tag_s"] * dqv_w,
                     pp["deletion_n"])
    free = ((~pin_s) & (rows == 0)) | ((~pin_e) & (rows == I))
    dele = jnp.where(free, 0.0, dele)

    extra_match = in_tpl & (seq_w == tb_extra)
    extra = jnp.where(extra_match,
                      pp["branch"] + pp["branch_s"] * ins_w,
                      pp["nce"] + pp["nce_s"] * ins_w)

    good = mrg_ok & (seq_w == tb_mrg) & (tb_mrg == tb_mrg2)
    tb = jnp.clip(tb_mrg, 0, 3)
    mrg_score = pp["merge"][tb[:, 0]][:, None] \
        + pp["merge_s"][tb[:, 0]][:, None] * mqv_w
    mrg = jnp.where(good, jnp.exp(mrg_score), 0.0)
    return jnp.exp(inc), jnp.exp(dele), jnp.exp(extra), mrg


def _forward_coeffs(feat: QuiverFeatureArrays, I, tpl, J, offsets, W: int,
                    pp, use_merge: bool, pin_s, pin_e):
    """Per-column band coefficients of the Quiver alpha recurrence for one
    read, mirroring recursor.quiver_forward column for column."""
    nc = offsets.shape[0]
    Jmax = tpl.shape[0]
    j = jnp.arange(nc, dtype=jnp.int32)[:, None]
    o = offsets[:, None]
    om1 = _edge_clip_rows(offsets, 1, nc)[:, None]
    om2 = _edge_clip_rows(offsets, 2, nc)[:, None]

    rows = _circ_rows_cols(offsets, W)
    valid = (rows >= 0) & (rows <= I)

    # feature windows at row index rows-1 (Inc/Extra/Merge read base) and
    # rows (Del tag/qv)
    seq_f = feat.seq.astype(jnp.float32)
    seq_m1 = _win_m1(seq_f, offsets, W, exact=False)
    subs_m1 = _win_m1(feat.subs_qv, offsets, W)
    ins_m1 = _win_m1(feat.ins_qv, offsets, W)
    mqv_m1 = _win_m1(feat.merge_qv, offsets, W)
    dtag_0 = _win(feat.del_tag, offsets, W, exact=False)
    dqv_0 = _win(feat.del_qv, offsets, W)

    tb_prev = _edge_clip_rows(tpl, 1, nc)[:, None]     # template base j-1
    tb_cur = _edge_clip_rows(tpl, 0, nc)[:, None]      # template base j
    tb_prev2 = _edge_clip_rows(tpl, 2, nc)[:, None]    # template base j-2

    inc, dele, extra, mrg = _emissions(
        pp, feat, rows, seq_m1, subs_m1, ins_m1, dtag_0, dqv_0, mqv_m1,
        tb_inc=tb_prev, tb_extra=tb_cur, tb_mrg=tb_prev2, tb_mrg2=tb_prev,
        I=I, in_tpl=j < J, mrg_ok=(j >= 2) & use_merge,
        pin_s=pin_s, pin_e=pin_e)

    live = (j >= 1) & (j <= J)
    cm = jnp.where(valid & (rows >= 1) & live
                   & _in_band2(rows - 1, om1, W), inc, 0.0)
    cd = jnp.where(valid & live & _in_band2(rows, om1, W), dele, 0.0)
    cg = jnp.where(valid & (rows >= 1) & live
                   & _in_band2(rows - 1, om2, W), mrg, 0.0)
    # column 0 chains Extra below the alpha(0,0) impulse; dead cols j > J
    # have no in-column move; rows > o cuts the circular scan at the
    # band's first row
    cc = jnp.where(valid & (rows >= 1) & (j <= J) & (rows > o), extra, 0.0)

    mask = (j[:, 0] <= J).astype(jnp.float32)
    seed = (jnp.arange(W) == 0).astype(jnp.float32)
    return cm, cd, cc, cg, mask, seed, jnp.int32(0)


def _backward_coeffs(feat: QuiverFeatureArrays, I, tpl, J, offsets, W: int,
                     pp, use_merge: bool, pin_s, pin_e):
    """Beta coefficients in the static kernel frame (kernel column cc holds
    beta column j = Jmax - cc, lanes reversed), mirroring
    recursor.quiver_backward column for column."""
    nc = offsets.shape[0]
    Jmax = tpl.shape[0]
    cc_idx = jnp.arange(nc, dtype=jnp.int32)[:, None]
    j = Jmax - cc_idx
    o_jv = _rev_clip_rows(offsets, Jmax, nc)
    o_j = o_jv[:, None]
    o_j1 = _rev_clip_rows(offsets, Jmax + 1, nc)[:, None]
    o_j2 = _rev_clip_rows(offsets, Jmax + 2, nc)[:, None]

    rows = _circ_rows_cols(o_jv, W)
    valid = (rows >= 0) & (rows <= I)

    # all backward lookups are at row index `rows` (shared circular lanes;
    # no lane reversal -- the kernel's backward mode rolls the other way)
    seq_0 = _win(feat.seq.astype(jnp.float32), o_jv, W, exact=False)
    subs_0 = _win(feat.subs_qv, o_jv, W)
    ins_0 = _win(feat.ins_qv, o_jv, W)
    mqv_0 = _win(feat.merge_qv, o_jv, W)
    dtag_0 = _win(feat.del_tag, o_jv, W, exact=False)
    dqv_0 = _win(feat.del_qv, o_jv, W)

    tb = _rev_clip_rows(tpl, Jmax, nc)[:, None]            # base j (clipped)
    tb_next = _rev_clip_rows(tpl, Jmax + 1, nc)[:, None]   # base j+1

    inc, dele, extra, mrg = _emissions(
        pp, feat, rows, seq_0, subs_0, ins_0, dtag_0, dqv_0, mqv_0,
        tb_inc=tb, tb_extra=tb, tb_mrg=tb, tb_mrg2=tb_next,
        I=I, in_tpl=j < J, mrg_ok=(j + 1 < J) & use_merge,
        pin_s=pin_s, pin_e=pin_e)

    live = (j >= 0) & (j < J)
    cm = jnp.where(valid & (rows < I) & live
                   & _in_band2(rows + 1, o_j1, W), inc, 0.0)
    cd = jnp.where(valid & live & _in_band2(rows, o_j1, W), dele, 0.0)
    cg = jnp.where(valid & (rows < I) & live
                   & _in_band2(rows + 1, o_j2, W), mrg, 0.0)
    # rows < o + W - 1 cuts the reverse circular scan at the band top
    cc = jnp.where(valid & (rows < I) & (j >= 0) & (j <= J)
                   & (rows < o_j + W - 1), extra, 0.0)

    mask = ((j[:, 0] >= 0) & (j[:, 0] <= J)).astype(jnp.float32)
    seed = (jnp.arange(W) == I % W).astype(jnp.float32)
    return cm, cd, cc, cg, mask, seed, \
        (Jmax - J).astype(jnp.int32)


def _batch(coeff_fn, feat, rlens, tpls, tlens, config, W, pin_start, pin_end,
           rev_store: bool):
    R, Imax = feat.seq.shape
    Jmax = tpls.shape[1]
    nc = _pad_cols(Jmax + 1)
    Rp = _pad_reads(R)
    pp = _move_params(config.qv_params)
    use_merge = bool(config.moves_available & MERGE)

    I = rlens.astype(jnp.int32)
    J = tlens.astype(jnp.int32)
    offsets = jax.vmap(lambda i, jl: band_offsets(i, jl, nc, W))(I, J)
    outs = jax.vmap(
        lambda f, i, t, jl, o: coeff_fn(
            f, i, t.astype(jnp.int32), jl, o, W, pp, use_merge,
            jnp.asarray(pin_start), jnp.asarray(pin_end)),
        out_axes=(1, 1, 1, 1, 1, 0, 0),
    )(feat, I, tpls, J, offsets)
    cm, cd, cc, cg, mask, seed, seedcol = outs
    cm, cd, cc, cg, mask = _pad_r([cm, cd, cc, cg, mask], R, Rp, axis=1)
    seed, seedcol = _pad_r([seed, seedcol], R, Rp)
    vals, ls = _run_fill(cm, cd, cc, mask, seed, seedcol,
                         rev_store=rev_store, cg=cg)
    return vals, ls[:, :, 0], offsets, nc


def pallas_quiver_forward_batch(feat: QuiverFeatureArrays, rlens, tpls,
                                tlens, config: QuiverConfig, width: int,
                                pin_start: bool = True,
                                pin_end: bool = True) -> BandedMatrix:
    """Batched banded Quiver alpha fills: feat leaves (R, Imax), tpls
    (R, Jmax), rlens/tlens (R,)."""
    vals, ls, offsets, _ = _batch(_forward_coeffs, feat, rlens, tpls, tlens,
                                  config, width, pin_start, pin_end,
                                  rev_store=False)
    R = rlens.shape[0]
    Jmax = tpls.shape[1]
    return BandedMatrix(vals[:R, : Jmax + 1], offsets[:, : Jmax + 1],
                        ls[:R, : Jmax + 1])


def pallas_quiver_backward_batch(feat: QuiverFeatureArrays, rlens, tpls,
                                 tlens, config: QuiverConfig, width: int,
                                 pin_start: bool = True,
                                 pin_end: bool = True) -> BandedMatrix:
    """Batched banded Quiver beta fills (kernel frame un-flipped here, as
    ops.fwdbwd_pallas.pallas_backward_batch does for Arrow)."""
    vals, ls, offsets, nc = _batch(_backward_coeffs, feat, rlens, tpls,
                                   tlens, config, width, pin_start, pin_end,
                                   rev_store=True)
    R = rlens.shape[0]
    Jmax = tpls.shape[1]
    lo = nc - 1 - Jmax
    return BandedMatrix(vals[:R, lo: lo + Jmax + 1],
                        offsets[:, : Jmax + 1], ls[:R, lo: lo + Jmax + 1])


def quiver_loglik_batch(alpha: BandedMatrix, rlens, tlens):
    """LL[r] = log alpha(I, J) + column scales, as masked reductions (the
    Quiver final column is a full band, so the pick is a 2-axis mask)."""
    I = rlens.astype(jnp.int32)[:, None]
    J = tlens.astype(jnp.int32)[:, None]
    from pbccs_tpu.ops.fwdbwd import circ_rows
    ncols = alpha.vals.shape[1]
    W = alpha.vals.shape[2]
    jcols = jnp.arange(ncols, dtype=jnp.int32)[None, :]
    at_J = (jcols == J)[:, :, None]
    rows = circ_rows(alpha.offsets, W)         # circular lane -> row
    final = jnp.sum(jnp.where(at_J & (rows == I[:, :, None]),
                              alpha.vals, 0.0), axis=(1, 2))
    ls = jnp.sum(jnp.where(jcols <= J, alpha.log_scales, 0.0), axis=1)
    return jnp.log(jnp.maximum(final, _TINY)) + ls
