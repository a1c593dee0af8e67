"""Banded Arrow pair-HMM forward/backward as fixed-shape JAX array programs.

TPU-first re-design of the reference's adaptive-banded recursor
(reference ConsensusCore/src/C++/Arrow/SimpleRecursor.cpp:62-296):

* The reference adapts the band per column by score thresholding and refills
  ("flip-flops") until alpha/beta agree.  TPU/XLA wants static shapes, so we
  use a **static band of width W** per column, centered on the read/template
  diagonal, with per-column integer offsets computed from traced lengths.
  Band adequacy is *checked* (|LL_alpha - LL_beta| <= tol, the reference's
  AlphaBetaMismatch test, SimpleRecursor.cpp:667-691) and inadequate reads are
  dropped or re-run at a wider band bucket by the host.

* The reference fills each column serially because the insertion move creates
  a first-order recurrence within the column: a(i,j) = b(i) + c(i)*a(i-1,j).
  We evaluate it as an **associative affine scan** over the band (log2(W)
  vector steps on the VPU) and `lax.scan` over template columns; everything
  vmaps over reads / mutations / ZMWs, which is where the parallelism is.

* The reference's ScaledMatrix rescales every column by its max to stay in
  natural scale (Matrix/ScaledMatrix-inl.hpp:74-123).  Same here: per-column
  max-rescale, log-scale accumulated, so float32 suffices in the inner loop.
  Dynamic-range note: float32 holds ~87 nats of in-column range below each
  column's max, so paths further below it (e.g. contiguous insert runs over
  ~20 bases) flush to zero and alpha/beta can disagree -- such reads drop at
  the mating gate, after one wider-band retry by the host (scorer.py).
  This is MORE permissive than the reference, whose adaptive band keeps
  only cells within ScoreDiff = 12.5 nats of the column max
  (SimpleRecursor.cpp:101-158) and drops the same reads through
  AlphaBetaMismatchException after 5 flip-flop refills.

Matrix convention matches the reference: (I+1) read rows x (J+1) template
columns, both endpoints pinned to Match; trans[k] are the probabilities of
moves leaving template position k.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from pbccs_tpu.models.arrow.params import (
    TRANS_BRANCH,
    TRANS_DARK,
    TRANS_MATCH,
    TRANS_STICK,
    MISMATCH_PROBABILITY,
)

_TINY = 1e-30


class BandedMatrix(NamedTuple):
    """A column-banded DP matrix in CIRCULAR lane layout.

    vals:       (Jmax+1, W) band values; vals[j, L] is matrix cell (r, j)
                for the unique in-band row r with r === L (mod W), i.e.
                r = circ_rows(offsets[j], W)[L]; rescaled so each column's
                max is 1.
    offsets:    (Jmax+1,) int32 first row of each column's band.
    log_scales: (Jmax+1,) accumulated log column scale factors.

    Why circular: cell (i, j) always lives at lane i mod W whatever the
    column's offset, so the cross-column band alignment of every DP
    recurrence is a STATIC lane rotation (roll by +-1) plus an in-band
    mask -- the per-column dynamic shift-variant select chains this
    replaced were the dominant VPU op count of the fill and mutation
    kernels and the source of the Mosaic compile blowup at long
    templates (8 variants for Arrow, 15 for the Quiver merge carry).
    Lane-permutation-invariant consumers (column max/sum reductions,
    occupancy counters, log-likelihood extraction via one-hot) are
    unchanged by construction."""

    vals: jax.Array
    offsets: jax.Array
    log_scales: jax.Array

    @property
    def width(self) -> int:
        return self.vals.shape[-1]


# The FRAMED band: what the Pallas fills write and the dense kernel, the
# edge program and the QV sweep read in place (docs/DESIGN.md "One band
# layout").  vals is (band_frame_rows(Jmax + 1), W) with column j at row
# BAND_LEAD + j; offsets and log_scales stay (Jmax + 1,).  The lead rows
# let the dense kernel reach columns p-3.. of its first positions without
# a pad, the tail rows its last positions' p+2 and the edge program's
# J+2; the row count is a multiple of both kernels' 64-row steps.  A plain
# (Jmax + 1, W) band (the XLA fills below) is the frame with no lead.
BAND_LEAD = 4


def row_major(x):
    """Pin a band-sized array to the row-major layout the Pallas kernels
    read and write.  Left to itself the TPU compiler carries a band whose
    W is not a multiple of 128 lanes with the column axis minor-most (no
    lane padding) and copies it to row-major in front of every kernel
    call and back behind it."""
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim))))


def band_frame_rows(n_cols: int) -> int:
    """Rows of the framed band of n_cols columns: the lead, the columns,
    two more for the edge program's J + 2, up to a multiple of 64."""
    return -(-(BAND_LEAD + n_cols + 2) // 64) * 64


def band_lead(bm: BandedMatrix) -> int:
    """Rows in front of column 0 (static, from the shapes)."""
    return BAND_LEAD if bm.vals.shape[-2] != bm.offsets.shape[-1] else 0


def band_columns(bm: BandedMatrix) -> BandedMatrix:
    """The plain (..., Jmax + 1, W) band of a framed one (a slice: for the
    paths off the refine loop, which index vals by column)."""
    lead = band_lead(bm)
    if not lead:
        return bm
    n = bm.offsets.shape[-1]
    return bm._replace(vals=bm.vals[..., lead: lead + n, :])


def band_frame(bm: BandedMatrix) -> BandedMatrix:
    """The framed band of a plain one (zero rows around the columns): what
    the Pallas fills make unnecessary, for fills that came from XLA."""
    if band_lead(bm):
        return bm
    n = bm.offsets.shape[-1]
    pad = [(0, 0)] * (bm.vals.ndim - 2) + [
        (BAND_LEAD, band_frame_rows(n) - BAND_LEAD - n), (0, 0)]
    return bm._replace(vals=jnp.pad(bm.vals, pad))


def band_offsets(read_len, tpl_len, n_cols: int, width: int):
    """Static-shape band layout: column j covers rows
    [o(j), o(j)+W) with o(j) centered on the diagonal i = j * I/J.

    Replaces the reference's adaptive RangeGuide/RowRange banding
    (SimpleRecursor.cpp:693-757) with a host/trace-time computable layout.
    """
    j = jnp.arange(n_cols, dtype=jnp.float32)
    center = j * (read_len.astype(jnp.float32) / jnp.maximum(tpl_len.astype(jnp.float32), 1.0))
    off = jnp.floor(center).astype(jnp.int32) - width // 2
    hi = jnp.maximum(read_len + 1 - width, 0)
    return jnp.clip(off, 0, hi)


def circ_rows(offset, width: int):
    """(..., W) absolute row of each circular lane for columns with band
    offsets `offset` (scalar or any-shape array; a trailing lane axis is
    appended): lane L holds the unique row r in [offset, offset+W) with
    r === L (mod W)."""
    offset = jnp.asarray(offset, jnp.int32)[..., None]
    L = jnp.arange(width, dtype=jnp.int32)
    q = offset % width
    return offset - q + L + jnp.where(L < q, width, 0)


def circ_roll(x, t: int):
    """Circular lane roll: y[..., L] = x[..., (L - t) mod W] (static t).
    t=+1 aligns the previous row's value under each lane (row r-1 lives at
    lane L-1); t=-1 the next row's."""
    if t == 0:
        return x
    W = x.shape[-1]
    t = t % W
    return jnp.concatenate([x[..., W - t:], x[..., : W - t]], axis=-1)


def in_band(rows, offset, width: int):
    """Mask: absolute row inside the band [offset, offset+W) of a column
    with this offset (shapes broadcast)."""
    return (rows >= offset) & (rows < offset + width)


def _affine_scan_circ(b, c, reverse: bool = False):
    """Hillis-Steele solve of v[L] = b[L] + c[L] * v[L-1] over CIRCULAR
    lanes (reverse: v[L] = b[L] + c[L] * v[L+1]).

    Correct iff the caller zeroed c at the scan's cut lane (the band's
    first row forward / last row backward): every wrapped contribution's
    cumulative c-product then contains that zero, so the circular rolls
    never leak mass across the band boundary."""
    W = b.shape[-1]
    t = -1 if reverse else 1
    d = 1
    while d < W:
        b = b + c * circ_roll(b, t * d)
        c = c * circ_roll(c, t * d)
        d *= 2
    return b


#: Slope clamp of guided_band_offsets (rows of band advance per template
#: column).  A banding-QUALITY choice, not a kernel constraint: the
#: circular-lane kernels handle arbitrary per-column advance via in-band
#: masks; the clamp just keeps re-centered bands smooth so adjacent
#: columns overlap enough to carry probability mass.
MAX_BAND_ADVANCE = 7


def guided_band_offsets(alpha_vals, alpha_offsets, read_len, tpl_len,
                        width: int, n_cols: int | None = None,
                        smooth: int = 8) -> jax.Array:
    """Re-center the band on the alignment path observed in a prior alpha
    fill: per-column centers are the band argmax rows (the posterior mode
    path), smoothed, made monotone, slope-clamped to MAX_BAND_ADVANCE, and
    pinned to the (0,0)/(I,J) corners.

    This is the TPU re-design of the reference's guide-matrix rebanding +
    alpha/beta flip-flop (reference ConsensusCore/src/C++/Arrow/
    SimpleRecursor.cpp:642-757): instead of adaptively re-thresholding the
    band per column on the host, a fixed-width band is re-laid along the
    path the previous fill found — a pure array program that runs inside
    jit.  At long templates (15 kb) the indel random-walk drifts the true
    path ~sqrt(L) rows off the straight diagonal, past W/2; one or two
    guided refills recover it (the reference's flip-flop count analogue).

    alpha_vals (ncA, W) or framed, alpha_offsets (ncA,): a prior fill's band.
    Returns (n_cols,) int32 offsets (n_cols defaults to ncA; extra columns
    repeat the last value so kernel shift/overflow math sees slope 0).
    """
    ncA = alpha_offsets.shape[0]
    n_cols = ncA if n_cols is None else n_cols
    W = width
    S = MAX_BAND_ADVANCE
    I = jnp.asarray(read_len, jnp.int32)
    J = jnp.asarray(tpl_len, jnp.int32)
    j = jnp.arange(ncA, dtype=jnp.float32)

    lane = jnp.argmax(alpha_vals, axis=-1).astype(jnp.int32)
    if lane.shape[0] != ncA:               # a framed band: its column rows
        lane = lane[BAND_LEAD: BAND_LEAD + ncA]
    q = alpha_offsets % W                  # circular layout: lane -> row
    c = (alpha_offsets - q + lane
         + jnp.where(lane < q, W, 0)).astype(jnp.float32)
    c = jnp.where(j <= J, c, I.astype(jnp.float32))
    c = jnp.minimum(c, I.astype(jnp.float32))
    if smooth:
        # boxcar mean via cumsum (edge-padded)
        k = smooth
        cp = jnp.concatenate([jnp.broadcast_to(c[0:1], (k,)), c,
                              jnp.broadcast_to(c[-1:], (k,))])
        cs = jnp.cumsum(cp)
        c = (cs[2 * k:] - jnp.concatenate([jnp.zeros(1), cs[:-2 * k - 1]])) \
            / (2 * k + 1)
    c = lax.associative_scan(jnp.maximum, c)                 # monotone
    # slope <= S: o(j) = min_{k<=j} (c(k) + S*(j-k))
    o = lax.associative_scan(jnp.minimum, c - S * j) + S * j
    # left-edge anchor: the pinned start means columns 0/1 must keep rows
    # 0/1 in band (alpha seed / EDGE_CONDITION); same envelope from (0, 0)
    o = jnp.minimum(o, 1.0 + S * jnp.maximum(j - 1.0, 0.0))
    off = jnp.clip(jnp.floor(o).astype(jnp.int32) - W // 2, 0,
                   jnp.maximum(I + 1 - W, 0))
    off = lax.associative_scan(jnp.maximum, off)             # monotone again
    if n_cols > ncA:
        off = jnp.concatenate([
            off, jnp.broadcast_to(off[-1:], (n_cols - ncA,))])
    return off[:n_cols]


def _affine_scan(b: jax.Array, c: jax.Array, reverse: bool = False) -> jax.Array:
    """Solve v[k] = b[k] + c[k] * v[k-1] (v[-1] = 0) along the last axis.

    With reverse=True solves v[k] = b[k] + c[k] * v[k+1] instead.
    """

    def combine(left, right):
        cl, bl = left
        cr, br = right
        return cl * cr, br + cr * bl

    _, v = lax.associative_scan(combine, (c, b), axis=b.ndim - 1, reverse=reverse)
    return v


def _gather_band(col_vals, col_offset, rows):
    """Read band column values at absolute `rows` (vector); 0 outside band.
    col_vals are in circular lane layout: row r lives at lane r mod W."""
    W = col_vals.shape[-1]
    ok = (rows >= col_offset) & (rows < col_offset + W)
    return jnp.where(ok, jnp.take(col_vals, rows % W, axis=-1), 0.0)


def banded_forward(read, read_len, tpl, trans, tpl_len, width: int,
                   pr_miscall: float = MISMATCH_PROBABILITY,
                   offsets=None) -> BandedMatrix:
    """Banded forward (alpha) fill.

    read: (Imax,) int8 codes (padded); read_len: scalar int32 I.
    tpl:  (Jmax,) int8 codes (padded); tpl_len:  scalar int32 J.
    trans: (Jmax, 4) natural-scale transition probs (padded with zeros).
    offsets: optional (Jmax+1,) precomputed band offsets (e.g. guided;
    see guided_band_offsets); default is the diagonal band layout.

    Returns BandedMatrix over columns 0..Jmax (column 0 is the pinned seed;
    the final pinned cell (I, J) lives in column J of the band).
    Parity: SimpleRecursor::FillAlpha (SimpleRecursor.cpp:62-181).
    """
    Imax = read.shape[0]
    Jmax = tpl.shape[0]
    W = width
    eps = pr_miscall
    em_hit, em_miss = 1.0 - eps, eps / 3.0

    I = jnp.asarray(read_len, jnp.int32)
    J = jnp.asarray(tpl_len, jnp.int32)
    if offsets is None:
        offsets = band_offsets(I, J, Jmax + 1, W)
    else:
        offsets = jnp.asarray(offsets, jnp.int32)[: Jmax + 1]

    col0 = jnp.zeros(W, jnp.float32).at[0].set(1.0)  # row 0 only: alpha(0,0)=1
    # offsets[0] is 0 by construction, so col0's band starts at row 0.

    read_i32 = read.astype(jnp.int32)
    tpl_i32 = tpl.astype(jnp.int32)

    def step(carry, j):
        prev_vals, prev_off = carry
        o = offsets[j]
        rows = circ_rows(o, W)                             # absolute row ids
        rbase = jnp.take(read_i32, jnp.clip(rows - 1, 0, Imax - 1))
        t_cur = tpl_i32[j - 1]
        t_next = tpl_i32[jnp.minimum(j, Jmax - 1)]
        tr_prev = trans[jnp.maximum(j - 2, 0)]             # moves leaving pos j-2
        tr_cur = trans[j - 1]                              # moves leaving pos j-1

        valid = (rows >= 1) & (rows <= I - 1)
        em = jnp.where(rbase == t_cur, em_hit, em_miss)

        pm1 = _gather_band(prev_vals, prev_off, rows - 1)  # alpha(i-1, j-1)
        p0 = _gather_band(prev_vals, prev_off, rows)       # alpha(i,   j-1)

        # Match factor: pinned start has no transition; row 1 only reachable
        # by match when j == 1 (SimpleRecursor.cpp:119-141 EDGE_CONDITION).
        mfac = jnp.where(
            j == 1,
            jnp.where(rows == 1, 1.0, 0.0),
            jnp.where(rows == 1, 0.0, tr_prev[TRANS_MATCH]),
        )
        b = pm1 * em * mfac
        b = b + jnp.where(j > 1, p0 * tr_prev[TRANS_DARK], 0.0)
        b = jnp.where(valid, b, 0.0)

        ins = jnp.where(rbase == t_next, tr_cur[TRANS_BRANCH], tr_cur[TRANS_STICK] / 3.0)
        # rows > o additionally cuts the circular scan at the band's first
        # row (its in-column predecessor is out of band)
        c = jnp.where(valid & (rows > 1) & (rows > o), ins, 0.0)

        col = _affine_scan_circ(b, c)

        active = j < J
        cmax = jnp.max(col)
        scale = jnp.where(active & (cmax > 0), cmax, 1.0)
        col = jnp.where(active, col / scale, 0.0)
        log_scale = jnp.log(jnp.maximum(scale, _TINY))

        new_vals = jnp.where(active, col, prev_vals)
        new_off = jnp.where(active, o, prev_off)
        return (new_vals, new_off), (col, log_scale)

    (_, _), (cols, log_scales) = lax.scan(
        step, (col0, offsets[0]), jnp.arange(1, Jmax + 1, dtype=jnp.int32)
    )

    vals = jnp.concatenate([col0[None], cols], axis=0)           # (Jmax+1, W)
    log_scales = jnp.concatenate([jnp.zeros(1), log_scales])

    # Final pinned cell alpha(I, J) = alpha(I-1, J-1) * em(read[I-1], tpl[J-1])
    # (SimpleRecursor.cpp:171-180).  Written into column J of the band.
    prev_col = vals[jnp.maximum(J - 1, 0)]
    prev_off = offsets[jnp.maximum(J - 1, 0)]
    a_prev = _gather_band(prev_col, prev_off, (I - 1)[None])[0]
    em_last = jnp.where(read_i32[jnp.clip(I - 1, 0, Imax - 1)]
                        == tpl_i32[jnp.clip(J - 1, 0, Jmax - 1)],
                        em_hit, em_miss)
    final = a_prev * em_last
    vals = vals.at[J].set(jnp.zeros(W).at[I % W].set(final))
    return BandedMatrix(vals, offsets, log_scales)


def banded_backward(read, read_len, tpl, trans, tpl_len, width: int,
                    pr_miscall: float = MISMATCH_PROBABILITY,
                    offsets=None) -> BandedMatrix:
    """Banded backward (beta) fill; mirror of banded_forward.

    Parity: SimpleRecursor::FillBeta (SimpleRecursor.cpp:185-296).
    Returns BandedMatrix over columns 0..Jmax; column J holds the pinned seed
    (beta(I, J) = 1), column 0 holds beta(0, 0) in its band at row 0.
    """
    Imax = read.shape[0]
    Jmax = tpl.shape[0]
    W = width
    eps = pr_miscall
    em_hit, em_miss = 1.0 - eps, eps / 3.0

    I = jnp.asarray(read_len, jnp.int32)
    J = jnp.asarray(tpl_len, jnp.int32)
    if offsets is None:
        offsets = band_offsets(I, J, Jmax + 1, W)
    else:
        offsets = jnp.asarray(offsets, jnp.int32)[: Jmax + 1]

    read_i32 = read.astype(jnp.int32)
    tpl_i32 = tpl.astype(jnp.int32)

    seed = jnp.zeros(W, jnp.float32)
    # beta(I, J) = 1 at column J, band offset offsets[J].

    def step(carry, j):
        prev_vals, prev_off = carry  # column j+1 of beta (or seed when j+1==J)
        # Splice in the seed column when we reach the last interior column.
        at_seed = j == J - 1
        seed_col = seed.at[I % W].set(1.0)
        prev_vals = jnp.where(at_seed, seed_col, prev_vals)
        prev_off = jnp.where(at_seed, offsets[J], prev_off)

        o = offsets[j]
        rows = circ_rows(o, W)
        rnext = jnp.take(read_i32, jnp.clip(rows, 0, Imax - 1))  # read[i] = base i+1
        t_next = tpl_i32[jnp.minimum(j, Jmax - 1)]               # base of column j+1
        tr_cur = trans[j - 1]                                    # moves leaving pos j-1

        valid = (rows >= 1) & (rows <= I - 1)
        nxt_match = rnext == t_next
        em = jnp.where(nxt_match, em_hit, em_miss)

        n11 = _gather_band(prev_vals, prev_off, rows + 1)  # beta(i+1, j+1)
        n01 = _gather_band(prev_vals, prev_off, rows)      # beta(i,   j+1)

        mfac = jnp.where(
            rows < I - 1,
            tr_cur[TRANS_MATCH],
            jnp.where((rows == I - 1) & (j == J - 1), 1.0, 0.0),
        )
        b = n11 * em * mfac
        b = b + jnp.where((j >= 1) & (j < J - 1), n01 * tr_cur[TRANS_DARK], 0.0)
        b = jnp.where(valid, b, 0.0)

        ins = jnp.where(nxt_match, tr_cur[TRANS_BRANCH], tr_cur[TRANS_STICK] / 3.0)
        # rows < o + W - 1 cuts the reverse circular scan at the band's
        # last row (its in-column successor is out of band)
        c = jnp.where(valid & (rows < I - 1) & (rows < o + W - 1), ins, 0.0)

        col = _affine_scan_circ(b, c, reverse=True)

        active = (j >= 1) & (j < J)
        cmax = jnp.max(col)
        scale = jnp.where(active & (cmax > 0), cmax, 1.0)
        col = jnp.where(active, col / scale, 0.0)
        log_scale = jnp.log(jnp.maximum(scale, _TINY))

        new_vals = jnp.where(active, col, prev_vals)
        new_off = jnp.where(active, o, prev_off)
        return (new_vals, new_off), (col, log_scale)

    (_, _), (cols_rev, ls_rev) = lax.scan(
        step, (seed, offsets[Jmax]),
        jnp.arange(Jmax - 1, 0, -1, dtype=jnp.int32),
    )
    cols = cols_rev[::-1]            # columns 1..Jmax-1
    log_scales_mid = ls_rev[::-1]

    # Column J seed, then column 0 terminal from the *assembled* column 1
    # (for J == 1 column 1 is the seed itself).
    seedJ = jnp.zeros(W, jnp.float32).at[I % W].set(1.0)
    vals = jnp.concatenate([jnp.zeros((1, W)), cols], axis=0)  # cols 0..Jmax-1
    vals = jnp.concatenate([vals, jnp.zeros((1, W))], axis=0)
    vals = vals.at[J].set(seedJ)
    b11 = _gather_band(vals[1], offsets[1], jnp.asarray([1], jnp.int32))[0]
    em0 = jnp.where(read_i32[0] == tpl_i32[0], em_hit, em_miss)
    beta00 = b11 * em0
    vals = vals.at[0].set(jnp.zeros(W, jnp.float32).at[0].set(beta00))
    log_scales = jnp.concatenate([jnp.zeros(1), log_scales_mid, jnp.zeros(1)])
    return BandedMatrix(vals, offsets, log_scales)


def forward_loglik(alpha: BandedMatrix, read_len, tpl_len) -> jax.Array:
    """LL = log(alpha(I, J)) + sum of column log-scales (MutationScorer::Score
    semantics, MutationScorer.cpp:93-97, via the alpha matrix)."""
    J = jnp.asarray(tpl_len, jnp.int32)
    I = jnp.asarray(read_len, jnp.int32)
    final = _gather_band(alpha.vals[J], alpha.offsets[J], I[None])[0]
    n_cols = alpha.vals.shape[0]
    mask = jnp.arange(n_cols) <= J
    return jnp.log(jnp.maximum(final, _TINY)) + jnp.sum(jnp.where(mask, alpha.log_scales, 0.0))


def backward_loglik(beta: BandedMatrix, tpl_len) -> jax.Array:
    J = jnp.asarray(tpl_len, jnp.int32)
    b00 = beta.vals[0, 0]
    n_cols = beta.vals.shape[0]
    mask = jnp.arange(n_cols) <= J
    return jnp.log(jnp.maximum(b00, _TINY)) + jnp.sum(jnp.where(mask, beta.log_scales, 0.0))
