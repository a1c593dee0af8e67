"""Batched mutation scoring on device: the TPU re-design of the reference's
Extend+Link fast path.

The reference scores one candidate mutation at a time per read by recomputing
~2 DP columns next to the mutation ("ExtendAlpha") and stitching them to the
saved backward matrix ("LinkAlphaBeta"); see
reference ConsensusCore/src/C++/Arrow/SimpleRecursor.cpp:373-487 (ExtendAlpha),
:306-357 (LinkAlphaBeta) and MutationScorer.cpp:165-266 (dispatch).

Here the same algebra is evaluated as one batched array program over the
whole (mutation x read) grid: every interior mutation is exactly two banded
affine scans plus one band dot-product, so the grid vmaps cleanly onto the
VPU.  Mutations too close to a template end (the reference's atBegin/atEnd
special cases) are scored by a full banded refill of the mutated window --
they are O(template ends), not O(template length).

Virtual-mutation semantics (no mutated template is ever materialized for the
interior path) mirror TemplateParameterPair::ApplyVirtualMutation /
GetTemplatePosition (reference TemplateParameterPair.cpp:70-140, .hpp:88-118):
a mutation patches (base, transition) at virtual positions p-1 and p and
index-shifts everything beyond p.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from pbccs_tpu.models.arrow.params import (
    TRANS_BRANCH,
    TRANS_DARK,
    TRANS_MATCH,
    TRANS_STICK,
    MISMATCH_PROBABILITY,
    context_index,
)
from pbccs_tpu.ops.fwdbwd import (BandedMatrix, _affine_scan_circ,
                                  _gather_band, band_columns,
                                  banded_forward, circ_roll, circ_rows,
                                  forward_loglik, in_band)
from pbccs_tpu.ops.fwdbwd_pallas import window_rows_circ

SUB, INS, DEL = 0, 1, 2
_TINY = 1e-30


class MutationPatch(NamedTuple):
    """Virtual-mutation patch on one oriented full template: new (base,
    transition) values at virtual positions p-1 and p, plus the index shift
    for positions beyond p."""

    bases: jax.Array    # (2,) int32: virtual bases at p-1, p
    trans: jax.Array    # (2, 4) transition rows at p-1, p
    shift: jax.Array    # scalar int32: index offset for idx > p (0/+1/-1)


def make_patch(tpl, trans, trans_table, tpl_len, pos, mtype, new_base) -> MutationPatch:
    """Compute the virtual-mutation patch on a full oriented template.

    tpl: (L,) int32 codes; trans: (L, 4); trans_table: (8, 4); tpl_len: L.
    pos/mtype/new_base: the (oriented) mutation.
    Parity: ApplyVirtualMutation (TemplateParameterPair.cpp:70-140).
    """
    L = jnp.asarray(tpl_len, jnp.int32)
    Lm = tpl.shape[0]
    get = lambda i: tpl[jnp.clip(i, 0, Lm - 1)]
    gett = lambda i: trans[jnp.clip(i, 0, Lm - 1)]
    ctx_of = lambda a, b: trans_table[jnp.clip(context_index(a, b), 0, 7)]

    prev_b = get(pos - 1)
    next_b = get(pos + 1)
    cur_b = get(pos)
    nb = jnp.asarray(new_base, jnp.int32)
    zeros4 = jnp.zeros(4, trans.dtype)

    # SUBSTITUTION
    sub_b = jnp.stack([prev_b, nb])
    sub_t = jnp.stack([
        jnp.where(pos > 0, ctx_of(prev_b, nb), zeros4),
        jnp.where(pos + 1 < L, ctx_of(nb, next_b), zeros4),
    ])
    # DELETION (single base); org_last = L-1
    org_last = L - 1
    del_b = jnp.stack([prev_b, next_b])
    mid = (pos > 0) & (pos < org_last)
    del_t = jnp.stack([
        jnp.where(mid, ctx_of(prev_b, next_b), zeros4),
        jnp.where(pos < org_last, gett(pos + 1), zeros4),
    ])
    # INSERTION before pos
    ins_b = jnp.stack([prev_b, nb])
    ins_t = jnp.stack([
        jnp.where(pos > 0, ctx_of(prev_b, nb), zeros4),
        jnp.where(pos < L, ctx_of(nb, cur_b), zeros4),
    ])

    mtype = jnp.asarray(mtype, jnp.int32)
    bases = jnp.select([mtype == SUB, mtype == INS], [sub_b, ins_b], del_b)
    transp = jnp.select([mtype == SUB, mtype == INS], [sub_t, ins_t], del_t)
    shift = jnp.select([mtype == SUB, mtype == INS], [jnp.int32(0), jnp.int32(-1)], jnp.int32(1))
    return MutationPatch(bases, transp, shift)


def _virtual_base(win_tpl, p, patch: MutationPatch, idx):
    """Virtual-template base at window index idx (int32)."""
    Jm = win_tpl.shape[0]
    src = idx + jnp.where(idx > p, patch.shift, 0)
    base = win_tpl[jnp.clip(src, 0, Jm - 1)]
    base = jnp.where(idx == p - 1, patch.bases[0], base)
    base = jnp.where(idx == p, patch.bases[1], base)
    return base


def _virtual_trans(win_trans, p, patch: MutationPatch, idx):
    Jm = win_trans.shape[0]
    idx = jnp.asarray(idx)
    src = idx + jnp.where(idx > p, patch.shift, 0)
    t = win_trans[jnp.clip(src, 0, Jm - 1)]
    cond0 = jnp.expand_dims(idx == p - 1, -1) if idx.ndim else (idx == p - 1)
    cond1 = jnp.expand_dims(idx == p, -1) if idx.ndim else (idx == p)
    t = jnp.where(cond0, patch.trans[0], t)
    t = jnp.where(cond1, patch.trans[1], t)
    return t


def extend_link_score(read, read_len, win_tpl, win_trans, win_len,
                      alpha: BandedMatrix, beta: BandedMatrix,
                      alpha_prefix, beta_suffix,
                      p, mtype, patch: MutationPatch,
                      pr_miscall: float = MISMATCH_PROBABILITY):
    """Absolute log-likelihood of this read under the virtually mutated
    window template, for an *interior* mutation (3 <= p, end <= J-3).

    read: (Imax,) int32; win_tpl: (Jmax,) int32; win_trans: (Jmax, 4).
    alpha/beta: saved banded matrices on the unmutated window.
    alpha_prefix[k] = sum of alpha log-scales for columns < k.
    beta_suffix[k]  = sum of beta  log-scales for columns >= k.
    p: oriented window-frame mutation start; mtype: SUB/INS/DEL.

    Parity: MutationScorer::ScoreMutation mid-template branch
    (MutationScorer.cpp:191-206) = ExtendAlpha(2 cols) + LinkAlphaBeta.
    """
    alpha, beta = band_columns(alpha), band_columns(beta)
    W = alpha.width
    Imax = read.shape[0]
    eps = pr_miscall
    em_hit, em_miss = 1.0 - eps, eps / 3.0

    I = jnp.asarray(read_len, jnp.int32)
    J = jnp.asarray(win_len, jnp.int32)
    ld = jnp.where(mtype == INS, 1, jnp.where(mtype == DEL, -1, 0))
    mend = p + jnp.where(mtype == INS, 0, 1)

    s = jnp.where(mtype == DEL, p - 1, p)   # first recomputed DP column
    max_left = J + ld                        # virtual template length
    max_down = I

    beta_link_col = 1 + mend
    abs_col = beta_link_col + ld

    vb = lambda i: _virtual_base(win_tpl, p, patch, i)
    vt = lambda i: _virtual_trans(win_trans, p, patch, i)

    def fill_col(prev_vals, prev_off, j):
        """One ExtendAlpha column at virtual DP column j (template pos j-1)."""
        o = alpha.offsets[jnp.clip(j, 0, alpha.offsets.shape[0] - 1)]
        rows = circ_rows(o, W)
        rbase = jnp.take(read, jnp.clip(rows - 1, 0, Imax - 1))
        cur_b = vb(j - 1)
        prev_tr = vt(j - 2)
        cur_tr = vt(j - 1)
        next_b = vb(j)

        in_read = (rows >= 1) & (rows <= I)
        em = jnp.where(rbase == cur_b, em_hit, em_miss)
        pm1 = _gather_band(prev_vals, prev_off, rows - 1)
        p0 = _gather_band(prev_vals, prev_off, rows)

        generic = (rows < max_down) & (j < max_left)
        pinned = (rows == max_down) & (j == max_left)
        mfac = jnp.where(generic, prev_tr[TRANS_MATCH], jnp.where(pinned, 1.0, 0.0))
        # (1,1) start case never occurs for interior mutations (s >= 2).
        b = pm1 * em * mfac
        b = b + jnp.where((j > 1) & (j < max_left) & (rows != max_down),
                          p0 * prev_tr[TRANS_DARK], 0.0)
        b = jnp.where(in_read, b, 0.0)

        ins_em = jnp.where(rbase == next_b, cur_tr[TRANS_BRANCH], cur_tr[TRANS_STICK] / 3.0)
        c = jnp.where(in_read & (rows > 1) & (rows < max_down)
                      & (j != max_left) & (rows > o), ins_em, 0.0)
        return _affine_scan_circ(b, c), o

    a_prev = alpha.vals[jnp.clip(s - 1, 0, alpha.vals.shape[0] - 1)]
    a_prev_off = alpha.offsets[jnp.clip(s - 1, 0, alpha.offsets.shape[0] - 1)]
    ext0, o0 = fill_col(a_prev, a_prev_off, s)
    ext1, o1 = fill_col(ext0, o0, s + 1)

    # LinkAlphaBeta (SimpleRecursor.cpp:306-357): stitch ext1 (virtual column
    # s+1 = absolute link col - 1) to beta columns beta_link_col / +1.
    rows = circ_rows(o1, W)                             # row ids i
    link_tr = vt(abs_col - 2)
    link_b = vb(abs_col - 1)
    rbase_next = jnp.take(read, jnp.clip(rows, 0, Imax - 1))  # read base i+1
    em_link = jnp.where(rbase_next == link_b, em_hit, em_miss)

    bcol_vals = beta.vals[jnp.clip(beta_link_col, 0, beta.vals.shape[0] - 1)]
    bcol_off = beta.offsets[jnp.clip(beta_link_col, 0, beta.offsets.shape[0] - 1)]
    beta_ip1 = _gather_band(bcol_vals, bcol_off, rows + 1)
    beta_i = _gather_band(bcol_vals, bcol_off, rows)

    match_term = jnp.where(rows < I, ext1 * link_tr[TRANS_MATCH] * em_link * beta_ip1, 0.0)
    del_term = ext1 * link_tr[TRANS_DARK] * beta_i
    v = jnp.sum(match_term + del_term)

    n_cols = alpha.log_scales.shape[0]
    apre = alpha_prefix[jnp.clip(s, 0, n_cols)]
    bsuf = beta_suffix[jnp.clip(beta_link_col, 0, n_cols)]
    return jnp.log(jnp.maximum(v, _TINY)) + apre + bsuf


def mutated_window(win_tpl, win_trans, win_len, p, mtype, patch: MutationPatch):
    """Materialize the mutated window (bases, trans, new_len) for the
    full-refill path (edge mutations)."""
    Jm = win_tpl.shape[0]
    idx = jnp.arange(Jm, dtype=jnp.int32)
    bases = _virtual_base(win_tpl, p, patch, idx)
    trans = _virtual_trans(win_trans, p, patch, idx)
    ld = jnp.where(mtype == INS, 1, jnp.where(mtype == DEL, -1, 0))
    new_len = win_len + ld
    valid = idx < new_len
    bases = jnp.where(valid, bases, 4)
    trans = jnp.where(valid[:, None] & (idx[:, None] < new_len - 1), trans, 0.0)
    return bases.astype(jnp.int8), trans, new_len


def full_refill_score(read, read_len, win_tpl, win_trans, win_len,
                      p, mtype, patch: MutationPatch, width: int,
                      pr_miscall: float = MISMATCH_PROBABILITY):
    """Absolute LL of the mutated window via a full banded forward — the
    reference's atBegin/atEnd/tiny-template branches (MutationScorer.cpp:
    208-258) unified into one batched fallback."""
    bases, trans, new_len = mutated_window(win_tpl, win_trans, win_len, p, mtype, patch)
    alpha = banded_forward(read.astype(jnp.int8), read_len, bases, trans, new_len,
                           width, pr_miscall)
    return forward_loglik(alpha, read_len, new_len)


def scale_prefix(log_scales):
    """alpha_prefix[k] = sum(log_scales[:k]); shape (n+1,)."""
    return jnp.concatenate([jnp.zeros(1), jnp.cumsum(log_scales)])


def scale_suffix(log_scales):
    """beta_suffix[k] = sum(log_scales[k:]); shape (n+1,)."""
    return jnp.concatenate([jnp.cumsum(log_scales[::-1])[::-1], jnp.zeros(1)])


# --------------------------------------------------------------------------
# TPU-fast batched interior scoring (gather-free)
#
# jnp.take / vmapped dynamic_slice with runtime indices lower to the TPU
# scalar core (measured ~50x slower than the arithmetic they feed).  The
# batched path below reformulates every lookup in extend_link_score as
# either a one-hot matmul row-select (MXU; exact, since one-hot rows pick a
# single f32 value) or a bounded-range shift-variant select on the band
# axis (VPU).
# --------------------------------------------------------------------------


def _row_select(idx, src):
    """sel[m] = src[clip(idx[m], 0, n-1)] as a one-hot matmul.

    idx: (M,) int; src: (n, K) -> (M, K) f32 (exact: one-hot rows pick a
    single element, f32 * 1.0 sums of one term)."""
    n = src.shape[0]
    oh = (jnp.clip(idx, 0, n - 1)[:, None] ==
          jnp.arange(n, dtype=jnp.int32)[None, :]).astype(jnp.float32)
    # HIGHEST precision is load-bearing: the default TPU f32 dot truncates
    # operands to bf16, which corrupts selected values (e.g. a -38.09 scale
    # prefix picks up ~0.1 of error -- enough to flip mutation decisions)
    return jax.lax.dot(oh, src.astype(jnp.float32),
                       preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)

# virtual-template neighborhood half-widths: the interior scorer looks up
# virtual positions p-3..p+3; the edge scorer's refill-from-begin needs p-4..p+4
_NB_INTERIOR = 7
_NB_EDGE = 11


def _neighborhoods(win_tpl_f32, win_trans, nb: int):
    """Per-column neighborhood matrices: nb_tpl[j, c] = win_tpl[clip(j+c-nb//2)],
    nb_trans[j, c, :] = win_trans[clip(j+c-nb//2)]; static shifts only."""
    Jm = win_tpl_f32.shape[0]
    cols_t, cols_r = [], []
    for c in range(nb):
        t = c - nb // 2
        idx_lo, idx_hi = max(0, -t), Jm - max(0, t)
        head = max(0, -t)
        tail = max(0, t)
        tpl_sh = jnp.concatenate([
            jnp.broadcast_to(win_tpl_f32[0:1], (head,)),
            win_tpl_f32[max(0, t): Jm + min(0, t)],
            jnp.broadcast_to(win_tpl_f32[Jm - 1:], (tail,)),
        ])
        tr_sh = jnp.concatenate([
            jnp.broadcast_to(win_trans[0:1], (head, 4)),
            win_trans[max(0, t): Jm + min(0, t)],
            jnp.broadcast_to(win_trans[Jm - 1:], (tail, 4)),
        ], axis=0)
        cols_t.append(tpl_sh)
        cols_r.append(tr_sh)
    return jnp.stack(cols_t, axis=1), jnp.stack(cols_r, axis=1)


def _virtual_lookup(win_tpl, win_trans, p, patch_bases, patch_trans,
                    patch_shift, nb: int):
    """Build the (vb, vt) virtual-template lookup closures shared by the
    interior and edge scorers: vb(c)/vt(c) return the base / transition row
    at virtual window index p + c (c in [-(nb//2)+1, nb//2-1]), with the
    mutation's patched values at p-1 and p and the index shift beyond p
    (TemplateParameterPair::GetTemplatePosition semantics)."""
    nbh = nb // 2
    nb_tpl, nb_trans = _neighborhoods(win_tpl.astype(jnp.float32),
                                      win_trans, nb)
    sel_p = _row_select(p, jnp.concatenate(
        [nb_tpl, nb_trans.reshape(nb_tpl.shape[0], nb * 4)], axis=1))
    nbt = sel_p[:, :nb]
    nbr = sel_p[:, nb:].reshape(-1, nb, 4)
    pb0 = patch_bases[:, 0].astype(jnp.float32)
    pb1 = patch_bases[:, 1].astype(jnp.float32)

    def vb(c):
        c = jnp.broadcast_to(jnp.asarray(c, jnp.int32), p.shape)
        col = jnp.clip(c + nbh + jnp.where(c > 0, patch_shift, 0), 0, nb - 1)
        raw = jnp.sum(jnp.where(col[:, None] == jnp.arange(nb), nbt, 0.0),
                      axis=1)
        return jnp.where(c == -1, pb0, jnp.where(c == 0, pb1, raw))

    def vt(c):
        c = jnp.broadcast_to(jnp.asarray(c, jnp.int32), p.shape)
        col = jnp.clip(c + nbh + jnp.where(c > 0, patch_shift, 0), 0, nb - 1)
        raw = jnp.sum(jnp.where((col[:, None] == jnp.arange(nb))[:, :, None],
                                nbr, 0.0), axis=1)
        raw = jnp.where((c == -1)[:, None], patch_trans[:, 0], raw)
        return jnp.where((c == 0)[:, None], patch_trans[:, 1], raw)

    return vb, vt


def _circ_rows_batch(o, W: int):
    """(M, W) absolute rows of each circular lane for per-row offsets o."""
    return circ_rows(o, W)


def _in_band(rows, o, W: int):
    """(M, W) mask: row in the band [o, o+W) of a column with offset o."""
    return in_band(rows, o[:, None], W)


def _ext_col(prev_vals, o_prev, o_col, rbase_row, jcol, cur_b, next_b,
             prev_tr, cur_tr, *, I, max_left, hit, em_miss, W):
    """One batched virtual-template DP column (the ExtendAlpha column fill of
    the gather-free scorers): solves the within-column insertion recurrence
    over the band for every mutation row at virtual DP column `jcol`.

    prev_vals: (M, W) previous virtual column in circular lane layout;
    o_prev / o_col: (M,) band offsets of the previous / this column;
    rbase_row / cur_b / next_b / prev_tr / cur_tr: per-mutation
    read/template context.  Handles the j == 1 start column (reachable
    only by the pinned initial match, reference SimpleRecursor.cpp:
    119-141) and the pinned (I, J) corner.

    Circular layout makes the cross-column band alignment a static lane
    roll + in-band mask for ANY offset delta -- the bounded shift-variant
    selects this replaced capped the delta at 7 rows/column."""
    rows = _circ_rows_batch(o_col, W)
    in_read = (rows >= 1) & (rows <= I)
    em = jnp.where(rbase_row == cur_b[:, None], hit, em_miss)
    pm1 = jnp.where(_in_band(rows - 1, o_prev, W),
                    circ_roll(prev_vals, 1), 0.0)
    p0 = jnp.where(_in_band(rows, o_prev, W), prev_vals, 0.0)

    generic = (rows < I) & (jcol < max_left)[:, None]
    pinned = (rows == I) & (jcol == max_left)[:, None]
    mfac = jnp.where(generic, prev_tr[:, TRANS_MATCH][:, None],
                     jnp.where(pinned, 1.0, 0.0))
    mfac = jnp.where((jcol == 1)[:, None],
                     jnp.where(rows == 1, 1.0, 0.0), mfac)
    b = pm1 * em * mfac
    b = b + jnp.where(((jcol > 1) & (jcol < max_left))[:, None]
                      & (rows != I),
                      p0 * prev_tr[:, TRANS_DARK][:, None], 0.0)
    b = jnp.where(in_read, b, 0.0)

    ins_em = jnp.where(rbase_row == next_b[:, None],
                       cur_tr[:, TRANS_BRANCH][:, None],
                       cur_tr[:, TRANS_STICK][:, None] / 3.0)
    c = jnp.where(in_read & (rows > 1) & (rows < I)
                  & (jcol != max_left)[:, None]
                  & (rows > o_col[:, None]), ins_em, 0.0)
    return _affine_scan_circ(b, c)


def interior_scores_fast(read, read_len, win_tpl, win_trans, win_len,
                         alpha: BandedMatrix, beta: BandedMatrix,
                         alpha_prefix, beta_suffix,
                         p, mtype, patch_bases, patch_trans, patch_shift,
                         pr_miscall: float = MISMATCH_PROBABILITY):
    """(M,) absolute mutated-template log-likelihoods of one read for
    *interior* mutations; gather-free equivalent of
    vmap(extend_link_score) over the mutation axis.

    read: (Imax,) int32; p/mtype: (M,) oriented window-frame mutations;
    patch_*: (M, 2), (M, 2, 4), (M,) oriented virtual-mutation patches.
    """
    alpha, beta = band_columns(alpha), band_columns(beta)
    W = alpha.width
    nc = alpha.vals.shape[0]
    eps = pr_miscall
    hit, em_miss = 1.0 - eps, eps / 3.0

    I = jnp.asarray(read_len, jnp.int32)
    J = jnp.asarray(win_len, jnp.int32)
    ld = jnp.where(mtype == INS, 1, jnp.where(mtype == DEL, -1, 0))
    mend = p + jnp.where(mtype == INS, 0, 1)
    s = jnp.where(mtype == DEL, p - 1, p)
    max_left = J + ld
    blc = 1 + mend                       # beta link column
    abs_col = blc + ld

    # ---- read windows per column (MXU im2col, circular lanes) ----------
    read_f = read.astype(jnp.float32)
    offs = alpha.offsets
    # base codes 0..4 are bf16-exact, so the fast bf16 matmul path is safe
    rnext_win = window_rows_circ(read_f, offs, W)            # read[row(L)]
    rbase_win = window_rows_circ(
        jnp.concatenate([read_f[0:1], read_f]), offs, W)     # read[row(L)-1]

    # ---- per-mutation row-selects (one matmul per index array) ---------
    offs_f = offs.astype(jnp.float32)[:, None]
    sel_sm1 = _row_select(s - 1, jnp.concatenate([alpha.vals, offs_f], axis=1))
    A_prev, o_sm1 = sel_sm1[:, :W], sel_sm1[:, W].astype(jnp.int32)

    apre_col = alpha_prefix[:nc][:, None]
    sel_s = _row_select(s, jnp.concatenate([rbase_win, offs_f, apre_col], axis=1))
    rb_s, o_s, apre_s = sel_s[:, :W], sel_s[:, W].astype(jnp.int32), sel_s[:, W + 1]

    sel_s1 = _row_select(s + 1, jnp.concatenate(
        [rbase_win, rnext_win, offs_f], axis=1))
    rb_s1 = sel_s1[:, :W]
    rn_s1 = sel_s1[:, W: 2 * W]
    o_s1 = sel_s1[:, 2 * W].astype(jnp.int32)

    boffs_f = beta.offsets.astype(jnp.float32)[:, None]
    bsuf_col = beta_suffix[:nc][:, None]
    sel_b = _row_select(blc, jnp.concatenate([beta.vals, boffs_f, bsuf_col], axis=1))
    B_col, o_b, bsuf_b = sel_b[:, :W], sel_b[:, W].astype(jnp.int32), sel_b[:, W + 1]

    vb, vt = _virtual_lookup(win_tpl, win_trans, p, patch_bases, patch_trans,
                             patch_shift, _NB_INTERIOR)
    one_col = functools.partial(_ext_col, I=I, max_left=max_left,
                                hit=hit, em_miss=em_miss, W=W)

    c_sm1 = s - 1 - p
    c_s = s - p
    c_s1 = s + 1 - p
    ext0 = one_col(A_prev, o_sm1, o_s, rb_s, s,
                   vb(c_sm1), vb(c_s), vt(c_sm1 - 1), vt(c_sm1))
    ext1 = one_col(ext0, o_s, o_s1, rb_s1, s + 1,
                   vb(c_s), vb(c_s1), vt(c_s - 1), vt(c_s))

    # LinkAlphaBeta
    rows = _circ_rows_batch(o_s1, W)
    link_tr = vt(abs_col - 2 - p)
    link_b = vb(abs_col - 1 - p)
    em_link = jnp.where(rn_s1 == link_b[:, None], hit, em_miss)
    beta_ip1 = jnp.where(_in_band(rows + 1, o_b, W),
                         circ_roll(B_col, -1), 0.0)
    beta_i = jnp.where(_in_band(rows, o_b, W), B_col, 0.0)
    match_term = jnp.where(rows < I, ext1 * link_tr[:, TRANS_MATCH][:, None]
                           * em_link * beta_ip1, 0.0)
    del_term = ext1 * link_tr[:, TRANS_DARK][:, None] * beta_i
    v = jnp.sum(match_term + del_term, axis=1)
    return jnp.log(jnp.maximum(v, _TINY)) + apre_s + bsuf_b


def interior_read_scores_fast(read, rlen, strand, ts, te, win_tpl, win_trans,
                              wl, alpha: BandedMatrix, beta: BandedMatrix,
                              apre, bsuf, mpos_f, mend_f, mtype,
                              patches_f: MutationPatch, patches_r: MutationPatch):
    """(M,) absolute mutated-template LLs of one read: orients the
    forward-frame mutations into the read's window frame, then runs the
    gather-free batched interior scorer.  Drop-in for
    vmap(extend_link_score)-based interior_read_scores."""
    p = jnp.where(strand == 0, mpos_f - ts, te - mend_f)
    fwd = strand == 0
    pb = jnp.where(fwd, patches_f.bases, patches_r.bases)
    pt = jnp.where(fwd, patches_f.trans, patches_r.trans)
    ps = jnp.where(fwd, patches_f.shift, patches_r.shift)
    return interior_scores_fast(read.astype(jnp.int32), rlen,
                                win_tpl.astype(jnp.int32), win_trans, wl,
                                alpha, beta, apre, bsuf,
                                p, mtype, pb, pt, ps)


def edge_scores_fast(read, read_len, win_tpl, win_trans, win_len,
                     alpha: BandedMatrix, beta: BandedMatrix,
                     alpha_prefix, beta_suffix,
                     p, mtype, patch_bases, patch_trans, patch_shift,
                     pr_miscall: float = MISMATCH_PROBABILITY):
    """(M,) absolute mutated-template log-likelihoods of one read for
    mutations near a window boundary — the gather-free batched form of the
    reference's extend-from-begin / extend-to-end specializations
    (MutationScorer.cpp:208-231), which the full-refill fallback previously
    served at O(window) cost per pair.

    near-begin (p <= 2):  refill virtual DP columns 1..4 from the pinned
        start column, then LinkAlphaBeta at virtual column 5 (old-frame
        column 5 - ld) against the saved beta.
    near-end (p >= 3, caller guarantees the mutation end is within 1 of the
        window end):  extend saved alpha columns s..s+2 through the pinned
        (I, J') corner; LL = log corner + alpha scale prefix.

    Caller guarantees win_len >= 8, so the two regimes cannot overlap; tiny
    windows stay on the full-refill path.
    """
    alpha, beta = band_columns(alpha), band_columns(beta)
    W = alpha.width
    nc = alpha.vals.shape[0]
    eps = pr_miscall
    hit, em_miss = 1.0 - eps, eps / 3.0

    I = jnp.asarray(read_len, jnp.int32)
    J = jnp.asarray(win_len, jnp.int32)
    ld = jnp.where(mtype == INS, 1, jnp.where(mtype == DEL, -1, 0))
    s = jnp.where(mtype == DEL, p - 1, p)
    max_left = J + ld
    is_nb = p <= 2

    read_f = read.astype(jnp.float32)
    offs = alpha.offsets
    rnext_win = window_rows_circ(read_f, offs, W)            # read[row(L)]
    rbase_win = window_rows_circ(
        jnp.concatenate([read_f[0:1], read_f]), offs, W)     # read[row(L)-1]

    vb, vt = _virtual_lookup(win_tpl, win_trans, p, patch_bases, patch_trans,
                             patch_shift, _NB_EDGE)
    one_col = functools.partial(_ext_col, I=I, max_left=max_left,
                                hit=hit, em_miss=em_miss, W=W)
    M = p.shape[0]
    karange = jnp.arange(W, dtype=jnp.int32)[None, :]

    # ---------------------------------------------------- near-begin branch
    seed = jnp.zeros((M, W), jnp.float32).at[:, 0].set(1.0)   # alpha(0, 0)=1
    ext = seed
    o_prev = jnp.zeros((), jnp.int32)
    for j in range(1, 5):
        o_j = offs[j]
        ext = one_col(ext, jnp.broadcast_to(o_prev, (M,)),
                      jnp.broadcast_to(o_j, (M,)),
                      jnp.broadcast_to(rbase_win[j], (M, W)),
                      jnp.full((M,), j, jnp.int32),
                      vb(j - 1 - p), vb(j - p), vt(j - 2 - p), vt(j - 1 - p))
        o_prev = o_j

    blc_nb = 5 - ld                                          # old-frame col
    boffs_f = beta.offsets.astype(jnp.float32)[:, None]
    bsuf_col = beta_suffix[:nc][:, None]
    sel_b = _row_select(blc_nb, jnp.concatenate(
        [beta.vals, boffs_f, bsuf_col], axis=1))
    B_col, o_b = sel_b[:, :W], sel_b[:, W].astype(jnp.int32)
    bsuf_b = sel_b[:, W + 1]

    rows4 = _circ_rows_batch(jnp.broadcast_to(offs[4], (M,)), W)
    link_tr = vt(3 - p)
    link_b = vb(4 - p)
    em_link = jnp.where(jnp.broadcast_to(rnext_win[4], (M, W)) == link_b[:, None],
                        hit, em_miss)
    beta_ip1 = jnp.where(_in_band(rows4 + 1, o_b, W),
                         circ_roll(B_col, -1), 0.0)
    beta_i = jnp.where(_in_band(rows4, o_b, W), B_col, 0.0)
    match_term = jnp.where(rows4 < I, ext * link_tr[:, TRANS_MATCH][:, None]
                           * em_link * beta_ip1, 0.0)
    del_term = ext * link_tr[:, TRANS_DARK][:, None] * beta_i
    v_nb = jnp.sum(match_term + del_term, axis=1)
    score_nb = jnp.log(jnp.maximum(v_nb, _TINY)) + bsuf_b

    # ------------------------------------------------------ near-end branch
    offs_f = offs.astype(jnp.float32)[:, None]
    sel_sm1 = _row_select(s - 1, jnp.concatenate([alpha.vals, offs_f], axis=1))
    A_prev, o_sm1 = sel_sm1[:, :W], sel_sm1[:, W].astype(jnp.int32)
    apre_col = alpha_prefix[:nc][:, None]
    sel_s = _row_select(s, jnp.concatenate([rbase_win, offs_f, apre_col], axis=1))
    rb_s, o_s, apre_s = sel_s[:, :W], sel_s[:, W].astype(jnp.int32), sel_s[:, W + 1]
    sel_s1 = _row_select(s + 1, jnp.concatenate([rbase_win, offs_f], axis=1))
    rb_s1, o_s1 = sel_s1[:, :W], sel_s1[:, W].astype(jnp.int32)
    sel_s2 = _row_select(s + 2, jnp.concatenate([rbase_win, offs_f], axis=1))
    rb_s2, o_s2 = sel_s2[:, :W], sel_s2[:, W].astype(jnp.int32)

    c0 = s - p
    ext0 = one_col(A_prev, o_sm1, o_s, rb_s, s,
                   vb(c0 - 1), vb(c0), vt(c0 - 2), vt(c0 - 1))
    ext1 = one_col(ext0, o_s, o_s1, rb_s1, s + 1,
                   vb(c0), vb(c0 + 1), vt(c0 - 1), vt(c0))
    ext2 = one_col(ext1, o_s1, o_s2, rb_s2, s + 2,
                   vb(c0 + 1), vb(c0 + 2), vt(c0), vt(c0 + 1))

    kstar = max_left - s                                     # 1 or 2
    corner_vals = jnp.where((kstar == 1)[:, None], ext1, ext2)
    o_corner = jnp.where(kstar == 1, o_s1, o_s2)
    in_b = ((I >= o_corner) & (I < o_corner + W))[:, None]
    corner = jnp.sum(jnp.where((karange == (I % W)) & in_b,
                               corner_vals, 0.0), axis=1)
    score_ne = jnp.log(jnp.maximum(corner, _TINY)) + apre_s

    return jnp.where(is_nb, score_nb, score_ne)


def edge_read_scores_fast(read, rlen, strand, ts, te, win_tpl, win_trans,
                          wl, alpha: BandedMatrix, beta: BandedMatrix,
                          apre, bsuf, mpos_f, mend_f, mtype,
                          patches_f: MutationPatch, patches_r: MutationPatch):
    """(M,) edge-mutation LLs of one read: orient forward-frame mutations
    into the read's window frame, then run the batched edge scorer."""
    p = jnp.where(strand == 0, mpos_f - ts, te - mend_f)
    fwd = strand == 0
    pb = jnp.where(fwd, patches_f.bases, patches_r.bases)
    pt = jnp.where(fwd, patches_f.trans, patches_r.trans)
    ps = jnp.where(fwd, patches_f.shift, patches_r.shift)
    return edge_scores_fast(read.astype(jnp.int32), rlen,
                            win_tpl.astype(jnp.int32), win_trans, wl,
                            alpha, beta, apre, bsuf,
                            p, mtype, pb, pt, ps)


def _shift_rows(x, t: int):
    """y[i] = x[clip(i + t, 0, n-1)] along axis 0 (static t, edge-replicated)."""
    if t == 0:
        return x
    n = x.shape[0]
    if t > 0:
        tail = jnp.broadcast_to(x[n - 1:], (t,) + x.shape[1:])
        return jnp.concatenate([x[t:], tail], axis=0)
    head = jnp.broadcast_to(x[0:1], (-t,) + x.shape[1:])
    return jnp.concatenate([head, x[:t]], axis=0)


def make_patches_fast(tpl, trans, trans_table, tpl_len, pos, mtype, new_base) -> MutationPatch:
    """Batched virtual-mutation patches, gather-free.

    tpl: (Lm,) int32; trans: (Lm, 4); trans_table: (8, 4); pos/mtype/
    new_base: (M,).  Returns MutationPatch with leaves (M, 2), (M, 2, 4),
    (M,).  Same values as vmap(make_patch) but every template lookup is a
    one-hot matmul row-select and every SNR-table lookup a (M, 8) one-hot
    matmul, so nothing lowers to the TPU scalar core."""
    L = jnp.asarray(tpl_len, jnp.int32)
    tpl_f = tpl.astype(jnp.float32)[:, None]
    # stacked per-position source: [tpl[i-1], tpl[i], tpl[i+1], trans[i+1]]
    src = jnp.concatenate(
        [_shift_rows(tpl_f, -1), tpl_f, _shift_rows(tpl_f, 1),
         _shift_rows(trans, 1)], axis=1)                      # (Lm, 7)
    sel = _row_select(pos, src)
    prev_b = sel[:, 0].astype(jnp.int32)
    cur_b = sel[:, 1].astype(jnp.int32)
    next_b = sel[:, 2].astype(jnp.int32)
    trans_p1 = sel[:, 3:7]
    nb = jnp.asarray(new_base, jnp.int32)

    def ctx_of(a, b):
        idx = jnp.clip(context_index(a, b), 0, 7)
        oh = (idx[:, None] == jnp.arange(8)).astype(jnp.float32)
        return jax.lax.dot(oh, trans_table.astype(jnp.float32),
                           preferred_element_type=jnp.float32,
                           precision=jax.lax.Precision.HIGHEST)

    zeros4 = jnp.zeros_like(trans_p1)
    ctx_prev_nb = ctx_of(prev_b, nb)
    sub_b = jnp.stack([prev_b, nb], axis=1)
    sub_t = jnp.stack([
        jnp.where((pos > 0)[:, None], ctx_prev_nb, zeros4),
        jnp.where((pos + 1 < L)[:, None], ctx_of(nb, next_b), zeros4),
    ], axis=1)
    org_last = L - 1
    mid = (pos > 0) & (pos < org_last)
    del_b = jnp.stack([prev_b, next_b], axis=1)
    del_t = jnp.stack([
        jnp.where(mid[:, None], ctx_of(prev_b, next_b), zeros4),
        jnp.where((pos < org_last)[:, None], trans_p1, zeros4),
    ], axis=1)
    ins_b = jnp.stack([prev_b, nb], axis=1)
    ins_t = jnp.stack([
        jnp.where((pos > 0)[:, None], ctx_prev_nb, zeros4),
        jnp.where((pos < L)[:, None], ctx_of(nb, cur_b), zeros4),
    ], axis=1)

    mtype = jnp.asarray(mtype, jnp.int32)
    is_sub = (mtype == SUB)[:, None]
    is_ins = (mtype == INS)[:, None]
    bases = jnp.where(is_sub, sub_b, jnp.where(is_ins, ins_b, del_b))
    transp = jnp.where(is_sub[:, :, None], sub_t,
                       jnp.where(is_ins[:, :, None], ins_t, del_t))
    shift = jnp.where(mtype == SUB, 0, jnp.where(mtype == INS, -1, 1)).astype(jnp.int32)
    return MutationPatch(bases, transp, shift)
def mutated_windows_per_pair(wt_e, wtr_e, wlens_e, p, mtype,
                             patch: MutationPatch):
    """Dense mutated windows for (E,) pairs each with its own window.

    wt_e: (E, Jm) int32; wtr_e: (E, Jm, 4); wlens_e/p/mtype: (E,);
    patch leaves (E, 2)/(E, 2, 4)/(E,).  Static-shift, gather-free."""
    E, Jm = wt_e.shape
    idx = jnp.arange(Jm, dtype=jnp.int32)[None, :]
    p2 = p[:, None]
    tpl_f = wt_e.astype(jnp.float32)

    def sh_cols(x, t):
        """x[..., clip(col+t, 0, Jm-1), ...] along the window axis."""
        if t == 0:
            return x
        if t > 0:
            tail = jnp.repeat(x[:, Jm - 1:], t, axis=1)
            return jnp.concatenate([x[:, t:], tail], axis=1)
        head = jnp.repeat(x[:, 0:1], -t, axis=1)
        return jnp.concatenate([head, x[:, :t]], axis=1)

    sh = patch.shift[:, None]
    shifted_b = jnp.where(sh == -1, sh_cols(tpl_f, -1),
                          jnp.where(sh == 1, sh_cols(tpl_f, 1), tpl_f))
    sh3 = patch.shift[:, None, None]
    shifted_t = jnp.where(sh3 == -1, sh_cols(wtr_e, -1),
                          jnp.where(sh3 == 1, sh_cols(wtr_e, 1), wtr_e))
    bases = jnp.where(idx <= p2, tpl_f, shifted_b)
    trans = jnp.where((idx <= p2)[:, :, None], wtr_e, shifted_t)
    bases = jnp.where(idx == p2 - 1, patch.bases[:, 0:1].astype(jnp.float32), bases)
    bases = jnp.where(idx == p2, patch.bases[:, 1:2].astype(jnp.float32), bases)
    trans = jnp.where((idx == p2 - 1)[:, :, None], patch.trans[:, 0][:, None, :], trans)
    trans = jnp.where((idx == p2)[:, :, None], patch.trans[:, 1][:, None, :], trans)

    ld = jnp.where(mtype == INS, 1, jnp.where(mtype == DEL, -1, 0))
    new_len = wlens_e + ld
    valid = idx < new_len[:, None]
    bases = jnp.where(valid, bases, 4.0).astype(jnp.int8)
    trans = jnp.where((valid & (idx < new_len[:, None] - 1))[:, :, None], trans, 0.0)
    return bases, trans, new_len


def slot_geometry(ts, te, strand, ms, me, is_ins):
    """Interior-vs-edge classification of mutation slots against read
    windows (ONE definition, shared by the chunked and dense scoring
    paths; mirrors the host _dispatch_chunk rules).  All args broadcast;
    returns (overlap, interior, wlen)."""
    # and / or, not a select of booleans: the kernels' compiler has none
    overlap = (is_ins & (ts <= me) & (ms <= te)) | \
        (~is_ins & (ts < me) & (ms < te))
    p_w = jnp.where(strand == 0, ms - ts, te - me)
    e_w = jnp.where(strand == 0, me - ts, te - ms)
    wlen = te - ts
    interior = (p_w >= 3) & (e_w <= wlen - 2)
    return overlap, interior, wlen
