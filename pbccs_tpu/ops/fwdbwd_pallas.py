"""Pallas TPU kernel for the banded Arrow forward/backward fill.

This is the fused-device version of pbccs_tpu.ops.fwdbwd: the same banded
pair-HMM recurrence (reference ConsensusCore/src/C++/Arrow/
SimpleRecursor.cpp:62-296), evaluated as

  1. an XLA **coefficient precompute** -- for every (read, column) the three
     band-coefficient vectors of the column recurrence in CIRCULAR lane
     layout (fwdbwd.BandedMatrix: cell (i, j) at lane i mod W):

         col[L] = cm[L] * roll(prev, 1)[L]     (match enters from (i-1, j-1))
                + cd[L] * prev[L]              (deletion enters from (i, j-1))
                + cc[L] * col[L-1 circ]        (insertion enters from (i-1, j))

     with every cross-column band-membership mask folded into cm/cd and the
     circular scan's cut (the band's first row) into cc; and

  2. a **Pallas kernel** that runs the sequential column scan with the band
     state resident in VMEM: per column one STATIC lane roll, the in-column
     first-order recurrence as a log2(W) circular Hillis-Steele affine scan,
     and the ScaledMatrix per-column max-rescale
     (reference Matrix/ScaledMatrix-inl.hpp:74-123).  Reads ride the sublane
     axis (RB per block), the band rides the lanes, and the template-column
     grid axis is sequential with the running column carried in VMEM scratch.
     (The circular layout replaced per-column 8-variant dynamic shift-select
     chains -- the kernel's dominant VPU op count and the source of the
     Mosaic compile blowup at long-template column counts.)

The backward (beta) fill reuses the *same* kernel in backward mode (rolls
and scan run the other circular direction), iterating kernel columns as the
*static* map j = top - cc so every index is computable with static slices.
The per-read seed column (j = J) is injected by the kernel via a
seed-column select, and the output index map statically reverses columns so
no per-read re-assembly is needed.

The band leaves the kernel FRAMED and read-major (fwdbwd.BAND_LEAD): vals
(R, rows, W) with template column j at row BAND_LEAD + j, which is the
buffer the dense scoring kernel windows, the edge program reads and the
refine loop carries -- nothing transposes, slices, pads or copies it on the
way (docs/DESIGN.md "One band layout").  The frame costs the kernel its
lead rows as dead columns (zero coefficients, zero values).

A caller that needs only some reads filled packs them first and says how
many (`live`): the kernel skips the read blocks that hold none, and
place_reads, a copy-only kernel, puts each finished band in its own
read's rows of the buffer the caller carries.

TPU lowering notes (all load-bearing, each worth ~10-100x on v5e):
  * every precompute lookup is a static pad/slice or a one-hot matmul;
    per-element jnp.take and scatter (.at[].set) forms of the same lower
    to scalar-core loops.
  * the coefficients enter columns-leading (the scan loads a column by
    address arithmetic) and the band leaves read-major (the scan stores a
    column into one row of every read's tile).
  * log-likelihoods are masked reductions, not per-read gathers.

Numerics: the Hillis-Steele scan associates the affine recurrence in a
different order than the JAX lax.associative_scan path, so values agree to
float32 rounding (~1e-4 absolute on log-likelihoods), not bit-exactly.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pbccs_tpu.models.arrow.params import (
    TRANS_BRANCH,
    TRANS_DARK,
    TRANS_MATCH,
    TRANS_STICK,
    MISMATCH_PROBABILITY,
)
from pbccs_tpu.ops.fwdbwd import (BAND_LEAD, MAX_BAND_ADVANCE, BandedMatrix,
                                  band_frame_rows, band_lead, band_offsets,
                                  circ_roll, circ_rows, in_band, row_major)

_TINY = 1e-30
# band may advance at most this many rows per column; single source of
# truth lives in fwdbwd (guided_band_offsets clamps its slope to it)
_MAX_SHIFT = MAX_BAND_ADVANCE
_RB = 32                # reads per block (sublane axis)
_JB = 64                # template columns per grid step
_UNROLL = 4             # columns per fori_loop iteration


def fills_use_pallas() -> bool:
    """Route full alpha/beta fills through the Pallas kernel?

    Env override PBCCS_PALLAS=1/0; default on for TPU backends, off
    elsewhere (the pure-JAX path is the CPU reference)."""
    env = os.environ.get("PBCCS_PALLAS")
    if env is not None:
        return env.strip().lower() not in ("0", "false", "off", "no", "")
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------
# coefficient precompute (XLA, parallel over columns)
# --------------------------------------------------------------------------


def _edge_clip_rows(x, shift0: int, nc: int):
    """y[j] = x[clip(j - shift0, 0, n-1)] for j in range(nc), via pad+slice."""
    n = x.shape[0]
    lead = jnp.broadcast_to(x[0:1], (shift0,) + x.shape[1:]) if shift0 else x[:0]
    tail_n = max(0, nc - n - shift0)
    tail = jnp.broadcast_to(x[n - 1:n], (tail_n,) + x.shape[1:]) if tail_n else x[:0]
    return jnp.concatenate([lead, x, tail], axis=0)[:nc]


def _rev_clip_rows(x, top: int, nc: int):
    """y[cc] = x[clip(top - cc, 0, n-1)] for cc in range(nc) (static top)."""
    n = x.shape[0]
    idx0 = min(max(top, 0), n - 1)
    lead = jnp.broadcast_to(x[idx0:idx0 + 1], (max(top - (n - 1), 0),) + x.shape[1:])
    body = x[: idx0 + 1][::-1]
    got = lead.shape[0] + body.shape[0]
    tail = jnp.broadcast_to(x[0:1], (max(nc - got, 0),) + x.shape[1:])
    return jnp.concatenate([lead, body, tail], axis=0)[:nc]


def window_rows(x, starts, W: int, exact: bool = False):
    """y[j] = x[starts[j] : starts[j] + W] as a one-hot matmul on the MXU.

    Gathers with runtime start indices lower to the TPU scalar core (~50x
    slower than the fill they feed), so the windows are picked by a (nc, N)
    one-hot times the (N, W) im2col of x on the systolic array instead.
    With exact=False both operands ride bf16 -- exact for the 0..4 base
    codes; exact=True keeps f32 at HIGHEST precision for general values
    (the default TPU f32 dot truncates operands to bf16)."""
    N = x.shape[0]
    xf = x.astype(jnp.float32)
    xp = jnp.concatenate([xf, jnp.zeros(W, jnp.float32)])
    im2col = jnp.stack([xp[k: k + N] for k in range(W)], axis=1)   # (N, W)
    onehot = starts[:, None] == jnp.arange(N, dtype=starts.dtype)[None, :]
    if exact:
        res = jax.lax.dot(onehot.astype(jnp.float32), im2col,
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)
    else:
        res = jax.lax.dot(onehot.astype(jnp.bfloat16),
                          im2col.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return res.astype(x.dtype)


_window_rows = window_rows  # internal alias used by the coefficient builders


def window_rows_circ(x, starts, W: int, exact: bool = False):
    """y[j, L] = x[circ_rows(starts[j], W)[L]] — the circular-lane form of
    window_rows.  The circular window [o, o+W) splits at the lane wrap
    into two CONTIGUOUS windows (base b = o - o%W and b + W), so it costs
    two one-hot matmuls + one select — no per-lane gathers."""
    starts = starts.astype(jnp.int32)
    q = starts % W
    b = starts - q
    win1 = window_rows(x, b, W, exact)
    win2 = window_rows(x, b + W, W, exact)
    L = jnp.arange(W, dtype=jnp.int32)
    return jnp.where(L[None, :] >= q[:, None], win1, win2)


def band_read_windows(reads, offsets, width: int, rows: int | None = None):
    """(rbase, rnext): every column's circular-lane read window for a flat
    read batch — rbase[r, j, L] = read_pad1 value at the band row lane L
    of column j holds (emission operand), rnext the read_pad0 value (the
    insertion/link operand).  ONE shared computation serves the interior
    kernel, the edge programs (dense_score_pallas._edge_read_windows
    slices it) AND the alpha fill's coefficient precompute below: the
    same call on the same reads and offsets, which the compiler merges
    within a program, so a fill rebuild runs these window matmuls once
    (the beta fill keeps its own, in its reversed column order).  With
    `rows` the pair comes out in the band frame ((R, rows, W), column j
    at row BAND_LEAD + j, the rows around the columns holding the first and
    the last column's windows): the windows are computed where they are
    read, so nothing pads or copies them afterwards.

    Only rnext rides the one-hot window matmul; rbase derives from it:
    rbase[j][L] = read_pad0[rows_j[L] - 1], and because circular lanes
    are column-independent (lane = row mod W), that value is
    circ_roll(rnext[j], 1) at every lane except the band's FIRST row
    (the cut lane o_j % W), whose operand row o_j - 1 lives in column
    j-1's window at the same rolled lane.

    Safety of the remaining garbage lanes: when o_j == o_{j-1} (flat
    offsets are routine, and the frame's rows past the last column repeat
    its offset) the cut-lane derivation returns rf[o_j + W - 1]
    instead of rf[o_j - 1] — but every consumer masks exactly that
    contribution: the cut lane's row is the band's first row, whose
    match operand is gated by in_band(rows - 1, o_prev) (ext_b /
    mutation_score._ext_col / _forward_coeffs' cm) and whose insertion
    operand by rows > o_col (cmask / _forward_coeffs' cc), and rows
    outside [1, I] are masked by in_read / valid.
    Any new consumer of rbase must preserve those gates.
    This halves the (nc, N) one-hot build + MXU windowing cost."""
    read_f = reads.astype(jnp.float32)
    offsets = offsets.astype(jnp.int32)
    if rows is not None:
        offsets = jax.vmap(lambda o: _edge_clip_rows(o, BAND_LEAD, rows))(offsets)
    rnext = jax.vmap(lambda rf, o: window_rows_circ(rf, o, width))(
        read_f, offsets)
    prev_col = jnp.concatenate([rnext[:, :1], rnext[:, :-1]], axis=1)
    lane = jnp.arange(width, dtype=jnp.int32)
    cut = (offsets % width)[:, :, None] == lane
    rbase = jnp.where(cut, circ_roll(prev_col, 1), circ_roll(rnext, 1))
    return rbase, rnext



# shared circular-layout helpers (single source of truth in ops.fwdbwd)
_circ_rows_cols = circ_rows
_in_band2 = in_band


def _forward_coeffs(read, I, tpl, trans, J, offsets, rbase, W: int,
                    eps: float, lead: int = 0):
    """Per-column circular-lane band coefficients of the alpha recurrence
    for one read.

    read: (Imax,) int32; tpl: (Jmax,) int32; trans: (Jmax, 4) f32;
    offsets: (nc,) int32 band offsets of the kernel's columns; rbase:
    (nc, W) their read windows (band_read_windows: read base i-1 at the
    lane of row i).  Kernel column t holds template column j = t - lead
    (the frame's lead rows are dead columns: zero coefficients, zero
    values).  Returns
    (cm, cd, cc) each (nc, W), rescale mask (nc,) f32, seed (W,) f32,
    seedcol int32.

    Circular layout: lane L of column j holds row circ_rows(o(j))[L], so
    the kernel reads the previous column with ONE static lane roll; the
    cross-column band-membership masks (is row-1 / row inside column
    j-1's band?) are folded into cm / cd here, and the in-column scan's
    circular cut (row == o(j) has no in-band predecessor) into cc.
    Mirrors the JAX step in fwdbwd.banded_forward column for column.
    """
    Imax = read.shape[0]
    Jmax = tpl.shape[0]
    nc = offsets.shape[0]
    hit, miss = 1.0 - eps, eps / 3.0

    j = jnp.arange(nc, dtype=jnp.int32)[:, None] - lead     # (nc, 1)
    o = offsets[:, None]
    om1 = _edge_clip_rows(offsets, 1, nc)[:, None]          # offset of col j-1

    rows = _circ_rows_cols(offsets, W)                      # (nc, W)
    t_cur = _edge_clip_rows(tpl, 1 + lead, nc)[:, None]
    t_next = _edge_clip_rows(tpl, lead, nc)[:, None]
    tr_prev = _edge_clip_rows(trans, 2 + lead, nc)          # (nc, 4)
    tr_cur = _edge_clip_rows(trans, 1 + lead, nc)

    valid = (rows >= 1) & (rows <= I - 1)
    em = jnp.where(rbase == t_cur, hit, miss)
    mfac = jnp.where(
        j == 1,
        jnp.where(rows == 1, 1.0, 0.0),
        jnp.where(rows == 1, 0.0, tr_prev[:, TRANS_MATCH][:, None]),
    )
    cm = jnp.where(valid & _in_band2(rows - 1, om1, W), em * mfac, 0.0)
    cd = jnp.where(valid & (j > 1) & _in_band2(rows, om1, W),
                   tr_prev[:, TRANS_DARK][:, None], 0.0)
    ins = jnp.where(rbase == t_next,
                    tr_cur[:, TRANS_BRANCH][:, None],
                    tr_cur[:, TRANS_STICK][:, None] / 3.0)
    cc = jnp.where(valid & (rows > 1) & (rows > o), ins, 0.0)

    # final pinned column j == J: alpha(I, J) = alpha(I-1, J-1) * em_last
    # (SimpleRecursor.cpp:171-180)
    em_last = jnp.where(
        read[jnp.clip(I - 1, 0, Imax - 1)] == tpl[jnp.clip(J - 1, 0, Jmax - 1)],
        hit, miss)
    pinned = j == J
    cm = jnp.where(pinned,
                   jnp.where((rows == I) & _in_band2(rows - 1, om1, W),
                             em_last, 0.0), cm)
    cd = jnp.where(pinned, 0.0, cd)
    cc = jnp.where(pinned, 0.0, cc)

    dead = (j <= 0) | (j > J)
    cm = jnp.where(dead, 0.0, cm)
    cd = jnp.where(dead, 0.0, cd)
    cc = jnp.where(dead, 0.0, cc)

    mask = ((j[:, 0] >= 1) & (j[:, 0] < J)).astype(jnp.float32)
    seed = (jnp.arange(W) == 0).astype(jnp.float32)
    return cm, cd, cc, mask, seed, jnp.int32(lead)


def _backward_coeffs(read, I, tpl, trans, J, offsets, W: int, eps: float,
                     nc: int, top: int):
    """Beta coefficients: kernel column cc holds beta column j = top - cc
    in the SAME circular lane layout as alpha (lane L = row r === L mod W;
    no lane reversal -- the kernel's backward mode rolls the other way).
    The kernel stores its columns reversed, so beta column j sits at output
    row nc - 1 - cc = j + (nc - 1 - top): the frame's lead, which the
    caller folds into `top`.  offsets: (>= Jmax + 1,) band offsets by
    template column (columns past the last repeat it).  The read windows
    are its own matmul, in the kernel's reversed column order: taking
    them from band_read_windows' rnext through a reverse read wrong from
    the kernel's 320th column on at 1,024 reads x 640 rows x W 64 on the
    chip (PR 28; right at 256 reads, and on the CPU).

    Mirrors the JAX step in fwdbwd.banded_backward column for column."""
    Imax = read.shape[0]
    hit, miss = 1.0 - eps, eps / 3.0

    cc_idx = jnp.arange(nc, dtype=jnp.int32)[:, None]
    j = top - cc_idx                                        # beta column (static)
    o_j = _rev_clip_rows(offsets, top, nc)
    o_j1 = _rev_clip_rows(offsets, top + 1, nc)[:, None]    # offset of col j+1

    rows = _circ_rows_cols(o_j, W)                          # (nc, W)
    o_j = o_j[:, None]
    read_pad = jnp.concatenate([read, read[Imax - 1:]]).astype(jnp.float32)
    rnext = window_rows_circ(read_pad, o_j[:, 0], W)        # read base i+1
    t_next = _rev_clip_rows(tpl, top, nc)[:, None]          # base of col j+1
    tr_cur = _rev_clip_rows(trans, top - 1, nc)             # moves leaving j-1

    valid = (rows >= 1) & (rows <= I - 1)
    nxt_match = rnext == t_next
    em = jnp.where(nxt_match, hit, miss)
    mfac = jnp.where(
        rows < I - 1,
        tr_cur[:, TRANS_MATCH][:, None],
        jnp.where((rows == I - 1) & (j == J - 1), 1.0, 0.0),
    )
    cm = jnp.where(valid & _in_band2(rows + 1, o_j1, W), em * mfac, 0.0)
    cd = jnp.where(valid & (j >= 1) & (j < J - 1) & _in_band2(rows, o_j1, W),
                   tr_cur[:, TRANS_DARK][:, None], 0.0)
    ins = jnp.where(nxt_match,
                    tr_cur[:, TRANS_BRANCH][:, None],
                    tr_cur[:, TRANS_STICK][:, None] / 3.0)
    # rows < o + W - 1 cuts the reverse circular scan at the band's top row
    cc = jnp.where(valid & (rows < I - 1) & (rows < o_j + W - 1), ins, 0.0)

    # terminal beta column j == 0: beta(0,0) = beta(1,1) * em(read[0], tpl[0])
    em0 = jnp.where(read[0] == tpl[0], hit, miss)
    at0 = j == 0
    cm = jnp.where(at0,
                   jnp.where((rows == 0) & _in_band2(rows + 1, o_j1, W),
                             em0, 0.0), cm)
    cd = jnp.where(at0, 0.0, cd)
    cc = jnp.where(at0, 0.0, cc)

    dead = (j >= J) | (j < 0)
    cm = jnp.where(dead, 0.0, cm)
    cd = jnp.where(dead, 0.0, cd)
    cc = jnp.where(dead, 0.0, cc)

    mask = ((j[:, 0] >= 1) & (j[:, 0] <= J - 1)).astype(jnp.float32)
    seed = (jnp.arange(W) == I % W).astype(jnp.float32)
    return cm, cd, cc, mask, seed, (top - J).astype(jnp.int32)


# --------------------------------------------------------------------------
# the kernel
# --------------------------------------------------------------------------


_roll_lanes = circ_roll    # Mosaic-friendly: two static slices + concat


def _fill_kernel(*refs, jb_size: int, rev_store: bool, merge: bool,
                 backward: bool, gated: bool = False):
    """Column scan over circular-lane bands.  The coefficient inputs are in
    kernel layout (columns, R, W): the column axis is the *leading*
    (untiled) dimension, so loading a column is plain VMEM address
    arithmetic.  (Dynamic LOADS on the sublane axis of an (R, columns, W)
    layout measured ~20x slower on v5e.)  The outputs are read-major
    (R, columns, W) blocks: storing a column to one row of each read's
    tile costs a few percent of the scan (_run_fill has the numbers).

    Circular lanes (fwdbwd.BandedMatrix): cell (i, j) lives at lane
    i mod W whatever the column offset, so the cross-column operand is ONE
    static lane roll -- the per-column 8-variant (15 for Merge) dynamic
    shift-select chains this replaced were the kernel's dominant VPU op
    count and the Mosaic compile blowup at long templates.  All band-
    membership masks are folded into cm/cd/cg and the scan cut into cc by
    the XLA precompute, so the kernel body is pure fma + roll + scan.

    The seed column is injected into b BEFORE the in-column scan: for the
    Arrow fills the seed columns have zero in-column coefficients so this
    equals the old post-scan replace, and it additionally serves the Quiver
    fills, whose seed columns chain the Extra move through the scan
    (alpha column 0; beta column J below the pin).

    With merge=True (the Quiver recurrence) one extra input (cg) and two
    extra scratch slots (prev2, its scale) carry the j-2 Merge operand:
    b += cg[L] * roll(prev2)[L] / scale_prev
    (Quiver/SimpleRecursor.cpp merge move; models/quiver/recursor.py).

    With gated=True the first ref is the scalar-prefetched count of LIVE
    read blocks: the scan runs for read blocks [0, count) and a dead
    block's steps do nothing.  _run_fill's index maps hand a dead step the
    blocks of the last live step, so the pipeline fetches no coefficient
    and stores no band for it either: a dead block costs its grid steps'
    overhead (about 0.35 us each) and its rows of the outputs are never
    written."""
    if gated:
        live_ref, *refs = refs
    if merge:
        (seed_ref, seedcol_ref, mask_ref, cm_ref, cd_ref,
         cc_ref, cg_ref, vals_ref, ls_ref, prev_ref, prev2_ref,
         sprev_ref) = refs
    else:
        (seed_ref, seedcol_ref, mask_ref, cm_ref, cd_ref,
         cc_ref, vals_ref, ls_ref, prev_ref) = refs
    jb = pl.program_id(1)
    seed = seed_ref[...]
    seedcol = seedcol_ref[...]                              # (RB, 1) int32
    RB, W = seed.shape
    u = _UNROLL
    t = -1 if backward else 1   # roll direction: row i-1 fwd / i+1 bwd

    def one_col(prev, prev2, sprev, jglob, cm, cd, cco, m, cg):
        b = cm * _roll_lanes(prev, t) + cd * prev
        if merge:
            b = b + cg * (_roll_lanes(prev2, t) / sprev)
        b = jnp.where(seedcol == jglob, b + seed, b)
        c = cco
        d = 1
        while d < W:                # circular affine prefix scan (cut in c)
            b = b + c * _roll_lanes(b, t * d)
            c = c * _roll_lanes(c, t * d)
            d *= 2

        col = b
        cmax = jnp.max(col, axis=1, keepdims=True)
        do_scale = m & (cmax > 0)
        scale = jnp.where(do_scale, cmax, 1.0)
        col = jnp.where(m, col / scale, col)
        ls = jnp.where(do_scale, jnp.log(scale), 0.0)
        return col, ls, scale

    def body(jc, _):
        base = jc * u
        prev = prev_ref[...]
        # scratch is uninitialized at the first column of each read block
        first = jb * jb_size + base == 0
        prev = jnp.where(first, jnp.zeros_like(prev), prev)
        if merge:
            prev2 = jnp.where(first, jnp.zeros_like(prev), prev2_ref[...])
            sprev = jnp.where(first, jnp.ones((RB, 1), jnp.float32),
                              sprev_ref[...])
            cg_c = cg_ref[pl.dslice(base, u)]
        cm_c = cm_ref[pl.dslice(base, u)]                   # (u, RB, W)
        cd_c = cd_ref[pl.dslice(base, u)]
        cc_c = cc_ref[pl.dslice(base, u)]
        m_c = mask_ref[pl.dslice(base, u)]

        cols, lss = [], []
        for k in range(u):
            jglob = jb * jb_size + base + k
            col, ls, scale = one_col(
                prev, prev2 if merge else None,
                sprev if merge else None, jglob, cm_c[k],
                cd_c[k], cc_c[k], m_c[k] > 0,
                cg_c[k] if merge else None)
            cols.append(col)
            lss.append(ls)
            if merge:
                prev2, sprev = prev, scale
            prev = col

        # the outputs are READ-major blocks (rb, jb, W): a column goes to
        # one row of every read's tile
        for k in range(u):
            row = (jb_size - 1 - base - k) if rev_store else base + k
            vals_ref[:, pl.dslice(row, 1), :] = cols[k][:, None, :]
            ls_ref[:, pl.dslice(row, 1), :] = lss[k][:, None, :]
        prev_ref[...] = prev
        if merge:
            prev2_ref[...] = prev2
            sprev_ref[...] = sprev
        return 0

    def scan():
        lax.fori_loop(0, jb_size // u, body, 0)

    if gated:
        pl.when(pl.program_id(0) < live_ref[0])(scan)
    else:
        scan()


def _held_step(i, c, n, last_c):
    """Grid step (i, c) of a kernel whose leading axis has n live rows:
    itself, or for a dead row (i >= n) the last live step, (n - 1,
    last_c).  Index maps built on it leave a dead step's blocks unchanged
    from the step before, so the pipeline fetches and stores nothing."""
    dead = i >= n
    return (jnp.where(dead, jnp.maximum(n - 1, 0), i),
            jnp.where(dead, last_c, c))


def _run_fill(cm, cd, cc, mask, seed, seedcol, rev_store: bool,
              cg=None, backward: bool | None = None, live=None):
    """Invoke the column-scan kernel.

    cm/cd/cc: (nc, R, W) KERNEL layout, columns leading (the scan indexes
    a column with plain VMEM address arithmetic); mask: (nc, R);
    seed: (R, W); seedcol: (R,).
    Returns vals (R, nc, W) and log-scales (R, nc, 1), READ-major: the
    scan stores each column into a row of the step's (rb, jb, W) output
    block, so the band leaves the kernel in the layout every reader of
    it takes (the dense kernel's BlockSpecs, the edge program's windows,
    the log-likelihood reductions) and no XLA transpose follows.  (On one
    TPU v5e, PR 28: alpha + beta fills with their precompute at 384 x
    2,304 x W96 took 90.1 ms storing so, 90.6 ms transposing a collected
    (jb, rb, W) block once a step, 92.1 ms reading it back a read at a
    time; 99.7 ms with the columns-leading output and XLA's transpose.)
    With rev_store, output row t holds kernel column nc-1-t.  Passing cg engages the Merge
    carry (Quiver recurrence).  backward sets the kernel's roll/scan
    direction (defaults to rev_store).

    `live` (optional int32 scalar, traced): only reads [0, live) need a
    fill.  The kernel then scans the ceil(live / rb) read blocks that
    hold one and skips the rest (their count is scalar-prefetched; see
    _fill_kernel for what a dead block costs), and the rows of the dead
    blocks come back UNWRITTEN: whatever the buffers held.  A caller
    packs the reads it needs first and takes rows [0, live) of the
    result (models/arrow/scorer.fill_pass places them with
    place_reads).  Without `live` every block is scanned by the program
    this was before the gate."""
    nc, R, W = cm.shape
    merge = cg is not None
    backward = rev_store if backward is None else backward
    # the Merge carry (Quiver) doubles the live column state (prev2 + its
    # scale), so merge fills run half-width read blocks for VMEM headroom
    rb = min(_RB // 2 if merge else _RB, R)
    # a grid step holds four (jb, rb, W) coefficient/output blocks, each
    # double-buffered: at the 2x-band mating retry's W=192 that came to
    # 20 MB, over the 16 MB of VMEM the v5e's compiler grants a kernel,
    # so bands wider than 128 take half the columns per step
    jb = min(_JB if W <= 128 else _JB // 2, nc)
    assert nc % jb == 0 and R % rb == 0
    njb = nc // jb

    gated = live is not None
    kernel = functools.partial(_fill_kernel, jb_size=jb, rev_store=rev_store,
                               merge=merge, backward=backward, gated=gated)

    def step(r, j, *count):
        """The (read block, column step) whose blocks grid step (r, j)
        holds: its own, or for a dead read block the last live step's."""
        return _held_step(r, j, count[0][0], njb - 1) if gated else (r, j)

    def spec(shape, index):
        return pl.BlockSpec(shape, lambda r, j, *n: index(*step(r, j, *n)))

    out_col = (lambda r, j: (r, njb - 1 - j, 0)) if rev_store else \
        (lambda r, j: (r, j, 0))
    col_ospec = spec((rb, jb, W), out_col)
    vec_ospec = spec((rb, jb, 1), out_col)
    in_col = spec((jb, rb, W), lambda r, j: (j, r, 0))
    in_vec = spec((jb, rb, 1), lambda r, j: (j, r, 0))
    in_specs = [
        spec((rb, W), lambda r, j: (r, 0)),             # seed
        spec((rb, 1), lambda r, j: (r, 0)),             # seedcol
        in_vec,                                          # mask
        in_col, in_col, in_col,                          # cm, cd, cc
    ]
    operands = [seed, seedcol[:, None], mask[:, :, None], cm, cd, cc]
    scratch = [pltpu.VMEM((rb, W), jnp.float32)]         # running column
    if merge:
        in_specs += [in_col]                             # cg
        operands += [cg]
        scratch += [pltpu.VMEM((rb, W), jnp.float32),    # prev2
                    pltpu.VMEM((rb, 1), jnp.float32)]    # its scale
    if gated:
        blocks = (jnp.asarray(live, jnp.int32) + rb - 1) // rb
        operands = [jnp.minimum(blocks, R // rb).reshape(1)] + operands
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=int(gated), grid=(R // rb, njb),
            in_specs=in_specs, out_specs=[col_ospec, vec_ospec],
            scratch_shapes=scratch),
        out_shape=[
            jax.ShapeDtypeStruct((R, nc, W), jnp.float32),
            jax.ShapeDtypeStruct((R, nc, 1), jnp.float32),
        ],
        # dead read blocks revisit the last live block's outputs, so the
        # read-block axis of a gated call is no longer independent steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if gated else "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
    )(*operands)


# Band rows a step of place_reads moves (1 MB at W = 96 with the double
# buffers' four copies well inside VMEM); other slab sizes were not
# measured on the chip (PERF.md section 7).
_PLACE_ROWS = 2048


def place_reads(packed, dest, n, into):
    """`into` ((R, rows, W)) with into[dest[i]] = packed[i] for i < n;
    packed (P, rows, W) with P >= n; dest (P,) int32, rows of `into`, its
    first n distinct; n a traced int32 scalar.  `into` is aliased to the
    result.

    A copy-only kernel, one grid step a packed read (and a slab of at most
    _PLACE_ROWS rows): a placed read is read once and written once, and a
    read that is not placed is not touched -- step i >= n holds the blocks
    step n - 1 held, so the pipeline moves nothing for it.  An XLA scatter
    or select of a band moves every read's rows instead (and, PR 28, first
    turns the whole band into a layout of its liking).  This is how a
    packed, gated fill (_run_fill's `live`) gets its bands back to their
    own reads.  (With n == 0 the one block the pipeline must still store
    is written with what it held: the aliased input is fetched there.)"""
    P, rows, W = packed.shape
    slab = max(d for d in range(8, min(rows, _PLACE_ROWS) + 1, 8)
               if rows % d == 0)
    C = rows // slab

    def src(i, c, dest_ref, n_ref):
        i, c = _held_step(i, c, n_ref[0], C - 1)
        return i, c, 0

    def dst(i, c, dest_ref, n_ref):
        i, c = _held_step(i, c, n_ref[0], C - 1)
        return dest_ref[i], c, 0

    def held(i, c, dest_ref, n_ref):
        return dest_ref[0], C - 1, 0

    def kernel(dest_ref, n_ref, src_ref, held_ref, out_ref):
        n = n_ref[0]

        @pl.when(pl.program_id(0) < n)
        def _():
            out_ref[...] = src_ref[...]

        @pl.when(n == 0)
        def _():
            out_ref[...] = held_ref[...]

    block = (None, slab, W)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(P, C),
            in_specs=[pl.BlockSpec(block, src), pl.BlockSpec(block, held)],
            out_specs=pl.BlockSpec(block, dst)),
        out_shape=jax.ShapeDtypeStruct(into.shape, into.dtype),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(dest.astype(jnp.int32), jnp.asarray(n, jnp.int32).reshape(1),
      packed, into)


def _pad_cols(n: int) -> int:
    return ((n + _JB - 1) // _JB) * _JB


def _resolve_offsets(offsets, I, J, nc: int, width: int):
    """(R, nc) band offsets by template column: diagonal unless
    precomputed ones are supplied."""
    if offsets is None:
        return jax.vmap(lambda i, jl: band_offsets(i, jl, nc, width))(I, J)
    return jnp.asarray(offsets, jnp.int32)[:, :nc]


def _pad_reads(r: int) -> int:
    rb = min(_RB, r)
    return ((r + rb - 1) // rb) * rb


def _pad_r(arrs, R, Rp, axis: int = 0):
    """Pad the read axis (at `axis`) from R to Rp rows."""
    if Rp == R:
        return arrs
    def pad(a):
        assert a.ndim > axis, (a.shape, axis)
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, Rp - R)
        return jnp.pad(a, widths)
    return [pad(a) for a in arrs]


# --------------------------------------------------------------------------
# public batched fills
# --------------------------------------------------------------------------


def _framed(vals, ls, offsets, R: int) -> BandedMatrix:
    """The fill's outputs as a framed BandedMatrix (fwdbwd.BAND_LEAD): the
    values as the kernel wrote them, the log-scales by template column."""
    n = offsets.shape[1]
    if vals.shape[0] != R:                  # read lanes padded to a block
        vals, ls = vals[:R], ls[:R]
    return BandedMatrix(row_major(vals), offsets,
                        ls[:, BAND_LEAD: BAND_LEAD + n, 0])


def pallas_forward_batch(reads, rlens, tpls, trans, tlens, width: int,
                         pr_miscall: float = MISMATCH_PROBABILITY,
                         offsets=None, live=None) -> BandedMatrix:
    """Batched banded forward fills: reads (R, Imax) int8/int32, rlens (R,),
    tpls (R, Jmax), trans (R, Jmax, 4), tlens (R,).  Returns a FRAMED
    BandedMatrix (fwdbwd.BAND_LEAD): vals (R, band_frame_rows(Jmax + 1), W)
    with column j at row BAND_LEAD + j, written once by the kernel;
    offsets / log_scales (R, Jmax + 1).

    offsets: optional (R, >= Jmax+1) precomputed band offsets (guided
    rebanding, fwdbwd.guided_band_offsets); default diagonal layout.
    Must be monotone (any per-column advance is representable in the
    circular lane layout; columns whose bands do not overlap simply
    carry no mass).

    live: optional traced count: only reads [0, live) are filled, in whole
    kernel blocks; the vals and log-scales of the rest are unwritten
    memory (_run_fill)."""
    R, Imax = reads.shape
    Jmax = tpls.shape[1]
    nc = band_frame_rows(Jmax + 1)
    Rp = _pad_reads(R)

    I = rlens.astype(jnp.int32)
    J = tlens.astype(jnp.int32)
    offsets = _resolve_offsets(offsets, I, J, Jmax + 1, width)
    rbase, _ = band_read_windows(reads, offsets, width, nc)
    # turned columns-leading once, here, and not once a coefficient tensor
    rbase = row_major(jnp.swapaxes(rbase, 0, 1))
    cm, cd, cc, mask, seed, seedcol = jax.vmap(
        lambda r, i, t, tr, jl, o, rb: _forward_coeffs(
            r.astype(jnp.int32), i, t.astype(jnp.int32), tr, jl,
            _edge_clip_rows(o, BAND_LEAD, nc), rb, width, pr_miscall,
            lead=BAND_LEAD),
        in_axes=(0, 0, 0, 0, 0, 0, 1), out_axes=(1, 1, 1, 1, 0, 0),
    )(reads, I, tpls, trans, J, offsets, rbase)

    cm, cd, cc, mask = _pad_r([cm, cd, cc, mask], R, Rp, axis=1)
    seed, seedcol = _pad_r([seed, seedcol], R, Rp)
    vals, ls = _run_fill(cm, cd, cc, mask, seed, seedcol, rev_store=False,
                         live=live)
    return _framed(vals, ls, offsets, R)


def pallas_backward_batch(reads, rlens, tpls, trans, tlens, width: int,
                          pr_miscall: float = MISMATCH_PROBABILITY,
                          offsets=None, live=None) -> BandedMatrix:
    """Batched banded backward fills; same conventions as
    pallas_forward_batch."""
    R, Imax = reads.shape
    Jmax = tpls.shape[1]
    nc = band_frame_rows(Jmax + 1)
    Rp = _pad_reads(R)

    I = rlens.astype(jnp.int32)
    J = tlens.astype(jnp.int32)
    offsets = _resolve_offsets(offsets, I, J, Jmax + 1, width)
    # the kernel stores column cc at row nc-1-cc: with beta column
    # j = top - cc that is row j + BAND_LEAD
    cm, cd, cc, mask, seed, seedcol = jax.vmap(
        lambda r, i, t, tr, jl, o: _backward_coeffs(
            r.astype(jnp.int32), i, t.astype(jnp.int32), tr, jl, o,
            width, pr_miscall, nc, top=nc - 1 - BAND_LEAD),
        out_axes=(1, 1, 1, 1, 0, 0),
    )(reads, I, tpls, trans, J, offsets)

    cm, cd, cc, mask = _pad_r([cm, cd, cc, mask], R, Rp, axis=1)
    seed, seedcol = _pad_r([seed, seedcol], R, Rp)
    vals, ls = _run_fill(cm, cd, cc, mask, seed, seedcol, rev_store=True,
                         live=live)
    return _framed(vals, ls, offsets, R)


# --------------------------------------------------------------------------
# batched log-likelihoods (masked reductions; no per-read gathers)
# --------------------------------------------------------------------------


def _scale_total(log_scales, J):
    """(R,) sum of each read's column log-scales over columns [0, J], in
    an order that is the code's own: the halves of the row are added
    elementwise until one column is left.  A jnp.sum is summed in an
    order XLA picks from the operand's shape and layout: on the chip a
    pass of 64 reads and a batch of 384 summed the same 2,305 log-scales
    of a read one or two ulp apart (PR 30), which is enough to move a QV
    character.  Elementwise float adds are not reassociated, so a read's
    likelihood is the same bits in whatever batch, pass or layout it is
    summed."""
    cols = jnp.arange(log_scales.shape[1], dtype=jnp.int32)[None, :]
    x = jnp.where(cols <= J, log_scales, 0.0)
    n = 1 << (x.shape[1] - 1).bit_length()
    x = jnp.pad(x, ((0, 0), (0, n - x.shape[1])))
    while n > 1:
        n //= 2
        x = x[:, :n] + x[:, n:]
    return x[:, 0]


def forward_loglik_batch(alpha: BandedMatrix, rlens, tlens):
    """LL[r] = log alpha(I, J) + sum of column log-scales.  Column J is
    one-hot (only the pinned final cell is non-zero), so the final value is a
    masked sum over the whole band (framed or plain)."""
    J = tlens.astype(jnp.int32)[:, None]
    rows = jnp.arange(alpha.vals.shape[1], dtype=jnp.int32)[None, :]
    final = jnp.sum(jnp.where((rows == J + band_lead(alpha))[:, :, None],
                              alpha.vals, 0.0), axis=(1, 2))
    return jnp.log(jnp.maximum(final, _TINY)) + _scale_total(
        alpha.log_scales, J)


def backward_loglik_batch(beta: BandedMatrix, tlens):
    J = tlens.astype(jnp.int32)[:, None]
    b00 = beta.vals[:, band_lead(beta), 0]
    return jnp.log(jnp.maximum(b00, _TINY)) + _scale_total(
        beta.log_scales, J)
