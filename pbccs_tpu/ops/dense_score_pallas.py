"""Pallas TPU kernel for dense interior mutation scoring over the slot grid.

The round-3 device profile showed the chunked
mutation-scoring programs are HBM-bandwidth-bound: every elementwise step of
the packed (Z, R, chunk, W) pipeline materializes a ~1.6 GB intermediate.
This kernel replaced that path.  Its achieved-vs-bound gap is not
quoted here: the benchmark's `dense_roofline` (benchmark/kernels/dense.py
over the device trace) measures it on the chip.  The kernel evaluates the
Extend(2 cols)+Link algebra
(reference ConsensusCore/src/C++/Arrow/SimpleRecursor.cpp:373-487, :306-357)
for EVERY slot of the position-major mutation grid (9 slots per template
position: 4 subs, 4 ins, 1 del -- models/arrow/mutations._SLOT_* order) with
all intermediates resident in VMEM, writing only the (positions, 9) score
grid back to HBM.

Why the dense grid maps perfectly onto a kernel: for slot (p, k) every DP
row the scorer touches -- alpha columns p-2..p+1, beta columns p+1..p+2,
band offsets, read windows, scale prefixes, virtual-template patches -- sits
at a STATIC offset from p, so a position-block loads a handful of contiguous
VMEM slices and the whole 9-slot computation is straight vector math: no
one-hot row-select matmuls, no candidate packing, no per-mutation gathers.

Scope contract: kernel values are only valid for INTERIOR mutations (window
position >= 3 and mutation end <= window_len - 2, the same classification
the batch scorer applies); the interior mask guarantees the simplified
masks used here (no j==1 start column, no pinned corner, no max_left
clamps) agree with ops.mutation_score._ext_col.  Non-interior entries
compute finite garbage that the caller masks out.

Numerics: the in-column first-order recurrence is associated as a
Hillis-Steele scan (same as ops/fwdbwd_pallas), while the JAX reference path
uses lax.associative_scan -- values agree to float32 rounding (~1e-5
relative), not bit-exactly.  Parity: tests/test_dense_score.py fuzzes this
kernel (interpret mode) against interior_scores_fast and the per-mutation
extend_link_score oracle.
"""

from __future__ import annotations

import functools
import os
import typing

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import numpy as np

from pbccs_tpu.runtime import tuning as _tuning

from pbccs_tpu.models.arrow.params import (
    MISMATCH_PROBABILITY,
    TRANS_BRANCH,
    TRANS_DARK,
    TRANS_MATCH,
    TRANS_STICK,
    transition_lookup,
)
from pbccs_tpu.ops.fwdbwd import (BAND_LEAD, BandedMatrix, _affine_scan_circ,
                                  band_frame, band_frame_rows, circ_roll,
                                  circ_rows, row_major)
from pbccs_tpu.ops.fwdbwd_pallas import band_read_windows
from pbccs_tpu.ops.mutation_score import slot_geometry

_TINY = 1e-30
_PB = 64          # template positions per kernel sub-block
_OFF0 = BAND_LEAD  # row of position 0 in every position-indexed input
_HALO = 16        # halo rows per step (offsets span [-3, +2] around _OFF0)
_CB_DEFAULT = 4   # position sub-blocks per kernel grid step (see below)
N_SLOTS = 9

SUB, INS, DEL = 0, 1, 2


# Safety cap on the kernel's template length.  VMEM residency is CONSTANT
# in Jmax (the grid streams overlapping windows of the framed inputs), so
# this only keeps absurd bucket sizes on the chunked path; every
# BASELINE.json config sits far below it.
DENSE_MAX_JMAX = 65536


def dense_score_enabled(jmax: int | None = None) -> bool:
    """Route full-grid interior scoring through this kernel?

    Env override PBCCS_DENSE=1/0; default on for TPU backends, off
    elsewhere (the packed-chunk JAX path is the CPU reference).  Buckets
    beyond DENSE_MAX_JMAX always use the chunked path (VMEM footprint)."""
    if jmax is not None and jmax > DENSE_MAX_JMAX:
        return False
    env = os.environ.get("PBCCS_DENSE")
    if env is not None:
        return env.strip().lower() not in ("0", "false", "off", "no", "")
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def dense_cols_per_step(nb: int | None = None) -> int:
    """Multi-column blocking: how many _PB-row position sub-blocks one
    kernel grid step processes (amortizing the per-step scan/setup and
    pipeline-fetch overhead of one _PB block per step; the measured
    multiple of the bound is the benchmark's `dense_roofline`).  Liveness
    granularity stays one _PB sub-block: dead sub-blocks inside a live
    grid step still skip their compute.

    Env override PBCCS_DENSE_CB (>= 1), then an applied `ccs tune`
    host profile (runtime/tuning.py resolution ladder), then
    _CB_DEFAULT; clamped to the block count so short templates keep a
    non-degenerate grid."""
    env = os.environ.get("PBCCS_DENSE_CB")
    if env:
        cb = max(1, int(env))
    else:
        tuned = _tuning.knob_int("dense_cb")
        cb = max(1, tuned) if tuned is not None else _CB_DEFAULT
    if nb is not None:
        cb = min(cb, max(nb, 1))
    return cb


def cell_vmem_bytes(jmax: int, width: int) -> int:
    """Static per-grid-cell VMEM footprint estimate of the kernel's input
    refs (f32 lanes: 4 W-wide fills/reads + the packed 8-lane aux plane
    (off/apre/bsuf/wtpl/wtrans) + the 72-lane patch grid + 9 output
    lanes), at the current multi-column blocking factor."""
    cb = dense_cols_per_step(-(-jmax // _PB))
    return (cb * _PB + _HALO) * (4 * width + 8 + 72 + 9) * 4


# --------------------------------------------------------------------------
# XLA precompute: window-frame patch grids (static shifts, no row selects)
# --------------------------------------------------------------------------


def _shift_pos(x, t: int):
    """y[j] = x[clip(j + t, 0, n-1)] along axis 0 (static t)."""
    if t == 0:
        return x
    n = x.shape[0]
    if t > 0:
        tail = jnp.broadcast_to(x[n - 1:], (t,) + x.shape[1:])
        return jnp.concatenate([x[t:], tail], axis=0)
    head = jnp.broadcast_to(x[0:1], (-t,) + x.shape[1:])
    return jnp.concatenate([head, x[:t]], axis=0)


def dense_patch_grids(win_tpl, win_trans, table, wl, lead: int = 0,
                      rows: int | None = None):
    """Virtual-mutation patch TRANSITION planes for the full window-frame
    slot grid.

    win_tpl: (Jm,) int; win_trans: (Jm, 4); table: (8, 4); wl: scalar.
    `lead` / `rows` lay the grid out in the band frame: `rows` rows with
    position p at row lead + p (rows outside the window are never read).
    Returns trans (rows or Jm, 9, 2, 4) f32 with the same values
    make_patches_fast produces for (pos=j, mtype, new_base) of each slot
    -- but via static shifts and a tiny one-hot table lookup only (pos is
    an arange, so no runtime row selects are needed).  The patch BASES are
    not materialized: the kernel reads them straight off the window
    template (bases[0] is always tpl[p-1]; bases[1] is the slot's new
    base, a constant, or tpl[p+1] for deletions).
    Slot order: subs A,C,G,T; ins A,C,G,T; del (mutations._SLOT_* tables).
    """
    n = win_tpl.shape[0]
    Jm = n if rows is None else rows
    L = jnp.asarray(wl, jnp.int32)
    pos = jnp.arange(Jm, dtype=jnp.int32) - lead
    frame = lambda x: jnp.pad(
        x, [(lead, Jm - lead - n)] + [(0, 0)] * (x.ndim - 1))
    t32 = frame(win_tpl.astype(jnp.int32))
    win_trans = frame(win_trans)
    prev_b = _shift_pos(t32, -1)
    next_b = _shift_pos(t32, 1)
    trans_p1 = _shift_pos(win_trans, 1)

    def T(a, b):
        return transition_lookup(a, b, table)

    zeros4 = jnp.zeros((Jm, 4), jnp.float32)
    gate = lambda cond, v: jnp.where(cond[:, None], v, zeros4)

    trans = []
    for b in range(4):                                       # SUB b
        nb = jnp.full(Jm, b, jnp.int32)
        trans.append(jnp.stack([
            gate(pos > 0, T(prev_b, nb)),
            gate(pos + 1 < L, T(nb, next_b)),
        ], 1))
    for b in range(4):                                       # INS b
        nb = jnp.full(Jm, b, jnp.int32)
        trans.append(jnp.stack([
            gate(pos > 0, T(prev_b, nb)),
            gate(pos < L, T(nb, t32)),
        ], 1))
    trans.append(jnp.stack([                                 # DEL
        gate((pos > 0) & (pos < L - 1), T(prev_b, next_b)),
        gate(pos < L - 1, trans_p1),
    ], 1))
    return jnp.stack(trans, 1)


# --------------------------------------------------------------------------
# the kernel
# --------------------------------------------------------------------------


# shared circular-layout helpers (single source of truth in ops.fwdbwd)
_shift_lanes_circ = circ_roll
_hs_scan_circ = lambda b, c, W: _affine_scan_circ(b, c)


def _dense_kernel(alpha_ref, beta_ref, rbase_ref, rnext_ref, aux_ref,
                  pt_ref, i_ref, live_ref, out_ref, *, W: int, cb: int = 1):
    """Score all 9 slots of ONE (read, position-block-group) grid cell.

    Multi-column blocking: each grid step covers `cb` consecutive _PB-row
    position sub-blocks, so the per-step pipeline setup (block fetch,
    index maps, scan prologue) amortizes over cb * _PB template positions
    instead of _PB.  Each position-indexed ref is a (cb*_PB + _HALO, n)
    window of the FRAMED input (row _OFF0 + j holds position j; grid step
    b's window starts at row b*cb*_PB and overlaps the next step's by
    _HALO rows: an element-indexed BlockSpec on the one buffer the fill
    wrote, no blocked copy of it), so every slice below is (_PB, ...) at a
    static offset and the whole cell is contiguous VMEM reads + vector
    math.  Gridding over position block-groups keeps VMEM residency
    CONSTANT in template length and lets the pipeline stream the windows.

    aux_ref is the 8-lane packed plane of the five narrow operands
    (lane 0 off, 1 apre, 2 bsuf, 3 wtpl, 4:8 wtrans): one sublane read
    stream instead of five 1-to-4-lane streams.

    live_ref ((1, cb, 1) int32) gates each SUB-BLOCK: rounds > 0 of the
    refinement loop restrict candidates to nearby windows, so most
    (read, sub-block) cells have no valid slot and skip all compute
    (their scores are masked downstream; zeros written here are never
    read)."""
    for b2 in range(cb):
        lv = live_ref[0, b2, 0]

        @pl.when(lv == 0)
        def _dead(b2=b2):
            out_ref[pl.dslice(b2 * _PB, _PB)] = jnp.zeros(
                (_PB, N_SLOTS), jnp.float32)

        @pl.when(lv != 0)
        def _live(b2=b2):
            out_ref[pl.dslice(b2 * _PB, _PB)] = _dense_kernel_body(
                alpha_ref, beta_ref, rbase_ref, rnext_ref, aux_ref,
                pt_ref, i_ref, W=W, base_off=b2 * _PB)


def _dense_kernel_body(alpha_ref, beta_ref, rbase_ref, rnext_ref, aux_ref,
                       pt_ref, i_ref, *, W: int, base_off=0):
    hit = 1.0 - MISMATCH_PROBABILITY
    miss = MISMATCH_PROBABILITY / 3.0
    I = i_ref[...]  # (1, 1) int32, broadcasts against (PB, W)
    # base_off: this sub-block's row offset in the step's window
    def crows(o_col):
        """(PB, W) absolute row per circular lane for (PB, 1) per-position
        offsets (fwdbwd.circ_rows over the position axis)."""
        return circ_rows(o_col[:, 0], W)

    def in_band(rows, o):
        return (rows >= o) & (rows < o + W)

    def ext_parts(prev, o_prev, rows):
        """The (pm1, p0) cross-column operands of ExtendAlpha — they
        depend only on (prev, o_prev, rows), so callers sharing a
        previous column compute them ONCE (the four SUB/INS ext0
        columns share everything but the insertion coefficient; the
        s/i second columns at one base share their prev)."""
        pm1 = jnp.where(in_band(rows - 1, o_prev),
                        _shift_lanes_circ(prev, 1), 0.0)
        p0 = jnp.where(in_band(rows, o_prev), prev, 0.0)
        return pm1, p0

    def ext_b(pm1, p0, rows, em, prev_tr):
        """b-coefficient from shared cross-column operands + emission."""
        in_read = (rows >= 1) & (rows <= I)
        b = pm1 * em * jnp.where(rows < I, prev_tr[:, TRANS_MATCH:TRANS_MATCH + 1], 0.0)
        b = b + jnp.where(rows != I,
                          p0 * prev_tr[:, TRANS_DARK:TRANS_DARK + 1], 0.0)
        return jnp.where(in_read, b, 0.0)

    def cmask(rows, o_col):
        """Shared insertion-coefficient gate of one (rows, o_col) pair."""
        return (rows > 1) & (rows < I) & (rows > o_col)

    def ext_c(mask_c, rbase, next_b, cur_tr):
        ins_em = jnp.where(rbase == next_b,
                           cur_tr[:, TRANS_BRANCH:TRANS_BRANCH + 1],
                           cur_tr[:, TRANS_STICK:TRANS_STICK + 1] / 3.0)
        return jnp.where(mask_c, ins_em, 0.0)

    def ext_col(prev, o_prev, o_col, rows, rbase, cur_b, next_b,
                prev_tr, cur_tr):
        """One interior ExtendAlpha column over (_PB, W); mirrors
        ops.mutation_score._ext_col with the interior-only masks.
        Circular lanes: the cross-column operand is one static roll +
        in-band mask (any offset delta), replacing the bounded
        shift-variant selects."""
        pm1, p0 = ext_parts(prev, o_prev, rows)
        em = jnp.where(rbase == cur_b, hit, miss)
        b = ext_b(pm1, p0, rows, em, prev_tr)
        c = ext_c(cmask(rows, o_col), rbase, next_b, cur_tr)
        return _hs_scan_circ(b, c, W)

    def beta_pair(rows, bcol, o_b):
        """(beta_{i+1}, beta_i) operands of LinkAlphaBeta — shared by
        every link against the same (rows, beta column)."""
        beta_ip1 = jnp.where(in_band(rows + 1, o_b),
                             _shift_lanes_circ(bcol, -1), 0.0)
        beta_i = jnp.where(in_band(rows, o_b), bcol, 0.0)
        return beta_ip1, beta_i

    def link_shared(ext1, link_tr, mterm, beta_i, apre_s, bsuf_b):
        """LinkAlphaBeta with the (em_link * beta_{i+1} * [rows < I])
        match operand precomputed (mterm) — it is slot-independent for
        every slot family linking the same beta column."""
        match = ext1 * link_tr[:, TRANS_MATCH:TRANS_MATCH + 1] * mterm
        dele = ext1 * link_tr[:, TRANS_DARK:TRANS_DARK + 1] * beta_i
        v = jnp.sum(match + dele, axis=1)
        return jnp.log(jnp.maximum(v, _TINY)) + apre_s[:, 0] + bsuf_b[:, 0]

    def link(ext1, rows, rn_s1, link_tr, link_b, bcol, o_b, apre_s, bsuf_b):
        em_link = jnp.where(rn_s1 == link_b, hit, miss)
        beta_ip1, beta_i = beta_pair(rows, bcol, o_b)
        mterm = jnp.where(rows < I, em_link * beta_ip1, 0.0)
        return link_shared(ext1, link_tr, mterm, beta_i, apre_s, bsuf_b)

    def at(ref, off):
        return ref[pl.dslice(base_off + _OFF0 + off, _PB)]

    # shared position-aligned slices; the five narrow operands ride ONE
    # packed 8-lane aux plane (lane 0 off | 1 apre | 2 bsuf | 3 wtpl |
    # 4:8 wtrans), so each row offset costs one sublane read
    a_m1, a_m2 = at(alpha_ref, -1), at(alpha_ref, -2)
    b_p1, b_p2 = at(beta_ref, 1), at(beta_ref, 2)
    rb_m1, rb_0, rb_p1 = at(rbase_ref, -1), at(rbase_ref, 0), at(rbase_ref, 1)
    rn_0, rn_p1 = at(rnext_ref, 0), at(rnext_ref, 1)
    ax_m3, ax_m2, ax_m1 = at(aux_ref, -3), at(aux_ref, -2), at(aux_ref, -1)
    ax_0, ax_p1, ax_p2 = at(aux_ref, 0), at(aux_ref, 1), at(aux_ref, 2)
    off = lambda ax: ax[:, 0:1].astype(jnp.int32)  # exact: offsets < 2^24
    o_m2, o_m1, o_0 = off(ax_m2), off(ax_m1), off(ax_0)
    o_p1, o_p2 = off(ax_p1), off(ax_p2)
    ap_m1, ap_0 = ax_m1[:, 1:2], ax_0[:, 1:2]
    bs_p1, bs_p2 = ax_p1[:, 2:3], ax_p2[:, 2:3]
    w_m2, w_m1 = ax_m2[:, 3:4], ax_m1[:, 3:4]
    w_0, w_p1 = ax_0[:, 3:4], ax_p1[:, 3:4]
    wt_m3, wt_m2 = ax_m3[:, 4:8], ax_m2[:, 4:8]
    rows_m1, rows_0, rows_p1 = crows(o_m1), crows(o_0), crows(o_p1)

    outs = [None] * N_SLOTS
    # ---- SUB + INS slots (s = p): patch = [prev_b, nb] --------------
    # SUB b and INS b have the IDENTICAL first extend column (same
    # patched transitions T(prev_b, nb) and same alpha seed); compute
    # ext0 once per base and branch only on the second column.  The b-
    # coefficient of ALL FOUR ext0 columns is fully shared (same prev
    # column, emission against the same unmutated base w_m1, same
    # transitions wt_m2) — only the insertion coefficient differs per
    # base — and the second columns / links share their cross-column
    # and beta operands per family; everything slot-invariant is
    # hoisted out of the per-base loop.
    pm1_0, p0_0 = ext_parts(a_m1, o_m1, rows_0)
    em_0 = jnp.where(rb_0 == w_m1, hit, miss)
    b0_shared = ext_b(pm1_0, p0_0, rows_0, em_0, wt_m2)
    mask_c0 = cmask(rows_0, o_0)
    mask_c1 = cmask(rows_p1, o_p1)
    # link operands per family: s-links hit beta col p+2, i-links p+1
    lt_p1 = rows_p1 < I
    bip1_s, bi_s = beta_pair(rows_p1, b_p2, o_p2)
    em_s = jnp.where(rn_p1 == w_p1, hit, miss)
    mterm_s = jnp.where(lt_p1, em_s * bip1_s, 0.0)
    bip1_i, bi_i = beta_pair(rows_p1, b_p1, o_p1)
    em_i = jnp.where(rn_p1 == w_0, hit, miss)
    mterm_i = jnp.where(lt_p1, em_i * bip1_i, 0.0)
    for b in range(4):
        t0 = pt_ref[pl.dslice(base_off + _OFF0, _PB),
                     pl.dslice((b * 2 + 0) * 4, 4)]
        t1s = pt_ref[pl.dslice(base_off + _OFF0, _PB),
                      pl.dslice((b * 2 + 1) * 4, 4)]
        t1i = pt_ref[pl.dslice(base_off + _OFF0, _PB),
                      pl.dslice((8 + b * 2 + 1) * 4, 4)]
        nb = jnp.float32(b)
        ext0 = _hs_scan_circ(b0_shared, ext_c(mask_c0, rb_0, nb, t0), W)
        pm1_1, p0_1 = ext_parts(ext0, o_0, rows_p1)
        em_1 = jnp.where(rb_p1 == nb, hit, miss)
        b1 = ext_b(pm1_1, p0_1, rows_p1, em_1, t0)
        ext1s = _hs_scan_circ(b1, ext_c(mask_c1, rb_p1, w_p1, t1s), W)
        outs[b] = link_shared(ext1s, t1s, mterm_s, bi_s, ap_0, bs_p2)
        ext1i = _hs_scan_circ(b1, ext_c(mask_c1, rb_p1, w_0, t1i), W)
        outs[4 + b] = link_shared(ext1i, t1i, mterm_i, bi_i, ap_0, bs_p1)
    # ---- DEL slot (s = p-1): patch = [prev_b, next_b] ---------------
    t0 = pt_ref[pl.dslice(base_off + _OFF0, _PB), pl.dslice(16 * 4, 4)]
    ext0 = ext_col(a_m2, o_m2, o_m1, rows_m1, rb_m1, w_m2, w_m1,
                   wt_m3, wt_m2)
    ext1 = ext_col(ext0, o_m1, o_0, rows_0, rb_0, w_m1, w_p1, wt_m2, t0)
    outs[8] = link(ext1, rows_0, rn_0, t0, w_p1, b_p2,
                   o_p2, ap_m1, bs_p2)

    return jnp.stack(outs, axis=1)


def _dense_grid_shape(jmax: int) -> tuple[int, int]:
    """(cb, NBC) of the kernel grid at this template bucket: cb sub-blocks
    per grid step (dense_cols_per_step), NBC grid steps."""
    nb = -(-jmax // _PB)
    cb = dense_cols_per_step(nb)
    return cb, -(-nb // cb)


def _pad_pos(x, total: int):
    """A narrow position-indexed per-read array in the band frame: row
    _OFF0 + j = x[:, j], `total` rows."""
    n = x.shape[1]
    return jnp.pad(x, [(0, 0), (_OFF0, total - _OFF0 - n)]
                   + [(0, 0)] * (x.ndim - 2))


class DenseLayout(typing.NamedTuple):
    """What a dense score call reads besides the alpha and beta bands, in
    the same frame as they (fwdbwd.BAND_LEAD: (R, rows, n), position j at
    row _OFF0 + j), built ONCE per fill rebuild instead of inside every
    per-round score graph and written where it is read: no pad, halo copy
    or transpose follows.  Produced by prepare_dense_layout (or
    build_dense_layout under an enclosing trace), consumed by
    dense_interior_scores_batch + edge_window_scores_batch; carried
    across refinement rounds by device_refine.RefineLoopState so rounds
    that apply no mutation relaunch on the previous round's buffers.

    rbase/rnext: the band_read_windows pair (W lanes); aux: the packed
    8-lane narrow-operand plane (off|apre|bsuf|wtpl|wtrans4); ptr: the
    72-lane patch-transition plane.  The kernel takes overlapping
    windows of each, the edge program two 16-row ones (_edge_windows)."""

    rbase: jax.Array
    rnext: jax.Array
    aux: jax.Array
    ptr: jax.Array


def build_dense_layout(reads, rlens, win_tpl, win_trans, wlens, tables,
                       alpha: BandedMatrix, beta: BandedMatrix, apre, bsuf,
                       width: int, windows=None) -> DenseLayout:
    """Build the DenseLayout for a flat read batch (trace-time helper;
    prepare_dense_layout is the jitted entry).  Takes the score calls'
    operands, so one argument tuple serves all three.  `windows`: the
    (rbase, rnext) planes where the caller holds them already (the refine
    loop's rebuild makes them for the reads it refills)."""
    R = reads.shape[0]
    W = width
    rows = band_frame_rows(alpha.offsets.shape[1])
    rbase, rnext = windows or band_read_windows(reads, alpha.offsets, W, rows)
    ptr = jax.vmap(
        lambda t, tr, tb, wl: dense_patch_grids(t, tr, tb, wl, _OFF0, rows)
    )(win_tpl.astype(jnp.int32), win_trans, tables, wlens)
    # the five narrow per-position operands pack into ONE 8-lane plane
    # (kernel lane map: 0 off, 1 apre, 2 bsuf, 3 wtpl, 4:8 wtrans) so the
    # kernel reads one sublane stream instead of five; each is framed
    # first (their native column counts differ: nc, nc+1, Jm).  Selected
    # lane by lane into the plane in one pass: a (R, rows, 1) piece tiles
    # to 128 lanes, so padding and concatenating eight of them wrote the
    # plane's bytes nine times over
    f32 = lambda x: _pad_pos(x.astype(jnp.float32), rows)
    lane = jnp.arange(8, dtype=jnp.int32)
    aux = jnp.zeros((R, rows, 8), jnp.float32)
    for k, x in enumerate([f32(alpha.offsets), f32(apre), f32(bsuf),
                           f32(win_tpl)]
                          + [f32(win_trans[:, :, c]) for c in range(4)]):
        aux = jnp.where(lane == k, x[:, :, None], aux)
    return DenseLayout(*map(row_major, (rbase, rnext, aux,
                                        ptr.reshape(R, rows, 72))))


prepare_dense_layout = jax.jit(build_dense_layout, static_argnames=("width",))


@functools.partial(jax.jit, static_argnames=("width",))
def dense_interior_scores_batch(reads, rlens, win_tpl, win_trans, wlens,
                                tables, alpha: BandedMatrix,
                                beta: BandedMatrix, apre, bsuf, width: int,
                                live=None,
                                layout: DenseLayout | None = None):
    """(R, Jm, 9) window-frame interior scores for a flat read batch.

    reads (R, Imax) int; rlens (R,); win_tpl (R, Jm); win_trans (R, Jm, 4);
    wlens (R,); tables (R, 8, 4); alpha/beta batched banded fills on the
    unmutated windows, framed as the Pallas fills write them (plain XLA
    fills are framed here, a pad); apre/bsuf (R, nc+1) scale prefixes.
    Entry [r, p, k] is the absolute mutated-window log-likelihood of slot
    (p, k) for read r, valid where the caller's interior classification
    holds.  `layout`: a pre-baked DenseLayout (prepare_dense_layout) --
    without one it is derived in-graph."""
    R = reads.shape[0]
    Jm = win_tpl.shape[1]
    W = width
    cb, NBC = _dense_grid_shape(Jm)
    NB = -(-Jm // _PB)

    alpha, beta = band_frame(alpha), band_frame(beta)
    if layout is None:
        layout = build_dense_layout(reads, rlens, win_tpl, win_trans,
                                    wlens, tables, alpha, beta, apre, bsuf,
                                    width)
    i_in = rlens[:, None, None].astype(jnp.int32)

    # sub-block liveness survives multi-column blocking: the (R, NB) mask
    # pads to (R, NBC*cb) with dead cells and reshapes per step
    live_nb = jnp.ones((R, NB), jnp.int32) if live is None \
        else live.astype(jnp.int32)
    live_in = jnp.pad(live_nb, [(0, 0), (0, NBC * cb - NB)]).reshape(
        R, NBC, cb)[:, :, :, None]
    # step b reads rows [b*step, b*step + PBH) of each framed operand;
    # sub-blocks past the template's last are dead, so where the last
    # window passes the frame's end it is the window's padding they see
    step, PBH = cb * _PB, cb * _PB + _HALO
    over = max(0, (NBC - 1) * step + PBH - alpha.vals.shape[1])
    win = lambda n: pl.BlockSpec(
        (None, pl.Element(PBH, (0, over)), pl.Element(n)),
        lambda r, b: (r, b * step, 0))
    return pl.pallas_call(
        functools.partial(_dense_kernel, W=W, cb=cb),
        grid=(R, NBC),
        in_specs=[
            win(W), win(W), win(W), win(W),              # alpha/beta/rb/rn
            win(8),                                      # packed aux
            win(72),                                     # patch trans
            pl.BlockSpec((None, 1, 1), lambda r, b: (r, 0, 0)),  # rlen
            pl.BlockSpec((None, 1, cb, 1),
                         lambda r, b: (r, b, 0, 0)),     # live
        ],
        out_specs=pl.BlockSpec((None, step, N_SLOTS),
                               lambda r, b: (r, b, 0)),
        out_shape=jax.ShapeDtypeStruct((R, Jm, N_SLOTS), jnp.float32),
        interpret=_interpret(),
    )(
        alpha.vals, beta.vals, layout.rbase, layout.rnext,
        layout.aux, layout.ptr, i_in, live_in,
    )


# --------------------------------------------------------------------------
# window-frame edge-slot scoring
#
# Slots the interior kernel cannot score live at STATIC window-frame
# positions: near-begin rows {0, 1, 2} and near-end rows {J-2, J-1, J}
# (sub/del are edge from J-2, ins from J-1; slot_geometry's classification
# expressed in window frame).  The template-frame edge machinery the dense
# path previously reused (_batch_edge_fast_totals over a packed edge
# slab) rebuilt full-window im2cols, neighborhoods and
# one-hot row-selects per read per round -- ~half of all device time on the
# round-4 profile.  Here the same extend/link algebra (the edge_scores_fast
# oracle, reference MutationScorer.cpp:208-231) is evaluated once per read
# over a (6, 9) window-frame slot grid with STATIC per-slot geometry:
# every index is either a static slice or one J-relative contiguous
# dynamic slice, so the whole program is ~7 small column extensions over
# (R, 27, W) tensors.  Parity: tests/test_dense_score.py fuzzes against
# edge_scores_fast.
# --------------------------------------------------------------------------

# static 27-slot tables (3 position rows x 9 slots, slot order = host
# enumeration: subs A,C,G,T; ins A,C,G,T; del)
_K27 = np.tile(np.arange(9), 3)
_Q27 = np.repeat(np.arange(3), 9)
_SHIFT27 = np.array([0, 0, 0, 0, -1, -1, -1, -1, 1])[_K27]
_LD27 = -_SHIFT27
_NEWBASE27 = np.array([0, 1, 2, 3, 0, 1, 2, 3, -1])[_K27]
_ISDEL27 = (_K27 == 8)
# near-end replace mask: row J-2 keeps its ins slots (they are interior)
_NE_MASK9 = np.array([[True] * 4 + [False] * 4 + [True],
                      [True] * 9,
                      [True] * 9])


_EW = 16   # rows of an edge window: 8-aligned, and holds any 7 rows in a row


def _edge_windows(alpha_v, beta_v, layout: DenseLayout, J, jmax: int):
    """Every row of the framed buffers the edge programs read, as seven
    (R, _EW, n) windows fetched by one copy-only kernel: rows [0, _EW) of
    rbase, rnext, beta and the patch plane (near-begin: columns 1..4,
    beta columns 4..6, positions 0..2) and the _EW rows from the 8-row
    tile that holds column J - 4 of alpha, rbase and the patch plane
    (near-end: columns J-4..J+2).  Returns (windows, rem), rem = the row
    of column J - 4 in the near-end windows.

    A kernel, because XLA relayouts a WHOLE band for each of these reads
    (a gather wants its sliced axis major-most, and even a one-row static
    slice pulled the band into the layout of its small result): five
    band copies a round, from buffers the kernels need row-major."""
    R = alpha_v.shape[0]
    col = _OFF0 + J.astype(jnp.int32) - 4
    tile = col // 8          # prefetched; * 8 in the index map is provably aligned
    over = max(0, jmax // 8 * 8 + _EW - alpha_v.shape[1])  # J <= jmax

    def win(x, at_j: bool):
        idx = (lambda r, t: (r, t[r] * 8, 0)) if at_j else \
            (lambda r, b: (r, 0, 0))
        return pl.BlockSpec((None, pl.Element(_EW, (0, over if at_j else 0)),
                             pl.Element(x.shape[2])), idx)

    ops = [(layout.rbase, False), (layout.rnext, False), (beta_v, False),
           (layout.ptr, False), (alpha_v, True), (layout.rbase, True),
           (layout.ptr, True)]

    def kernel(tile_ref, *refs):
        for src, dst in zip(refs[:len(ops)], refs[len(ops):]):
            dst[...] = src[...]

    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R,),
            in_specs=[win(x, at_j) for x, at_j in ops],
            out_specs=[pl.BlockSpec((None, _EW, x.shape[2]),
                                    lambda r, b: (r, 0, 0)) for x, _ in ops]),
        out_shape=[jax.ShapeDtypeStruct((R, _EW, x.shape[2]), jnp.float32)
                   for x, _ in ops],
        interpret=_interpret(),
    )(tile, *(x for x, _ in ops))
    return outs, col - tile * 8


def _edge_read_windows(rb_begin, rn_begin, rb_end, rem, W: int):
    """(R, 11, W) circular-lane read windows for the edge programs, from
    the edge windows of DenseLayout.rbase / .rnext (read_pad1 / read_pad0
    windows at every column's band offset).

    Rows 0-3: columns 1..4 (the near-begin refill columns); row 4: the
    read_pad0 window at column 4's offset (the near-begin link row);
    rows 5-10: columns J-3..J+2 (the near-end extension columns; the
    frame's rows past the last column hold its offset's window, like the
    edge oracle's offs_pad)."""
    wins_ne = jax.vmap(
        lambda rb, k: lax.dynamic_slice(rb, (k + 1, 0), (6, W))
    )(rb_end, rem)                                               # (R, 6, W)
    return jnp.concatenate([rb_begin[:, _OFF0 + 1: _OFF0 + 5],
                            rn_begin[:, _OFF0 + 4: _OFF0 + 5],
                            wins_ne], axis=1)


def _edge_nb_read(wins, I, tpl, trans, J, offs, bvals, boffs, bsuf, pt3,
                  *, W: int):
    """Near-begin scores of one read: (27,) absolute LLs for slots at
    window positions {0, 1, 2} (rows of pt3).  Mirrors edge_scores_fast's
    near-begin branch: refill virtual DP columns 1..4 from the pinned
    start, LinkAlphaBeta at virtual column 4 against saved beta column
    5 - ld.  `wins` are this read's precomputed circular read windows
    (_edge_read_windows rows: 0-3 = columns 1..4, 4 = the link row);
    `bvals` holds the framed beta band's first rows."""
    from pbccs_tpu.ops.mutation_score import (_circ_rows_batch, _ext_col,
                                              _in_band)

    eps = MISMATCH_PROBABILITY
    hit, em_miss = 1.0 - eps, eps / 3.0
    M = 27
    tplf = tpl.astype(jnp.float32)
    maxl = J + jnp.asarray(_LD27, jnp.int32)

    # per-slot virtual template bases/trans at static absolute window
    # indices (p, k, shift all static per slot; patch overrides at
    # p-1 / p; index shift beyond p).  Deliberately per-slot static
    # SLICES stacked in a Python loop: the "vectorized" static-fancy-index
    # form lowers to TPU scalar-core gathers and measured ~6% slower
    # end to end.
    def vB(v: int):
        cols = []
        for m in range(M):
            p = int(_Q27[m])
            if v == p - 1:
                cols.append(tplf[max(p - 1, 0)])
            elif v == p:
                if _ISDEL27[m]:
                    cols.append(tplf[p + 1])
                else:
                    cols.append(jnp.float32(_NEWBASE27[m]))
            else:
                idx = v + (int(_SHIFT27[m]) if v > p else 0)
                cols.append(tplf[min(max(idx, 0), tpl.shape[0] - 1)])
        return jnp.stack(cols)

    def vT(v: int):
        rows = []
        for m in range(M):
            p, k = int(_Q27[m]), int(_K27[m])
            if v == p - 1:
                rows.append(pt3[p, k, 0])
            elif v == p:
                rows.append(pt3[p, k, 1])
            else:
                idx = v + (int(_SHIFT27[m]) if v > p else 0)
                rows.append(trans[min(max(idx, 0), trans.shape[0] - 1)])
        return jnp.stack(rows)

    one_col = functools.partial(_ext_col, I=I, max_left=maxl,
                                hit=hit, em_miss=em_miss, W=W)
    ext = jnp.zeros((M, W), jnp.float32).at[:, 0].set(1.0)  # alpha(0,0)=1
    o_prev = offs[0]
    for j in range(1, 5):
        o_j = offs[j]
        rb_j = jnp.broadcast_to(wins[j - 1], (M, W))
        ext = one_col(ext, jnp.broadcast_to(o_prev, (M,)),
                      jnp.broadcast_to(o_j, (M,)), rb_j,
                      jnp.full((M,), j, jnp.int32),
                      vB(j - 1), vB(j), vT(j - 2), vT(j - 1))
        o_prev = o_j

    blc = 5 + _SHIFT27                                   # 5 - ld, static
    B_col = bvals[_OFF0 + blc]                           # (27, W)
    o_b = boffs[blc]
    bsuf_b = bsuf[blc]
    rows4 = _circ_rows_batch(jnp.broadcast_to(offs[4], (M,)), W)
    link_tr = vT(3)
    link_b = vB(4)
    rn4 = jnp.broadcast_to(wins[4], (M, W))
    em_link = jnp.where(rn4 == link_b[:, None], hit, em_miss)
    from pbccs_tpu.ops.fwdbwd import circ_roll
    beta_ip1 = jnp.where(_in_band(rows4 + 1, o_b, W),
                         circ_roll(B_col, -1), 0.0)
    beta_i = jnp.where(_in_band(rows4, o_b, W), B_col, 0.0)
    match = jnp.where(rows4 < I, ext * link_tr[:, TRANS_MATCH][:, None]
                      * em_link * beta_ip1, 0.0)
    dele = ext * link_tr[:, TRANS_DARK][:, None] * beta_i
    v = jnp.sum(match + dele, axis=1)
    return jnp.log(jnp.maximum(v, _TINY)) + bsuf_b


def _edge_ne_read(wins, I, tpl, trans, J, A5, offs, apre, ptS,
                  *, W: int):
    """Near-end scores of one read: (27,) absolute LLs for slots at
    window positions {J-2, J-1, J}.  Mirrors edge_scores_fast's near-end
    branch: extend saved alpha columns s..s+2 through the pinned (I, J')
    corner; LL = log corner + alpha scale prefix.  Geometry is static in
    the J-relative frame, so every load is one contiguous dynamic slice.
    `wins` are this read's precomputed circular read windows
    (_edge_read_windows rows 5-10 = columns J-3..J+2); `A5` is alpha
    columns J-4..J and `ptS` the (3, 9, 2, 4) patch transitions of
    positions J-2..J.
    Caller guarantees J >= 8 (tiny windows bail to the host path)."""
    from pbccs_tpu.ops.mutation_score import _ext_col

    eps = MISMATCH_PROBABILITY
    hit, em_miss = 1.0 - eps, eps / 3.0
    M = 27
    nc = offs.shape[0]
    tplf = tpl.astype(jnp.float32)
    maxl = J + jnp.asarray(_LD27, jnp.int32)

    # J-relative contiguous slices (padded so no dynamic_slice clamping)
    offs_pad = jnp.concatenate([offs, jnp.broadcast_to(offs[nc - 1:], (2,))])
    offs7 = lax.dynamic_slice(offs_pad, (J - 4,), (7,))      # J-4..J+2
    apre4 = lax.dynamic_slice(apre, (J - 3,), (4,))          # cols J-3..J
    tplS = lax.dynamic_slice(
        jnp.concatenate([tplf, jnp.full(4, 4.0)]), (J - 6,), (10,))
    transS = lax.dynamic_slice(
        jnp.concatenate([trans, jnp.zeros((3, 4))]), (J - 6, 0), (9, 4))
    rb6 = wins[5:11]                                         # cols J-3..J+2

    # t = s - (J-4) in {1..4}, static per slot (s = p - [k==del])
    t_np = _Q27 + 2 - _ISDEL27.astype(int)

    def pick7(idx_np):
        return offs7[np.clip(idx_np, 0, 6)]

    o_sm1, o_s = pick7(t_np - 1), pick7(t_np)
    o_s1, o_s2 = pick7(t_np + 1), pick7(t_np + 2)
    A_prev = A5[np.clip(t_np - 1, 0, 4)]                     # (27, W)
    rb_s = rb6[np.clip(t_np - 1, 0, 5)]
    rb_s1 = rb6[np.clip(t_np, 0, 5)]
    rb_s2 = rb6[np.clip(t_np + 1, 0, 5)]
    s_col = J - 4 + jnp.asarray(t_np, jnp.int32)
    apre_s = apre4[np.clip(t_np - 1, 0, 3)]

    # virtual lookups at J-relative static indices: rel r = v - (J-6);
    # v queried at s-1..s+2 (bases) and s-2..s+1 (trans), p = J-2+q.
    # Per-slot static slices (not fancy-index gathers; see vB above).
    def vB_rel(dv: int):
        cols = []
        for m in range(M):
            q = int(_Q27[m])
            s_rel = 2 + int(t_np[m])                  # s - (J-6)
            v = s_rel + dv                            # v - (J-6)
            p_rel = 4 + q                             # p - (J-6)
            if v == p_rel - 1:
                cols.append(tplS[p_rel - 1])
            elif v == p_rel:
                if _ISDEL27[m]:
                    cols.append(tplS[p_rel + 1])
                else:
                    cols.append(jnp.float32(_NEWBASE27[m]))
            else:
                idx = v + (int(_SHIFT27[m]) if v > p_rel else 0)
                cols.append(tplS[min(max(idx, 0), 9)])
        return jnp.stack(cols)

    def vT_rel(dv: int):
        rows = []
        for m in range(M):
            q, k = int(_Q27[m]), int(_K27[m])
            s_rel = 2 + int(t_np[m])
            v = s_rel + dv
            p_rel = 4 + q
            if v == p_rel - 1:
                rows.append(ptS[q, k, 0])
            elif v == p_rel:
                rows.append(ptS[q, k, 1])
            else:
                idx = v + (int(_SHIFT27[m]) if v > p_rel else 0)
                rows.append(transS[min(max(idx, 0), 8)])
        return jnp.stack(rows)

    one_col = functools.partial(_ext_col, I=I, max_left=maxl,
                                hit=hit, em_miss=em_miss, W=W)
    ext0 = one_col(A_prev, o_sm1, o_s, rb_s, s_col,
                   vB_rel(-1), vB_rel(0), vT_rel(-2), vT_rel(-1))
    ext1 = one_col(ext0, o_s, o_s1, rb_s1, s_col + 1,
                   vB_rel(0), vB_rel(1), vT_rel(-1), vT_rel(0))
    ext2 = one_col(ext1, o_s1, o_s2, rb_s2, s_col + 2,
                   vB_rel(1), vB_rel(2), vT_rel(0), vT_rel(1))

    kstar = maxl - s_col                                     # 1 or 2
    corner_vals = jnp.where((kstar == 1)[:, None], ext1, ext2)
    o_corner = jnp.where(kstar == 1, o_s1, o_s2)
    karange = jnp.arange(W, dtype=jnp.int32)[None, :]
    in_b = ((I >= o_corner) & (I < o_corner + W))[:, None]
    corner = jnp.sum(jnp.where((karange == (I % W)) & in_b,
                               corner_vals, 0.0), axis=1)
    return jnp.log(jnp.maximum(corner, _TINY)) + apre_s


@functools.partial(jax.jit, static_argnames=("width",))
def edge_window_scores_batch(reads, rlens, win_tpl, win_trans, wlens,
                             tables, alpha: BandedMatrix,
                             beta: BandedMatrix, apre, bsuf, width: int,
                             layout: DenseLayout | None = None):
    """(R, 6, 9) window-frame edge-slot scores: rows 0..2 = window
    positions {0, 1, 2} (near-begin), rows 3..5 = {J-2, J-1, J}
    (near-end).  Entries whose slot is actually interior (ins at J-2) or
    invalid are garbage the caller masks/splices around.  Same operands
    as dense_interior_scores_batch: the framed bands and a DenseLayout
    (derived in-graph without one), read in place through _edge_windows
    -- a few rows of a read's bands, windows and 72-lane patch plane,
    which is viewed as (9, 2, 4) only after slicing (a whole plane in
    that view tiles its (2, 4) minor dims to (4, 128): 14x the bytes)."""
    alpha, beta = band_frame(alpha), band_frame(beta)
    if layout is None:
        layout = build_dense_layout(reads, rlens, win_tpl, win_trans,
                                    wlens, tables, alpha, beta, apre, bsuf,
                                    width)
    J = wlens.astype(jnp.int32)
    (rb_b, rn_b, beta_b, pt_b, alpha_e, rb_e, pt_e), rem = _edge_windows(
        alpha.vals, beta.vals, layout, J, win_tpl.shape[1])
    wins = _edge_read_windows(rb_b, rn_b, rb_e, rem, width)

    def one(w11, I, tpl, trans, J, aoffs, bvals, boffs, ap, bs, pt3, a16,
            pt16, k):
        nb = _edge_nb_read(w11, I, tpl, trans, J, aoffs, bvals, boffs, bs,
                           pt3.reshape(3, 9, 2, 4), W=width)
        ne = _edge_ne_read(
            w11, I, tpl, trans, J, lax.dynamic_slice(a16, (k, 0), (5, width)),
            aoffs, ap, lax.dynamic_slice(pt16, (k + 2, 0),
                                         (3, 72)).reshape(3, 9, 2, 4),
            W=width)
        return jnp.concatenate([nb.reshape(3, 9), ne.reshape(3, 9)])

    return jax.vmap(one)(wins, rlens.astype(jnp.int32),
                         win_tpl.astype(jnp.int32), win_trans, J,
                         alpha.offsets.astype(jnp.int32), beta_b,
                         beta.offsets.astype(jnp.int32), apre, bsuf,
                         pt_b[:, _OFF0: _OFF0 + 3], alpha_e, pt_e, rem)


def slot_major_spliced(grid, e6, J):
    """The dense kernel's (R, Jm, 9) window-frame scores turned slot-major,
    (R, 9, Jm): positions on the lanes, so the nine slots no longer tile
    to 128 (a (.., Jm, 9) float32 array is 14x its own bytes in HBM), with
    every read's window-frame rows {0,1,2, J-2,J-1,J} overwritten by the
    (R, 6, 9) edge scores `e6` (ins at J-2 keeps its interior-kernel
    value).  This is the one read of the kernel's output in a score call;
    what follows (slot_grid_totals: the orientation mapping, the masks,
    the reduction over reads) runs on slot-major arrays.

    The splice is masked selects: per-read dynamic_update_slices lower to
    vmapped scatters."""
    Jm = grid.shape[1]
    g = row_major(jnp.swapaxes(grid, 1, 2))                       # (R, 9, Jm)
    e = jnp.swapaxes(e6, 1, 2)                                    # (R, 9, 6)
    pos = jnp.arange(Jm, dtype=jnp.int32)[None, None, :]
    Jc = J.astype(jnp.int32)[:, None, None]
    ne_mask = jnp.asarray(_NE_MASK9)                              # (3, 9)
    for i in range(3):
        g = jnp.where(pos == i, e[:, :, i:i + 1], g)
    for i in range(3):
        g = jnp.where((pos == Jc - 2 + i) & ne_mask[i][None, :, None],
                      e[:, :, 3 + i:4 + i], g)
    return g


# --------------------------------------------------------------------------
# orientation mapping: window-frame grid -> template-frame slot grid
# --------------------------------------------------------------------------

def _reverse_frame(grid_s):
    """A slot-major (R, 9, Jm) grid reversed along its positions, with the
    rev-frame slot permutation sub b <-> sub 3-b, ins b <-> ins 3-b, del:
    [3, 2, 1, 0, 7, 6, 5, 4, 8] is each base quartet reversed, so it is
    three static reversals and no gather."""
    return jnp.concatenate([jnp.flip(grid_s[:, 0:4], (1, 2)),
                            jnp.flip(grid_s[:, 4:8], (1, 2)),
                            jnp.flip(grid_s[:, 8:9], 2)], axis=1)


def _totals_kernel(par_ref, base_ref, x_ref, valid_ref, ms_ref, me_ref,
                   ins_ref, o_ref, acc_ref, *, R: int, Jm: int):
    """One ZMW: its reads' oriented window-frame slot grids (R, 9, L)
    shifted onto the template frame, masked, baselined and summed over the
    reads into (9, L).  par_ref (5, Z*R) int32 in SMEM: shift, ts, te,
    strand, live of every read; base_ref (Z*R,) float32 in SMEM."""
    z = pl.program_id(0)
    ms, me, ins = ms_ref[...], me_ref[...], ins_ref[...] != 0
    valid = valid_ref[...] != 0
    pos = lax.broadcasted_iota(jnp.int32, ms.shape, 1)
    n = acc_ref.shape[0]
    for r in range(R):
        k = z * R + r
        ts, te, strand = par_ref[1, k], par_ref[2, k], par_ref[3, k]
        rev_ins = ins & (strand != 0)
        x = pltpu.roll(x_ref[r], par_ref[0, k], axis=1)
        # a reverse read's insertion slots sit one row further on
        x = jnp.where(rev_ins, pltpu.roll(x, 1, axis=1), x)
        row = jnp.where(strand == 0, pos - ts,
                        jnp.where(ins, te, te - 1) - pos)
        mapped = jnp.where((row >= 0) & (row < Jm), x, 0.0)
        overlap, _, _ = slot_geometry(ts, te, strand, ms, me, ins)
        acc_ref[r] = jnp.where(valid & overlap & (par_ref[4, k] != 0),
                               mapped - base_ref[k], 0.0)
    for r in range(R, n):
        acc_ref[r] = jnp.zeros(ms.shape, jnp.float32)
    # the reduction over reads in an order that is the code's own: the
    # halves of the read axis added elementwise until one read is left
    # (as fwdbwd_pallas._scale_total sums a read's log-scales)
    while n > 1:
        n //= 2
        acc_ref[0:n] = acc_ref[0:n] + acc_ref[n:2 * n]
    o_ref[...] = acc_ref[0]


@jax.jit
def slot_grid_totals(grid_s, strand, ts, te, live, baselines, valid_s,
                     start_s, end_s, ins_s):
    """(Z, 9, Jmax) template-frame totals over each ZMW's reads of the
    slot-major window-frame score grids `grid_s` (Z*R, 9, Jm), reads
    ZMW-major: the orientation mapping, the overlap mask, the baselines
    and the reduction over reads in one pass over the grid.

    strand, ts, te, live, baselines are (Z*R,): a read scores a slot where
    the slot is `valid_s` (Z, 9, Jmax) for its ZMW, overlaps its window
    [ts, te) (slot_geometry over the slots' (9, Jmax) start_s / end_s /
    ins_s planes) and the read is `live`; there it adds its mapped score
    less its baseline.

    Mapping.  Forward reads: template position P reads window row P - ts.
    Reverse reads: the window scores live on the reverse-complement
    template, so slot (P, sub b) reads row te-1-P of slot sub 3-b,
    (P, ins b) row te-P of ins 3-b, and (P, del) row te-1-P of del
    (mutations.reverse_complement_arrays frame algebra); a row outside
    the window frame reads 0.  Nothing in it depends on data but two
    integers a read, so it is data movement with static structure, not a
    gather (one index a (read, position): the largest device operation
    of a refine round until PR 34): reverse reads take _reverse_frame,
    whose position q holds row Jm-1-q, and then every read's rows are
    its own shifted along the lanes, by ts (forward), by te - Jm (reverse
    sub and del) or te + 1 - Jm (reverse ins) -- a dynamic lane rotate in
    the kernel, circular over the lane-padded frame, with what wrapped
    round masked.

    Reduction.  Pairwise over the read axis in the kernel's own order, so
    a ZMW's totals are the same bits at any Z, beside any batch-mates and
    in lanes padded to any R (XLA orders a jnp.sum by its operand's shape
    and layout; elementwise float adds are not reassociated)."""
    N, _, Jm = grid_s.shape
    Z, _, Jmax = valid_s.shape
    R = N // Z
    L = -(-max(Jm, Jmax) // 128) * 128        # the rotate wants whole vregs
    fwd = strand == 0
    ts, te = ts.astype(jnp.int32), te.astype(jnp.int32)
    x = jnp.where(fwd[:, None, None], grid_s, _reverse_frame(grid_s))
    lanes = lambda a: jnp.pad(
        a, [(0, 0)] * (a.ndim - 1) + [(0, L - a.shape[-1])])
    par = jnp.stack([jnp.mod(jnp.where(fwd, ts, te - Jm), L), ts, te,
                     strand.astype(jnp.int32), live.astype(jnp.int32)])
    plane = pl.BlockSpec((N_SLOTS, L), lambda z: (0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    n_acc = 1 << (R - 1).bit_length()
    out = pl.pallas_call(
        functools.partial(_totals_kernel, R=R, Jm=Jm),
        grid=(Z,),
        in_specs=[smem, smem,
                  pl.BlockSpec((R, N_SLOTS, L), lambda z: (z, 0, 0)),
                  pl.BlockSpec((None, N_SLOTS, L), lambda z: (z, 0, 0)),
                  plane, plane, plane],
        out_specs=pl.BlockSpec((None, N_SLOTS, L), lambda z: (z, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Z, N_SLOTS, L), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_acc, N_SLOTS, L), jnp.float32)],
        interpret=_interpret(),
    )(par, baselines.astype(jnp.float32), lanes(x),
      *(lanes(a.astype(jnp.int32))
        for a in (valid_s, start_s, end_s, ins_s)))
    return out[:, :, :Jmax]
