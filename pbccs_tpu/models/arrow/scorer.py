"""Multi-read mutation scorer: the per-ZMW polish-stage state machine.

TPU re-design of ArrowMultiReadMutationScorer (reference
ConsensusCore/src/C++/Arrow/MultiReadMutationScorer.cpp): owns the forward and
reverse-complement template tracks, one banded alpha/beta pair per read, and
scores candidate template mutations as batched device calls over the whole
(read x mutation) grid instead of the reference's per-read serial loop.

Host/device split: mutation lists, favorability selection and template
splicing are host-side (they are tiny and data-dependent); window building,
forward/backward fills, Z-scores and mutation scoring are jitted batched
device programs with static (R, M, Imax, Jmax, W) bucket shapes.
"""

from __future__ import annotations

import functools
import os
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pbccs_tpu.models.arrow import mutations as mutlib
from pbccs_tpu.models.arrow.expectations import per_base_mean_and_variance
from pbccs_tpu.models.arrow.params import (
    ArrowConfig,
    effective_band_width,
    revcomp,
    snr_to_transition_table_host,
    template_transition_params,
    transition_lookup,
)
from pbccs_tpu.ops.fwdbwd import (
    backward_loglik,
    banded_backward,
    banded_forward,
    forward_loglik,
)
from pbccs_tpu.ops.fwdbwd import MAX_BAND_ADVANCE as _MAX_BAND_SHIFT
from pbccs_tpu.ops.fwdbwd_pallas import fills_use_pallas
from pbccs_tpu.utils import next_pow2 as _next_pow2
from pbccs_tpu.ops.mutation_score import (
    INS,
    SUB,
    MutationPatch,
    interior_read_scores_fast,
    make_patches_fast,
    scale_prefix,
    scale_suffix,
)

# AddRead outcome codes (reference Arrow/MultiReadMutationScorer.hpp:60-61).
ADD_SUCCESS, ADD_ALPHABETAMISMATCH, ADD_MEM_FAIL, ADD_POOR_ZSCORE, ADD_OTHER = range(5)

_AB_MISMATCH_TOL = 1e-3  # reference SimpleRecursor.cpp:53


def mated_mask(ll_a, ll_b, rlens, tstarts, tends):
    """Reads whose alpha/beta fills mate: |1 - LL_a/LL_b| within tolerance,
    both finite, and read-vs-window slope plausible.  The slope gate
    (rlens <= MAX_BAND_ADVANCE * window span) is deliberate POLICY, not a
    kernel constraint (the circular-lane kernels represent any band
    advance): a read more than ~8x its template window is insert-junk the
    reference also sheds, via AlphaBetaMismatchException
    (SimpleRecursor.cpp:683-688).
    All args are host numpy arrays with matching leading shape."""
    mated = np.abs(1.0 - ll_a / np.where(ll_b == 0, 1.0, ll_b)) <= _AB_MISMATCH_TOL
    mated &= np.isfinite(ll_a) & np.isfinite(ll_b)
    mated &= rlens <= _MAX_BAND_SHIFT * np.maximum(tends - tstarts, 1)
    return mated





def oriented_window(strand, ts, te, tpl_f, tpl_r, L, table):
    """Build one read's oriented template window (bases, transitions, len).

    Only the BASES are gathered — one (Jmax,) gather from the stacked
    fwd/rev template.  The transition track is recomputed from the window
    itself: win_trans[j] = T(win[j], win[j+1]) equals the full-template
    track inside the window (template_transition_params conditions on
    (t[i], t[i+1]); rows j >= wlen-1 are masked to zero either way), and
    the 4-lane f32 trans gather this replaces was ~4/5 of the rebuild's
    scalar-core gather volume on the round-5 device profile.  The (8, 4)
    table lookup rides a tiny one-hot matmul, not a gather."""
    Jmax = tpl_f.shape[0]
    ws = jnp.where(strand == 0, ts, L - te)
    wlen = te - ts
    idx = jnp.arange(Jmax, dtype=jnp.int32)
    src = jnp.clip(ws + idx, 0, Jmax - 1)
    both = jnp.concatenate([tpl_f, tpl_r])
    base = both[jnp.where(strand == 0, 0, Jmax) + src]
    win_tpl = jnp.where(idx < wlen, base, 4).astype(jnp.int8)
    w32 = win_tpl.astype(jnp.int32)
    params = transition_lookup(w32, jnp.roll(w32, -1), table)
    win_trans = jnp.where((idx < wlen - 1)[:, None], params, 0.0)
    return win_tpl, win_trans, wlen


def guided_fill_passes(jmax: int) -> int:
    """How many argmax-guided refill ("flip-flop") passes the fill dispatch
    runs after the diagonal-band fill at this template bucket.

    At long templates the alignment path's indel random walk drifts
    ~sqrt(L) rows off the straight diagonal; past ~W/2 the fixed band
    clips real probability mass -- alpha and beta stay CONSISTENT (same
    band) so the mating gate passes, but the likelihood surface is wrong
    and polish accuracy collapses (the round-4 15 kb regression).  Guided
    refills re-center the band on the observed path (fwdbwd.
    guided_band_offsets), the TPU analogue of the reference's guide-matrix
    rebanding + flip-flop (SimpleRecursor.cpp:642-757).  Short templates
    drift well within W/2 (measured +-16 rows at 2 kb) and skip the cost.

    Env override PBCCS_GUIDED: integer pass count, or 0 to disable.

    Thresholds from the drift model (std ~ sqrt(2 * p_indel * L) rows):
    at 2 kb measured drift is +-16 (well inside W/2 = 48, no passes); at
    3 kb ~2 sigma reaches W/2 (start guiding); by 8 kb+ the diagonal can
    be multiple band-widths off.  Buckets past 8 kb run THREE passes
    (round 6): the third pass is what lets the occupancy-driven W
    schedule (params.effective_band_width) hold W=96 at 15 kb -- the
    round-5 W=128 escape hatch existed because two passes left one read's
    post-apply drift outside a 96-row band.  Re-centering is O(fill) and
    shares the fill executables; width is paid on every fill, score, and
    VMEM byte of the polish."""
    env = os.environ.get("PBCCS_GUIDED")
    if env is not None:
        return max(0, int(env))
    if jmax <= 3072:
        return 0
    return 1 if jmax <= 8192 else 3


def fill_alpha_beta_batch(reads, rlens, win_tpl, win_trans, wlens, width: int,
                          use_pallas: bool | None = None, offsets=None,
                          guided_passes: int = 0, need=None):
    """Batched alpha/beta fills + log-likelihoods + scale prefixes.

    Dispatches to the Pallas TPU kernel (ops.fwdbwd_pallas) when available,
    else the pure-JAX banded path.  All args carry a leading read-batch axis.
    Returns (alpha, beta, ll_a, ll_b, alpha_prefix, beta_suffix).

    `use_pallas` must be resolved by the caller when this runs under jit --
    the dispatch is a trace-time decision, so jitted callers thread it
    through as a static argument (else a stale executable would silently
    ignore a changed PBCCS_PALLAS).

    `offsets` (R, nc) pins the band layout (e.g. carried from a previous
    round's guided fill); `guided_passes` > 0 additionally re-centers the
    band on the alpha argmax path and refills that many times (static
    trace-time count -- see guided_fill_passes).

    `need` ((R,) bool) names the reads to fill: they are filled in passes
    (for_needed_reads, fill_pass), and a read it leaves out costs no
    precompute and no scan and comes back with zero bands and zero
    likelihoods."""
    if use_pallas is None:
        use_pallas = fills_use_pallas()
    if need is None:
        alpha, beta, ll_a, ll_b = _fill_pair(
            reads, rlens, win_tpl, win_trans, wlens, width, use_pallas,
            offsets, guided_passes)
        return alpha, beta, ll_a, ll_b, *_scale_sums(alpha, beta)

    N = need.shape[0]
    inputs = (reads, rlens, win_tpl, win_trans, wlens)

    def one_pass(idx, live, carry):
        take = lambda a: None if a is None else jnp.take(a, idx, axis=0)
        return fill_pass(tuple(map(take, inputs)), idx, live, *carry, width,
                         use_pallas, take(offsets), guided_passes)[:2]

    ll0 = jnp.zeros(N, jnp.float32)
    (alpha, beta), (ll_a, ll_b) = for_needed_reads(
        need, one_pass,
        (_zero_bands(N, win_tpl.shape[1], width, framed=use_pallas),
         (ll0, ll0)))
    return alpha, beta, ll_a, ll_b, *_scale_sums(alpha, beta)


def _scale_sums(alpha, beta):
    return (jax.vmap(scale_prefix)(alpha.log_scales),
            jax.vmap(scale_suffix)(beta.log_scales))


# Reads a pass over the needed reads takes: two blocks of the fill kernel.
# Not measured against 32 or 128 on the chip (PERF.md section 7).
_FILL_CHUNK = 64


def for_needed_reads(need, one_pass, carry):
    """`carry` after one_pass(idx, live, carry) has run over the reads the
    (R,) bool `need` names, _FILL_CHUNK at a pass: idx (C,) int32 are a
    pass's reads, its first `live` the needed ones (the last pass is part
    empty).  The needed reads come in their own order; the loop makes
    ceil(needed / C) passes, so what a pass does costs nothing for a read
    that needs none of it."""
    N = need.shape[0]
    C = min(_FILL_CHUNK, N)
    n = jnp.sum(need, dtype=jnp.int32)
    order = jnp.argsort(~need, stable=True).astype(jnp.int32)
    order = jnp.pad(order, (0, -N % C), constant_values=N - 1)

    def body(k, carry):
        idx = lax.dynamic_slice(order, (k * C,), (C,))
        return one_pass(idx, jnp.minimum(n - k * C, C), carry)

    return lax.fori_loop(0, (n + C - 1) // C, body, carry)


def put_rows(old, idx, live, new):
    """`old` with old[idx[i]] = new[i] for i < live: a plain scatter, for
    the small per-read arrays (on the chip a band goes by
    fwdbwd_pallas.place_reads)."""
    rows = jnp.where(jnp.arange(idx.shape[0]) < live, idx, old.shape[0])
    return old.at[rows].set(new.astype(old.dtype), mode="drop")


def band_placer(use_pallas: bool):
    """place(new, idx, live, old) for band-sized arrays: put_rows's result
    by the copy-only kernel where the fills are the kernel's (no row that
    is not placed is touched), else by put_rows itself."""
    from pbccs_tpu.ops.fwdbwd_pallas import place_reads

    return place_reads if use_pallas else (
        lambda new, idx, live, old: put_rows(old, idx, live, new))


def fill_pass(inputs, idx, live, bands, lls, width: int, use_pallas: bool,
              offsets=None, guided_passes: int = 0):
    """One pass of for_needed_reads over the fills: `inputs` are the
    pass's (reads, rlens, win_tpl, win_trans, wlens), its first `live`
    needed.  The precompute and the fill run on them, and each finished
    read goes to its own row idx[i] of what the caller carries: `bands`
    ((alpha, beta) of the whole batch) and `lls` ((ll_a, ll_b) of the
    batch).  No other row is touched.  With the kernel, its `live` skips
    the dead block (fwdbwd_pallas._run_fill) and place_reads copies each
    band, so a read that is not filled moves no band byte; offsets,
    log-scales and likelihoods go back by scatter, as everything does on
    the pure-JAX path.  A read's likelihoods are the same bits from a
    pass as from a fill of every read (fwdbwd_pallas._scale_total).
    Returns (bands, lls, the pass's own (alpha, beta))."""
    from pbccs_tpu.ops.fwdbwd import BandedMatrix, band_frame, band_lead

    *filled, ll_a, ll_b = _fill_pair(*inputs, width, use_pallas, offsets,
                                     guided_passes,
                                     live=live if use_pallas else None)
    if band_lead(bands[0]):     # the pure-JAX fills' bands come plain
        filled = [band_frame(new) for new in filled]
    place = band_placer(use_pallas)
    bands = tuple(
        BandedMatrix(place(new.vals, idx, live, old.vals),
                     put_rows(old.offsets, idx, live, new.offsets),
                     put_rows(old.log_scales, idx, live, new.log_scales))
        for old, new in zip(bands, filled))
    lls = (put_rows(lls[0], idx, live, ll_a),
           put_rows(lls[1], idx, live, ll_b))
    return bands, lls, filled


def _zero_bands(n: int, jmax: int, width: int, framed: bool):
    """(alpha, beta) of n reads that no fill has written yet, framed as
    the kernel's fills are or plain as the pure-JAX fills'."""
    from pbccs_tpu.ops.fwdbwd import BandedMatrix, band_frame_rows

    cols = jmax + 1
    zeros = BandedMatrix(
        jnp.zeros((n, band_frame_rows(cols) if framed else cols, width),
                  jnp.float32),
        jnp.zeros((n, cols), jnp.int32), jnp.zeros((n, cols), jnp.float32))
    return zeros, zeros


def _fill_pair(reads, rlens, win_tpl, win_trans, wlens, width: int,
               use_pallas: bool, offsets, guided_passes: int, live=None):
    """(alpha, beta, ll_a, ll_b) of a read batch, guided passes included.
    With `live`, rows [0, live) only (the rest holds no meaning)."""
    from pbccs_tpu.ops.fwdbwd import BandedMatrix, guided_band_offsets

    alpha, ll_a = _fill_alpha(reads, rlens, win_tpl, win_trans, wlens,
                              width, use_pallas, offsets, live)
    for _ in range(guided_passes):
        g_off = jax.vmap(
            lambda av, ao, i, jl: guided_band_offsets(av, ao, i, jl, width)
        )(alpha.vals, alpha.offsets, rlens, wlens)
        alpha_g, ll_g = _fill_alpha(reads, rlens, win_tpl, win_trans, wlens,
                                    width, use_pallas, g_off, live)
        # keep-better per read: a re-centered band normally recovers the
        # probability mass the diagonal band clipped, but when the first
        # fill locked onto a wrong ridge the guided band can LOSE mass --
        # never trade down (same keep-better-width rule as the host's 2x
        # band retry, and the reference's flip-flop acceptance test)
        keep = ll_g >= ll_a
        alpha = BandedMatrix(
            jnp.where(keep[:, None, None], alpha_g.vals, alpha.vals),
            jnp.where(keep[:, None], alpha_g.offsets, alpha.offsets),
            jnp.where(keep[:, None], alpha_g.log_scales, alpha.log_scales))
        ll_a = jnp.where(keep, ll_g, ll_a)
    beta, ll_b = _fill_beta(reads, rlens, win_tpl, win_trans, wlens,
                            width, use_pallas,
                            alpha.offsets if guided_passes else offsets, live)
    return alpha, beta, ll_a, ll_b


def _fill_alpha(reads, rlens, win_tpl, win_trans, wlens, width: int,
                use_pallas: bool, offsets, live=None):
    from pbccs_tpu.ops import fwdbwd_pallas as fpal

    if use_pallas:
        alpha = fpal.pallas_forward_batch(reads, rlens, win_tpl, win_trans,
                                          wlens, width, offsets=offsets,
                                          live=live)
        return alpha, fpal.forward_loglik_batch(alpha, rlens, wlens)
    alpha = jax.vmap(
        lambda r, i, t, tr, j, o: banded_forward(r, i, t, tr, j, width,
                                                 offsets=o),
        in_axes=(0, 0, 0, 0, 0, None if offsets is None else 0),
    )(reads, rlens, win_tpl, win_trans, wlens, offsets)
    return alpha, jax.vmap(forward_loglik)(alpha, rlens, wlens)


def _fill_beta(reads, rlens, win_tpl, win_trans, wlens, width: int,
               use_pallas: bool, offsets, live=None):
    from pbccs_tpu.ops import fwdbwd_pallas as fpal

    if use_pallas:
        beta = fpal.pallas_backward_batch(reads, rlens, win_tpl, win_trans,
                                          wlens, width, offsets=offsets,
                                          live=live)
        return beta, fpal.backward_loglik_batch(beta, wlens)
    beta = jax.vmap(
        lambda r, i, t, tr, j, o: banded_backward(r, i, t, tr, j, width,
                                                  offsets=o),
        in_axes=(0, 0, 0, 0, 0, None if offsets is None else 0),
    )(reads, rlens, win_tpl, win_trans, wlens, offsets)
    return beta, jax.vmap(backward_loglik)(beta, wlens)


def fill_alpha_beta_batch_zr(reads, rlens, win_tpl, win_trans, wlens,
                             width: int, use_pallas: bool, mesh=None,
                             guided_passes: int = 0, need=None):
    """(Z, R)-leading alpha/beta fills + log-likelihoods + scale prefixes.

    Unsharded (mesh=None) this flattens to the (Z*R,) read batch and
    delegates to fill_alpha_beta_batch.  Under a ('zmw','read') mesh with
    the Pallas kernel enabled, the fills run inside jax.shard_map: each
    device flattens ITS OWN (Z/nz, R/nr) block and launches the kernel on
    it -- pallas_call has no GSPMD partitioning rule, so without this
    wrapper mesh runs had to fall back to the pure-JAX fill path and
    forfeit the kernel's measured ~69x single-chip advantage.  Reads are
    independent, so no collectives are needed in the body; boundary
    shardings match the batch arrays' native P('zmw','read') layout.

    `need` ((Z, R) bool) as in fill_alpha_beta_batch; each device of a
    mesh packs the needed reads of its own block."""
    Z, R = reads.shape[:2]
    flat = lambda a: a.reshape((Z * R,) + a.shape[2:])
    unflat = lambda a: a.reshape((Z, R) + a.shape[1:])

    if mesh is None or not use_pallas:
        out = fill_alpha_beta_batch(
            flat(reads), flat(rlens), flat(win_tpl), flat(win_trans),
            flat(wlens), width, use_pallas, guided_passes=guided_passes,
            need=None if need is None else flat(need))
        return jax.tree.map(unflat, out)

    from jax.sharding import PartitionSpec
    from pbccs_tpu.parallel.mesh import READ_AXIS, ZMW_AXIS

    def body(r, i, t, tr, j, *need):
        # each device runs the unsharded path on its local (Z/nz, R/nr) block
        return fill_alpha_beta_batch_zr(r, i, t, tr, j, width, True, None,
                                        guided_passes=guided_passes,
                                        need=need[0] if need else None)

    spec = PartitionSpec(ZMW_AXIS, READ_AXIS)
    # check_vma=False: pallas_call's out_shapes carry no varying-mesh-axes
    # metadata; the body is per-read elementwise so nothing varies anyway
    return jax.shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec,
                     check_vma=False)(
        reads, rlens, win_tpl, win_trans, wlens,
        *(() if need is None else (need,)))


@functools.partial(jax.jit, static_argnames=("width", "use_pallas",
                                             "guided_passes"))
def _setup_reads(reads, rlens, strands, tstarts, tends,
                 tpl_f, tpl_r, L, table, width: int,
                 use_pallas: bool, guided_passes: int = 0):
    """Build per-read oriented windows and fill alpha/beta for each read."""
    win_tpl, win_trans, wlens = jax.vmap(
        lambda s, a, b: oriented_window(s, a, b, tpl_f, tpl_r, L, table)
    )(strands, tstarts, tends)
    alpha, beta, ll_a, ll_b, apre, bsuf = fill_alpha_beta_batch(
        reads, rlens, win_tpl, win_trans, wlens, width, use_pallas,
        guided_passes=guided_passes)
    return (win_tpl, win_trans, wlens, alpha, beta, ll_a, ll_b, apre, bsuf)


def window_moments(strand, ts, te, mean_f, var_f, mean_r, var_r, L):
    """(mu, var) of E[log-lik] over one read's window of the oriented
    template (closed-form HMM moments, Expectations.hpp:45).

    Note: the reference indexes the reverse template's moments with
    forward-frame coordinates (MultiReadMutationScorer.cpp:299-317); we use
    the read's actual window on the oriented template, which is the intended
    statistic (documented deviation)."""
    s = jnp.where(strand == 0, ts, L - te)
    e = jnp.where(strand == 0, te, L - ts)
    pos = jnp.arange(mean_f.shape[0])
    m = (pos >= s) & (pos < e - 1)
    mu = jnp.sum(jnp.where(m, jnp.where(strand == 0, mean_f, mean_r), 0.0))
    v = jnp.sum(jnp.where(m, jnp.where(strand == 0, var_f, var_r), 0.0))
    return mu, v


@jax.jit
def _read_moments(strands, tstarts, tends, trans_f, trans_r, L):
    """Per-read (mu, var) over each read's oriented window."""
    mean_f, var_f = per_base_mean_and_variance(trans_f)
    mean_r, var_r = per_base_mean_and_variance(trans_r)

    def one(strand, ts, te):
        return window_moments(strand, ts, te, mean_f, var_f, mean_r, var_r, L)

    return jax.vmap(one)(strands, tstarts, tends)


@jax.jit
def _make_patches(tpl, trans, trans_table, L, pos, mtype, new_base):
    return make_patches_fast(tpl, trans, trans_table, L, pos, mtype, new_base)


def interior_read_scores(read, rlen, strand, ts, te, wt, wtr, wl,
                         alpha, beta, apre, bsuf,
                         mpos_f, mend_f, mtype,
                         patches_f: MutationPatch, patches_r: MutationPatch):
    """(M,) absolute mutated-template log-likelihoods of one read via
    extend+link, given forward-frame mutation arrays + fwd/rev patches.

    Routed through the gather-free batched scorer
    (ops.mutation_score.interior_read_scores_fast); the per-mutation
    extend_link_score path it replaced is kept in ops.mutation_score as the
    reference implementation, with parity enforced by
    tests/test_mutation_fast.py."""
    return interior_read_scores_fast(read, rlen, strand, ts, te, wt, wtr, wl,
                                     alpha, beta, apre, bsuf,
                                     mpos_f, mend_f, mtype,
                                     patches_f, patches_r)


@jax.jit
def _score_interior(reads, rlens, strands, tstarts, tends,
                    win_tpl, win_trans, wlens,
                    alpha_vals, alpha_offs, alpha_ls,
                    beta_vals, beta_offs, beta_ls,
                    a_prefix, b_suffix,
                    mpos_f, mend_f, mtype,
                    patches_f: MutationPatch, patches_r: MutationPatch):
    """(R, M) absolute mutated-template log-likelihoods via extend+link."""
    from pbccs_tpu.ops.fwdbwd import BandedMatrix

    def per_read(read, rlen, strand, ts, te, wt, wtr, wl,
                 av, ao, als, bv, bo, bls, apre, bsuf):
        return interior_read_scores(
            read, rlen, strand, ts, te, wt, wtr, wl,
            BandedMatrix(av, ao, als), BandedMatrix(bv, bo, bls), apre, bsuf,
            mpos_f, mend_f, mtype, patches_f, patches_r)

    return jax.vmap(per_read)(reads, rlens, strands, tstarts, tends,
                              win_tpl, win_trans, wlens,
                              alpha_vals, alpha_offs, alpha_ls,
                              beta_vals, beta_offs, beta_ls,
                              a_prefix, b_suffix)


@functools.partial(jax.jit, static_argnames=("width", "use_pallas"))
def _score_edge(reads, rlens, win_tpl, win_trans, wlens,
                pair_read, pair_p, pair_type,
                patch_bases, patch_trans, patch_shift, width: int,
                use_pallas: bool):
    """(E,) absolute LLs via full banded refill of the mutated window.

    Per-pair read/window rows are picked with one-hot matmuls (runtime-index
    row gathers lower to the TPU scalar core) and the mutated windows are
    built densely with static shifts; the (E,) fills then run through the
    batched fill dispatch (Pallas kernel on TPU)."""
    from pbccs_tpu.ops.fwdbwd_pallas import (
        forward_loglik_batch, pallas_forward_batch)
    from pbccs_tpu.ops.mutation_score import _row_select, mutated_windows_per_pair

    R, Imax = reads.shape
    Jm = win_tpl.shape[1]
    reads_e = _row_select(pair_read, reads.astype(jnp.float32)).astype(jnp.int8)
    sel = _row_select(pair_read, jnp.concatenate(
        [rlens[:, None].astype(jnp.float32),
         wlens[:, None].astype(jnp.float32),
         win_tpl.astype(jnp.float32)], axis=1))
    rlens_e = sel[:, 0].astype(jnp.int32)
    wlens_e = sel[:, 1].astype(jnp.int32)
    wt_e = sel[:, 2:].astype(jnp.int32)
    wtr_e = _row_select(pair_read, win_trans.reshape(R, Jm * 4)).reshape(-1, Jm, 4)

    patch = MutationPatch(patch_bases, patch_trans, patch_shift)
    bases, trans, new_lens = mutated_windows_per_pair(
        wt_e, wtr_e, wlens_e, pair_p, pair_type, patch)

    if use_pallas:
        alpha = pallas_forward_batch(reads_e, rlens_e, bases, trans,
                                     new_lens, width)
        return forward_loglik_batch(alpha, rlens_e, new_lens)
    alpha = jax.vmap(lambda r, i, t, tr, j: banded_forward(r, i, t, tr, j, width))(
        reads_e, rlens_e, bases, trans, new_lens)
    return jax.vmap(forward_loglik)(alpha, rlens_e, new_lens)


class ArrowMultiReadScorer:
    """Per-ZMW polish state (MultiReadMutationScorer equivalent).

    Reads are provided pre-mapped (strand + [tstart, tend) template window
    from the draft stage).  AddRead gating (alpha/beta mating + Z-score,
    reference MultiReadMutationScorer.cpp:276-325) happens in batch at
    construction; gate outcomes are in `self.statuses`.
    """

    def __init__(self, tpl: np.ndarray, snr: np.ndarray,
                 read_codes: Sequence[np.ndarray], strands: Sequence[int],
                 tstarts: Sequence[int], tends: Sequence[int],
                 config: ArrowConfig | None = None,
                 min_zscore: float = float("nan"),
                 imax: int | None = None, jmax: int | None = None):
        self.config = config or ArrowConfig()
        self.snr = np.asarray(snr, np.float64)
        self.tpl = np.asarray(tpl, np.int8)
        self.n_reads = len(read_codes)
        self.min_zscore = min_zscore

        R = _next_pow2(self.n_reads, 4)
        self._R = R
        self._Imax = imax or _next_pow2(max(len(r) for r in read_codes) + 8, 64)
        self._Jmax = jmax or _next_pow2(len(tpl) + 8, 64)
        self._W = effective_band_width(self.config.banding, self._Jmax)

        self._reads = np.full((R, self._Imax), 4, np.int8)
        self._rlens = np.zeros(R, np.int32)
        for i, rc in enumerate(read_codes):
            n = min(len(rc), self._Imax)
            self._reads[i, :n] = rc[:n]
            self._rlens[i] = n
        self._strands = np.zeros(R, np.int32)
        self._strands[: self.n_reads] = strands
        self._tstarts = np.zeros(R, np.int32)
        self._tstarts[: self.n_reads] = tstarts
        self._tends = np.zeros(R, np.int32)
        self._tends[: self.n_reads] = tends
        # padding rows: map to a trivial window to keep kernels finite
        for i in range(self.n_reads, R):
            self._rlens[i] = 2
            self._reads[i, :2] = [0, 0]
            self._tends[i] = min(2, len(tpl))

        self.trans_table = jnp.asarray(
            snr_to_transition_table_host(self.snr), jnp.float32)
        self.active = np.zeros(R, bool)
        self.statuses = np.full(self.n_reads, ADD_OTHER, np.int32)
        self.zscores = np.full(self.n_reads, np.nan)
        self.band_retried = False
        self.n_band_retries = 0

        self._rebuild(first=True)
        failed = self.statuses == ADD_ALPHABETAMISMATCH
        if failed.any():
            # The reference refills a mismatched alpha/beta pair up to 5
            # times with rebanding before dropping the read
            # (SimpleRecursor.cpp:642-691).  The static-band analogue is one
            # escalation of the whole scorer to a 2x band -- per-read widths
            # would break the (R, J+1, W) lockstep shapes.  Escalation is
            # kept only when it MATES more reads: for insert-heavy reads the
            # float32 in-column dynamic range (~87 nats/column) binds before
            # band coverage does, and a wider band can then lose mass and
            # unmate reads the narrow band kept, so the better width wins.
            # The first build is snapshotted so the revert (the common case)
            # and any failure of the speculative wide build (e.g. device
            # memory) restore it without a third set of fills.
            snap = {k: getattr(self, k) for k in self._RETRY_SNAPSHOT}
            gates = (self.statuses.copy(), self.active.copy(),
                     self.zscores.copy())
            w0 = self._W
            n0 = int((self.statuses != ADD_ALPHABETAMISMATCH).sum())
            try:
                self._W *= 2
                self._reset_gates()
                self._rebuild(first=True)
                better = int((self.statuses
                              != ADD_ALPHABETAMISMATCH).sum()) > n0
            except Exception:  # noqa: BLE001 -- speculative build only
                better = False
            if better:
                self.band_retried = True
                self.n_band_retries = int(
                    (failed & (self.statuses != ADD_ALPHABETAMISMATCH)).sum())
            else:
                self._W = w0
                for k, v in snap.items():
                    setattr(self, k, v)
                self.statuses, self.active, self.zscores = gates

    # ------------------------------------------------------------------ setup

    _RETRY_SNAPSHOT = (
        "tpl_f", "trans_f", "tpl_r", "trans_r", "win_tpl", "win_trans",
        "wlens", "alpha", "beta", "a_prefix", "b_suffix", "baselines",
        "_ll_mu", "_ll_var")

    def _reset_gates(self) -> None:
        self.statuses[:] = ADD_OTHER
        self.active[:] = False
        self.zscores[:] = np.nan

    def _template_tensors(self):
        L = len(self.tpl)
        padded = np.full(self._Jmax, 4, np.int8)
        padded[:L] = self.tpl
        tpl_f = jnp.asarray(padded)
        trans_f = template_transition_params(tpl_f, self.trans_table, L)
        rc = np.full(self._Jmax, 4, np.int8)
        rc[:L] = revcomp(self.tpl)
        tpl_r = jnp.asarray(rc)
        trans_r = template_transition_params(tpl_r, self.trans_table, L)
        return tpl_f, trans_f, tpl_r, trans_r

    def _rebuild(self, first: bool = False):
        """(Re)build windows + alpha/beta for all reads against self.tpl.

        On the first build, gate reads (mating + Z-score).  On rebuilds after
        ApplyMutations, only the mating check can deactivate reads
        (reference MultiReadMutationScorer.cpp:237-267)."""
        L = len(self.tpl)
        self.tpl_f, self.trans_f, self.tpl_r, self.trans_r = self._template_tensors()
        (self.win_tpl, self.win_trans, self.wlens, self.alpha, self.beta,
         ll_a, ll_b, self.a_prefix, self.b_suffix) = _setup_reads(
            jnp.asarray(self._reads), jnp.asarray(self._rlens),
            jnp.asarray(self._strands), jnp.asarray(self._tstarts),
            jnp.asarray(self._tends),
            self.tpl_f, self.tpl_r, jnp.int32(L), self.trans_table,
            self._W, fills_use_pallas(),
            guided_fill_passes(self._Jmax))

        ll_a = np.asarray(ll_a, np.float64)
        ll_b = np.asarray(ll_b, np.float64)
        self.baselines = ll_b
        mated = mated_mask(ll_a, ll_b, self._rlens, self._tstarts, self._tends)

        mu, var = _read_moments(
            jnp.asarray(self._strands), jnp.asarray(self._tstarts),
            jnp.asarray(self._tends), self.trans_f, self.trans_r, jnp.int32(L))
        self._ll_mu = np.asarray(mu, np.float64)
        self._ll_var = np.asarray(var, np.float64)

        if first:
            z = (ll_b - self._ll_mu) / np.sqrt(np.maximum(self._ll_var, 1e-12))
            for i in range(self.n_reads):
                if not mated[i]:
                    self.statuses[i] = ADD_ALPHABETAMISMATCH
                    self.active[i] = False
                    continue
                self.zscores[i] = z[i]
                if not np.isnan(self.min_zscore) and (
                        not np.isfinite(z[i]) or z[i] < self.min_zscore):
                    self.statuses[i] = ADD_POOR_ZSCORE
                    self.active[i] = False
                else:
                    self.statuses[i] = ADD_SUCCESS
                    self.active[i] = True
        else:
            self.active[: self.n_reads] &= mated[: self.n_reads]
        self.active[self.n_reads:] = False

    # ------------------------------------------------------------- scoring

    def baseline_total(self) -> float:
        return float(self.baselines[self.active].sum())

    def global_zscore(self) -> float:
        """Z-score of the summed log-likelihood over all active reads
        (reference MultiReadMutationScorer::ZScores global statistic,
        Arrow/MultiReadMutationScorer.hpp:174-263)."""
        act = self.active
        if not act.any():
            return float("nan")
        var = self._ll_var[act].sum()
        if var <= 0:
            return float("nan")
        ll = self.baselines[act].sum()
        return float((ll - self._ll_mu[act].sum()) / np.sqrt(var))

    def _mutation_arrays(self, muts: Sequence[mutlib.Mutation]):
        L = len(self.tpl)
        M = len(muts)
        pos_f = np.array([m.start for m in muts], np.int32)
        end_f = np.array([m.end for m in muts], np.int32)
        mtype = np.array([m.mtype for m in muts], np.int32)
        base_f = np.array([m.new_base for m in muts], np.int32)
        rcm = [mutlib.reverse_complement_mutation(m, L) for m in muts]
        pos_r = np.array([m.start for m in rcm], np.int32)
        base_r = np.array([m.new_base for m in rcm], np.int32)
        return pos_f, end_f, mtype, base_f, pos_r, base_r

    def score_mutations(self, muts: Sequence[mutlib.Mutation]) -> np.ndarray:
        """Sum over active overlapping reads of (LL(mutated) - LL(current)).

        Parity: MultiReadMutationScorer::Score (MultiReadMutationScorer.cpp:
        339-368) without the serial FastScore early-exit (the masked batched
        sum makes the same favorability decisions)."""
        if not muts:
            return np.zeros(0)
        L = len(self.tpl)
        R, nR = self._R, self.n_reads
        pos_f, end_f, mtype, base_f, pos_r, base_r = self._mutation_arrays(muts)
        M = len(muts)
        Mpad = _next_pow2(M, 16)
        pad = lambda a, fill: np.concatenate([a, np.full(Mpad - M, fill, a.dtype)])
        pos_fp, end_fp = pad(pos_f, L // 2), pad(end_f, L // 2 + 1)
        mtypep, base_fp = pad(mtype, SUB), pad(base_f, 0)
        pos_rp, base_rp = pad(pos_r, L // 2), pad(base_r, 0)

        patches_f = _make_patches(self.tpl_f.astype(jnp.int32), self.trans_f,
                                  self.trans_table, jnp.int32(L),
                                  jnp.asarray(pos_fp), jnp.asarray(mtypep),
                                  jnp.asarray(base_fp))
        patches_r = _make_patches(self.tpl_r.astype(jnp.int32), self.trans_r,
                                  self.trans_table, jnp.int32(L),
                                  jnp.asarray(pos_rp), jnp.asarray(mtypep),
                                  jnp.asarray(base_rp))

        # host-side classification per (read, mut): overlap, window coords,
        # interior vs edge
        ts = self._tstarts[:, None]
        te = self._tends[:, None]
        strand = self._strands[:, None]
        ms, me = pos_f[None, :], end_f[None, :]
        is_ins = (mtype == INS)[None, :]
        overlap = np.where(is_ins, (ts <= me) & (ms <= te), (ts < me) & (ms < te))
        p_w = np.where(strand == 0, ms - ts, te - me)
        e_w = np.where(strand == 0, me - ts, te - ms)
        wlen = (te - ts)
        interior = (p_w >= 3) & (e_w <= wlen - 2)
        act = self.active[:, None]
        valid = act & overlap
        int_mask = valid & interior
        edge_mask = valid & ~interior

        abs_ll = np.asarray(_score_interior(
            jnp.asarray(self._reads), jnp.asarray(self._rlens),
            jnp.asarray(self._strands), jnp.asarray(self._tstarts),
            jnp.asarray(self._tends),
            self.win_tpl, self.win_trans, self.wlens,
            self.alpha.vals, self.alpha.offsets, self.alpha.log_scales,
            self.beta.vals, self.beta.offsets, self.beta.log_scales,
            self.a_prefix, self.b_suffix,
            jnp.asarray(pos_fp), jnp.asarray(end_fp), jnp.asarray(mtypep),
            patches_f, patches_r), np.float64)[:, :M]

        totals = np.where(int_mask, abs_ll - self.baselines[:, None], 0.0).sum(axis=0)

        # edge pairs via full refill
        er, em_ = np.nonzero(edge_mask)
        if len(er):
            E = len(er)
            Epad = _next_pow2(E, 8)
            pr = np.zeros(Epad, np.int32)
            pp = np.zeros(Epad, np.int32)
            pt = np.zeros(Epad, np.int32)
            pr[:E] = er
            pp[:E] = p_w[er, em_]
            pt[:E] = mtype[em_]
            pb = np.zeros((Epad, 2), np.int32)
            ptr = np.zeros((Epad, 2, 4), np.float32)
            psh = np.zeros(Epad, np.int32)
            pf_b = np.asarray(patches_f.bases)
            pf_t = np.asarray(patches_f.trans)
            pf_s = np.asarray(patches_f.shift)
            pr_b = np.asarray(patches_r.bases)
            pr_t = np.asarray(patches_r.trans)
            pr_s = np.asarray(patches_r.shift)
            fwd = self._strands[er] == 0
            pb[:E] = np.where(fwd[:, None], pf_b[em_], pr_b[em_])
            ptr[:E] = np.where(fwd[:, None, None], pf_t[em_], pr_t[em_])
            psh[:E] = np.where(fwd, pf_s[em_], pr_s[em_])
            edge_ll = np.asarray(_score_edge(
                jnp.asarray(self._reads), jnp.asarray(self._rlens),
                self.win_tpl, self.win_trans, self.wlens,
                jnp.asarray(pr), jnp.asarray(pp), jnp.asarray(pt),
                jnp.asarray(pb), jnp.asarray(ptr), jnp.asarray(psh),
                self._W, fills_use_pallas()), np.float64)[:E]
            np.add.at(totals, em_, edge_ll - self.baselines[er])

        return totals

    # ------------------------------------------------------------- mutation

    def apply_mutations(self, muts: Sequence[mutlib.Mutation]) -> None:
        """Splice mutations into the template, remap read windows, refill.

        Parity: MultiReadMutationScorer::ApplyMutations
        (MultiReadMutationScorer.cpp:237-267)."""
        if not muts:
            return
        L = len(self.tpl)
        mtp = mutlib.target_to_query_positions(muts, L)
        self.tpl = mutlib.apply_mutations(self.tpl, muts)
        newJ = _next_pow2(len(self.tpl) + 8, 64)
        if newJ != self._Jmax:
            self._Jmax = newJ
        self._tstarts = mtp[np.clip(self._tstarts, 0, L)].astype(np.int32)
        self._tends = mtp[np.clip(self._tends, 0, L)].astype(np.int32)
        self._rebuild(first=False)

    # ------------------------------------------------------------------- QVs

    def consensus_qvs(self) -> np.ndarray:
        """Per-position QVs from single-base mutation scores, via the
        generic sweep shared with Quiver (models.arrow.refine.consensus_qvs;
        reference ConsensusQVs, Consensus-inl.hpp:277-297)."""
        from pbccs_tpu.models.arrow.refine import consensus_qvs

        return consensus_qvs(self)
