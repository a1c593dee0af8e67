"""Batched ZMW polishing: many ZMWs per device program, sharded over a mesh.

This is the TPU replacement for the reference's one-thread-per-ZMW WorkQueue
(reference include/pacbio/ccs/WorkQueue.h:53-217) *and* the per-ZMW serial
mutation-testing loop (reference ConsensusCore/include/ConsensusCore/
Consensus-inl.hpp:160-245): Z bucketed ZMWs advance through the refinement
loop in lockstep, each round being one jitted batched program over the
(ZMW, read, mutation) grid.  Mutation-score totals reduce over the read
axis, so sharding reads across the 'read' mesh axis makes XLA insert the
all-reduce; the ZMW axis is pure data parallelism.

Selection semantics per ZMW are identical to the host refinement loop
(models/arrow/refine.py): favorable = score above the f32 noise floor
(refine.favorability_threshold, recomputed per round -- a deliberate
scaled-floor deviation from the reference's FIXED +0.04-nat acceptance
threshold, MultiReadMutationScorer.cpp:56; rationale in docs/PARITY.md),
greedy well-separated best subset, template-hash cycle avoidance,
converged ZMWs drop out of the mutation workload (their slots are
masked, not recompiled away).
"""

from __future__ import annotations

import os
import dataclasses
import functools
import threading
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pbccs_tpu.models.arrow import mutations as mutlib
from pbccs_tpu.models.arrow.expectations import per_base_mean_and_variance
from pbccs_tpu.models.arrow.params import (
    ArrowConfig,
    effective_band_width,
    revcomp_padded,
    snr_to_transition_table_host,
    template_transition_params,
)
from pbccs_tpu.models.arrow import refine as refine_mod
from pbccs_tpu.models.arrow.refine import RefineOptions, RefineResult
from pbccs_tpu.models.arrow.scorer import (
    ADD_ALPHABETAMISMATCH,
    ADD_POOR_ZSCORE,
    ADD_SUCCESS,
    fill_alpha_beta_batch_zr,
    fills_use_pallas,
    guided_fill_passes,
    interior_read_scores,
    oriented_window,
    window_moments,
)
from pbccs_tpu.ops.fwdbwd import BandedMatrix
from pbccs_tpu.ops.mutation_score import (
    INS,
    SUB,
    edge_read_scores_fast,
    make_patches_fast,
)
from pbccs_tpu.obs import flight as obs_flight
from pbccs_tpu.obs import trace as obs_trace
from pbccs_tpu.obs.metrics import default_registry, log_buckets
from pbccs_tpu.parallel.mesh import READ_AXIS, ZMW_AXIS, pad_to
from pbccs_tpu.runtime.timing import device_fetch
from pbccs_tpu.utils import next_pow2

# bucket fill / padding-waste observability: pow2 padding of the (Z, R)
# axes is real device work, so the fill ratios tell later perf PRs how
# much of a batch's FLOPs polish actual reads vs padding
_reg = default_registry()
_m_polishes = _reg.counter("ccs_batch_polishes_total",
                           "BatchPolisher batches constructed")
_m_zmw_slots = _reg.counter("ccs_batch_slots_total",
                            "Padded batch slots by axis", axis="zmw")
_m_zmw_used = _reg.counter("ccs_batch_slots_used_total",
                           "Occupied batch slots by axis", axis="zmw")
_m_read_slots = _reg.counter("ccs_batch_slots_total", axis="read")
_m_read_used = _reg.counter("ccs_batch_slots_used_total", axis="read")
_FILL_BUCKETS = log_buckets(0.0625, 1.0, 2.0)
_m_zmw_fill = _reg.histogram("ccs_batch_fill_ratio",
                             "Used/padded slot ratio per batch by axis",
                             buckets=_FILL_BUCKETS, axis="zmw")
_m_read_fill = _reg.histogram("ccs_batch_fill_ratio",
                              buckets=_FILL_BUCKETS, axis="read")
# every shape set is a family of programs to trace, lower and load
# (minutes at 2 kb, whatever the cache holds): the count of them is what
# a run's set-up costs, and it moving late in a run is a stall
_m_shape_sets = _reg.counter(
    "ccs_polish_shape_sets_total",
    "Polish shape sets (Imax, Jmax, R, Z at one band width) this process "
    "built a BatchPolisher at for the first time")
_shape_sets_seen: set[tuple] = set()
_shape_sets_lock = threading.Lock()
_m_cycle_stops = {kind: _reg.counter(
    "ccs_refine_cycle_stops_total",
    "ZMWs the device loop stopped, not converged, because their rounds "
    "had become periodic (zmws), and the rounds of the budget they did "
    "not run (rounds_spared)", kind=kind)
    for kind in ("zmws", "rounds_spared")}
# a pin the menu opens or grows is a family of programs to load: after a
# file's first batch neither should move
_m_menu_pins = {kind: _reg.counter(
    "ccs_menu_pins_total",
    "Pins of the process's shape menu: opened (new) and widened in "
    "Imax, Jmax or, up to 12, lanes to hold a batch (grown)", kind=kind)
    for kind in ("new", "grown")}


def shape_sets_seen() -> set[tuple]:
    """The (Imax, Jmax, R, Z, W) this process has built a polisher at."""
    with _shape_sets_lock:
        return set(_shape_sets_seen)


# mutation-axis chunk: every scoring call uses this static M so one compiled
# program serves every refinement round and the QV sweep
MUT_CHUNK = 512
# edge-mutation slab width: boundary mutations are O(reads), not O(template),
# so their batched program uses a small static mutation axis
EDGE_SLAB = 64
# windows shorter than this score boundary mutations by full refill: the
# extend-from-begin and extend-to-end regimes would overlap
MIN_FAST_EDGE_WLEN = 8
# ZMW axis of a batch driver's wide-band retry (BatchPolisher.wide_band_subs)
WIDE_BAND_Z = 4


def _jmax_bucket(max_len: int) -> int:
    """Template-axis bucket: headroom PROPORTIONAL to length, not the old
    flat +16 -- net insertions during refinement scale with template
    length, and a 15 kb polish whose templates outgrew a +16 bucket
    overflow-bailed the device-resident loop every round (straight into
    the host loop's per-round fetches + length-scaled chunk programs).
    Past 2 kb the granularity scales with length too (about the headroom
    itself, power-of-two steps, floor 64): the longest draft of a batch
    moves by a per cent or two from batch to batch (2,169-2,224 over 96
    batches of one 2 kb x 3-10 pass library), and 64-column steps put one
    batch in ten of such a file in a bucket of its own, a second family
    of programs."""
    need = max_len + max(16, max_len // 32)
    return pad_to(need, _jmax_step(need))


def _jmax_step(n: int) -> int:
    return max(64, 1 << max(n - 1, 1).bit_length() - 5)


def _imax_bucket(raw_imax: int) -> int:
    """Read-axis bucket: granularity scales with length (~1/8th,
    power-of-two steps, floor 64): long-read workloads draw max read
    lengths that differ by hundreds of bases run to run, and a fixed
    64-step bucket minted a fresh executable set per draw -- a ~90 s
    recompile inside every timed 15 kb repeat."""
    return pad_to(raw_imax, _imax_step(raw_imax))


def _imax_step(n: int) -> int:
    return max(64, 1 << max(n - 1, 1).bit_length() - 3)


# The read-lane axis steps on this ladder and on nothing between: 4, 8 and
# 12 lanes, then 32 and its doublings.  Every step a file straddles is a
# family of programs to trace, lower and load (a minute or more at 2 kb),
# so past the 3-10-pass libraries' 12 lanes the ladder only doubles: a
# chunk of a cell's file, whose most passes wander over 13-30, lands on
# 32 whichever ZMWs it holds.
_LANE_STEPS = (4, 8, 12)


def lane_step(n_reads: int) -> int:
    """The fewest lanes of the ladder (4, 8, 12, 32, 64, ..) that hold
    `n_reads` reads."""
    for step in _LANE_STEPS:
        if n_reads <= step:
            return step
    return next_pow2(n_reads, 32)


def length_bucket(tpl_len: int, max_read_len: int) -> tuple[int, int]:
    """The (Jmax, Imax) compiled-shape bucket a ZMW of this geometry
    would polish in alone -- the router's sticky-routing key
    (pbccs_tpu.serve.router), read off raw read lengths before any
    draft exists.  (The serving engine's batcher groups by the pin of
    the ZMW's length class instead: ShapeMenu, below.)"""
    return _jmax_bucket(tpl_len), _imax_bucket(max_read_len + 8)


def effective_shapes(n_zmws: int, max_reads: int, max_read_len: int,
                     max_tpl_len: int, *,
                     buckets: tuple[int, int, int] | None = None,
                     min_z: int = 1, zq: int = 1, rq: int = 1
                     ) -> tuple[int, int, int, int]:
    """The (Imax, Jmax, R, Z) a BatchPolisher with these inputs compiles
    at -- the ONE place the bucket arithmetic lives.  BatchPolisher's
    constructor uses it, and the quarantine bisection path
    (pipeline._pinned_batch_shapes) uses it to pin sub-dispatches to the
    parent batch's shapes, so isolating a poison ZMW replays compiled
    programs and (W being a function of Jmax) reproduces surviving ZMWs
    byte-identically."""
    Z = pad_to(max(n_zmws, min_z), zq)
    R = pad_to(lane_step(max_reads), rq)
    Imax = _imax_bucket(max_read_len + 8)
    Jmax = _jmax_bucket(max_tpl_len)
    if buckets is not None:
        Imax = max(Imax, buckets[0])
        R = max(R, buckets[2])
        # adopt the parent's Jmax bucket EXACTLY when templates fit:
        # letting _jmax_bucket of a mid-refinement template overshoot
        # the parent bucket would mint a fresh draw-dependent shape
        # (a cold compile, the very thing buckets exist to prevent)
        if max_tpl_len + 2 <= buckets[1]:
            Jmax = buckets[1]
        else:
            Jmax = max(Jmax, buckets[1])
    return Imax, Jmax, R, Z


def _length_class_statics(jmax: int) -> tuple:
    """What a Jmax bucket decides beyond padding: the band width and the
    guided refill passes.  Two buckets that agree here polish a ZMW to
    the same bytes (padding changes no arithmetic); two that differ do
    not.  (The dense scoring route has a Jmax ceiling too, 65,536:
    no bucket of this repo's configurations comes near it.)"""
    return (effective_band_width(ArrowConfig().banding, jmax),
            guided_fill_passes(jmax))


class ShapeMenu:
    """The (Imax, Jmax, R) a process polishes at, one pin for each length
    class and lane step it has met, so that the programs a file needs are
    a closed set.

    Left to itself every batch picks its own bucket, and a file's batches
    straddle bucket edges (the longest read, the longest draft and the
    most passes of 64 ZMWs move from batch to batch): each new bucket is a
    family of programs, traced, lowered and loaded when it first appears,
    minutes into a run.  The scheduled driver and `ccs serve` ask here
    instead.  A batch joins a pin of its length class whose lanes hold
    its reads, however few of them it fills (a chunk of 3-pass ZMWs
    polishes in the 32 lanes its file's first chunk opened: the fills
    skip a lane without a read): of several, the one with the fewest
    lanes, and of those one it fits as it stands before one it would
    widen.  Past the ladder's 12 a pin's lanes never grow: a batch with
    more reads than any pin of its class holds opens a new pin at its
    own step of the lane ladder (`lane_step`), at the class's widest
    lengths.  (Up to 12 lanes a pin still grows to a batch of at most
    twice its lanes, as before the ladder: the flushes of a 3-10-pass
    library wander over 8 and 12, and one pin and one batcher key serve
    both.)  A pin widens in Imax or Jmax, by the one bucket step a class
    spans, for a batch whose reads or drafts do not fit it
    (`ccs_menu_pins_total` counts every growth: after a file's first
    batch none should move).  A
    class is a neighbourhood, not the whole menu: a pin and a bucket at
    most one step of their grids apart in Imax and in Jmax, with the
    same band width and guided passes, so the bytes are those of the
    batch's own bucket and a 500 bp batch never pads to a 15 kb
    neighbour.  The pins live as long as the process, as its loaded
    programs do."""

    def __init__(self):
        self._pins: list[tuple[int, int, int]] = []
        self._lock = threading.Lock()

    @staticmethod
    def _same_class(pin, own) -> bool:
        return (abs(pin[0] - own[0]) <= _imax_step(max(pin[0], own[0]))
                and abs(pin[1] - own[1]) <= _jmax_step(max(pin[1], own[1]))
                and _length_class_statics(pin[1])
                == _length_class_statics(own[1]))

    def shapes(self, n_zmws: int, max_reads: int, max_read_len: int,
               max_tpl_len: int) -> tuple[int, int, int, int]:
        """effective_shapes of these inputs under their class's pin."""
        extents = (n_zmws, max_reads, max_read_len, max_tpl_len)
        own = effective_shapes(*extents)
        with self._lock:
            mates = [k for k, pin in enumerate(self._pins)
                     if self._same_class(pin, own)]
            fits = [k for k in mates if own[2] <= self._pins[k][2]]
            if not fits:
                # up to the ladder's 12 lanes a pin grows to a batch of at
                # most twice its lanes, as it did before the ladder
                fits = [k for k in mates
                        if own[2] <= min(2 * self._pins[k][2],
                                         _LANE_STEPS[-1])]
            if not fits:
                pin = (max([own[0]] + [self._pins[k][0] for k in mates]),
                       max([own[1]] + [self._pins[k][1] for k in mates]),
                       own[2])
                self._pins.append(pin)
                _m_menu_pins["new"].inc()
                return effective_shapes(*extents, buckets=pin)
            k = min(fits, key=lambda k: (
                self._pins[k][2],
                own[0] > self._pins[k][0] or own[1] > self._pins[k][1]))
            got = effective_shapes(*extents, buckets=self._pins[k])
            if got[:3] != self._pins[k]:
                self._pins[k] = got[:3]
                _m_menu_pins["grown"].inc()
            return got

    def reset_for_tests(self) -> None:
        with self._lock:
            self._pins.clear()


shape_menu = ShapeMenu()


@dataclasses.dataclass
class ZmwTask:
    """One ZMW's polish-stage inputs (draft template + mapped reads)."""

    id: str
    tpl: np.ndarray           # (L,) int8 draft consensus
    snr: np.ndarray           # (4,)
    reads: Sequence[np.ndarray]
    strands: Sequence[int]
    tstarts: Sequence[int]
    tends: Sequence[int]


@dataclasses.dataclass
class PrebakedBatch:
    """Bucket-shaped host marshalling of a ZmwTask batch, pre-built off
    the device thread (premarshal): the padded numpy planes and the f64
    SNR transition tables that BatchPolisher.__init__ otherwise derives
    inline.  The sched/ prepare pool builds these per batch
    (pipeline.prebake_polish) so the device executor thread adopts
    arrays instead of marshalling -- the same prepare/polish overlap the
    pool already gives the POA stage, extended to the polish setup.

    One code path: BatchPolisher without a prebake calls premarshal()
    itself, so prepared and inline batches are byte-identical by
    construction."""

    tasks: list
    shapes: tuple[int, int, int, int]   # (Imax, Jmax, R, Z)
    snrs: np.ndarray
    reads: np.ndarray
    rlens: np.ndarray
    strands: np.ndarray
    tstarts: np.ndarray
    tends: np.ndarray
    n_reads: np.ndarray
    real_rows: np.ndarray
    host_tables: np.ndarray


def premarshal_nbytes(shapes: tuple[int, int, int, int]) -> int:
    """Host bytes a premarshal() of these effective (Imax, Jmax, R, Z)
    shapes holds -- the per-batch charge the resource governor's
    HostBudget gates the prepare pool on (resilience.resources).  Sums
    the marshalled planes exactly (reads int8 dominates); the ZmwTask
    arrays themselves are references into the reader's buffers and are
    bounded separately by the pipeline's in-flight count."""
    imax, _jmax, r, z = shapes
    return (z * r * imax          # reads int8
            + 4 * z * r * 4       # rlens/strands/tstarts/tends int32
            + z * 4 * 8           # snrs float64
            + z * 4               # n_reads int32
            + z * r               # real_rows bool
            + z * 8 * 4 * 4)      # host_tables float32 (8, 4) per ZMW


def premarshal(tasks: Sequence[ZmwTask], *,
               buckets: tuple[int, int, int] | None = None,
               min_z: int = 1, zq: int = 1, rq: int = 1) -> PrebakedBatch:
    """Marshal a ZmwTask batch into its bucket-shaped numpy planes
    (effective_shapes geometry).  Pure host work -- safe on any thread;
    the heavy item is the per-ZMW float64 SNR transition tables."""
    if not tasks:
        raise ValueError("empty batch")
    Imax, Jmax, R, Z = effective_shapes(
        len(tasks),
        max(len(t.reads) for t in tasks),
        max((len(r) for t in tasks for r in t.reads), default=8),
        max(len(t.tpl) for t in tasks),
        buckets=buckets, min_z=min_z, zq=zq, rq=rq)

    snrs = np.full((Z, 4), 8.0)
    reads = np.full((Z, R, Imax), 4, np.int8)
    rlens = np.zeros((Z, R), np.int32)
    strands = np.zeros((Z, R), np.int32)
    tstarts = np.zeros((Z, R), np.int32)
    tends = np.zeros((Z, R), np.int32)
    n_reads = np.zeros(Z, np.int32)
    for z, t in enumerate(tasks):
        snrs[z] = t.snr
        n_reads[z] = len(t.reads)
        for i, rc in enumerate(t.reads):
            n = min(len(rc), Imax)
            reads[z, i, :n] = rc[:n]
            rlens[z, i] = n
        strands[z, : len(t.reads)] = t.strands
        tstarts[z, : len(t.reads)] = t.tstarts
        tends[z, : len(t.reads)] = t.tends
    # padding read rows (and whole padding ZMWs) get a trivial window
    for z in range(Z):
        L = len(tasks[z].tpl) if z < len(tasks) else 2
        nr = int(n_reads[z])
        reads[z, nr:, :2] = 0
        rlens[z, nr:] = 2
        tends[z, nr:] = min(2, L)

    real_rows = np.zeros((Z, R), bool)
    for z in range(len(tasks)):
        real_rows[z, : int(n_reads[z])] = True

    host_tables = np.stack(
        [snr_to_transition_table_host(snrs[z]) for z in range(Z)]
    ).astype(np.float32)
    return PrebakedBatch(list(tasks), (Imax, Jmax, R, Z), snrs, reads,
                         rlens, strands, tstarts, tends, n_reads,
                         real_rows, host_tables)


@functools.partial(jax.jit, static_argnames=("width", "use_pallas", "mesh",
                                             "guided_passes"))
def _batch_setup(tpls, tlens, tables, reads, rlens, strands, tstarts, tends,
                 width: int, use_pallas: bool, mesh: Mesh | None = None,
                 guided_passes: int = 0, real_rows=None):
    """Per-ZMW template tracks + per-read window fills + moments.

    All leading axes are (Z, ...) with reads (Z, R, Imax).  `tables` are the
    per-ZMW (8, 4) SNR transition tables, computed on host in float64
    (snr_to_transition_table_host) so batched and per-ZMW scorers agree.
    Window building vmaps over (ZMW, read); the alpha/beta fills run on the
    flattened (Z*R) read batch so the Pallas kernel path serves every read
    in one launch.  With `real_rows` ((Z, R) bool) the fills leave the
    lanes that hold no read unfilled (zero bands, zero likelihoods:
    scorer.fill_alpha_beta_batch's `need`)."""

    def one_zmw(tpl, L, table, st1, ts1, te1):
        trans_f = template_transition_params(tpl, table, L)
        tpl_r = revcomp_padded(tpl, L)
        trans_r = template_transition_params(tpl_r, table, L)

        win = jax.vmap(
            lambda s, a, b: oriented_window(s, a, b, tpl, tpl_r, L, table)
        )(st1, ts1, te1)

        mean_f, var_f = per_base_mean_and_variance(trans_f)
        mean_r, var_r = per_base_mean_and_variance(trans_r)
        mu, var = jax.vmap(
            lambda s, a, b: window_moments(s, a, b, mean_f, var_f, mean_r, var_r, L)
        )(st1, ts1, te1)

        return win + (trans_f, tpl_r, trans_r, table, mu, var)

    (win_tpl, win_trans, wlens, trans_f, tpl_r, trans_r, table, mu, var) = \
        jax.vmap(one_zmw)(tpls, tlens, tables, strands, tstarts, tends)

    alpha, beta, ll_a, ll_b, apre, bsuf = fill_alpha_beta_batch_zr(
        reads, rlens, win_tpl, win_trans, wlens, width, use_pallas, mesh,
        guided_passes=guided_passes, need=real_rows)
    return (win_tpl, win_trans, wlens, alpha, beta,
            ll_a, ll_b, apre, bsuf,
            trans_f, tpl_r, trans_r, table, mu, var)


def lowering_target():
    """The canonical per-bucket program, the jitted _batch_setup, for
    callers that lower it without building a polisher
    (tests/test_chip_compile.py)."""
    return _batch_setup


@jax.jit
def _stack_chunks(chunks):
    """Stack per-chunk (Z, M) totals into one (C, Z, M) device array."""
    return jnp.stack(chunks)


def _mated_mask_dev(ll_a, ll_b, rlens, tstarts, tends):
    """Device-side mated_mask (scorer.mated_mask) so refinement rounds can
    update the read-active mask without a device->host stats fetch."""
    from pbccs_tpu.models.arrow.scorer import _AB_MISMATCH_TOL, _MAX_BAND_SHIFT

    mated = jnp.abs(1.0 - ll_a / jnp.where(ll_b == 0, 1.0, ll_b)) <= _AB_MISMATCH_TOL
    mated &= jnp.isfinite(ll_a) & jnp.isfinite(ll_b)
    mated &= rlens <= _MAX_BAND_SHIFT * jnp.maximum(tends - tstarts, 1)
    return mated


@jax.jit
def _update_active(active, ll_a, ll_b, rlens, tstarts, tends):
    return active & _mated_mask_dev(ll_a, ll_b, rlens, tstarts, tends)


@jax.jit
def _update_active_partial(active, ll_a, ll_b, rlens, tstarts, tends,
                           real_sub, idx):
    nz = active.shape[0]
    prev = active[jnp.clip(idx, 0, nz - 1)]
    rows = prev & real_sub & _mated_mask_dev(ll_a, ll_b, rlens,
                                             tstarts, tends)
    return active.at[idx].set(rows, mode="drop")


@jax.jit
def _favorability_eps(baselines, active):
    """(Z,) per-round favorability floor from the CURRENT device-side
    baselines/active mask (refine.favorability_threshold) -- bit-identical
    to the device-resident loop's in-program computation, so the host
    fallback loop selects exactly as the device loop does."""
    return refine_mod.favorability_threshold(
        jnp.sum(jnp.where(active, jnp.abs(baselines), 0.0), axis=1))


@jax.jit
def _fold_edge_slab(totals, et, sel_idx, used):
    """totals[z, sel_idx[z,k]] += et[z,k] where used — on device, so edge
    slabs cost no extra device->host fetch (each fetch is a host
    synchronisation, whatever its size)."""
    upd = jnp.where(used, et, 0.0)
    z = jnp.arange(totals.shape[0], dtype=jnp.int32)[:, None]
    return totals.at[z, sel_idx].add(upd)


@jax.jit
def _fold_fallback(totals, ll, baselines, active, ez, er, em, valid):
    """totals[ez, em] += ll - baselines[ez, er] for fallback pairs (pairs of
    inactive reads are dropped -- the host pair list is geometry-only)."""
    base = baselines[ez, er]
    upd = jnp.where(valid & active[ez, er], ll - base, 0.0)
    return totals.at[ez, em].add(upd)


@jax.jit
def _scatter_z(full, subset, idx):
    """full[leaf][idx[k]] = subset[leaf][k] for every pytree leaf; OOB pad
    indices are dropped."""
    return jax.tree.map(
        lambda f, s: f.at[idx].set(s.astype(f.dtype), mode="drop"),
        full, subset)


@jax.jit
def _batch_interior_totals(reads, rlens, strands, tstarts, tends,
                           win_tpl, win_trans, wlens,
                           alpha_vals, alpha_offs, alpha_ls,
                           beta_vals, beta_offs, beta_ls,
                           a_prefix, b_suffix, baselines,
                           tpl32_f, trans_f, tpl32_r, trans_r, table, tlens,
                           mpos_f, mend_f, mtype, mbase_f, mpos_r, mbase_r,
                           int_mask, active):
    """(Z, M) = sum over reads of masked (LL(mut) - baseline), plus the
    fwd/rev virtual-mutation patches (built in the same program: a separate
    patch dispatch per chunk costs two extra device round-trips per
    refinement round).  int_mask is geometry-only; the read-active mask
    lives on device (active, (Z, R) bool).

    The read-axis reduction is the collective: with reads sharded over the
    'read' mesh axis XLA lowers the sum to an all-reduce over ICI."""
    int_mask = int_mask & active[:, :, None]

    def one_patches(t, tr, tb, l, p1, mt1, b1):
        return make_patches_fast(t, tr, tb, l, p1, mt1, b1)

    patches_f = jax.vmap(one_patches)(tpl32_f, trans_f, table, tlens,
                                      mpos_f, mtype, mbase_f)
    patches_r = jax.vmap(one_patches)(tpl32_r, trans_r, table, tlens,
                                      mpos_r, mtype, mbase_r)

    def one_zmw(read1, rlen1, st1, ts1, te1, wt1, wtr1, wl1,
                av1, ao1, als1, bv1, bo1, bls1, apre1, bsuf1, base1,
                mp1, me1, mt1, pf1, pr1, mask1):
        def one_read(read, rlen, strand, ts, te, wt, wtr, wl,
                     av, ao, als, bv, bo, bls, apre, bsuf, bl, mask):
            lls = interior_read_scores(
                read, rlen, strand, ts, te, wt, wtr, wl,
                BandedMatrix(av, ao, als), BandedMatrix(bv, bo, bls),
                apre, bsuf, mp1, me1, mt1, pf1, pr1)
            return jnp.where(mask, lls - bl, 0.0)

        per_read = jax.vmap(one_read)(
            read1, rlen1, st1, ts1, te1, wt1, wtr1, wl1,
            av1, ao1, als1, bv1, bo1, bls1, apre1, bsuf1, base1, mask1)
        return jnp.sum(per_read, axis=0)

    totals = jax.vmap(one_zmw)(reads, rlens, strands, tstarts, tends,
                               win_tpl, win_trans, wlens,
                               alpha_vals, alpha_offs, alpha_ls,
                               beta_vals, beta_offs, beta_ls,
                               a_prefix, b_suffix, baselines,
                               mpos_f, mend_f, mtype,
                               patches_f, patches_r, int_mask)
    return totals, patches_f, patches_r


@jax.jit
def _batch_edge_fast_totals(reads, rlens, strands, tstarts, tends,
                            win_tpl, win_trans, wlens,
                            alpha_vals, alpha_offs, alpha_ls,
                            beta_vals, beta_offs, beta_ls,
                            a_prefix, b_suffix, baselines,
                            tpl32_f, trans_f, tpl32_r, trans_r, table, tlens,
                            mpos_f, mend_f, mtype, mbase_f, mpos_r, mbase_r,
                            edge_mask, active):
    """(Z, ME) = sum over reads of masked (LL(mut) - baseline) for
    near-window-boundary mutations via the short extension programs
    (ops.mutation_score.edge_scores_fast); same layout/collective shape as
    _batch_interior_totals.  edge_mask is geometry-only; the read-active
    mask lives on device (active, (Z, R) bool)."""
    edge_mask = edge_mask & active[:, :, None]

    def one_patches(t, tr, tb, l, p1, mt1, b1):
        return make_patches_fast(t, tr, tb, l, p1, mt1, b1)

    patches_f = jax.vmap(one_patches)(tpl32_f, trans_f, table, tlens,
                                      mpos_f, mtype, mbase_f)
    patches_r = jax.vmap(one_patches)(tpl32_r, trans_r, table, tlens,
                                      mpos_r, mtype, mbase_r)

    def one_zmw(read1, rlen1, st1, ts1, te1, wt1, wtr1, wl1,
                av1, ao1, als1, bv1, bo1, bls1, apre1, bsuf1, base1,
                mp1, me1, mt1, pf1, pr1, mask1):
        def one_read(read, rlen, strand, ts, te, wt, wtr, wl,
                     av, ao, als, bv, bo, bls, apre, bsuf, bl, mask):
            lls = edge_read_scores_fast(
                read, rlen, strand, ts, te, wt, wtr, wl,
                BandedMatrix(av, ao, als), BandedMatrix(bv, bo, bls),
                apre, bsuf, mp1, me1, mt1, pf1, pr1)
            return jnp.where(mask, lls - bl, 0.0)

        per_read = jax.vmap(one_read)(
            read1, rlen1, st1, ts1, te1, wt1, wtr1, wl1,
            av1, ao1, als1, bv1, bo1, bls1, apre1, bsuf1, base1, mask1)
        return jnp.sum(per_read, axis=0)

    return jax.vmap(one_zmw)(reads, rlens, strands, tstarts, tends,
                             win_tpl, win_trans, wlens,
                             alpha_vals, alpha_offs, alpha_ls,
                             beta_vals, beta_offs, beta_ls,
                             a_prefix, b_suffix, baselines,
                             mpos_f, mend_f, mtype,
                             patches_f, patches_r, edge_mask)


@functools.partial(jax.jit, static_argnames=("width", "use_pallas"))
def _batch_edge(reads, rlens, win_tpl, win_trans, wlens,
                zidx, ridx, pw, mt, pb, ptr, psh, width: int,
                use_pallas: bool):
    """(E,) absolute LLs of edge (read, mutation) pairs via full refill.

    Flattens (Z, R) and delegates to the scorer's batched edge program
    (one-hot row selects + dense mutated windows + batched fills)."""
    Z, R = reads.shape[:2]
    flat = lambda a: a.reshape((Z * R,) + a.shape[2:])
    from pbccs_tpu.models.arrow.scorer import _score_edge
    return _score_edge.__wrapped__(
        flat(reads), flat(rlens), flat(win_tpl), flat(win_trans), flat(wlens),
        zidx * R + ridx, pw, mt, pb, ptr, psh, width, use_pallas)


@dataclasses.dataclass
class _Continuation:
    """Device-loop outcome state that later BatchPolisher calls must
    respect — the straggler-continuation + QV-cache bookkeeping that grew
    ad hoc across refine_device/consensus_qvs (round-4 review ask).

    Invariants:
    * `sub_polishers` maps parent ZMW index -> (sub BatchPolisher, sub
      row).  Non-empty implies `stale_fills`: those parent rows' device
      fills are PRE-continuation, so any later refine() must rebuild
      (begin_refine) before reusing them; QVs for those ZMWs must come
      from the sub-polisher (delegated_qvs), never the parent sweep.
    * `qv_cache` holds (skip set at sweep time, (Z, Jmax) int32 QVs) from
      the loop's eager run_qv_ints sweep against the loop's FINAL
      templates.  It is only valid while those templates are current:
      begin_refine clears it.  A cached sweep serves a later
      consensus_qvs call iff no ZMW live in that call was skipped in the
      cached sweep.
    """

    stale_fills: bool = False
    qv_cache: tuple | None = None
    sub_polishers: dict = dataclasses.field(default_factory=dict)

    def begin_refine(self, polisher: "BatchPolisher") -> None:
        """Entering a new refinement: rebuild stale fills from the current
        host templates and drop state tied to the previous loop's end."""
        if self.stale_fills:
            polisher._setup(first=False)
            self.stale_fills = False
        self.sub_polishers = {}
        self.qv_cache = None

    def record_continuation(self, mapping: dict) -> None:
        """A straggler sub-batch finished rows for these parent ZMWs."""
        self.sub_polishers.update(mapping)
        self.stale_fills = True

    def cached_qvs(self, n_zmws: int, skip: set, tpls) -> list | None:
        """Serve consensus QVs from the loop-time sweep if every ZMW live
        in THIS call was live in the cached sweep too."""
        if self.qv_cache is None:
            return None
        cached_skip, qv_m = self.qv_cache
        if (set(range(n_zmws)) - skip) & cached_skip:
            return None
        return [np.zeros(0, np.int32) if z in skip
                else qv_m[z, : len(tpls[z])].copy() for z in range(n_zmws)]

    def delegated_qvs(self, out: list, skip: set) -> list:
        """Overwrite QVs of continuation-finished ZMWs from their
        sub-polishers (grouped per sub so each sweeps at most once)."""
        subs = self.sub_polishers
        for sub in {id(s): s for s, _ in subs.values()}.values():
            wanted = {i: z for z, (s, i) in subs.items()
                      if s is sub and z not in skip}
            if not wanted:
                continue  # all delegated ZMWs are skipped: no sweep at all
            sub_skip = {i for z, (s, i) in subs.items()
                        if s is sub and z in skip}
            sub_q = sub.consensus_qvs(skip=sub_skip)
            for i, z in wanted.items():
                out[z] = sub_q[i]
        return out


class BatchPolisher:
    """Z bucketed ZMWs polished in lockstep on one device mesh.

    Equivalent per-ZMW semantics to models.arrow.scorer.ArrowMultiReadScorer
    + models.arrow.refine.refine_consensus, with leading (Z,) batch axes and
    optional ('zmw' x 'read') mesh sharding."""

    def __init__(self, tasks: Sequence[ZmwTask],
                 config: ArrowConfig | None = None,
                 min_zscore: float = float("nan"),
                 mesh: Mesh | None = None, *,
                 buckets: tuple[int, int, int] | None = None,
                 min_z: int = 1, fixed_z: bool = False,
                 prebaked: PrebakedBatch | None = None):
        """`buckets` = (Imax, Jmax, R) lower bounds and `min_z` a ZMW-axis
        lower bound: sub-batches carved out of a parent batch (straggler
        continuations, wide-band retries) pin their shapes to the parent's
        buckets and a pow2 Z so the compiled-program menu is bounded --
        letting each draw's straggler count pick its own shapes compiled a
        fresh ~minute-long device loop mid-bench (the round-3 53x
        tail-latency outlier).

        `fixed_z`: the caller polishes every batch of this bucket at this
        one Z however many ZMWs it holds (`ccs serve`: `min_z` is its
        --maxBatch; the scheduled driver under a governor's ceiling, the
        parts of a split and `ccs warmup`: theirs), so this polisher
        stands for all that follow and the shape set's first polish
        loads the wide-band retry's program whatever Z is
        (warm_shape_set).

        `prebaked`: a PrebakedBatch marshalled ahead of time on a prepare
        worker (pipeline.prebake_polish); adopted when its shapes match
        this construction's effective shapes, else silently re-marshalled
        (premarshal is the single marshalling code path either way)."""
        if not tasks:
            raise ValueError("empty batch")
        self.config = config or ArrowConfig()
        self.min_zscore = min_zscore
        self.mesh = mesh
        self.fixed_z = fixed_z
        self.n_zmws = len(tasks)
        self.ids = [t.id for t in tasks]
        self.tpls: list[np.ndarray] = [np.asarray(t.tpl, np.int8) for t in tasks]

        zq = mesh.shape[ZMW_AXIS] if mesh else 1
        rq = mesh.shape[READ_AXIS] if mesh else 1
        shapes = effective_shapes(
            self.n_zmws,
            max(len(t.reads) for t in tasks),
            max((len(r) for t in tasks for r in t.reads), default=8),
            max(len(t.tpl) for t in tasks),
            buckets=buckets, min_z=min_z, zq=zq, rq=rq)
        pb = prebaked
        # adoption requires the prebake to be THIS task batch (object
        # identity), not merely shape-compatible: two same-bucket batches
        # premarshal to identical shapes, and silently adopting the
        # wrong one would polish the wrong reads
        if pb is None or pb.shapes != shapes or len(pb.tasks) != len(tasks) \
                or any(a is not b for a, b in zip(pb.tasks, tasks)):
            pb = premarshal(tasks, buckets=buckets, min_z=min_z,
                            zq=zq, rq=rq)
        self._Imax, self._Jmax, self._R, self._Z = pb.shapes
        self._W = effective_band_width(self.config.banding, self._Jmax)

        self._snrs = pb.snrs
        self._reads = pb.reads
        self._rlens = pb.rlens
        self._strands = pb.strands
        # the window planes are mutated in place by apply_mutations, so a
        # prebake that may be replayed (a device-failure requeue re-runs
        # the same polish closure) hands each polisher its own copy
        self._tstarts = pb.tstarts.copy()
        self._tends = pb.tends.copy()
        self._n_reads = pb.n_reads
        self._real_rows = pb.real_rows

        Z, R = self._Z, self._R
        n_reads_real = int(self._n_reads[: self.n_zmws].sum())
        _m_polishes.inc()
        _m_zmw_slots.inc(Z)
        _m_zmw_used.inc(self.n_zmws)
        _m_read_slots.inc(Z * R)
        _m_read_used.inc(n_reads_real)
        _m_zmw_fill.observe(self.n_zmws / Z)
        _m_read_fill.observe(n_reads_real / (Z * R))

        self._stats_host = None  # lazily fetched AddRead statistics
        self._cont = _Continuation()
        self._host_tables = pb.host_tables
        # flight-recorder batch tag: first ZMW id + batch size names the
        # batch compactly in postmortem dumps
        self._flight_tag = f"{self.ids[0]}+{self.n_zmws}"
        # the first polisher of a shape set loads its family of programs
        # (and then, warm_shape_set, what its later batches meet by chance)
        key = (self._Imax, self._Jmax, self._R, self._Z, self._W)
        with _shape_sets_lock:
            self.first_of_shape_set = key not in _shape_sets_seen
            _shape_sets_seen.add(key)
        if self.first_of_shape_set:
            _m_shape_sets.inc()
        self._setup(first=True)

    # --------------------------------------------------- AddRead statistics

    def _ensure_stats(self) -> None:
        """Materialize the host-visible AddRead statistics from the device
        stack in ONE fetch, on first access.  The gate DECISIONS (statuses,
        active) are fetched verbatim from the device computation so host
        and device never disagree; z-score VALUES are recomputed in f64
        for reporting (as before the gates moved on device)."""
        if self._stats_host is not None:
            return
        stats = device_fetch(self._addread_stats_dev, np.float64)
        ll_a_h, ll_b_h, mu_h, var_h, statuses_f = stats
        statuses = statuses_f.astype(np.int32)
        real = self._real_rows
        mated = real & (statuses != ADD_ALPHABETAMISMATCH)
        z = (ll_b_h - mu_h) / np.sqrt(np.maximum(var_h, 1e-12))
        self._stats_host = {
            "baselines": ll_b_h,
            "ll_mu": mu_h,
            "ll_var": var_h,
            "zscores": np.where(mated, z, np.nan),
            "statuses": statuses,
            "active": real & (statuses == ADD_SUCCESS),
        }

    @property
    def baselines(self) -> np.ndarray:
        self._ensure_stats()
        return self._stats_host["baselines"]

    @property
    def _ll_mu(self) -> np.ndarray:
        self._ensure_stats()
        return self._stats_host["ll_mu"]

    @property
    def _ll_var(self) -> np.ndarray:
        self._ensure_stats()
        return self._stats_host["ll_var"]

    @property
    def zscores(self) -> np.ndarray:
        self._ensure_stats()
        return self._stats_host["zscores"]

    @property
    def statuses(self) -> np.ndarray:
        self._ensure_stats()
        return self._stats_host["statuses"]

    @property
    def active(self) -> np.ndarray:
        """AddRead-time active mask (host snapshot; the live refinement
        mask stays on device as _active_dev)."""
        self._ensure_stats()
        return self._stats_host["active"]

    # ------------------------------------------------------------------ setup

    def _shard(self, arr, read_axis: int | None = None):
        if self.mesh is None:
            return jnp.asarray(arr)
        parts: list = [None] * np.ndim(arr)
        parts[0] = ZMW_AXIS
        if read_axis is not None:
            parts[read_axis] = READ_AXIS
        return jax.device_put(np.asarray(arr),
                              NamedSharding(self.mesh, P(*parts)))

    def _tpl_lengths(self) -> np.ndarray:
        """(Z,) template lengths (padding rows = 2), cached between
        apply_mutations calls; shared by the marshalling paths for their
        mid-template default-dummy geometry."""
        if getattr(self, "_tpl_lengths_cache", None) is None:
            self._tpl_lengths_cache = np.array(
                [len(self.tpls[z]) for z in range(self.n_zmws)]
                + [2] * (self._Z - self.n_zmws), np.int32)
        return self._tpl_lengths_cache

    def _template_arrays(self):
        Z = self._Z
        tl = np.full((Z, self._Jmax), 4, np.int8)
        tlens = np.full(Z, 2, np.int32)
        for z in range(self.n_zmws):
            L = len(self.tpls[z])
            if L > self._Jmax:
                raise ValueError("template outgrew bucket")
            tl[z, :L] = self.tpls[z]
            tlens[z] = L
        return tl, tlens

    def _setup(self, first: bool) -> None:
        """(Re)build all window fills; gate reads on the first build.

        Device copies of the loop-invariant read arrays are cached here:
        re-uploading (Z, R, Imax) tensors on every scoring call costs a
        host->device transfer per refinement round."""
        tl, tlens = self._template_arrays()
        self._tlens = tlens
        if not hasattr(self, "_reads_dev"):
            self._reads_dev = self._shard(self._reads, 1)
            self._rlens_dev = self._shard(self._rlens, 1)
            self._strands_dev = self._shard(self._strands, 1)
        self._tstarts_dev = self._shard(self._tstarts, 1)
        self._tends_dev = self._shard(self._tends, 1)
        self._tlens_dev = self._shard(tlens)
        self._baselines_dev = None  # set after fills below
        (self.win_tpl, self.win_trans, self.wlens, alpha, beta,
         ll_a, ll_b, self.a_prefix, self.b_suffix,
         self.trans_f, self.tpl_r, self.trans_r, self.table,
         mu, var) = _batch_setup(
            self._shard(tl), self._tlens_dev,
            self._shard(self._host_tables),
            self._reads_dev,
            self._rlens_dev,
            self._strands_dev,
            self._tstarts_dev,
            self._tends_dev,
            self._W,
            # under a mesh the Pallas fills run per-device inside
            # jax.shard_map (fill_alpha_beta_batch_zr); pallas_call itself
            # has no GSPMD partitioning rule
            use_pallas=fills_use_pallas(),
            mesh=self.mesh,
            guided_passes=guided_fill_passes(self._Jmax),
            real_rows=self._shard(self._real_rows, 1))
        self.alpha, self.beta = alpha, beta
        self._tpl_dev = self._shard(tl)
        self._tpl32_dev = self._tpl_dev.astype(jnp.int32)
        self._tpl32_r_dev = self.tpl_r.astype(jnp.int32)

        self._baselines_dev = ll_b
        if first:
            # the AddRead gate runs on DEVICE (no fetch: each device->host
            # round trip is a host synchronisation whatever the
            # payload); the host-visible statistics (statuses, zscores,
            # baselines, active) are fetched LAZILY on first access from
            # the stashed stack -- a bench-style refine+QV run never pays
            # for them at all
            z32 = (ll_b - mu) / jnp.sqrt(jnp.maximum(var, 1e-12))
            if np.isnan(self.min_zscore):
                ok_z = jnp.ones_like(z32, bool)
            else:
                ok_z = jnp.isfinite(z32) & (z32 >= np.float32(self.min_zscore))
            mated = _mated_mask_dev(ll_a, ll_b, self._rlens_dev,
                                    self._tstarts_dev, self._tends_dev)
            real = self._shard(self._real_rows, 1)
            self._active_dev = real & mated & ok_z
            statuses = jnp.where(
                ~real, -1,
                jnp.where(~mated, ADD_ALPHABETAMISMATCH,
                          jnp.where(~ok_z, ADD_POOR_ZSCORE, ADD_SUCCESS)))
            self._addread_stats_dev = jnp.stack(
                [ll_a, ll_b, mu, var, statuses.astype(ll_b.dtype)])
            self._stats_host = None
        else:
            # refinement-round rebuild: the active-mask update stays on
            # device (no stats fetch); host copies of baselines/active
            # reflect the AddRead-time state, which is all the pipeline
            # reads (statuses/zscores/global z-scores are draft statistics)
            self._active_dev = _update_active(
                self._active_dev, ll_a, ll_b, self._rlens_dev,
                self._tstarts_dev, self._tends_dev)

    def _setup_partial(self, changed: list[int]) -> None:
        """Refill only the ZMWs whose template changed this round, scattering
        the new windows/fills into the cached device state.  Late refinement
        rounds typically mutate a small fraction of the batch, and the full
        (Z, R) refill was a profiled per-round cost."""
        tl, tlens = self._template_arrays()
        self._tlens = tlens
        Zc = next_pow2(len(changed), 4)
        idx = np.full(Zc, self._Z, np.int32)      # OOB pad -> dropped scatter
        idx[: len(changed)] = changed
        safe = np.clip(idx, 0, self._Z - 1)
        g = lambda a: jnp.asarray(np.asarray(a)[safe])

        sub = _batch_setup(
            g(tl), g(tlens), g(self._host_tables),
            g(self._reads), g(self._rlens), g(self._strands),
            g(self._tstarts), g(self._tends), self._W,
            use_pallas=fills_use_pallas(),
            guided_passes=guided_fill_passes(self._Jmax))
        (w_tpl, w_trans, wlens, s_alpha, s_beta, ll_a, ll_b, apre, bsuf,
         trans_f, tpl_r, trans_r, _table, mu, var) = sub

        full = (self.win_tpl, self.win_trans, self.wlens, self.alpha,
                self.beta, self.a_prefix, self.b_suffix, self.trans_f,
                self.tpl_r, self.trans_r)
        subset = (w_tpl, w_trans, wlens, s_alpha, s_beta, apre, bsuf,
                  trans_f, tpl_r, trans_r)
        (self.win_tpl, self.win_trans, self.wlens, self.alpha, self.beta,
         self.a_prefix, self.b_suffix, self.trans_f, self.tpl_r,
         self.trans_r) = _scatter_z(full, subset, jnp.asarray(idx))

        self._tstarts_dev = self._shard(self._tstarts, 1)
        self._tends_dev = self._shard(self._tends, 1)
        self._tlens_dev = self._shard(tlens)
        tl_dev = jnp.asarray(tl)
        self._tpl_dev = tl_dev
        self._tpl32_dev = tl_dev.astype(jnp.int32)
        self._tpl32_r_dev = self.tpl_r.astype(jnp.int32)

        self._baselines_dev = _scatter_z(self._baselines_dev, ll_b,
                                         jnp.asarray(idx))
        real = self._real_rows[safe]
        self._active_dev = _update_active_partial(
            self._active_dev, ll_a, ll_b, g(self._rlens),
            g(self._tstarts), g(self._tends), jnp.asarray(real),
            jnp.asarray(idx))

    # ---------------------------------------------------------------- scoring

    def _dispatch_chunk(self, pos_f, end_f, mtype, base_f, pos_r, base_r,
                        valid):
        """Dispatch one (Z, MUT_CHUNK) slab's device programs without
        blocking; pair with _collect_chunk.  Keeping several chunks in
        flight hides dispatch latency behind device compute (the profile
        showed ~2 host syncs per chunk serializing the refinement round)."""
        Z = self._Z
        # (Z, R, M) host-side classification
        ts = self._tstarts[:, :, None]
        te = self._tends[:, :, None]
        strand = self._strands[:, :, None]
        ms, me = pos_f[:, None, :], end_f[:, None, :]
        is_ins = (mtype == INS)[:, None, :]
        overlap = np.where(is_ins, (ts <= me) & (ms <= te), (ts < me) & (ms < te))
        p_w = np.where(strand == 0, ms - ts, te - me)
        e_w = np.where(strand == 0, me - ts, te - ms)
        wlen = te - ts
        interior = (p_w >= 3) & (e_w <= wlen - 2)
        # geometry-only masks (real read rows only): the read-active mask
        # stays on device and is ANDed in-program, so refinement rounds need
        # no active-mask fetch
        geo = valid[:, None, :] & overlap & self._real_rows[:, :, None]
        int_mask = geo & interior
        edge_mask = geo & ~interior

        totals_dev, patches_f, patches_r = _batch_interior_totals(
            self._reads_dev, self._rlens_dev,
            self._strands_dev, self._tstarts_dev,
            self._tends_dev,
            self.win_tpl, self.win_trans, self.wlens,
            self.alpha.vals, self.alpha.offsets, self.alpha.log_scales,
            self.beta.vals, self.beta.offsets, self.beta.log_scales,
            self.a_prefix, self.b_suffix, self._baselines_dev,
            self._tpl32_dev, self.trans_f, self._tpl32_r_dev, self.trans_r,
            self.table, self._tlens_dev,
            self._shard(pos_f), self._shard(end_f), self._shard(mtype),
            self._shard(base_f), self._shard(pos_r), self._shard(base_r),
            self._shard(int_mask, 1), self._active_dev)

        # boundary mutations on adequately long windows: short extension
        # programs over (Z, R, EDGE_SLAB) slabs
        fast_mask = edge_mask & (wlen >= MIN_FAST_EDGE_WLEN)
        fb_mask = edge_mask & (wlen < MIN_FAST_EDGE_WLEN)
        em_any = fast_mask.any(axis=1)                      # (Z, M)
        counts = em_any.sum(axis=1)
        if counts.any():
            # Vectorized ragged->dense marshalling: a stable argsort on
            # ~em_any packs each row's edge-mutation indices to the front
            # (True sorts before False), so every slab is a pure numpy
            # gather with no per-(slab, Z) Python loop.
            Mc = int(counts.max())
            order = np.argsort(~em_any, axis=1, kind="stable")[:, :Mc]
            packed_valid = np.take_along_axis(em_any, order, axis=1)
            L_arr = self._tpl_lengths()
            d_pos_f = np.broadcast_to((L_arr // 2)[:, None], (Z, Mc))
            d_end_f = d_pos_f + 1
            d_pos_r = np.broadcast_to((L_arr - L_arr // 2 - 1)[:, None],
                                      (Z, Mc))
            gath = lambda a: np.take_along_axis(a, order, axis=1)
            g_pos_f = np.where(packed_valid, gath(pos_f), d_pos_f)
            g_end_f = np.where(packed_valid, gath(end_f), d_end_f)
            g_mtype = np.where(packed_valid, gath(mtype), SUB)
            g_base_f = np.where(packed_valid, gath(base_f), 0)
            g_pos_r = np.where(packed_valid, gath(pos_r), d_pos_r)
            g_base_r = np.where(packed_valid, gath(base_r), 0)
            g_mask = np.take_along_axis(fast_mask, order[:, None, :],
                                        axis=2) & packed_valid[:, None, :]
            n_slabs = (Mc + EDGE_SLAB - 1) // EDGE_SLAB
            pad = n_slabs * EDGE_SLAB - Mc
            if pad:
                padz = lambda a, fill: np.concatenate(
                    [a, np.broadcast_to(fill, a.shape[:-1] + (pad,))], axis=-1)
                g_pos_f = padz(g_pos_f, d_pos_f[:, :1])
                g_end_f = padz(g_end_f, d_end_f[:, :1])
                g_mtype = padz(g_mtype, SUB)
                g_base_f = padz(g_base_f, 0)
                g_pos_r = padz(g_pos_r, d_pos_r[:, :1])
                g_base_r = padz(g_base_r, 0)
                g_mask = padz(g_mask, False)
                order = padz(order, 0)
                packed_valid = padz(packed_valid, False)
            for k in range(n_slabs):
                sl = slice(k * EDGE_SLAB, (k + 1) * EDGE_SLAB)
                spos_f = np.ascontiguousarray(g_pos_f[:, sl], np.int32)
                send_f = np.ascontiguousarray(g_end_f[:, sl], np.int32)
                smtype = np.ascontiguousarray(g_mtype[:, sl], np.int32)
                sbase_f = np.ascontiguousarray(g_base_f[:, sl], np.int32)
                spos_r = np.ascontiguousarray(g_pos_r[:, sl], np.int32)
                sbase_r = np.ascontiguousarray(g_base_r[:, sl], np.int32)
                smask = np.ascontiguousarray(g_mask[:, :, sl])
                sel_idx = np.ascontiguousarray(order[:, sl], np.int64)
                used = np.ascontiguousarray(packed_valid[:, sl])
                et_dev = _batch_edge_fast_totals(
                    self._reads_dev, self._rlens_dev,
                    self._strands_dev, self._tstarts_dev, self._tends_dev,
                    self.win_tpl, self.win_trans, self.wlens,
                    self.alpha.vals, self.alpha.offsets, self.alpha.log_scales,
                    self.beta.vals, self.beta.offsets, self.beta.log_scales,
                    self.a_prefix, self.b_suffix, self._baselines_dev,
                    self._tpl32_dev, self.trans_f, self._tpl32_r_dev,
                    self.trans_r, self.table, self._tlens_dev,
                    self._shard(spos_f), self._shard(send_f),
                    self._shard(smtype), self._shard(sbase_f),
                    self._shard(spos_r), self._shard(sbase_r),
                    self._shard(smask, 1), self._active_dev)
                totals_dev = _fold_edge_slab(totals_dev, et_dev,
                                             jnp.asarray(sel_idx),
                                             jnp.asarray(used))

        # tiny-window fallback pairs: marshalling needs patch values on the
        # host (one fetch); rare -- only windows below MIN_FAST_EDGE_WLEN
        ez_all, er_all, em_all = np.nonzero(fb_mask)
        if len(ez_all):
            pf_b = np.asarray(patches_f.bases)
            pf_t = np.asarray(patches_f.trans)
            pf_s = np.asarray(patches_f.shift)
            pr_b = np.asarray(patches_r.bases)
            pr_t = np.asarray(patches_r.trans)
            pr_s = np.asarray(patches_r.shift)
            # chunk the edge pairs: one huge pallas fill batch can exceed the
            # compiler's limits, and pow2 chunks keep the shape set bounded
            EDGE_CHUNK = 1024
            for lo in range(0, len(ez_all), EDGE_CHUNK):
                ez = ez_all[lo: lo + EDGE_CHUNK]
                er = er_all[lo: lo + EDGE_CHUNK]
                em = em_all[lo: lo + EDGE_CHUNK]
                E = len(ez)
                Epad = next_pow2(E, 64)
                zi = np.zeros(Epad, np.int32)
                ri = np.zeros(Epad, np.int32)
                pp = np.zeros(Epad, np.int32)
                pt = np.zeros(Epad, np.int32)
                pb = np.zeros((Epad, 2), np.int32)
                ptr = np.zeros((Epad, 2, 4), np.float32)
                psh = np.zeros(Epad, np.int32)
                mi = np.zeros(Epad, np.int32)
                ok = np.zeros(Epad, bool)
                zi[:E], ri[:E], mi[:E], ok[:E] = ez, er, em, True
                pp[:E] = p_w[ez, er, em]
                pt[:E] = mtype[ez, em]
                fwd = self._strands[ez, er] == 0
                pb[:E] = np.where(fwd[:, None], pf_b[ez, em], pr_b[ez, em])
                ptr[:E] = np.where(fwd[:, None, None], pf_t[ez, em], pr_t[ez, em])
                psh[:E] = np.where(fwd, pf_s[ez, em], pr_s[ez, em])
                ll_dev = _batch_edge(
                    self._reads_dev, self._rlens_dev,
                    self.win_tpl, self.win_trans, self.wlens,
                    jnp.asarray(zi), jnp.asarray(ri), jnp.asarray(pp),
                    jnp.asarray(pt), jnp.asarray(pb), jnp.asarray(ptr),
                    jnp.asarray(psh), self._W,
                    fills_use_pallas() and self.mesh is None)
                totals_dev = _fold_fallback(
                    totals_dev, ll_dev, self._baselines_dev,
                    self._active_dev,
                    jnp.asarray(zi), jnp.asarray(ri), jnp.asarray(mi),
                    jnp.asarray(ok))
        return totals_dev

    def score_mutation_arrays(self, arrs: Sequence[mutlib.MutationArrays]
                              ) -> list[np.ndarray]:
        """Per-ZMW arrays of summed mutation scores from MutationArrays
        batches — the vectorized-marshalling fast path (parity with
        ArrowMultiReadScorer.score_mutations, batched over Z)."""
        assert len(arrs) == self.n_zmws
        Z = self._Z
        Mmax = max((a.size for a in arrs), default=0)
        if Mmax == 0:
            return [np.zeros(0) for _ in arrs]
        rcs = [mutlib.reverse_complement_arrays(a, len(self.tpls[z]))
               for z, a in enumerate(arrs)]
        n_chunks = (Mmax + MUT_CHUNK - 1) // MUT_CHUNK

        # Ragged->dense marshalling without per-(chunk, Z) Python loops and
        # without (Z, Mmax)-padded planes: the per-ZMW mutation arrays are
        # concatenated once (actual data size, no padding) and every chunk's
        # (Z, MUT_CHUNK) slab is one vectorized clipped gather, ~15 MB of
        # transient per chunk regardless of Mmax.  Default dummies sit
        # mid-template to stay interior & cheap.
        sizes = np.array([a.size for a in arrs], np.int64)
        offs = np.zeros(self.n_zmws + 1, np.int64)
        np.cumsum(sizes, out=offs[1:])
        catf = lambda field, src: np.concatenate(
            [getattr(a, field) for a in src]) if offs[-1] else \
            np.zeros(0, np.int32)
        flat_pos_f = catf("start", arrs)
        flat_end_f = catf("end", arrs)
        flat_mtype = catf("mtype", arrs)
        flat_base_f = catf("new_base", arrs)
        flat_pos_r = catf("start", rcs)
        flat_base_r = catf("new_base", rcs)

        L_arr = self._tpl_lengths()
        d_pos_f = np.broadcast_to((L_arr // 2)[:, None], (Z, MUT_CHUNK))
        d_end_f = d_pos_f + 1
        d_pos_r = np.broadcast_to((L_arr - L_arr // 2 - 1)[:, None],
                                  (Z, MUT_CHUNK))

        # dispatch every chunk before collecting any: the device works
        # through the queued programs while the host marshals ahead
        states = []
        m = np.arange(MUT_CHUNK, dtype=np.int64)[None, :]
        for c in range(n_chunks):
            lo = c * MUT_CHUNK
            valid = np.zeros((Z, MUT_CHUNK), bool)
            valid[: self.n_zmws] = (lo + m) < sizes[:, None]
            gidx = np.zeros((Z, MUT_CHUNK), np.int64)
            gidx[: self.n_zmws] = np.minimum(
                offs[:-1, None] + lo + m, offs[1:, None] - 1)
            gidx = np.clip(gidx, 0, max(offs[-1] - 1, 0))
            pick = lambda flat, dflt: np.where(
                valid, flat[gidx], dflt) if len(flat) else \
                np.broadcast_to(dflt, (Z, MUT_CHUNK)).copy()
            states.append(self._dispatch_chunk(
                pick(flat_pos_f, d_pos_f).astype(np.int32),
                pick(flat_end_f, d_end_f).astype(np.int32),
                pick(flat_mtype, SUB).astype(np.int32),
                pick(flat_base_f, 0).astype(np.int32),
                pick(flat_pos_r, d_pos_r).astype(np.int32),
                pick(flat_base_r, 0).astype(np.int32),
                valid))

        # one stacked fetch for the whole call: every device->host transfer
        # is a host synchronisation regardless of payload
        stacked = device_fetch(_stack_chunks(states), np.float64)
        out = []
        for z in range(self.n_zmws):
            # (C, M) row view -> one contiguous copy of this ZMW's scores
            out.append(np.ascontiguousarray(
                stacked[:, z, :]).reshape(-1)[: arrs[z].size])
        return out

    def score_mutations(self, muts_per_zmw: Sequence[Sequence[mutlib.Mutation]]
                        ) -> list[np.ndarray]:
        """Object-list convenience wrapper over score_mutation_arrays."""
        return self.score_mutation_arrays(
            [mutlib.arrays_from_mutations(m) for m in muts_per_zmw])

    # --------------------------------------------------------------- mutation

    def apply_mutations(self, best_per_zmw: Sequence[Sequence[mutlib.Mutation]]
                        ) -> None:
        """Splice per-ZMW mutations, remap read windows, rebuild fills."""
        changed: list[int] = []
        self._tpl_lengths_cache = None
        self._cont.qv_cache = None
        for z, best in enumerate(best_per_zmw):
            if not best:
                continue
            changed.append(z)
            L = len(self.tpls[z])
            mtp = mutlib.target_to_query_positions(best, L)
            self.tpls[z] = mutlib.apply_mutations(self.tpls[z], best)
            self._tstarts[z] = mtp[np.clip(self._tstarts[z], 0, L)]
            self._tends[z] = mtp[np.clip(self._tends[z], 0, L)]
        if not changed:
            return
        max_l = max(len(t) for t in self.tpls)
        rebucket = max_l + 2 > self._Jmax
        if rebucket:
            self._Jmax = _jmax_bucket(max_l)  # rebucket (recompiles)
        # partial refill when a minority of ZMWs changed (mesh runs always
        # rebuild in full: the compacted sub-batch breaks the sharding)
        if (self.mesh is None and not rebucket
                and len(changed) * 2 <= self.n_zmws):
            self._setup_partial(changed)
        else:
            self._setup(first=False)

    # ------------------------------------------------------------- refinement

    def _device_resident_enabled(self) -> bool:
        """One source of truth for the device-resident-path gate (the
        refinement loop and the QV sweep must agree); opt-out via
        PBCCS_DEVICE_REFINE=0/false/off/no.  Mesh runs ride the sharded
        loop (device_refine.run_refine_loop_sharded), which requires the
        dense scoring path -- without it they fall back to the host
        loop's sharded per-round programs."""
        if os.environ.get("PBCCS_DEVICE_REFINE", "").strip().lower() in (
                "0", "false", "off", "no"):
            return False
        if self.mesh is not None:
            from pbccs_tpu.ops.dense_score_pallas import dense_score_enabled

            return dense_score_enabled(self._Jmax)
        return True

    def _loop_state(self, skip=None, it0: int = 0):
        """Assemble the device-resident loop/sweep state from the adopted
        device tensors (parallel/device_refine.RefineLoopState).

        When the dense scoring path is on, the kernel-layout pre-bake
        happens HERE (state_layout): the loop and the QV sweep launch on
        baked buffers, and only fill-rebuilding rounds re-derive them."""
        from pbccs_tpu.ops.dense_score_pallas import dense_score_enabled
        from pbccs_tpu.parallel import device_refine as dr

        Z, Jmax = self._Z, self._Jmax
        tl, tlens = self._template_arrays()
        done0 = np.zeros(Z, bool)
        done0[self.n_zmws:] = True
        for z in (skip or ()):
            done0[z] = True
        dlayout = None
        if dense_score_enabled(Jmax):
            dlayout = dr.state_layout(
                self._reads_dev, self._rlens_dev, self.win_tpl,
                self.win_trans, self.wlens,
                self._shard(self._host_tables), self.alpha, self.beta,
                self.a_prefix, self.b_suffix, width=self._W)
        H = 48
        return dr.RefineLoopState(
            tpl=jnp.asarray(tl), tlens=jnp.asarray(tlens),
            tstarts=self._tstarts_dev, tends=self._tends_dev,
            win_tpl=self.win_tpl, win_trans=self.win_trans,
            wlens=self.wlens, alpha=self.alpha, beta=self.beta,
            a_prefix=self.a_prefix, b_suffix=self.b_suffix,
            baselines=self._baselines_dev, trans_f=self.trans_f,
            tpl_r=self.tpl_r, trans_r=self.trans_r,
            active=self._active_dev,
            # it0 > 0 (a straggler continuation) starts the round counter
            # at the rounds already spent: the static max_iterations bound
            # is unchanged (one executable per shape) while the loop runs
            # at most the remaining rounds
            it=jnp.int32(it0),
            done=jnp.asarray(done0),
            converged=jnp.zeros(Z, bool),
            iterations=jnp.zeros(Z, jnp.int32),
            n_tested=jnp.zeros(Z, jnp.int32),
            n_applied=jnp.zeros(Z, jnp.int32),
            allowed=jnp.ones((Z, Jmax), bool),
            history=jnp.zeros((Z, H), jnp.uint32),
            hist_n=jnp.zeros(Z, jnp.int32),
            overflow=jnp.asarray(False),
            dlayout=dlayout, fill_reads=jnp.zeros(2, jnp.int32),
            # under Z = 32 the loop has no straggler exit, so a ZMW that
            # ping-pongs to the budget would hold the dispatch: it stops
            # as soon as its rounds are shown to repeat
            cycle=dr.new_cycle_watch(Z, H)
            if self.mesh is None and not dr.straggler_exit_zmws(Z)
            else None)

    def refine_device(self, opts: RefineOptions | None = None,
                      skip=None, budget: int | None = None
                      ) -> list[RefineResult] | None:
        """Device-resident refinement: the whole loop runs inside one
        jitted lax.while_loop (parallel/device_refine.py) and the host
        fetches ONCE at the end -- the host loop's per-round fetch chain
        stalls the device between rounds.

        Returns None when the loop bailed (template outgrew the bucket or
        a tiny-window fallback pair appeared); the caller falls back to
        the host loop.  Mesh runs shard the whole loop over the
        ('zmw', 'read') mesh (run_refine_loop_sharded): the read-axis
        score reduction all-reduces over ICI and the host still fetches
        ONCE at the end."""
        from pbccs_tpu.ops.dense_score_pallas import dense_score_enabled
        from pbccs_tpu.parallel import device_refine as dr

        if self.mesh is not None and not dense_score_enabled(self._Jmax):
            return None
        opts = opts or RefineOptions()
        budget = opts.max_iterations if budget is None else budget
        # rebuild-if-stale + drop loop-end state (invariants: _Continuation)
        self._cont.begin_refine(self)
        Z, R, Jmax = self._Z, self._R, self._Jmax

        st = self._loop_state(skip, it0=opts.max_iterations - budget)

        loop_statics = dict(
            width=self._W, use_pallas=fills_use_pallas(),
            max_iterations=opts.max_iterations,
            separation=opts.mutation_separation,
            neighborhood=opts.mutation_neighborhood,
            chunk=MUT_CHUNK, min_fast_edge=MIN_FAST_EDGE_WLEN,
            dense=dense_score_enabled(self._Jmax),
            guided_passes=guided_fill_passes(self._Jmax))
        loop_args = (st, self._reads_dev, self._rlens_dev,
                     self._strands_dev, self._shard(self._host_tables),
                     self._shard(self._real_rows, 1))
        if self.mesh is not None:
            out = dr.run_refine_loop_sharded(
                self.mesh, ZMW_AXIS, READ_AXIS, *loop_args, **loop_statics)
        else:
            out = dr.run_refine_loop(*loop_args, **loop_statics)
        # Eager QV sweep on the loop's final state, dispatched back-to-back
        # with the loop program (no host sync between them): consensus_qvs
        # serves from the cached integers, so a refine+QV polish pays ONE
        # device->host fetch total instead of a separate ~1.5 MB score
        # fetch + round trip.
        qv_skip = np.zeros(Z, bool)
        qv_skip[self.n_zmws:] = True
        for z in (skip or ()):
            qv_skip[z] = True
        qv_statics = dict(chunk=MUT_CHUNK, min_fast_edge=MIN_FAST_EDGE_WLEN,
                          dense=dense_score_enabled(self._Jmax))
        qv_args = (out, self._reads_dev, self._rlens_dev,
                   self._strands_dev, self._shard(self._host_tables),
                   self._shard(self._real_rows, 1), self._shard(qv_skip))
        if self.mesh is not None:
            qv_i, qv_fb = dr.run_qv_ints_sharded(
                self.mesh, ZMW_AXIS, READ_AXIS, *qv_args, **qv_statics)
        else:
            qv_i, qv_fb = dr.run_qv_ints(*qv_args, **qv_statics)
        # ONE stacked fetch of every outcome plane (each device->host round
        # trip is a host synchronisation; three sequential fetches here
        # were three stalls per polish)
        R = self._R
        packed = jnp.concatenate([
            jnp.stack([out.tlens.astype(jnp.int32),
                       out.converged.astype(jnp.int32),
                       out.iterations, out.n_tested, out.n_applied,
                       jnp.broadcast_to(out.overflow.astype(jnp.int32),
                                        (Z,)),
                       jnp.broadcast_to(qv_fb.astype(jnp.int32), (Z,)),
                       jnp.broadcast_to(out.fill_reads[0], (Z,)),
                       jnp.broadcast_to(out.fill_reads[1], (Z,)),
                       jnp.zeros(Z, jnp.int32) if out.cycle is None
                       else out.cycle.cycled.astype(jnp.int32)],
                      axis=1),
            out.tpl.astype(jnp.int32),
            out.tstarts.astype(jnp.int32),
            out.tends.astype(jnp.int32),
            qv_i,
        ], axis=1)
        h = device_fetch(packed, np.int64)
        tlens_h, conv_h, iters_h = h[:, 0], h[:, 1], h[:, 2]
        tested_h, applied_h, overflow_h = h[:, 3], h[:, 4], h[:, 5]
        obs_flight.record_fill_reads(h[0, 7], h[0, 8])
        if overflow_h[0]:
            return None  # host loop re-runs from the polisher's last state
        cycled_h = h[:, 9]
        planes = h[:, 10:]  # template, window starts, window ends, QVs
        if not h[0, 6]:  # no tiny-window fallback in the QV sweep
            self._cont.qv_cache = (frozenset(skip or ()),
                                   planes[:, Jmax + 2 * R:].astype(np.int32))

        tpl_h = planes[:, :Jmax].astype(np.int8)
        for z in range(self.n_zmws):
            self.tpls[z] = tpl_h[z, : tlens_h[z]].copy()
        self._tstarts = planes[:, Jmax: Jmax + R].astype(np.int32)
        self._tends = planes[:, Jmax + R: Jmax + 2 * R].astype(np.int32)
        self._tpl_lengths_cache = None

        # adopt the loop's final device state so the QV sweep reuses it
        (self.win_tpl, self.win_trans, self.wlens, self.alpha, self.beta,
         self.a_prefix, self.b_suffix) = (
            out.win_tpl, out.win_trans, out.wlens, out.alpha, out.beta,
            out.a_prefix, out.b_suffix)
        self._baselines_dev = out.baselines
        self._active_dev = out.active
        self.trans_f, self.tpl_r, self.trans_r = (out.trans_f, out.tpl_r,
                                                  out.trans_r)
        self._tpl_dev = out.tpl
        self._tpl32_dev = out.tpl.astype(jnp.int32)
        self._tpl32_r_dev = out.tpl_r.astype(jnp.int32)
        self._tstarts_dev = out.tstarts
        self._tends_dev = out.tends
        self._tlens_dev = out.tlens
        self._tlens = tlens_h.astype(np.int32)

        # skip/padding ZMWs start done and can never set converged on device
        results = [RefineResult(converged=bool(conv_h[z]),
                                n_tested=int(tested_h[z]),
                                n_applied=int(applied_h[z]),
                                iterations=int(iters_h[z]))
                   for z in range(self.n_zmws)]
        # ZMWs the loop stopped as periodic: final, not converged
        cycled = {z for z in range(self.n_zmws) if cycled_h[z]}
        for z in cycled:
            _m_cycle_stops["zmws"].inc()
            _m_cycle_stops["rounds_spared"].inc(budget - results[z].iterations)

        # flight recorder: the device-resident loop is one jitted program
        # (per-round host callbacks would reintroduce the fetch-per-round
        # chain), so its per-round occupancy is RECONSTRUCTED from the
        # fetched iteration counts -- a ZMW with k iterations was live in
        # rounds 0..k-1, which is exact for the lockstep loop
        it0_rounds = opts.max_iterations - budget
        iters_live = iters_h[: self.n_zmws]
        for rnd in range(int(iters_live.max(initial=0))):
            obs_flight.record_round(
                self._flight_tag, it0_rounds + rnd,
                int((iters_live > rnd).sum()), self.n_zmws, self._Z,
                source="device")

        # Straggler continuation: the loop exits early once few ZMWs remain
        # (full-width lockstep rounds for 1-2 cycling ZMWs would dominate,
        # e.g. a 40-round budget); finish them in a compact small-Z
        # sub-polisher whose own device loop runs tiny rounds fetch-free.
        skipset = set(skip or ())
        stragglers = [z for z in range(self.n_zmws)
                      if z not in skipset and not results[z].converged
                      and results[z].iterations < budget
                      and z not in cycled]
        # stragglers share one iteration count by construction: the device
        # loop is lockstep, a ZMW leaves it only by converging (which
        # excludes it from `stragglers`), so every straggler ran every
        # round up to the early exit -- max() == each straggler's count
        sub_budget = (budget - max(results[z].iterations
                                   for z in stragglers)) if stragglers else 0
        if stragglers and sub_budget > 0 and self.n_zmws > len(stragglers) \
                and self.mesh is None:
            # the continuation carries the REMAINING round budget (total
            # iterations across parent + sub match the host loop and the
            # reference's single max_iterations bound); the static
            # max_iterations stays the executable-cache key, the spent
            # rounds ride in as the dynamic initial round counter.
            # Shapes pin to the parent's buckets + ONE canonical Z (the
            # pow2 of the loop's straggler-exit threshold, an upper bound
            # on the straggler count) so every draw's straggler set --
            # whatever its size -- reuses the same compiled programs
            # (_straggler_sub; pre-warmable via warm_straggler_shapes).
            with obs_trace.span("polish.refine.straggler",
                                zmws=len(stragglers)):
                sub = self._straggler_sub(stragglers)
                # parent gating carries over; the sub-polisher must not
                # re-gate (it sees mid-refinement templates, not the
                # draft).  The live read-active mask is on device (host
                # copy is the AddRead-time snapshot by design); fetch just
                # the straggler rows.
                act = device_fetch(out.active)
                sub_active = np.zeros((sub._Z, sub._R), bool)
                for i, z in enumerate(stragglers):
                    n = min(sub._R, self._R)
                    sub_active[i, :n] = act[z, :n]
                sub._active_dev = sub._shard(sub_active, 1)
                sub_res = sub.refine(opts, budget=sub_budget)
                for i, z in enumerate(stragglers):
                    self.tpls[z] = sub.tpls[i]
                    r = sub_res[i]
                    results[z] = RefineResult(
                        converged=r.converged,
                        n_tested=results[z].n_tested + r.n_tested,
                        n_applied=results[z].n_applied + r.n_applied,
                        iterations=results[z].iterations + r.iterations)
                self._tpl_lengths_cache = None
                self._cont.record_continuation(
                    {z: (sub, i) for i, z in enumerate(stragglers)})
        return results

    def straggler_shape_min_z(self) -> int:
        """The canonical ZMW-axis size of this polisher's straggler
        continuation sub-batches (device_refine.run_refine_loop exits
        early once <= Z//32 ZMWs remain; the sub-batch pads to this one
        pow2 size so its compiled shapes are draw-independent)."""
        return next_pow2(max(self._Z // 32, 1), 4)

    def _row_tasks(self, zmws: Sequence[int], tag: str) -> list[ZmwTask]:
        """The given parent rows as tasks of their own: current
        template, real reads, current windows."""
        tasks = []
        for z in zmws:
            rows = np.nonzero(self._real_rows[z])[0]
            tasks.append(ZmwTask(
                f"{tag}/{z}", self.tpls[z].copy(), self._snrs[z],
                [self._reads[z, r, : self._rlens[z, r]].copy()
                 for r in rows],
                [int(self._strands[z, r]) for r in rows],
                [int(self._tstarts[z, r]) for r in rows],
                [int(self._tends[z, r]) for r in rows]))
        return tasks

    def _straggler_sub(self, zmws: Sequence[int]) -> "BatchPolisher":
        """Construct the canonical straggler-continuation sub-batch for
        the given parent rows — ONE shape recipe shared by the live
        continuation (refine_device) and warm_straggler_shapes, so the
        pre-warm compiles exactly the executables the continuation uses."""
        return BatchPolisher(self._row_tasks(zmws, "straggler"),
                             config=self.config,
                             buckets=(self._Imax, self._Jmax, self._R),
                             min_z=self.straggler_shape_min_z())

    def wide_band_subs(self, tasks: Sequence[ZmwTask]
                       ) -> list["BatchPolisher"]:
        """The 2x-band sub-batches of the pipeline's mating retry -- ONE
        shape recipe shared by the live retry (pipeline.
        _polish_batch_arrow) and warm_shape_set.  Shapes pin to the
        parent's buckets and to one Z, four, whatever the parent's:
        however many ZMWs reband, they polish four at a time in the
        program the shape set's first polish loaded, where a Z padded to
        their count minted a fresh family for every count past four.  2x
        the EFFECTIVE width (the W(L) schedule may have shrunk the parent
        below the configured width); a non-default width passes through
        the schedule."""
        wcfg = dataclasses.replace(
            self.config,
            banding=dataclasses.replace(self.config.banding,
                                        band_width=2 * self._W))
        return [BatchPolisher(tasks[i: i + WIDE_BAND_Z], config=wcfg,
                              min_zscore=self.min_zscore,
                              buckets=(self._Imax, self._Jmax, self._R),
                              min_z=WIDE_BAND_Z)
                for i in range(0, len(tasks), WIDE_BAND_Z)]

    def warm_straggler_shapes(self, opts: RefineOptions | None = None
                              ) -> None:
        """Compile the straggler-continuation shapes ahead of timed work.

        Whether a batch produces stragglers is data-dependent; their first
        appearance used to cold-compile a ~minute-long device loop inside
        a timed run (the round-3 53x tail-latency outlier), and with every
        executable cached still traces and lowers one (85-100 s at 2 kb).
        `opts` must match the opts later passed to refine() --
        max_iterations is part of the executable cache key."""
        if self._Z // 32 < 1 or self.n_zmws < 1:
            return  # this Z has no straggler early exit
        sub = self._straggler_sub([0])
        sub.refine(opts)
        sub.consensus_qvs()

    def warm_shape_set(self, opts: RefineOptions | None = None) -> None:
        """Load what a shape set's first polish leaves to chance: the
        straggler continuation's programs and the wide-band retry's
        (which batch first leaves a ZMW behind, first fails a mating, or
        first has a ZMW adopt the wide band, is data; each then stops
        the run to trace, lower and load: 85-100 s, 4-14 s and about
        90 s at 2 kb).  The one recipe of `ccs warmup`
        (sched/warmup.py) and of the batch path, which calls it from
        the first polish of each shape set
        (pipeline._polish_batch_arrow), a flush of `ccs serve` among
        them.  A `fixed_z` polisher (a part of the governor's split,
        every dispatch of the scheduled driver under a ceiling, every
        flush of `ccs serve`) stands for all that follow at its Z and
        loads the retry's program whatever Z is; one at a Z of its own
        under 32 has no continuation, and what follows it may polish at
        another Z: nothing is loaded for it."""
        if self._Z // 32 < 1 and not self.fixed_z:
            return
        with obs_trace.span("polish.warm", imax=self._Imax,
                            jmax=self._Jmax, r=self._R,
                            z=self.straggler_shape_min_z()):
            self.warm_straggler_shapes(opts)
            # built, gated and polished as the live retry does it
            wide, = self.wide_band_subs(self._row_tasks([0], "warm"))
            wide.statuses
            wide.refine(opts)
            wide.consensus_qvs()

    def refine(self, opts: RefineOptions | None = None,
               skip=None, budget: int | None = None) -> list[RefineResult]:
        """Lockstep greedy refinement across the batch.

        Single-device runs route through the device-resident loop
        (refine_device: the whole loop in one program, one fetch) unless
        PBCCS_DEVICE_REFINE=0; mesh runs and device-loop bails (template
        outgrew the bucket, tiny-window fallback pair) use the host loop
        below, whose behavior the device loop is parity-tested against.

        ZMW indices in `skip` take no part in refinement (their RefineResult
        stays non-converged): the pipeline excludes ZMWs that already failed
        a yield gate so their slots cost no mutation work and their templates
        cannot grow the bucket.

        `budget` caps the number of refinement rounds this call may run
        (defaults to opts.max_iterations); a straggler continuation passes
        its remaining rounds so parent + continuation together never exceed
        the reference's single max_iterations bound."""
        opts = opts or RefineOptions()
        if budget is None:
            budget = opts.max_iterations
        if self._device_resident_enabled():
            results = self.refine_device(opts, skip, budget=budget)
            if results is not None:
                return results
        Z = self.n_zmws
        results = [RefineResult(converged=False) for _ in range(Z)]
        history: list[set[int]] = [set() for _ in range(Z)]
        favorable: list[list[mutlib.Mutation]] = [[] for _ in range(Z)]
        done = np.zeros(Z, bool)
        for z in (skip or ()):
            done[z] = True

        empty = mutlib.MutationArrays(*(np.zeros(0, np.int32),) * 4)
        for it in range(budget):
            # f32 score-noise floor, recomputed PER ROUND from the current
            # device-side baselines/active mask -- the same favorability
            # rule (and the same f32 arithmetic) as the device-resident
            # loop and the per-round serial scorer, so all three polish
            # paths select identically.  One tiny (Z,)-fetch per round;
            # this loop is already the fetch-per-round fallback path.
            eps_z = device_fetch(
                _favorability_eps(self._baselines_dev, self._active_dev),
                np.float64)
            arrs: list[mutlib.MutationArrays] = []
            for z in range(Z):
                if done[z]:
                    arrs.append(empty)
                elif it == 0:
                    arrs.append(mutlib.enumerate_unique_arrays(self.tpls[z]))
                else:
                    arrs.append(mutlib.unique_nearby_arrays(
                        self.tpls[z], favorable[z], opts.mutation_neighborhood))
            if all(done):
                break
            live = int((~done).sum())
            obs_flight.record_round(self._flight_tag, it, live,
                                    self.n_zmws, self._Z)
            with obs_trace.span("polish.round", round=it, live=live):
                scores = self.score_mutation_arrays(arrs)

                best_per_zmw: list[list[mutlib.Mutation]] = []
                for z in range(Z):
                    if done[z]:
                        best_per_zmw.append([])
                        continue
                    results[z].iterations = it + 1
                    results[z].n_tested += arrs[z].size
                    favi = np.nonzero(scores[z] > eps_z[z])[0]
                    fav = arrs[z].take(favi).to_mutations(scores[z][favi])
                    favorable[z] = fav
                    if not fav:
                        results[z].converged = True
                        done[z] = True
                        best_per_zmw.append([])
                        continue
                    best = mutlib.best_subset(fav, opts.mutation_separation)
                    # cycle avoidance (Consensus-inl.hpp:229-241): trim a
                    # visited multi-mutation result to its best single
                    # mutation, but keep iterating (a repeated template does
                    # not terminate; see models/arrow/refine.py)
                    if len(best) > 1:
                        nxt = mutlib.apply_mutations(self.tpls[z], best)
                        if hash(nxt.tobytes()) in history[z]:
                            best = [max(best, key=lambda m: m.score)]
                    history[z].add(hash(self.tpls[z].tobytes()))
                    results[z].n_applied += len(best)
                    best_per_zmw.append(best)

                self.apply_mutations(best_per_zmw)

        return results

    # ------------------------------------------------------------------- QVs

    def consensus_qvs(self, skip=None) -> list[np.ndarray]:
        """Per-ZMW per-position QVs (parity: ConsensusQVs,
        Consensus-inl.hpp:277-297), one batched sweep.  ZMWs in `skip` get
        empty QV arrays and cost no device work.  ZMWs the device loop
        finished in a straggler sub-polisher (refine_device) pull their QVs
        from it -- the parent's fills for those slots are pre-continuation."""
        skip = set(skip or ())
        out = self._consensus_qvs_impl(
            skip | set(self._cont.sub_polishers))
        return self._cont.delegated_qvs(out, skip)

    def _consensus_qvs_impl(self, skip) -> list[np.ndarray]:
        # refine_device leaves per-position integer QVs computed on the
        # loop's final state (run_qv_ints); serve from that cache when
        # every live ZMW was live in the cached sweep too.  The cached
        # reduction ran in f32 on device; the fallback below reduces in
        # f64 on host -- identical except where the exact QV lands within
        # f32 rounding of a .5 boundary (a <=1-unit knife-edge, invisible
        # after the [0, 93] output clamp)
        cached = self._cont.cached_qvs(self.n_zmws, set(skip), self.tpls)
        if cached is not None:
            return cached
        empty = mutlib.MutationArrays(*(np.zeros(0, np.int32),) * 4)
        arrs = [empty if z in skip else mutlib.enumerate_unique_arrays(t)
                for z, t in enumerate(self.tpls[: self.n_zmws])]
        skipped = [z in skip for z in range(self.n_zmws)]
        scores = None
        if self._device_resident_enabled() and self.mesh is None:
            # mesh runs serve QVs from the refine-time cache (run_qv_ints
            # sharded); a cache miss falls through to the chunked sharded
            # scoring path rather than the unsharded grid program
            scores = self._qv_scores_device(skip, arrs)
        if scores is None:
            scores = self.score_mutation_arrays(arrs)
        out = []
        for z in range(self.n_zmws):
            if skipped[z]:
                out.append(np.zeros(0, np.int32))
                continue
            ssum = np.zeros(len(self.tpls[z]))
            neg = scores[z] < 0.0
            np.add.at(ssum, arrs[z].start[neg], np.exp(scores[z][neg]))
            out.append(mutlib.qvs_from_neg_sums(ssum))
        return out

    def _qv_scores_device(self, skip, arrs) -> list[np.ndarray] | None:
        """QV-sweep slot-grid scores in ONE device program + one fetch.

        The chunked host path (score_mutation_arrays) dispatches C programs
        with numpy mask building between them -- ~1 s of wall for ~80 ms of
        device compute on the bench workload.  Per-slot values are
        identical (packing only reorders the chunk axis), so the host
        aggregation downstream is unchanged.  Returns None when a
        tiny-window fallback pair exists (the chunked path handles it)."""
        from pbccs_tpu.parallel import device_refine as dr

        st = self._loop_state(skip)
        skip_mask = np.zeros(self._Z, bool)
        skip_mask[self.n_zmws:] = True
        for z in skip:
            skip_mask[z] = True
        from pbccs_tpu.ops.dense_score_pallas import dense_score_enabled

        packed, fb = dr.run_qv_grid(
            st, self._reads_dev, self._rlens_dev, self._strands_dev,
            self._shard(self._host_tables), jnp.asarray(self._real_rows),
            jnp.asarray(skip_mask),
            chunk=MUT_CHUNK, min_fast_edge=MIN_FAST_EDGE_WLEN,
            dense=dense_score_enabled(self._Jmax))
        stacked = device_fetch(jnp.concatenate(
            [packed, jnp.broadcast_to(fb.astype(packed.dtype),
                                      (1, packed.shape[1]))], axis=0),
            np.float64)
        if stacked[-1, 0] > 0.5:
            return None  # tiny-window fallback pair: chunked path handles
        out = []
        for z in range(self.n_zmws):
            if skip_mask[z]:
                out.append(np.zeros(0))
                continue
            # row z's leading entries are its valid-slot scores in host
            # enumeration order (run_qv_grid packing contract)
            out.append(stacked[z, : arrs[z].size])
        return out

    # -------------------------------------------------------------- banding

    def banding_report(self) -> dict:
        """Banding / matrix-usage introspection (the TPU analogue of the
        reference's AllocatedMatrixEntries / UsedMatrixEntries /
        NumFlipFlops counters, Arrow/MultiReadMutationScorer.hpp:139-144):
        band occupancy of the current alpha fills, mating-gate outcomes,
        and the static VMEM footprint of the dense kernel's grid cell.
        One device fetch; intended for logs and the bench artifact, and
        for justifying W-per-length-bucket schedules."""
        from pbccs_tpu.ops.dense_score_pallas import cell_vmem_bytes
        from pbccs_tpu.ops.fwdbwd import band_columns

        W = self._W
        vals = band_columns(self.alpha).vals
        nc = int(vals.shape[2])
        # occupancy: fraction of band lanes holding live probability mass
        # per in-window column, averaged over real active reads
        live_col = (jnp.arange(nc)[None, None, :]
                    <= self.wlens[:, :, None])
        nz = jnp.sum((vals > 0) & live_col[:, :, :, None], axis=(2, 3))
        denom = jnp.maximum(jnp.sum(live_col, axis=2) * W, 1)
        occ = nz / denom
        act = self._active_dev
        occ_mean = jnp.sum(jnp.where(act, occ, 0.0)) / jnp.maximum(
            jnp.sum(act), 1)
        occ_max = jnp.max(jnp.where(act, occ, 0.0))
        vals = device_fetch(jnp.stack([occ_mean, occ_max]), np.float64)
        self._ensure_stats()
        statuses = self._stats_host["statuses"]
        real = self._real_rows
        jm = int(self.win_tpl.shape[2])   # the kernel's actual bucket
        vmem_cell = cell_vmem_bytes(jm, W)
        return {
            "band_width": W,
            "jmax_bucket": self._Jmax,
            "imax_bucket": self._Imax,
            "band_occupancy_mean": round(float(vals[0]), 4),
            "band_occupancy_max": round(float(vals[1]), 4),
            "reads_total": int(real.sum()),
            "mating_failures": int(((statuses == ADD_ALPHABETAMISMATCH)
                                    & real).sum()),
            "zscore_drops": int(((statuses == ADD_POOR_ZSCORE)
                                 & real).sum()),
            "dense_kernel_mode": "halo",
            "dense_kernel_vmem_per_cell_bytes": int(vmem_cell),
            "guided_fill_passes": guided_fill_passes(self._Jmax),
        }

    def global_zscores(self) -> np.ndarray:
        """(Z,) z-score of the summed log-likelihood per ZMW.

        Reports DRAFT-template statistics: baselines/active are AddRead-time
        host snapshots by design (refinement rounds keep their updates on
        device; see _setup), so calling this after refine() still describes
        the pre-refinement template -- which is what the pipeline reports,
        matching the serial path and the reference's draft-time ZScores."""
        out = np.full(self.n_zmws, np.nan)
        for z in range(self.n_zmws):
            act = self.active[z]
            if not act.any():
                continue
            var = self._ll_var[z][act].sum()
            if var <= 0:
                continue
            ll = self.baselines[z][act].sum()
            out[z] = (ll - self._ll_mu[z][act].sum()) / np.sqrt(var)
        return out
