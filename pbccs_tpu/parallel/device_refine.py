"""Device-resident refinement primitives: enumeration, selection, splice.

The host lockstep refinement loop (parallel/batch.py refine) fetches the
(Z, M) mutation scores every round to run selection and template splicing
in numpy; each fetch is a device->host synchronisation that stalls the
enqueue pipeline, and the per-round fetch chain dominated polish wall
time.  These primitives re-express the
host-side round logic as fixed-shape device ops so the whole refinement
loop can run inside one jitted program (see batch.BatchPolisher.refine's
device path), fetching once at the end.

Parity targets (each pinned by tests/test_device_refine.py):
  * slot_candidates == mutations.enumerate_unique_arrays (same candidate
    set in the same pos-major order; rounds > 0 apply the same
    center-window position filter as unique_nearby_arrays, though the
    host's center-major candidate ORDER is not reproduced -- order only
    matters for exact score ties);
  * greedy_well_separated == mutations.best_subset (greedy max-score with
    inclusive +-separation start exclusion; ties resolve to the earlier
    candidate, matching the host's first-max rule in round 0).  At
    separation == 0 (unused by any caller) the device deviates: it keeps
    at most one mutation per start (see the in-function comment);
  * splice_templates == mutations.apply_mutations +
    target_to_query_positions (the mtp map: mtp[j] = j - dels(<j) +
    ins(<=j)).

Candidate slot grid: position-major, 9 slots per template position in the
host enumeration order (subs by base, ins by base, del); invalid slots are
masked, never reordered, so slot index == candidate identity.
"""

from __future__ import annotations

import functools
import typing
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pbccs_tpu.models.arrow.mutations import (_LN10 as _MUT_LN10,
                                              _SLOT_BASES, _SLOT_ENDOFF,
                                              _SLOT_TYPES, DELETION,
                                              INSERTION, QV_SATURATED,
                                              SUBSTITUTION)
from pbccs_tpu.ops.fwdbwd import BandedMatrix, row_major
from pbccs_tpu.ops.mutation_score import slot_geometry

N_SLOTS = 9
EDGE_BUDGET = 64  # packed edge-mutation slab width per scoring chunk
# slot layout per position: the host enumeration's own tables (one source
# of truth for the slot-index == candidate-identity contract)
SLOT_BASES = _SLOT_BASES
SLOT_TYPES = _SLOT_TYPES
SLOT_ENDOFF = _SLOT_ENDOFF

_HASH_MULT = np.uint32(2654435761)  # Knuth multiplicative constant
# the two multipliers of state_fingerprint (xxHash's 32-bit primes 2, 3)
_FP_MULTS = (np.uint32(2246822519), np.uint32(3266489917))


def slot_candidates(tpl: jax.Array, tlen: jax.Array,
                    allowed_pos: jax.Array | None = None):
    """All unique single-base mutation candidates of one padded template.

    Returns (start, end, mtype, new_base, valid), each (Jmax * 9,), in the
    host enumeration order.  `allowed_pos` ((Jmax,) bool) restricts
    candidate start positions (the nearby-window filter of rounds > 0)."""
    Jmax = tpl.shape[0]
    t = tpl.astype(jnp.int32)
    prev = jnp.concatenate([jnp.array([-1], jnp.int32), t[:-1]])
    pos = jnp.arange(Jmax, dtype=jnp.int32)

    valid = jnp.zeros((Jmax, N_SLOTS), bool)
    valid = valid.at[:, :4].set(SLOT_BASES[None, :4] != t[:, None])
    valid = valid.at[:, 4:8].set(SLOT_BASES[None, 4:8] != prev[:, None])
    valid = valid.at[:, 8].set(t != prev)
    valid &= (pos < tlen)[:, None]
    if allowed_pos is not None:
        valid &= allowed_pos[:, None]

    start = jnp.repeat(pos, N_SLOTS)
    end = start + jnp.asarray(SLOT_ENDOFF)[None, :].repeat(Jmax, 0).reshape(-1)
    mtype = jnp.tile(jnp.asarray(SLOT_TYPES), Jmax)
    base = jnp.tile(jnp.asarray(SLOT_BASES), Jmax)
    return start, end, mtype, base, valid.reshape(-1)


def rc_candidates(start, end, base, tlen):
    """Reverse-complement frame of the slot grid (mutations
    reverse_complement_arrays): (start_r, base_r)."""
    comp = jnp.where(base < 0, -1, 3 - base)
    return tlen - end, comp


def _lex_window_max(sc, sl, separation: int):
    """Windowed lexicographic max over positions: for each position p,
    the (score desc, slot asc) best among positions [p-sep, p+sep].
    2*sep static shift-combines (sep is small: default 10)."""
    def shift(x, d, fill):
        if d > 0:
            return jnp.concatenate([x[d:], jnp.full(d, fill, x.dtype)])
        return jnp.concatenate([jnp.full(-d, fill, x.dtype), x[:d]])

    best_sc, best_sl = sc, sl
    for d in range(1, separation + 1):
        for s in (d, -d):
            c_sc = shift(sc, s, -jnp.inf)
            c_sl = shift(sl, s, jnp.iinfo(sl.dtype).max)
            win = (c_sc > best_sc) | ((c_sc == best_sc) & (c_sl < best_sl))
            best_sc = jnp.where(win, c_sc, best_sc)
            best_sl = jnp.where(win, c_sl, best_sl)
    return best_sc, best_sl


def _window_or(mask, separation: int):
    """positions within +-separation of any set position (static shifts)."""
    out = mask
    for d in range(1, separation + 1):
        out = out | jnp.concatenate([mask[d:], jnp.zeros(d, bool)])
        out = out | jnp.concatenate([jnp.zeros(d, bool), mask[:-d]])
    return out


def greedy_well_separated(scores: jax.Array, start: jax.Array,
                          favorable: jax.Array, separation: int,
                          jmax: int) -> jax.Array:
    """(M,) bool taken-mask: greedy max-score subset with starts more than
    `separation` apart (inclusive exclusion), ties to the earlier slot.

    Data-parallel local-max PEELING instead of an M-step sequential scan
    (the scan's per-candidate scatter was ~7% of all device time in the
    round-3 profile): each peel round simultaneously takes every live
    candidate that is the lexicographic (score desc, slot asc) maximum
    among live candidates within +-separation of its start, then blocks
    their neighborhoods.  Winners of one round are mutually >separation
    apart by construction (two winners within the window would each have
    to lexicographically beat the other), and the result equals the
    sequential greedy scan: a candidate survives to be taken iff it is
    not dominated by a taken candidate in its window, which the peeling
    resolves layer by layer.  Parity with the scan implementation is
    pinned by tests/test_device_refine.py::test_greedy_peel_matches_scan.
    """
    M = scores.shape[0]
    if separation == 0:
        # DOCUMENTED DEVIATION from the host at separation == 0 (a setting
        # no caller uses; RefineOptions defaults to 10): host best_subset
        # keeps every favorable and apply_mutations can apply several
        # same-start edits, but splice_templates' scatters silently merge
        # same-start edits, so the device keeps only the best-scoring
        # favorable per start (ties to the earlier slot) rather than
        # corrupt the template
        seg = jnp.full(jmax, -jnp.inf).at[jnp.clip(start, 0, jmax - 1)].max(
            jnp.where(favorable, scores, -jnp.inf))
        is_best = favorable & (scores == seg[jnp.clip(start, 0, jmax - 1)])
        slot = jnp.arange(M, dtype=jnp.int32)
        first = jnp.full(jmax, M, jnp.int32).at[
            jnp.clip(start, 0, jmax - 1)].min(jnp.where(is_best, slot, M))
        return is_best & (slot == first[jnp.clip(start, 0, jmax - 1)])

    slot = jnp.arange(M, dtype=jnp.int32)
    sstart = jnp.clip(start, 0, jmax - 1)
    sc32 = scores.astype(jnp.float32)

    def body(st):
        taken, blocked, alive = st
        live_sc = jnp.where(alive, sc32, -jnp.inf)
        # per-position best live candidate: (max score, then min slot
        # among the score-achievers) -- two scatters
        pos_sc = jnp.full(jmax, -jnp.inf).at[sstart].max(live_sc)
        hit = alive & (sc32 == pos_sc[sstart])
        pos_sl = jnp.full(jmax, M, jnp.int32).at[sstart].min(
            jnp.where(hit, slot, M))
        win_sc, win_sl = _lex_window_max(pos_sc, pos_sl, separation)
        winner = alive & (win_sl[sstart] == slot)
        taken = taken | winner
        win_pos = jnp.zeros(jmax, bool).at[sstart].max(winner)
        blocked = blocked | _window_or(win_pos, separation)
        alive = alive & ~winner & ~blocked[sstart]
        return taken, blocked, alive

    taken, _, _ = lax.while_loop(
        lambda st: st[2].any(), body,
        (jnp.zeros(M, bool), jnp.zeros(jmax, bool), favorable))
    return taken


def greedy_well_separated_posmajor(scores: jax.Array, favorable: jax.Array,
                                   separation: int, jmax: int) -> jax.Array:
    """greedy_well_separated for the canonical position-major slot grid
    (slot_candidates: start[m] == m // N_SLOTS — what every loop-body
    caller passes).  The general form's per-peel `x[start]` gathers and
    `.at[start]` scatters (vmapped → TPU scalar core; ~6% of device time
    at the 30-pass config) all collapse to (jmax, 9) reshapes with axis
    reductions/broadcasts.  Parity with the general form is pinned by
    tests/test_device_refine.py."""
    M = scores.shape[0]
    ns = M // jmax
    sc2 = scores.astype(jnp.float32).reshape(jmax, ns)
    slot2 = jnp.arange(M, dtype=jnp.int32).reshape(jmax, ns)

    def body(st):
        taken, blocked, alive = st
        live_sc = jnp.where(alive, sc2, -jnp.inf)
        pos_sc = live_sc.max(axis=1)
        hit = alive & (sc2 == pos_sc[:, None])
        pos_sl = jnp.where(hit, slot2, M).min(axis=1)
        win_sc, win_sl = _lex_window_max(pos_sc, pos_sl, separation)
        winner = alive & (win_sl[:, None] == slot2)
        taken = taken | winner
        win_pos = winner.any(axis=1)
        blocked = blocked | _window_or(win_pos, separation)
        alive = alive & ~winner & ~blocked[:, None]
        return taken, blocked, alive

    taken, _, _ = lax.while_loop(
        lambda st: st[2].any(), body,
        (jnp.zeros((jmax, ns), bool), jnp.zeros(jmax, bool),
         favorable.reshape(jmax, ns)))
    return taken.reshape(M)


def greedy_well_separated_scan(scores: jax.Array, start: jax.Array,
                               favorable: jax.Array, separation: int,
                               jmax: int) -> jax.Array:
    """The original M-step sequential-scan greedy (kept as the parity
    oracle for the peeling implementation; not used on the hot path)."""
    M = scores.shape[0]
    if separation == 0:
        return greedy_well_separated(scores, start, favorable, 0, jmax)
    neg = jnp.where(favorable, -scores, jnp.inf)
    order = jnp.argsort(neg, stable=True)  # score desc, slot-index ties

    pos = jnp.arange(jmax, dtype=jnp.int32)

    def step(carry, i):
        blocked, taken = carry
        cand = order[i]
        s = start[cand]
        ok = favorable[cand] & ~blocked[s]
        window = (pos >= s - separation) & (pos <= s + separation) & ok
        return (blocked | window, taken.at[cand].set(ok)), None

    (blocked, taken), _ = lax.scan(
        step, (jnp.zeros(jmax, bool), jnp.zeros(M, bool)),
        jnp.arange(M))
    return taken


def splice_templates(tpl: jax.Array, tlen: jax.Array,
                     start: jax.Array, mtype: jax.Array, base: jax.Array,
                     taken: jax.Array):
    """Apply a well-separated taken-set of single-base mutations.

    Returns (new_tpl (Jmax,), new_tlen, mtp (Jmax+1,)) where mtp is the
    old->new position map (target_to_query_positions).  Separation >= 1
    guarantees at most one taken mutation per start position, so the edit
    at each position is unique and the splice is two scatters.

    Capacity contract: new_tlen is returned UNCLAMPED; bases past Jmax are
    dropped by the scatters, so the caller MUST treat new_tlen > Jmax as
    an overflow (the loop sets its bail-to-host flag) rather than carry
    the inconsistent (tpl, tlen) pair into another round."""
    Jmax = tpl.shape[0]
    pos = jnp.arange(Jmax, dtype=jnp.int32)

    # per-position edit planes from the taken set
    safe_start = jnp.clip(start, 0, Jmax - 1)
    is_sub = taken & (mtype == SUBSTITUTION)
    is_ins = taken & (mtype == INSERTION)
    is_del = taken & (mtype == DELETION)
    sub_at = jnp.zeros(Jmax, bool).at[safe_start].max(is_sub)
    sub_base = jnp.zeros(Jmax, jnp.int32).at[safe_start].max(
        jnp.where(is_sub, base, 0))
    ins_at = jnp.zeros(Jmax + 1, bool).at[jnp.clip(start, 0, Jmax)].max(is_ins)
    ins_base = jnp.zeros(Jmax + 1, jnp.int32).at[jnp.clip(start, 0, Jmax)].max(
        jnp.where(is_ins, base, 0))
    del_at = jnp.zeros(Jmax, bool).at[safe_start].max(is_del)

    # mtp[j] = j - dels(start < j) + ins(start <= j)
    dels_before = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(del_at.astype(jnp.int32))])
    ins_upto = jnp.cumsum(ins_at.astype(jnp.int32))
    mtp = jnp.arange(Jmax + 1, dtype=jnp.int32) - dels_before + ins_upto

    new_tlen = mtp[tlen]

    edited = jnp.where(sub_at, sub_base, tpl.astype(jnp.int32))
    new_tpl = jnp.full(Jmax, 4, jnp.int32)
    keep = (~del_at) & (pos < tlen)
    dst = jnp.where(keep, mtp[:-1], Jmax)           # OOB drop for dels/pad
    new_tpl = new_tpl.at[dst].set(edited, mode="drop")
    ins_dst = jnp.where(ins_at & (jnp.arange(Jmax + 1) <= tlen),
                        mtp - 1, Jmax)
    new_tpl = new_tpl.at[ins_dst].set(ins_base, mode="drop")
    return new_tpl.astype(tpl.dtype), new_tlen, mtp


def template_hash(tpl: jax.Array, tlen: jax.Array) -> jax.Array:
    """Rolling uint32 hash of the live template prefix (cycle detection)."""
    Jmax = tpl.shape[0]
    j = jnp.arange(Jmax, dtype=jnp.uint32)
    powers = jnp.power(_HASH_MULT, j + 1)  # uint32 wraparound
    live = (j < tlen.astype(jnp.uint32))
    vals = jnp.where(live, tpl.astype(jnp.uint32) + 2, 0)
    return (vals * powers).sum(dtype=jnp.uint32) ^ tlen.astype(jnp.uint32)


def state_fingerprint(tpl, tlen, tstarts, tends, active, allowed):
    """(2,) uint32: 64 bits over everything of one ZMW that a round of
    run_refine_loop reads and writes but its template-hash history: the
    live template, the read windows, the active reads and the candidate
    filter (fills, baselines and scores are functions of these).  Two
    rounds of a ZMW that start at equal fingerprints start at the same
    state."""
    j = jnp.arange(tpl.shape[0], dtype=jnp.uint32)
    per_pos = (jnp.where(j < tlen.astype(jnp.uint32),
                         tpl.astype(jnp.uint32) + 2, 0)
               + 7 * allowed.astype(jnp.uint32))
    r = jnp.arange(tstarts.shape[0], dtype=jnp.uint32)
    per_read = (tstarts.astype(jnp.uint32) * 31
                + tends.astype(jnp.uint32) * 17
                + active.astype(jnp.uint32))

    def fold(mult):
        return ((per_pos * jnp.power(mult, j + 1)).sum(dtype=jnp.uint32)
                ^ (per_read * jnp.power(mult, r + 1)).sum(dtype=jnp.uint32)
                * _HASH_MULT ^ tlen.astype(jnp.uint32))

    return jnp.stack([fold(m) for m in _FP_MULTS])


def straggler_exit_zmws(z: int) -> int:
    """run_refine_loop at Z = `z` returns once this many ZMWs or fewer are
    live (0: it has no early exit) and leaves them to the caller's
    continuation (batch.BatchPolisher.refine_device)."""
    return z // 32


class CycleWatch(NamedTuple):
    """What run_refine_loop carries to stop a ZMW whose rounds repeat.

    A ZMW that ping-pongs between templates runs to the round budget and
    ends not converged; in a loop with no straggler exit it holds every
    other slot of its dispatch until then.  Its rounds are a function of
    its state and of its template-hash history, and the history only
    grows and only ever turns a multi-mutation round into its single best
    mutation.  So once a round starts at the fingerprint an earlier round
    started at, and no round since that one was a multi-mutation round
    the history had not yet trimmed (`settled_from`), every later round
    repeats one already run: the ZMW cannot converge, and stops at once
    with the outcome the budget would have given it."""

    ring: jax.Array          # (Z, H, 2) uint32 fingerprints, by round run
    settled_from: jax.Array  # (Z,) int32 first round of the settled run
    cycled: jax.Array        # (Z,) bool stopped as periodic


def new_cycle_watch(z: int, depth: int) -> CycleWatch:
    return CycleWatch(jnp.zeros((z, depth, 2), jnp.uint32),
                      jnp.zeros(z, jnp.int32), jnp.zeros(z, bool))


class RefineLoopState(NamedTuple):
    """Carry of the device-resident refinement while_loop.

    Loop-constant read tensors (reads/rlens/strands/table) are closed over
    by the jitted loop, not carried."""

    tpl: jax.Array          # (Z, Jmax) int8 forward template
    tlens: jax.Array        # (Z,) int32
    tstarts: jax.Array      # (Z, R) int32 read windows (fwd frame)
    tends: jax.Array
    win_tpl: jax.Array      # per-read oriented windows + fills
    win_trans: jax.Array
    wlens: jax.Array
    alpha: BandedMatrix     # leaves (Z, R, ...)
    beta: BandedMatrix
    a_prefix: jax.Array
    b_suffix: jax.Array
    baselines: jax.Array    # (Z, R)
    trans_f: jax.Array      # (Z, Jmax, 4)
    tpl_r: jax.Array        # (Z, Jmax) int8 reverse-complement template
    trans_r: jax.Array
    active: jax.Array       # (Z, R) bool
    it: jax.Array           # () int32
    done: jax.Array         # (Z,) bool
    converged: jax.Array    # (Z,) bool
    iterations: jax.Array   # (Z,) int32
    n_tested: jax.Array     # (Z,) int32
    n_applied: jax.Array    # (Z,) int32
    allowed: jax.Array      # (Z, Jmax) bool candidate-position filter
    history: jax.Array      # (Z, H) uint32 template-hash ring
    hist_n: jax.Array       # (Z,) int32
    overflow: jax.Array     # () bool: bail-to-host flag
    # pre-baked dense-kernel layout (ops.dense_score_pallas.DenseLayout
    # with (Z, R)-leading leaves), rebuilt only when fills rebuild; None
    # on the chunked scoring path.  Rounds that apply no mutation (and
    # the eager QV sweep after the loop) relaunch the kernel on the
    # previous rebuild's baked buffers instead of re-deriving the
    # layout in-graph every round.
    dlayout: typing.Any = None
    # (2,) int32: reads the loop's rebuilds filled, and reads they would
    # have filled had each refilled the whole batch (Z * R a rebuild);
    # fetched with the outcome, ccs_refine_fill_reads_total.  A state
    # built without one starts at zero.
    fill_reads: typing.Any = None
    # CycleWatch where the loop stops periodic ZMWs (a loop with no
    # straggler exit, off a mesh: batch._loop_state); else None
    cycle: typing.Any = None


def reads_to_refill(applied, real_rows):
    """(Z, R) bool: the reads a rebuild of run_refine_loop refills, those
    of the ZMWs that applied a mutation this round ((Z,) bool) whose lane
    holds a read."""
    return applied[:, None] & real_rows


def _chunk_count(jmax: int, chunk: int) -> int:
    return (jmax * N_SLOTS + chunk - 1) // chunk


def _state_layout(reads, rlens, win_tpl, win_trans, wlens, table,
                  alpha: BandedMatrix, beta: BandedMatrix, a_prefix,
                  b_suffix, width: int, windows=None):
    """(Z, R)-leading DenseLayout for RefineLoopState.dlayout: flatten
    the batch to the kernel's (Z*R)-flat read frame, bake the layout
    (ops.dense_score_pallas.build_dense_layout), reshape leaves back.
    Plain function for enclosing traces (the loop's rebuild, which hands
    in the read `windows` it holds); state_layout below is the jitted
    prepare-time entry."""
    from pbccs_tpu.ops.dense_score_pallas import build_dense_layout

    Z, R = reads.shape[:2]
    flat = lambda a: a.reshape((Z * R,) + a.shape[2:])
    tables = flat(jnp.broadcast_to(table[:, None],
                                   (Z, R) + table.shape[1:]))
    lay = build_dense_layout(flat(reads), flat(rlens), flat(win_tpl),
                             flat(win_trans), flat(wlens), tables,
                             *jax.tree.map(flat, (alpha, beta)),
                             flat(a_prefix), flat(b_suffix), width,
                             jax.tree.map(flat, windows))
    return jax.tree.map(lambda a: a.reshape((Z, R) + a.shape[1:]), lay)


state_layout = functools.partial(jax.jit, static_argnames=("width",))(
    _state_layout)


def _score_slot_grid_dense(st: "RefineLoopState", reads, rlens, strands,
                           table, real_rows, start, end, mtype, base,
                           valid, *, min_fast_edge: int):
    """Dense-path (Z, M) slot-grid totals: interior scores come from the
    Pallas dense kernel (ops/dense_score_pallas) -- one whole-grid pass
    with VMEM-resident intermediates instead of the chunk scan whose
    materialized (Z, R, chunk, W) intermediates made the packed path
    HBM-bound.  Edge slots live at STATIC
    window-frame rows ({0,1,2} and {J-2,J-1,J}), so they are scored by
    the small window-frame edge program (edge_window_scores_batch) and
    spliced into the kernel grid before the orientation mapping -- the
    whole grid then maps and reduces in one pass, with no packed edge
    slab, no edge budget, and no template-frame edge machinery.

    The kernel's (Z*R, Jm, 9) output is read once, by the splice, which
    writes it slot-major, (Z*R, 9, Jm): positions on the lanes.  One more
    pass (slot_grid_totals) maps the reads onto the template frame, masks
    them (slot_geometry over the slots' (9, Jmax) start / end / type
    planes), takes the baselines off and sums over a ZMW's reads in a
    written-out order, and only the reduced (Z, 9, Jmax) is turned to the
    position-major (Z, M) the callers read."""
    from pbccs_tpu.ops.dense_score_pallas import (
        build_dense_layout, dense_interior_scores_batch,
        edge_window_scores_batch, slot_grid_totals, slot_major_spliced)

    Z, R = reads.shape[:2]
    jmax = st.tpl.shape[1]
    M = jmax * N_SLOTS

    # the candidates' planes, slot-major: (9, jmax) and (Z, 9, jmax)
    turn = lambda a: jnp.swapaxes(
        a.reshape(a.shape[:-1] + (jmax, N_SLOTS)), -1, -2)
    start_s, end_s, valid_s = turn(start), turn(end), turn(valid)
    ins_s = turn(mtype) == INSERTION

    # geometry classification over the full grid
    zr = lambda a: a[:, :, None, None]
    overlap, interior, wlen = slot_geometry(
        zr(st.tstarts), zr(st.tends), zr(strands), start_s, end_s, ins_s)
    geo = valid_s[:, None] & overlap & zr(real_rows)
    # tiny windows (wlen < min_fast_edge) cannot ride the window-frame
    # edge program (its two regimes would overlap); bail to the host loop
    fb = (geo & ~interior & (wlen < min_fast_edge)).any()

    flat = lambda a: a.reshape((Z * R,) + a.shape[2:])
    tables = flat(jnp.broadcast_to(table[:, None], (Z, R) + table.shape[1:]))
    W = st.alpha.vals.shape[-1]
    f_reads, f_rlens = flat(reads), flat(rlens)
    f_wt, f_wtr, f_wl = flat(st.win_tpl), flat(st.win_trans), flat(st.wlens)
    alpha_f, beta_f = jax.tree.map(flat, (st.alpha, st.beta))
    f_apre, f_bsuf = flat(st.a_prefix), flat(st.b_suffix)
    # the kernel layout carried in the loop state, its (Z, R)-leading
    # leaves flattened to the call's (Z*R)-flat read batch; a state
    # without one derives it here, once for the kernel and the edge program
    lay = jax.tree.map(flat, st.dlayout) if st.dlayout is not None else \
        build_dense_layout(f_reads, f_rlens, f_wt, f_wtr, f_wl, tables,
                           alpha_f, beta_f, f_apre, f_bsuf, W)
    # (read, position-block) live mask: rounds > 0 restrict candidates to
    # nearby windows, so most kernel grid cells have no valid slot and
    # can skip all compute.  A block is live iff any valid candidate
    # POSITION maps into its window rows (over-approximated by +-1 to
    # cover the ins/subdel row offset in the reverse frame).
    from pbccs_tpu.ops.dense_score_pallas import _PB
    NB = -(-jmax // _PB)
    pos_any = valid_s.any(1)
    pref = jnp.concatenate(
        [jnp.zeros((Z, 1), jnp.int32),
         jnp.cumsum(pos_any.astype(jnp.int32), axis=1)], axis=1)
    b = jnp.arange(NB, dtype=jnp.int32)[None, None, :]
    ts3, te3 = st.tstarts[:, :, None], st.tends[:, :, None]
    lo_f, hi_f = ts3 + b * _PB, ts3 + (b + 1) * _PB
    lo_r, hi_r = te3 - (b + 1) * _PB - 1, te3 - b * _PB + 1
    fwd3 = strands[:, :, None] == 0
    lo = jnp.clip(jnp.where(fwd3, lo_f, lo_r) - 1, 0, jmax)
    hi = jnp.clip(jnp.where(fwd3, hi_f, hi_r) + 1, 0, jmax)
    # pref[hi] - pref[lo]: below the size gate, ONE one-hot einsum on the
    # MXU (the take_along_axis pair lowers to the scalar core, ~4% of
    # device time at the headline config); the einsum's (Z, R*NB, jmax+1)
    # selector is O(jmax) larger than the gathers, so long-template
    # buckets keep the gather form.
    if Z * R * NB * (jmax + 1) <= (1 << 26):
        grid_pos = jnp.arange(jmax + 1, dtype=jnp.int32)
        sel = ((hi.reshape(Z, -1, 1) == grid_pos).astype(jnp.float32)
               - (lo.reshape(Z, -1, 1) == grid_pos).astype(jnp.float32))
        diff = jnp.einsum("zmn,zn->zm", sel, pref.astype(jnp.float32),
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST
                          ).reshape(Z, R, NB)
        live = diff > 0.5
    else:
        take = lambda idx: jnp.take_along_axis(
            pref, idx.reshape(Z, -1), axis=1).reshape(Z, R, NB)
        live = (take(hi) - take(lo)) > 0
    live = live & real_rows[:, :, None] & st.active[:, :, None]
    grid_w = dense_interior_scores_batch(
        f_reads, f_rlens, f_wt, f_wtr, f_wl, tables, alpha_f, beta_f,
        f_apre, f_bsuf, W, live.reshape(Z * R, NB), layout=lay)

    # edge slots always compute (not gated behind a cond): the edge
    # program has no data dependence on the kernel output, so XLA
    # overlaps the two -- a measured win over skipping edges in the
    # rounds that don't need them
    e6 = edge_window_scores_batch(f_reads, f_rlens, f_wt, f_wtr, f_wl,
                                  tables, alpha_f, beta_f, f_apre, f_bsuf,
                                  W, layout=lay)
    out_s = slot_grid_totals(
        slot_major_spliced(grid_w, e6, f_wl), flat(strands),
        flat(st.tstarts), flat(st.tends), flat(real_rows & st.active),
        flat(st.baselines), valid_s, start_s, end_s, ins_s)
    return jnp.swapaxes(out_s, 1, 2).reshape(Z, M), fb


def score_slot_grid(st: "RefineLoopState", reads, rlens, strands, table,
                    real_rows, start, end, mtype, base, valid, *,
                    chunk: int, min_fast_edge: int, dense: bool = False,
                    read_axis: str | None = None):
    """(Z, M) totals over all candidate slots; also returns the
    tiny-window fallback flag (LOCAL under shard_map -- the caller makes
    it global).  Shared by the refinement loop's per-round scoring and
    the one-dispatch QV sweep (run_qv_grid).

    `read_axis` names the mesh axis the read dimension is sharded over
    when running inside jax.shard_map: each device reduces its local
    reads and the final (Z, M) totals all-reduce over that axis (XLA
    lowers the psum onto ICI).  Only the dense path supports it.

    With dense=True the interior scores come from the Pallas dense-grid
    kernel (_score_slot_grid_dense, the TPU path).  Otherwise candidates
    are packed per ZMW (stable argsort puts each row's valid slots first)
    and scored in fixed chunks: the live work of sparse rounds -- nearby
    windows cover a small fraction of the slot grid after round 0 --
    compacts into the leading chunk(s) and the all-invalid tail chunks
    short-circuit.  Scores scatter back to slot-grid layout."""
    if dense:
        out, fb = _score_slot_grid_dense(st, reads, rlens, strands, table,
                                         real_rows, start, end, mtype,
                                         base, valid,
                                         min_fast_edge=min_fast_edge)
        if read_axis is not None:
            out = lax.psum(out, read_axis)
        return out, fb
    assert read_axis is None, "mesh scoring requires the dense path"
    from pbccs_tpu.parallel import batch as batchmod

    Z = reads.shape[0]
    jmax = st.tpl.shape[1]
    M = jmax * N_SLOTS
    C = _chunk_count(jmax, chunk)
    Mpad = C * chunk
    pad = Mpad - M

    pack = jnp.argsort(~valid, axis=1, stable=True)      # (Z, M)
    gz = lambda a: jnp.take_along_axis(a, pack, axis=1)
    gm = lambda a: jnp.take_along_axis(
        jnp.broadcast_to(a[None, :], (Z, M)), pack, axis=1)
    p_start, p_end = gm(start), gm(end)
    p_mtype, p_base = gm(mtype), gm(base)
    p_valid = gz(valid)

    def padz(a, fill):
        return jnp.pad(a, [(0, 0), (0, pad)], constant_values=fill)

    cshape = lambda a: a.reshape(Z, C, chunk).transpose(1, 0, 2)
    pos_f = cshape(padz(p_start, 0))
    end_f = cshape(padz(p_end, 1))
    mt = cshape(padz(p_mtype, SUBSTITUTION))
    mb = cshape(padz(p_base, 0))
    vz = cshape(padz(p_valid, False))

    tpl32 = st.tpl.astype(jnp.int32)
    tpl32_r = st.tpl_r.astype(jnp.int32)

    def one_chunk(_, xs):
        p1, e1, t1, b1, v1 = xs
        # rounds > 0 restrict candidates to the nearby windows, which
        # cluster in a few chunks: chunks with no valid candidate
        # short-circuit (their scores are -inf-masked anyway), cutting
        # most of the late-round interior compute the host loop avoids
        # by shrinking its mutation arrays
        return None, lax.cond(v1.any(),
                              lambda: _chunk_compute(p1, e1, t1, b1, v1),
                              lambda: (jnp.zeros((Z, chunk)),
                                       jnp.asarray(False)))

    def _chunk_compute(p1, e1, t1, b1, v1):
        # p1/e1/t1/b1/v1 are (Z, chunk): per-ZMW packed candidates
        mpos_f, mend_f, mtyp, mbase_f = p1, e1, t1, b1
        mpos_r = st.tlens[:, None] - e1
        mbase_r = jnp.where(b1 < 0, -1, 3 - b1)

        # geometry classification (the host _dispatch_chunk logic)
        overlap, interior, wlen = slot_geometry(
            st.tstarts[:, :, None], st.tends[:, :, None],
            strands[:, :, None], mpos_f[:, None, :], mend_f[:, None, :],
            (mtyp == INSERTION)[:, None, :])
        geo = v1[:, None, :] & overlap & real_rows[:, :, None]
        int_mask = geo & interior
        edge_mask = geo & ~interior
        fb = (edge_mask & (wlen < min_fast_edge)).any()

        int_tot, _, _ = batchmod._batch_interior_totals.__wrapped__(
            reads, rlens, strands, st.tstarts, st.tends,
            st.win_tpl, st.win_trans, st.wlens,
            st.alpha.vals, st.alpha.offsets, st.alpha.log_scales,
            st.beta.vals, st.beta.offsets, st.beta.log_scales,
            st.a_prefix, st.b_suffix, st.baselines,
            tpl32, st.trans_f, tpl32_r, st.trans_r, table, st.tlens,
            mpos_f, mend_f, mtyp, mbase_f, mpos_r, mbase_r,
            int_mask, st.active)

        # edge mutations are a handful per chunk (window boundaries):
        # pack them to a fixed slab on device (stable argsort puts
        # edge-active columns first) so the edge program runs at
        # EDGE_BUDGET width, not the full chunk; budget overflow bails
        # to the host loop
        eb = EDGE_BUDGET
        e_ok = edge_mask & (wlen >= min_fast_edge)
        em_any = e_ok.any(axis=1)                       # (Z, chunk)
        e_over = em_any.sum(axis=1).max() > eb
        order = jnp.argsort(~em_any, axis=1, stable=True)[:, :eb]
        packed = jnp.take_along_axis(em_any, order, axis=1)
        g = lambda a: jnp.take_along_axis(a, order, axis=1)
        ge_mask = jnp.take_along_axis(
            e_ok, order[:, None, :].repeat(e_ok.shape[1], 1), axis=2)
        edge_packed = batchmod._batch_edge_fast_totals.__wrapped__(
            reads, rlens, strands, st.tstarts, st.tends,
            st.win_tpl, st.win_trans, st.wlens,
            st.alpha.vals, st.alpha.offsets, st.alpha.log_scales,
            st.beta.vals, st.beta.offsets, st.beta.log_scales,
            st.a_prefix, st.b_suffix, st.baselines,
            tpl32, st.trans_f, tpl32_r, st.trans_r, table, st.tlens,
            g(mpos_f), g(mend_f), g(mtyp), g(mbase_f),
            g(mpos_r), g(mbase_r),
            ge_mask, st.active)
        zidx = jnp.arange(Z, dtype=jnp.int32)[:, None]
        edge_tot = jnp.zeros_like(int_tot).at[zidx, order].add(
            jnp.where(packed, edge_packed, 0.0))
        return (int_tot + edge_tot, fb | e_over)

    _, (totals, fbs) = lax.scan(one_chunk, None,
                                (pos_f, end_f, mt, mb, vz))
    packed_totals = totals.transpose(1, 0, 2).reshape(Z, Mpad)[:, :M]
    # scatter back to slot-grid layout
    zidx = jnp.arange(Z, dtype=jnp.int32)[:, None]
    out = jnp.zeros((Z, M)).at[zidx, pack].set(packed_totals)
    return out, fbs.any()


def qv_from_slot_grid(totals: jax.Array, valid: jax.Array) -> jax.Array:
    """(Z, Jmax) int32 per-position consensus QVs from slot-grid totals.

    Device analogue of mutations.qvs_from_neg_sums (reference ConsensusQVs,
    Consensus-inl.hpp:277-297): per position, t = logsumexp of the
    negative-scoring valid slots, QV = -10*(t - softplus(t))/ln 10 =
    -10*log10(ssum/(1+ssum)); positions with no negative slot saturate to
    QV_SATURATED.  Slot starts are position-major with start == position
    for every slot kind (slot_candidates), so the per-position reduction
    is a reshape."""
    Z, M = totals.shape
    sc = jnp.where(valid & (totals < 0.0), totals.astype(jnp.float32),
                   -jnp.inf).reshape(Z, M // N_SLOTS, N_SLOTS)
    m = jnp.max(sc, axis=-1)
    any_neg = jnp.isfinite(m)
    safe_m = jnp.where(any_neg, m, 0.0)
    t = safe_m + jnp.log(jnp.sum(jnp.exp(sc - safe_m[..., None]), axis=-1))
    qv = -10.0 * (t - jax.nn.softplus(t)) / _MUT_LN10
    return jnp.where(any_neg, jnp.round(qv),
                     float(QV_SATURATED)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("chunk", "min_fast_edge",
                                             "dense", "axis"))
def run_qv_ints(state: "RefineLoopState", reads, rlens, strands, table,
                real_rows, skip_mask, *, chunk: int, min_fast_edge: int,
                dense: bool = False,
                axis: tuple[str, str] | None = None):
    """One-dispatch QV sweep reduced to per-position integer QVs on
    device: (Z, Jmax) int32 + the tiny-window fallback flag.

    Dispatched back-to-back with run_refine_loop (its output state is
    this function's input, still enqueued -- no host sync between them)
    so the refine fetch and the QV fetch merge into ONE packed transfer:
    the separate (Z, 9*Jmax) f32 score fetch moved ~1.5 MB plus a
    dispatch round trip, for data whose only consumer was the host
    per-position reduction now done here."""
    start, end, mtype, base, _ = slot_candidates(state.tpl[0],
                                                 state.tlens[0])
    valid = jax.vmap(
        lambda t, L: slot_candidates(t, L)[4]
    )(state.tpl, state.tlens)
    valid &= ~skip_mask[:, None]
    totals, fb = score_slot_grid(
        state, reads, rlens, strands, table, real_rows,
        start, end, mtype, base, valid,
        chunk=chunk, min_fast_edge=min_fast_edge, dense=dense,
        read_axis=axis[1] if axis else None)
    if axis is not None:
        fb = lax.psum(fb.astype(jnp.int32), axis) > 0
    return qv_from_slot_grid(totals, valid), fb


@functools.partial(jax.jit, static_argnames=("chunk", "min_fast_edge",
                                             "dense"))
def run_qv_grid(state: "RefineLoopState", reads, rlens, strands, table,
                real_rows, skip_mask, *, chunk: int, min_fast_edge: int,
                dense: bool = False):
    """One-dispatch QV sweep: the full slot-grid scores of every non-skip
    ZMW against its current template, computed on device in a single
    program (the host-chunked path dispatched C programs with numpy mask
    building in between -- ~1 s of wall for ~80 ms of device time on the
    bench workload).  Returns (packed scores (Z, M) f32, fallback): each
    row's valid-slot scores packed to the front in slot order (stable
    argsort), which is the host enumeration order, so row z's first
    arrs[z].size entries line up with enumerate_unique_arrays(tpls[z]).
    Per-slot values are identical to the chunked path (packing only
    reorders the chunk axis; no cross-slot arithmetic), and the packed
    f32 fetch is ~4x smaller than fetching (scores, valid)."""
    start, end, mtype, base, _ = slot_candidates(state.tpl[0],
                                                 state.tlens[0])
    valid = jax.vmap(
        lambda t, L: slot_candidates(t, L)[4]
    )(state.tpl, state.tlens)
    valid &= ~skip_mask[:, None]
    totals, fb = score_slot_grid(
        state, reads, rlens, strands, table, real_rows,
        start, end, mtype, base, valid,
        chunk=chunk, min_fast_edge=min_fast_edge, dense=dense)
    pack = jnp.argsort(~valid, axis=1, stable=True)
    packed = jnp.take_along_axis(jnp.where(valid, totals, 0.0), pack, axis=1)
    return packed.astype(jnp.float32), fb


@functools.partial(jax.jit, static_argnames=(
    "width", "use_pallas", "max_iterations", "separation", "neighborhood",
    "chunk", "min_fast_edge", "dense", "axis", "guided_passes"))
def run_refine_loop(state: "RefineLoopState", reads, rlens, strands, table,
                    real_rows, *, width: int, use_pallas: bool,
                    max_iterations: int, separation: int,
                    neighborhood: int, chunk: int, min_fast_edge: int,
                    dense: bool = False,
                    axis: tuple[str, str] | None = None,
                    guided_passes: int = 0):
    """The jitted device refinement loop: up to max_iterations rounds of
    enumerate -> score -> select -> splice -> rebuild entirely on device
    (lax.while_loop with early exit), so the host fetches once.  A
    module-level jit keyed on shapes/statics: every BatchPolisher at the
    same bucket shape shares one executable.

    Semantics mirror BatchPolisher.refine's host loop (which mirrors the
    reference AbstractRefineConsensus, Consensus-inl.hpp:160-245), with two
    documented deviations: candidate ORDER in rounds > 0 is position-major
    rather than the host's center-major (ties across distinct mutations
    resolve differently -- same candidate set), and cycle detection uses a
    48-deep rolling-hash ring rather than an unbounded exact set.

    `axis` = (zmw_axis, read_axis) mesh axis names when the loop body runs
    inside jax.shard_map (see run_refine_loop_sharded): score totals
    all-reduce over the read axis, and the loop condition / overflow flag
    reduce over the WHOLE mesh so every device runs the same number of
    iterations (divergent conds would deadlock the in-body collectives).
    The straggler early exit is disabled under a mesh -- the continuation
    sub-batch is a host-side construct that would break the sharding.

    A state that carries a CycleWatch (`state.cycle`: batch._loop_state
    gives one to a loop with no straggler exit, off a mesh) stops a ZMW
    whose rounds have become periodic, not converged, where it would
    have run to the budget with every other slot waiting."""
    from pbccs_tpu.models.arrow.params import (revcomp_padded,
                                               template_transition_params)
    from pbccs_tpu.models.arrow.scorer import oriented_window
    from pbccs_tpu.parallel import batch as batchmod

    Z, R = reads.shape[:2]
    Jmax = None  # bound at trace time from state.tpl
    # whether this trace carries a pre-baked dense layout (static: the
    # initial state either has one or not; the dense scoring path uses
    # it when present and rebuilds it whenever the fills rebuild)
    with_layout = state.dlayout is not None

    flat = lambda a: a.reshape((Z * R,) + a.shape[2:])
    unflat = lambda a: a.reshape((Z, R) + a.shape[1:])

    def zmw_tracks(t, L, tb):
        t_r = revcomp_padded(t, L)
        return (template_transition_params(t, tb, L), t_r,
                template_transition_params(t_r, tb, L))

    def rebuild(tpl, tlens, tstarts, tends, applied, st: RefineLoopState):
        """Windows, fills and layout against the templates of this round.
        A read is refilled only if its ZMW applied a mutation this round
        and its lane holds a read (reads_to_refill): every other read has
        the template, window and offsets it had, so its bands are what a
        refill would write, and it keeps them, with its likelihoods and
        its gate.  One pass a chunk of the reads that need it
        (scorer.for_needed_reads): the read's window of its ZMW's new
        template, its fills (scorer.fill_pass) and, for the dense layout,
        its band read windows (the alpha fill's own: one computation,
        placed like a band).  Under a mesh each device packs the needed
        reads of its own block."""
        from pbccs_tpu.ops.fwdbwd_pallas import band_read_windows
        from pbccs_tpu.models.arrow.scorer import (_scale_sums, band_placer,
                                                   fill_pass,
                                                   for_needed_reads,
                                                   put_rows)

        need = reads_to_refill(applied, real_rows)
        trans_f, tpl_r, trans_r = jax.vmap(zmw_tracks)(tpl, tlens, table)
        per_read = tuple(map(flat, (reads, rlens, strands, tstarts, tends)))
        per_zmw = (tpl, tpl_r, tlens, table)
        place = band_placer(use_pallas)

        def one_pass(idx, live, carry):
            wins, bands, lls, lay = carry
            f_reads, f_rlens, st1, ts1, te1 = (
                jnp.take(a, idx, axis=0) for a in per_read)
            win = jax.vmap(oriented_window)(
                st1, ts1, te1, *(jnp.take(a, idx // R, axis=0)
                                 for a in per_zmw))
            wins = tuple(put_rows(old, idx, live, new)
                         for old, new in zip(wins, win))
            bands, lls, (alpha_c, _) = fill_pass(
                (f_reads, f_rlens, *win), idx, live, bands, lls, width,
                use_pallas, guided_passes=guided_passes)
            if lay is not None:
                lay = tuple(
                    place(new, idx, live, old) for old, new in zip(
                        lay, band_read_windows(f_reads, alpha_c.offsets,
                                               width, lay[0].shape[1])))
            return wins, bands, lls, lay

        wins, (alpha, beta), lls, lay = for_needed_reads(
            flat(need), one_pass,
            (tuple(map(flat, (st.win_tpl, st.win_trans, st.wlens))),
             jax.tree.map(flat, (st.alpha, st.beta)),
             # (ll_a, ll_b): a read that is not refilled keeps its
             # baseline (ll_b); its ll_a is not looked at
             (flat(st.baselines),) * 2,
             tuple(map(flat, st.dlayout[:2])) if with_layout else None))
        apre, bsuf = jax.tree.map(unflat, _scale_sums(alpha, beta))
        win_tpl, win_trans, wlens = map(unflat, wins)
        alpha, beta, (ll_a, ll_b), lay = jax.tree.map(
            unflat, (alpha, beta, lls, lay))
        active = jnp.where(need, batchmod._update_active.__wrapped__(
            st.active, ll_a, ll_b, rlens, tstarts, tends), st.active)
        dlay = _state_layout(reads, rlens, win_tpl, win_trans, wlens,
                             table, alpha, beta, apre, bsuf, width,
                             lay) if with_layout else None
        counts = jnp.stack([need.sum(dtype=jnp.int32), jnp.int32(need.size)])
        return (win_tpl, win_trans, wlens, alpha, beta, apre, bsuf,
                ll_b, trans_f, tpl_r, trans_r, active, dlay,
                st.fill_reads + counts)

    def score_all(st: RefineLoopState, start, end, mtype, base, valid):
        return score_slot_grid(st, reads, rlens, strands, table, real_rows,
                               start, end, mtype, base, valid,
                               chunk=chunk, min_fast_edge=min_fast_edge,
                               dense=dense,
                               read_axis=axis[1] if axis else None)

    def body(st: RefineLoopState) -> RefineLoopState:
        jmax = st.tpl.shape[1]

        # 0. a ZMW whose round starts where a settled earlier round of its
        # own started is periodic (CycleWatch): it stops before the round
        done0, watch = st.done, st.cycle
        if watch is not None:
            fp = jax.vmap(state_fingerprint)(
                st.tpl, st.tlens, st.tstarts, st.tends, st.active,
                st.allowed)
            depth = watch.ring.shape[1]
            rounds = jnp.arange(depth)[None, :]
            repeats = ((watch.ring == fp[:, None, :]).all(axis=2)
                       & (rounds >= watch.settled_from[:, None])
                       & (rounds < st.hist_n[:, None])).any(axis=1)
            cycled_now = ~st.done & repeats & (st.hist_n <= depth)
            done0 = st.done | cycled_now

        # 1. candidates (slot geometry is ZMW-independent; validity is not)
        start, end, mtype, base, _ = slot_candidates(
            st.tpl[0], st.tlens[0])
        valid = jax.vmap(
            lambda t, L, al: slot_candidates(t, L, al)[4]
        )(st.tpl, st.tlens, st.allowed)
        valid &= ~done0[:, None]

        # 2. scores
        totals, fb_any = score_all(st, start, end, mtype, base, valid)
        scores = jnp.where(valid, totals, -jnp.inf)
        # favorability above the f32 score-noise floor (one source of
        # truth: refine.favorability_threshold; the scaled floor is a
        # deliberate deviation from the reference's fixed +0.04-nat
        # acceptance, MultiReadMutationScorer.cpp:56 -- docs/PARITY.md)
        # -- sub-noise deltas at long templates read "favorable" in BOTH
        # directions of an ins/del pair and ping-pong the loop to its
        # budget
        from pbccs_tpu.models.arrow.refine import favorability_threshold
        eps_z = favorability_threshold(jnp.sum(
            jnp.where(st.active, jnp.abs(st.baselines), 0.0), axis=1))
        favorable = valid & (scores > eps_z[:, None])
        fav_any = favorable.any(axis=1)

        iterations = st.iterations + (~done0).astype(jnp.int32)
        n_tested = st.n_tested + jnp.where(done0, 0,
                                           valid.sum(axis=1, dtype=jnp.int32))

        newly_converged = (~done0) & (~fav_any)
        converged = st.converged | newly_converged
        done_now = done0 | newly_converged

        # 3. greedy selection + cycle trim (position-major fast form:
        # slot_candidates' start is m // N_SLOTS by construction)
        taken = jax.vmap(
            lambda s, f: greedy_well_separated_posmajor(s, f, separation,
                                                        jmax)
        )(scores.astype(jnp.float32), favorable & ~done_now[:, None])

        def splice_z(t, L, tk):
            return splice_templates(t, L, start, mtype, base, tk)

        nxt_tpl, nxt_tlen, _ = jax.vmap(splice_z)(st.tpl, st.tlens, taken)
        nxt_hash = jax.vmap(template_hash)(nxt_tpl, nxt_tlen)
        seen = ((st.history == nxt_hash[:, None])
                & (jnp.arange(st.history.shape[1])[None, :]
                   < st.hist_n[:, None])).any(axis=1)
        multi = taken.sum(axis=1) > 1
        trim = seen & multi
        top1 = jnp.argmax(jnp.where(taken, scores, -jnp.inf), axis=1)
        taken = jnp.where(
            trim[:, None],
            jax.nn.one_hot(top1, taken.shape[1], dtype=bool) & taken,
            taken)

        # 4. history push (current template, pre-apply) where a round ran
        cur_hash = jax.vmap(template_hash)(st.tpl, st.tlens)
        pushing = (~done0) & fav_any
        slot = st.hist_n % st.history.shape[1]
        history = jnp.where(
            pushing[:, None],
            st.history.at[jnp.arange(Z), slot].set(cur_hash),
            st.history)
        hist_n = st.hist_n + pushing.astype(jnp.int32)
        if watch is not None:
            # a multi-mutation round the history has not trimmed yet may
            # select otherwise once it has: the settled run starts after it
            watch = CycleWatch(
                ring=jnp.where(
                    pushing[:, None, None],
                    watch.ring.at[jnp.arange(Z), slot % depth].set(fp),
                    watch.ring),
                settled_from=jnp.where(pushing & multi & ~seen, hist_n,
                                       watch.settled_from),
                cycled=watch.cycled | cycled_now)

        # 5. apply
        apply_mask = pushing
        new_tpl, new_tlen, mtp = jax.vmap(splice_z)(st.tpl, st.tlens, taken)
        tpl = jnp.where(apply_mask[:, None], new_tpl, st.tpl)
        tlens = jnp.where(apply_mask, new_tlen, st.tlens)
        n_applied = st.n_applied + jnp.where(
            apply_mask, taken.sum(axis=1, dtype=jnp.int32), 0)

        def remap(m, ts_row, te_row, L):
            # host: mtp[clip(window, 0, old_L)]
            return m[jnp.clip(ts_row, 0, L)], m[jnp.clip(te_row, 0, L)]

        ts_new, te_new = jax.vmap(remap)(mtp, st.tstarts, st.tends, st.tlens)
        tstarts = jnp.where(apply_mask[:, None], ts_new, st.tstarts)
        tends = jnp.where(apply_mask[:, None], te_new, st.tends)

        ov_local = fb_any | \
            (jnp.where(apply_mask, new_tlen, 0) + 2 > jmax).any()
        if axis is not None:
            # global any: every device must agree on the bail-out (a
            # device continuing alone would hang on the body collectives)
            ov_local = lax.psum(ov_local.astype(jnp.int32), axis) > 0
        overflow = st.overflow | ov_local

        # 6. rebuild fills against the updated templates (skipped entirely
        # when no ZMW applied anything this round -- the final round of a
        # converging batch)
        same = (st.win_tpl, st.win_trans, st.wlens, st.alpha, st.beta,
                st.a_prefix, st.b_suffix, st.baselines, st.trans_f,
                st.tpl_r, st.trans_r, st.active, st.dlayout, st.fill_reads)
        (win_tpl, win_trans, wlens, alpha, beta, apre, bsuf, baselines,
         trans_f, tpl_r, trans_r, active, dlayout, fill_reads) = lax.cond(
            apply_mask.any(),
            lambda: rebuild(tpl, tlens, tstarts, tends, apply_mask, st),
            lambda: same)

        # 7. next round's nearby filter from this round's favorables
        def allowed_z(fv):
            return nearby_allowed(start, end, fv, neighborhood, jmax)

        allowed = jnp.where(fav_any[:, None],
                            jax.vmap(allowed_z)(favorable),
                            st.allowed)

        # the loop carries the bands and the layout as the kernels take them
        alpha = alpha._replace(vals=row_major(alpha.vals))
        beta = beta._replace(vals=row_major(beta.vals))
        dlayout = jax.tree.map(row_major, dlayout)
        return RefineLoopState(
            tpl=tpl, tlens=tlens, tstarts=tstarts, tends=tends,
            win_tpl=win_tpl, win_trans=win_trans, wlens=wlens,
            alpha=alpha, beta=beta, a_prefix=apre, b_suffix=bsuf,
            baselines=baselines, trans_f=trans_f, tpl_r=tpl_r,
            trans_r=trans_r, active=active,
            it=st.it + 1, done=done_now, converged=converged,
            iterations=iterations, n_tested=n_tested, n_applied=n_applied,
            allowed=allowed, history=history, hist_n=hist_n,
            overflow=overflow, dlayout=dlayout, fill_reads=fill_reads,
            cycle=watch)

    # Straggler early exit: each lockstep round costs full (Z, ...) compute
    # whatever the active count, so once only a handful of ZMWs remain
    # (e.g. one cycling toward the 40-round budget) the loop returns and
    # the caller finishes them in a compact small-Z sub-batch instead of
    # paying Z-wide rounds (batch.BatchPolisher.refine).  Z <= 32 has no
    # early exit (threshold 0); mesh runs have none (the continuation is a
    # host-side construct) and count live ZMWs across all zmw shards.
    straggler_exit = 0 if axis is not None else straggler_exit_zmws(
        reads.shape[0])

    def cond(st: RefineLoopState):
        live = (~st.done).sum()
        if axis is not None:
            live = lax.psum(live, axis[0])
        return ((st.it < max_iterations)
                & (live > straggler_exit)
                & ~st.overflow)

    if state.fill_reads is None:
        state = state._replace(fill_reads=jnp.zeros(2, jnp.int32))
    out = lax.while_loop(cond, body, state)
    if axis is not None:
        # each device counted its own reads (a rebuild is a local branch:
        # no collective may sit in it); the outcome carries the mesh's sum
        out = out._replace(fill_reads=lax.psum(out.fill_reads, axis))
    return out


def _state_specs(zmw: str, read: str,
                 with_layout: bool = False) -> "RefineLoopState":
    """PartitionSpec pytree of RefineLoopState under a (zmw, read) mesh:
    per-ZMW planes shard on the zmw axis, per-(ZMW, read) planes on both,
    scalars replicate.  `with_layout` mirrors whether the state carries a
    pre-baked DenseLayout (all of whose leaves are (Z, R)-leading)."""
    from jax.sharding import PartitionSpec as P

    from pbccs_tpu.ops.dense_score_pallas import DenseLayout

    z, zr, rep = P(zmw), P(zmw, read), P()
    bm = BandedMatrix(zr, zr, zr)
    return RefineLoopState(
        tpl=z, tlens=z, tstarts=zr, tends=zr,
        win_tpl=zr, win_trans=zr, wlens=zr,
        alpha=bm, beta=bm, a_prefix=zr, b_suffix=zr,
        baselines=zr, trans_f=z, tpl_r=z, trans_r=z, active=zr,
        it=rep, done=z, converged=z, iterations=z, n_tested=z,
        n_applied=z, allowed=z, history=z, hist_n=z, overflow=rep,
        dlayout=DenseLayout(*([zr] * 4)) if with_layout else None,
        fill_reads=rep)


@functools.lru_cache(maxsize=64)
def _sharded_loop_fn(mesh, zmw_axis: str, read_axis: str,
                     statics: tuple):
    """Memoized jitted shard_map wrapper for run_refine_loop: building a
    fresh jit(shard_map(partial(...))) per call would defeat the jit
    trace cache and re-trace the whole loop every polish."""
    from jax.sharding import PartitionSpec as P

    sd = dict(statics)
    # mesh states carry a pre-baked DenseLayout exactly when the dense
    # scoring path is on (batch._loop_state uses the same gate)
    specs = _state_specs(zmw_axis, read_axis,
                         with_layout=sd.get("dense", False))
    zr, z = P(zmw_axis, read_axis), P(zmw_axis)

    f = functools.partial(run_refine_loop.__wrapped__,
                          axis=(zmw_axis, read_axis), **sd)
    return jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(specs, zr, zr, zr, z, zr),
        out_specs=specs, check_vma=False))


@functools.lru_cache(maxsize=64)
def _sharded_qv_fn(mesh, zmw_axis: str, read_axis: str, statics: tuple):
    from jax.sharding import PartitionSpec as P

    sd = dict(statics)
    specs = _state_specs(zmw_axis, read_axis,
                         with_layout=sd.get("dense", False))
    zr, z = P(zmw_axis, read_axis), P(zmw_axis)

    f = functools.partial(run_qv_ints.__wrapped__,
                          axis=(zmw_axis, read_axis), **sd)
    return jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(specs, zr, zr, zr, z, zr, z),
        out_specs=(z, P()), check_vma=False))


def run_refine_loop_sharded(mesh, zmw_axis: str, read_axis: str,
                            state: "RefineLoopState", reads, rlens,
                            strands, table, real_rows, **statics):
    """run_refine_loop under jax.shard_map over a (zmw, read) mesh: each
    device owns a (Z/nz, R/nr) block and the WHOLE while_loop runs
    device-resident per shard, with the score all-reduce over the read
    axis and globally-agreed loop condition (the DP-over-ZMW-shards
    design of SURVEY.md section 2.3, with the read axis riding ICI).
    check_vma=False: pallas_call outputs carry no varying-mesh-axes
    metadata (same caveat as scorer.fill_alpha_beta_batch_zr)."""
    fn = _sharded_loop_fn(mesh, zmw_axis, read_axis,
                          tuple(sorted(statics.items())))
    return fn(state, reads, rlens, strands, table, real_rows)


def run_qv_ints_sharded(mesh, zmw_axis: str, read_axis: str,
                        state: "RefineLoopState", reads, rlens, strands,
                        table, real_rows, skip_mask, **statics):
    """run_qv_ints under the same shard_map contract as
    run_refine_loop_sharded; returns ((Z, Jmax) int32 QVs sharded on the
    zmw axis, global fallback flag)."""
    fn = _sharded_qv_fn(mesh, zmw_axis, read_axis,
                        tuple(sorted(statics.items())))
    return fn(state, reads, rlens, strands, table, real_rows, skip_mask)


def nearby_allowed(fav_start: jax.Array, fav_end: jax.Array,
                   fav_mask: jax.Array, neighborhood: int,
                   jmax: int) -> jax.Array:
    """(Jmax,) bool: positions within `neighborhood` of any favorable
    mutation's [start, end) -- the unique_nearby window filter.

    Matches unique_nearby_arrays: each center m contributes candidate
    starts in [m.start - n, m.end + n)."""
    lo = jnp.where(fav_mask, jnp.maximum(fav_start - neighborhood, 0), jmax)
    hi = jnp.where(fav_mask, jnp.minimum(fav_end + neighborhood, jmax), 0)
    diff = jnp.zeros(jmax + 1, jnp.int32)
    diff = diff.at[jnp.clip(lo, 0, jmax)].add(
        jnp.where(fav_mask, 1, 0), mode="drop")
    diff = diff.at[jnp.clip(hi, 0, jmax)].add(
        jnp.where(fav_mask, -1, 0), mode="drop")
    return jnp.cumsum(diff[:-1]) > 0
