"""Parity of the dense slot-grid Pallas scorer (ops/dense_score_pallas,
interpret mode on CPU) with the packed interior scorer it replaces on TPU.

The dense kernel computes every (position, slot) interior score with
VMEM-resident intermediates; values must match interior_read_scores_fast
(which is itself parity-tested against the per-mutation extend_link_score
oracle in test_mutation_fast.py) to float32 rounding on every
interior-classified slot, for both strands and clipped read windows."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from pbccs_tpu.models.arrow.params import (  # noqa: E402
    revcomp_padded,
    snr_to_transition_table_host,
    template_transition_params,
)
from pbccs_tpu.models.arrow.scorer import (  # noqa: E402
    fill_alpha_beta_batch,
    oriented_window,
)
from pbccs_tpu.ops import dense_score_pallas as dsp  # noqa: E402
from pbccs_tpu.ops import fwdbwd as fb  # noqa: E402
from pbccs_tpu.ops.fwdbwd import BandedMatrix  # noqa: E402
from pbccs_tpu.ops.mutation_score import (  # noqa: E402
    interior_read_scores_fast,
    make_patches_fast,
)
from pbccs_tpu.parallel import device_refine as dr  # noqa: E402
from pbccs_tpu.simulate import simulate_zmw  # noqa: E402

W = 16


def _setup_case(rng, L, n_reads, windows):
    """Build oriented windows + fills for one ZMW with given read windows
    [(strand, ts, te)] and return everything both scorers need."""
    tpl, reads, strands, snr = simulate_zmw(rng, L, n_reads)
    Jmax = ((L + 63) // 64) * 64
    Imax = Jmax + 32
    table = jnp.asarray(snr_to_transition_table_host(np.asarray(snr)))
    tpl_p = jnp.asarray(np.pad(tpl, (0, Jmax - L), constant_values=4))
    tlen = jnp.int32(L)
    trans_f = template_transition_params(tpl_p, table, tlen)
    tpl_r = revcomp_padded(tpl_p, tlen)
    trans_r = template_transition_params(tpl_r, table, tlen)

    R = len(windows)
    reads_p = np.full((R, Imax), 4, np.int8)
    rlens = np.zeros(R, np.int32)
    st = np.zeros(R, np.int32)
    ts_a = np.zeros(R, np.int32)
    te_a = np.zeros(R, np.int32)
    for i, (strand, ts, te) in enumerate(windows):
        r = np.asarray(reads[i % n_reads])
        # clip the read roughly to the window span so fills stay sane
        r = r[: max(te - ts + 8, 16)]
        reads_p[i, : len(r)] = r
        rlens[i] = len(r)
        st[i], ts_a[i], te_a[i] = strand, ts, te

    win = jax.vmap(
        lambda s, a, b: oriented_window(s, a, b, tpl_p, tpl_r, tlen, table)
    )(jnp.asarray(st), jnp.asarray(ts_a), jnp.asarray(te_a))
    win_tpl, win_trans, wlens = win
    alpha, beta, ll_a, ll_b, apre, bsuf = fill_alpha_beta_batch(
        jnp.asarray(reads_p), jnp.asarray(rlens), win_tpl, win_trans,
        wlens, W, use_pallas=False)
    return dict(tpl=tpl, tpl_p=tpl_p, tlen=tlen, table=table,
                trans_f=trans_f, tpl_r=tpl_r, trans_r=trans_r,
                reads=jnp.asarray(reads_p), rlens=jnp.asarray(rlens),
                strands=jnp.asarray(st), ts=jnp.asarray(ts_a),
                te=jnp.asarray(te_a), win_tpl=win_tpl,
                win_trans=win_trans, wlens=wlens, alpha=alpha, beta=beta,
                apre=apre, bsuf=bsuf, Jmax=Jmax)


def _framed(x, layout):
    """A per-column (R, nc, ...) array in the band frame of `layout`."""
    return dsp._pad_pos(x, layout.rbase.shape[1])


def _columns(x, n):
    """The n template columns of a framed (R, rows, ...) array."""
    return np.asarray(x)[:, dsp._OFF0: dsp._OFF0 + n]


def _expected_grid(case, r):
    """Template-frame (Jmax*9,) interior scores via the packed scorer."""
    Jmax = case["Jmax"]
    start, end, mtype, base, valid = dr.slot_candidates(
        case["tpl_p"].astype(jnp.int8), case["tlen"])
    mpos_r = case["tlen"] - end
    mbase_r = jnp.where(base < 0, -1, 3 - base)
    pf = make_patches_fast(case["tpl_p"].astype(jnp.int32), case["trans_f"],
                           case["table"], case["tlen"], start, mtype, base)
    pr = make_patches_fast(case["tpl_r"].astype(jnp.int32), case["trans_r"],
                           case["table"], case["tlen"], mpos_r, mtype,
                           mbase_r)
    lls = interior_read_scores_fast(
        case["reads"][r], case["rlens"][r], case["strands"][r],
        case["ts"][r], case["te"][r], case["win_tpl"][r],
        case["win_trans"][r], case["wlens"][r],
        BandedMatrix(case["alpha"].vals[r], case["alpha"].offsets[r],
                     case["alpha"].log_scales[r]),
        BandedMatrix(case["beta"].vals[r], case["beta"].offsets[r],
                     case["beta"].log_scales[r]),
        case["apre"][r], case["bsuf"][r], start, end, mtype, pf, pr)
    return np.asarray(lls), (start, end, mtype, base, valid)


def _interior_mask(case, r, start, end, mtype, valid):
    """The batch scorer's interior classification for one read."""
    ts, te = int(case["ts"][r]), int(case["te"][r])
    strand = int(case["strands"][r])
    s, e = np.asarray(start), np.asarray(end)
    is_ins = np.asarray(mtype) == dr.INSERTION
    overlap = np.where(is_ins, (ts <= e) & (s <= te), (ts < e) & (s < te))
    p_w = (s - ts) if strand == 0 else (te - e)
    e_w = (e - ts) if strand == 0 else (te - s)
    wlen = te - ts
    interior = (p_w >= 3) & (e_w <= wlen - 2)
    return np.asarray(valid) & overlap & interior


def _dense_grid(case, r):
    """Template-frame (Jmax, 9) scores via the dense kernel + mapping."""
    tables = jnp.broadcast_to(case["table"][None], (case["reads"].shape[0], 8, 4))
    grid_w = dsp.dense_interior_scores_batch(
        case["reads"], case["rlens"], case["win_tpl"], case["win_trans"],
        case["wlens"], tables, case["alpha"], case["beta"],
        case["apre"], case["bsuf"], W)
    return _to_template(grid_w, case)[r]


def _slot_planes(jmax):
    """The candidates' (9, jmax) start / end / is-insertion planes."""
    start = np.broadcast_to(np.arange(jmax, dtype=np.int32), (9, jmax))
    return (start, start + np.asarray(dr.SLOT_ENDOFF, np.int32)[:, None],
            np.broadcast_to((np.asarray(dr.SLOT_TYPES) == dr.INSERTION)
                            [:, None], (9, jmax)))


def _mapped_reads(grid_s, strand, ts, te, jmax):
    """(N, 9, jmax): every read's slot-major window-frame grid on the
    template frame, through the pass the score call runs
    (slot_grid_totals with each read a ZMW of its own, every slot valid,
    no baseline): the mapped score where the slot overlaps the read's
    window, 0 elsewhere."""
    n = grid_s.shape[0]
    return np.asarray(dsp.slot_grid_totals(
        jnp.asarray(grid_s), jnp.asarray(strand), jnp.asarray(ts),
        jnp.asarray(te), jnp.ones(n, bool), jnp.zeros(n, jnp.float32),
        jnp.ones((n, 9, jmax), bool), *map(jnp.asarray, _slot_planes(jmax))))


def _to_template(grid_w, case):
    """(R, Jmax, 9) template-frame scores of (R, Jm, 9) window-frame ones
    (0 where the slot does not overlap the read's window)."""
    return _mapped_reads(
        jnp.swapaxes(jnp.asarray(grid_w), 1, 2), case["strands"],
        case["ts"], case["te"], case["Jmax"]).transpose(0, 2, 1)


@pytest.mark.parametrize("windows", [
    [(0, 0, 60), (0, 0, 60)],              # forward, full window
    [(1, 0, 60), (1, 0, 60)],              # reverse, full window
    [(0, 5, 56), (1, 3, 58)],              # clipped windows, both strands
])
@pytest.mark.slow
def test_dense_matches_packed_interior(rng, windows):
    case = _setup_case(rng, 60, 2, windows)
    for r in range(len(windows)):
        want, (start, end, mtype, base, valid) = _expected_grid(case, r)
        got = _dense_grid(case, r).reshape(-1)
        mask = _interior_mask(case, r, start, end, mtype, valid)
        assert mask.sum() > 100, "test case exercises too few slots"
        np.testing.assert_allclose(got[mask], want[mask],
                                   rtol=2e-5, atol=2e-3,
                                   err_msg=f"read {r} windows={windows}")


@pytest.mark.slow
def test_qv_grid_dense_matches_chunked(rng):
    """End-to-end: run_qv_grid with dense=True (kernel in interpret mode)
    produces the same packed slot scores as the chunked path on a real
    polisher state, to float32 rounding."""
    from pbccs_tpu.parallel.batch import (MIN_FAST_EDGE_WLEN, MUT_CHUNK,
                                          BatchPolisher, ZmwTask)
    from pbccs_tpu.parallel.batch import device_fetch  # noqa: F401

    tasks = []
    for z in range(2):
        tpl, reads, strands, snr = simulate_zmw(rng, 60, 4)
        draft = tpl.copy()
        draft[30] = (draft[30] + 1) % 4
        tasks.append(ZmwTask(f"q/{z}", draft, snr, reads, strands,
                             [0] * 4, [len(draft)] * 4))
    p = BatchPolisher(tasks)
    st = p._loop_state(set())
    skip_mask = np.zeros(p._Z, bool)
    skip_mask[p.n_zmws:] = True
    args = (st, p._reads_dev, p._rlens_dev, p._strands_dev,
            p._shard(p._host_tables), jnp.asarray(p._real_rows),
            jnp.asarray(skip_mask))
    kw = dict(chunk=MUT_CHUNK, min_fast_edge=MIN_FAST_EDGE_WLEN)
    chunked, fb_c = dr.run_qv_grid(*args, **kw, dense=False)
    dense, fb_d = dr.run_qv_grid(*args, **kw, dense=True)
    assert bool(fb_c) == bool(fb_d)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(chunked),
                               rtol=2e-5, atol=2e-3)


@pytest.mark.parametrize("windows", [
    [(0, 0, 60), (1, 0, 60)],              # full windows, both strands
    [(0, 5, 56), (1, 3, 58)],              # clipped windows
    [(0, 0, 17), (1, 40, 60)],             # short-ish windows (>= 8)
])
@pytest.mark.slow
def test_edge_window_scores_match_oracle(rng, windows):
    """The window-frame edge program equals edge_scores_fast (the oracle
    that is itself pinned to the full-refill path in test_mutation_fast)
    on every near-begin/near-end slot of every read."""
    from pbccs_tpu.ops.mutation_score import edge_scores_fast

    case = _setup_case(rng, 60, 2, windows)
    R = case["reads"].shape[0]
    tables = jnp.broadcast_to(case["table"][None], (R, 8, 4))
    e6 = np.asarray(dsp.edge_window_scores_batch(
        case["reads"], case["rlens"], case["win_tpl"], case["win_trans"],
        case["wlens"], tables, case["alpha"], case["beta"], case["apre"],
        case["bsuf"], W))

    for r in range(R):
        J = int(case["wlens"][r])
        win_tpl = case["win_tpl"][r].astype(np.int32)
        win_trans = case["win_trans"][r]
        # oracle inputs: window-frame slot list for the 6 edge rows
        for row, p in enumerate([0, 1, 2, J - 2, J - 1, J]):
            for k in range(9):
                mtype = [0, 0, 0, 0, 1, 1, 1, 1, 2][k]
                nbase = [0, 1, 2, 3, 0, 1, 2, 3, -1][k]
                # validity in window frame: position exists on the window
                # template; del/sub need p < J, ins allows p <= J; skip
                # slots whose regime the edge program does not serve
                if mtype == 1:
                    if p > J or (row == 3):     # ins at J-2 is interior
                        continue
                else:
                    if p >= J:
                        continue
                if p <= 2 and row >= 3:
                    continue                     # tiny-window overlap
                from pbccs_tpu.ops.mutation_score import make_patches_fast
                patch = make_patches_fast(
                    jnp.asarray(win_tpl), win_trans, case["table"],
                    jnp.asarray(J, jnp.int32),
                    jnp.asarray([p], jnp.int32),
                    jnp.asarray([mtype], jnp.int32),
                    jnp.asarray([max(nbase, 0)], jnp.int32))
                want = float(np.asarray(edge_scores_fast(
                    case["reads"][r].astype(jnp.int32), case["rlens"][r],
                    jnp.asarray(win_tpl), win_trans,
                    jnp.asarray(J, jnp.int32),
                    BandedMatrix(case["alpha"].vals[r],
                                 case["alpha"].offsets[r],
                                 case["alpha"].log_scales[r]),
                    BandedMatrix(case["beta"].vals[r],
                                 case["beta"].offsets[r],
                                 case["beta"].log_scales[r]),
                    case["apre"][r], case["bsuf"][r],
                    jnp.asarray([p], jnp.int32),
                    jnp.asarray([mtype], jnp.int32),
                    patch.bases, patch.trans, patch.shift))[0])
                got = float(e6[r, row, k])
                np.testing.assert_allclose(
                    got, want, rtol=2e-5, atol=2e-3,
                    err_msg=f"read {r} row {row} p={p} k={k} J={J}")


def test_band_read_windows_flat_offset_garbage_lane(rng):
    """Consumer-gating invariant of band_read_windows' derived rbase
    (ops/dense_score_pallas.py:409): when o_j == o_{j-1} (flat offsets
    are routine -- clamped band starts/ends, and EVERY column of a read
    no longer than W) the cut-lane derivation returns rf[o_j + W - 1]
    instead of rf[o_j - 1], a garbage value every consumer must gate.

    Pinned two ways on a constructed all-flat read (I == W => offsets
    identically 0) plus two normal reads (flat runs at the clamps):

      * windows-fed vs DIRECT-window form: scores from the derived
        (rbase, rnext) equal scores from an explicitly built
        rbase_direct[j][L] = read_pad0[rows_j[L] - 1] (one extra
        window_rows_circ over the shifted read), bitwise, on every
        consumed slot of both the interior kernel and the edge programs;
      * poison probe: overwriting exactly the flat-offset cut lanes with
        an out-of-alphabet value changes no consumed score.

    Any new consumer of rbase that drops the in_band/cmask gates breaks
    this test."""
    from pbccs_tpu.ops.fwdbwd_pallas import window_rows_circ

    # read 2's window is 8 long => its clipped read has I = 16 = W, so
    # its band cannot advance: o_j == o_{j-1} at (essentially) every
    # column -- the all-flat extreme of the garbage-lane premise
    windows = [(0, 0, 60), (1, 0, 60), (0, 0, 8)]
    case = _setup_case(rng, 60, 2, windows)
    R = case["reads"].shape[0]
    offs = np.asarray(case["alpha"].offsets)
    flat = np.zeros_like(offs, bool)
    flat[:, 1:] = offs[:, 1:] == offs[:, :-1]
    assert flat[2, 1:].sum() >= flat[2, 1:].size - 2, \
        "constructed read must have (essentially) all-flat offsets"
    assert flat[0].any() and flat[1].any(), \
        "normal reads should flat-line at the band clamps"

    rwin = dsp.band_read_windows(case["reads"], case["alpha"].offsets, W)
    rbase, rnext = (np.asarray(a) for a in rwin)

    # direct-window form: one more MXU windowing over the 1-shifted read
    # (read_pad0[row - 1]; row 0 reads the pad base, which is gated)
    read_f = np.asarray(case["reads"]).astype(np.float32)
    shifted = np.concatenate(
        [np.full((R, 1), 4.0, np.float32), read_f[:, :-1]], axis=1)
    rbase_direct = np.asarray(jax.vmap(
        lambda r, o: window_rows_circ(r, o, W)
    )(jnp.asarray(shifted), case["alpha"].offsets))
    # the premise: the two forms genuinely DISAGREE on the garbage lanes
    assert not np.array_equal(rbase, rbase_direct)

    # poison probe: exactly the flat-offset cut lanes
    lane = offs % W
    poison = rbase.copy()
    rr, jj = np.nonzero(flat)
    poison[rr, jj, lane[rr, jj]] = 9.0
    assert not np.array_equal(poison, rbase)

    tables = jnp.broadcast_to(case["table"][None], (R, 8, 4))
    args = (case["reads"], case["rlens"], case["win_tpl"],
            case["win_trans"], case["wlens"], tables, case["alpha"],
            case["beta"], case["apre"], case["bsuf"], W)
    baked = dsp.prepare_dense_layout(*args)

    def probe(rb):
        """The baked layout with its read-base windows replaced."""
        return baked._replace(rbase=_framed(jnp.asarray(rb), baked))

    def interior(rb):
        return np.asarray(dsp.dense_interior_scores_batch(
            *args, layout=probe(rb)))

    def edges(rb):
        return np.asarray(dsp.edge_window_scores_batch(
            *args, layout=probe(rb)))

    int_ref, edge_ref = interior(rbase), edges(rbase)
    checked = 0
    for variant, (int_v, edge_v) in {
            "direct": (interior(rbase_direct), edges(rbase_direct)),
            "poison": (interior(poison), edges(poison))}.items():
        for r in range(R):
            # interior consumers: compare on the batch scorer's actual
            # interior classification, in template frame
            start, end, mtype, base, valid = dr.slot_candidates(
                case["tpl_p"].astype(jnp.int8), case["tlen"])
            mask = _interior_mask(case, r, start, end, mtype, valid)
            m_ref = _to_template(int_ref, case)[r].reshape(-1)
            m_v = _to_template(int_v, case)[r].reshape(-1)
            np.testing.assert_array_equal(
                m_v[mask], m_ref[mask],
                err_msg=f"{variant}: interior scores moved, read {r}")
            checked += int(mask.sum())
            # edge consumers: the served (row, slot) grid entries
            J = int(case["wlens"][r])
            for row, p in enumerate([0, 1, 2, J - 2, J - 1, J]):
                for k in range(9):
                    mt = [0, 0, 0, 0, 1, 1, 1, 1, 2][k]
                    if mt == 1:
                        if p > J or row == 3:
                            continue
                    elif p >= J:
                        continue
                    if p <= 2 and row >= 3:
                        continue
                    np.testing.assert_array_equal(
                        edge_v[r, row, k], edge_ref[r, row, k],
                        err_msg=f"{variant}: edge score moved, read {r} "
                                f"row {row} k {k}")
                    checked += 1
    assert checked > 400, "test exercised too few consumed slots"


def test_prepared_layout_matches_ingraph(rng):
    """Pre-baked DenseLayout path == in-graph derivation, BITWISE: the
    interior kernel and the edge programs launched on
    prepare_dense_layout buffers must produce exactly the scores the
    default (derive-inside-the-score-graph) path produces -- the pre-bake
    moves work between graphs, it must not change a ULP."""
    case = _setup_case(rng, 60, 2, [(0, 0, 60), (1, 3, 58), (0, 5, 56)])
    R = case["reads"].shape[0]
    tables = jnp.broadcast_to(case["table"][None], (R, 8, 4))
    args = (case["reads"], case["rlens"], case["win_tpl"],
            case["win_trans"], case["wlens"], tables, case["alpha"],
            case["beta"], case["apre"], case["bsuf"], W)

    layout = dsp.prepare_dense_layout(*args)
    got_int = np.asarray(dsp.dense_interior_scores_batch(
        *args, layout=layout))
    want_int = np.asarray(dsp.dense_interior_scores_batch(*args))
    np.testing.assert_array_equal(got_int, want_int)

    ptrans = jax.vmap(dsp.dense_patch_grids)(
        case["win_tpl"].astype(jnp.int32), case["win_trans"], tables,
        case["wlens"])
    got_e = np.asarray(dsp.edge_window_scores_batch(*args, layout=layout))
    want_e = np.asarray(dsp.edge_window_scores_batch(*args))
    np.testing.assert_array_equal(got_e, want_e)
    # the baked patch plane holds the per-position patch grids in place
    Jm = int(case["win_tpl"].shape[1])
    np.testing.assert_array_equal(
        _columns(layout.ptr, Jm), np.asarray(ptrans).reshape(R, Jm, 72))


def test_dense_scores_match_dense_oracle_prebaked(rng):
    """Pre-baked-path interior scores vs the float64 DENSE oracle
    (ops/fwdbwd_ref): with W >= I + 1 the band covers the whole matrix,
    so the kernel's absolute mutated-window log-likelihood must equal
    loglik_dense of the mutated window to f32 rounding.  Runs the
    LAYOUT path end to end (prepare_dense_layout -> kernel), so the
    oracle pins the baked buffers, not just their equivalence to the
    in-graph ones."""
    from pbccs_tpu.models.arrow import mutations as mutlib
    from pbccs_tpu.ops.fwdbwd_ref import loglik_dense

    Wo = 32
    case = _setup_case_w(rng, 24, 2, [(0, 0, 22), (1, 0, 22)], Wo)
    R = case["reads"].shape[0]
    tables = jnp.broadcast_to(case["table"][None], (R, 8, 4))
    args = (case["reads"], case["rlens"], case["win_tpl"],
            case["win_trans"], case["wlens"], tables, case["alpha"],
            case["beta"], case["apre"], case["bsuf"], Wo)
    layout = dsp.prepare_dense_layout(*args)
    grid_w = np.asarray(dsp.dense_interior_scores_batch(
        *args, layout=layout))

    checked = 0
    for r in range(R):
        J = int(case["wlens"][r])
        I = int(case["rlens"][r])
        assert Wo >= I + 1, "oracle regime needs a full-cover band"
        wt = np.asarray(case["win_tpl"][r])[:J].astype(np.int8)
        read = np.asarray(case["reads"][r])[:I].astype(np.int8)
        for p in range(3, J - 2, 3):
            for k in (0, 2, 4, 8):          # sub A, sub G, ins A, del
                mtype = [0, 0, 0, 0, 1, 1, 1, 1, 2][k]
                nbase = [0, 1, 2, 3, 0, 1, 2, 3, -1][k]
                end = p + (0 if mtype == 1 else 1)
                if end > J - 2:             # interior contract
                    continue
                if mtype == 0 and wt[p] == nbase:
                    continue                # not a real mutation slot
                mut = mutlib.Mutation(start=p, end=end, mtype=mtype,
                                      new_base=max(nbase, 0))
                mtpl = mutlib.apply_mutations(wt, [mut])
                table_j = case["table"]
                from pbccs_tpu.models.arrow.params import \
                    template_transition_params
                mtr = np.asarray(template_transition_params(
                    jnp.asarray(mtpl.astype(np.int32)), table_j,
                    jnp.int32(len(mtpl))), np.float64)[: len(mtpl)]
                want = loglik_dense(read, mtpl, mtr)
                got = float(grid_w[r, p, k])
                np.testing.assert_allclose(
                    got, want, rtol=5e-5, atol=5e-3,
                    err_msg=f"read {r} p={p} k={k}")
                checked += 1
    assert checked > 20, "oracle comparison exercised too few slots"


def _setup_case_w(rng, L, n_reads, windows, width):
    """_setup_case at an explicit band width (module W is the default)."""
    global W
    saved = W
    try:
        W = width
        return _setup_case(rng, L, n_reads, windows)
    finally:
        W = saved


def test_band_read_windows_prebake_equivalence(rng):
    """band_read_windows pre-bake at a NON-TRIVIAL offset pattern: with
    a synthetic monotone staircase band (mixed advances of 0/1/3 rows
    per column -- the shape guided rebanding produces), the pre-baked
    (rw_base, rw_next) pair must (a) be served verbatim by the layout,
    (b) equal a direct numpy model of the circular windows on every
    in-band lane, and (c) feed the kernel identically to the in-graph
    derivation."""
    case = _setup_case(rng, 60, 2, [(0, 0, 60), (1, 0, 60)])
    R = case["reads"].shape[0]
    nc = case["alpha"].offsets.shape[1]
    I = np.asarray(case["rlens"])

    # staircase offsets: advance 0/1/3 in a repeating pattern, clipped
    # to the legal [0, I+1-W] range (monotone, slope <= MAX_BAND_ADVANCE)
    steps = np.tile(np.array([0, 1, 3, 0, 1], np.int32), nc // 5 + 1)[:nc]
    offs = np.cumsum(steps)[None, :].repeat(R, 0)
    offs = np.minimum(offs, np.maximum(I[:, None] + 1 - W, 0)).astype(np.int32)
    offsets = jnp.asarray(offs)

    rbase, rnext = dsp.band_read_windows(case["reads"], offsets, W)
    rbase, rnext = np.asarray(rbase), np.asarray(rnext)

    # numpy model: rnext[r, j, L] = read_pad0[row] for the unique row in
    # [o, o+W) with row % W == L (0 past the read end)
    read_f = np.asarray(case["reads"]).astype(np.float32)
    for r in range(R):
        pad0 = np.concatenate([read_f[r], np.zeros(W, np.float32)])
        pad1 = np.concatenate([[read_f[r][0]], read_f[r],
                               np.zeros(W, np.float32)])
        for j in (0, 1, nc // 3, nc // 2, nc - 1):
            o = int(offs[r, j])
            q = o % W
            rows = o - q + np.arange(W) + np.where(np.arange(W) < q, W, 0)
            np.testing.assert_array_equal(
                rnext[r, j], pad0[np.minimum(rows, len(pad0) - 1)]
                * (rows < len(read_f[r]) + W),
                err_msg=f"rnext r={r} j={j}")
            # rbase non-cut lanes hold read_pad1[row] (= read_pad0[row-1])
            ok = np.arange(W) != q
            got = rbase[r, j][ok]
            want = pad1[np.minimum(rows, len(pad1) - 1)][ok]
            in_rng = rows[ok] < len(read_f[r]) + W
            np.testing.assert_array_equal(got * in_rng, want * in_rng,
                                          err_msg=f"rbase r={r} j={j}")

    # the layout serves the SAME pair, and the kernel consumes it
    # identically to the in-graph derivation
    alpha = BandedMatrix(case["alpha"].vals, offsets,
                         case["alpha"].log_scales)
    tables = jnp.broadcast_to(case["table"][None], (R, 8, 4))
    args = (case["reads"], case["rlens"], case["win_tpl"],
            case["win_trans"], case["wlens"], tables, alpha,
            case["beta"], case["apre"], case["bsuf"], W)
    layout = dsp.prepare_dense_layout(*args)
    np.testing.assert_array_equal(_columns(layout.rbase, nc), rbase)
    np.testing.assert_array_equal(_columns(layout.rnext, nc), rnext)
    np.testing.assert_array_equal(
        np.asarray(dsp.dense_interior_scores_batch(*args, layout=layout)),
        np.asarray(dsp.dense_interior_scores_batch(*args)))


def test_multi_column_blocking_parity(rng, monkeypatch):
    """PBCCS_DENSE_CB in {1, 2, 3} produces identical scores on a
    multi-block template (Jm spans several _PB sub-blocks), including a
    sparse live mask -- sub-block liveness granularity must survive the
    grouping.  The env is read at trace time, so each setting clears the
    jit cache first (same caveat as PBCCS_PALLAS)."""
    case = _setup_case(rng, 150, 2, [(0, 0, 150), (1, 5, 140)])
    R = case["reads"].shape[0]
    tables = jnp.broadcast_to(case["table"][None], (R, 8, 4))
    Jm = int(case["win_tpl"].shape[1])
    NB = -(-Jm // dsp._PB)
    assert NB >= 2, "case must span several position sub-blocks"
    live = np.zeros((R, NB), bool)
    live[:, 0] = True            # sparse: only the first sub-block live
    live[0, -1] = True
    args = (case["reads"], case["rlens"], case["win_tpl"],
            case["win_trans"], case["wlens"], tables, case["alpha"],
            case["beta"], case["apre"], case["bsuf"], W)

    outs = {}
    for cb in (1, 2, 3):
        monkeypatch.setenv("PBCCS_DENSE_CB", str(cb))
        dsp.dense_interior_scores_batch.clear_cache()
        dsp.prepare_dense_layout.clear_cache()
        layout = dsp.prepare_dense_layout(*args)
        outs[cb] = (
            np.asarray(dsp.dense_interior_scores_batch(*args)),
            np.asarray(dsp.dense_interior_scores_batch(
                *args, live=jnp.asarray(live), layout=layout)),
        )
    for cb in (2, 3):
        np.testing.assert_array_equal(outs[cb][0], outs[1][0])
        np.testing.assert_array_equal(outs[cb][1], outs[1][1])
    # dead sub-blocks really are zero, live ones really are not
    full, masked = outs[1]
    assert np.array_equal(masked[1, : dsp._PB], full[1, : dsp._PB])
    assert not masked[1, dsp._PB: 2 * dsp._PB].any()


@pytest.mark.slow
def test_refine_device_dense_with_layout_e2e(monkeypatch):
    """Full device-resident refinement with the dense path ON (so the
    loop state carries a pre-baked DenseLayout, rebuild refreshes it,
    and the eager QV sweep consumes it): an easy 2-ZMW draw must
    converge and recover the true templates end to end, pinning the
    lax.cond rebuild/carry plumbing the layout rides through.

    Seed 1234, not the shared fixture: on the fixture draw the dense
    path accepts one spurious near-end insert on ZMW 1 (a pre-existing
    f32 association-order property of the dense scorer, identical
    before and after the layout pre-bake -- verified bit-for-bit against
    the pre-round-6 tree), and this test pins the NEW plumbing, not that
    old knife-edge."""
    from pbccs_tpu.models.arrow.refine import RefineOptions
    from pbccs_tpu.parallel.batch import BatchPolisher, ZmwTask

    monkeypatch.setenv("PBCCS_DENSE", "1")
    rng = np.random.default_rng(1234)
    tasks, truths = [], []
    for z in range(2):
        tpl, reads, strands, snr = simulate_zmw(rng, 60, 6)
        draft = tpl.copy()
        draft[20 + 7 * z] = (draft[20 + 7 * z] + 1) % 4
        tasks.append(ZmwTask(f"dl/{z}", draft, snr, reads, strands,
                             [0] * 6, [len(draft)] * 6))
        truths.append(tpl)
    p = BatchPolisher(tasks)
    st = p._loop_state(set())
    assert st.dlayout is not None, "dense path must pre-bake the layout"
    results = p.refine_device(RefineOptions(max_iterations=10))
    assert results is not None and all(r.converged for r in results)
    for z in range(2):
        np.testing.assert_array_equal(p.tpls[z], truths[z])
    qvs = p.consensus_qvs()
    assert all(len(q) == len(p.tpls[z]) for z, q in enumerate(qvs))


def test_dense_patch_grids_match_make_patches(rng):
    """Window-frame patch planes equal make_patches_fast on the grid."""
    tpl, _, _, snr = simulate_zmw(rng, 50, 3)
    L = len(tpl)
    table = jnp.asarray(snr_to_transition_table_host(np.asarray(snr)))
    tpl_j = jnp.asarray(tpl.astype(np.int32))
    trans = template_transition_params(tpl_j, table, jnp.int32(L))

    ptrans = dsp.dense_patch_grids(tpl_j, trans, table, L)

    from pbccs_tpu.models.arrow.mutations import (_SLOT_BASES, _SLOT_TYPES)
    pos = np.repeat(np.arange(L, dtype=np.int32), 9)
    mtype = np.tile(np.asarray(_SLOT_TYPES), L)
    nbase = np.tile(np.asarray(_SLOT_BASES), L)
    ref = make_patches_fast(tpl_j, trans, table, jnp.int32(L),
                            jnp.asarray(pos), jnp.asarray(mtype),
                            jnp.asarray(np.where(nbase < 0, 0, nbase)))
    got_t = np.asarray(ptrans).reshape(L * 9, 2, 4)
    want_t = np.asarray(ref.trans)
    np.testing.assert_allclose(got_t, want_t, rtol=1e-6, atol=1e-7)


# --------------------------------------------------------------------------
# PR 28: the kernel reads overlapping windows of the one framed buffer
# --------------------------------------------------------------------------


def _halo_blocks(x, nbc, cb):
    """The (R, NBC, cb*_PB + _HALO, n) overlapped copy of a padded
    (R, (NBC+1)*cb*_PB, n) input the kernel used to be handed."""
    R, n = x.shape[0], x.shape[2:]
    step = cb * dsp._PB
    core = x[:, : nbc * step].reshape((R, nbc, step) + n)
    nxt = x[:, step: (nbc + 1) * step].reshape(
        (R, nbc, step) + n)[:, :, :dsp._HALO]
    return jnp.concatenate([core, nxt], axis=2)


def _scores_on_halo_copies(args, live):
    """dense_interior_scores_batch as it launched before PR 28: every
    position-indexed operand zero-padded, copied into halo'd step blocks
    and handed to the same kernel body through blocked BlockSpecs."""
    import functools

    from jax.experimental import pallas as pl

    reads, rlens, win_tpl, win_trans, wlens, tables, alpha, beta, apre, \
        bsuf, width = args
    R, Jm = win_tpl.shape
    cb, nbc = dsp._dense_grid_shape(Jm)
    NB = -(-Jm // dsp._PB)
    total = (nbc + 1) * cb * dsp._PB
    rbase, rnext = dsp.band_read_windows(reads, alpha.offsets, width)
    ptr = jax.vmap(dsp.dense_patch_grids)(
        win_tpl.astype(jnp.int32), win_trans, tables, wlens)
    f32 = lambda x: x.astype(jnp.float32)
    aux = jnp.concatenate([
        dsp._pad_pos(f32(alpha.offsets)[:, :, None], total),
        dsp._pad_pos(apre[:, :, None], total),
        dsp._pad_pos(bsuf[:, :, None], total),
        dsp._pad_pos(f32(win_tpl)[:, :, None], total),
        dsp._pad_pos(win_trans, total)], axis=2)
    ops = [_halo_blocks(dsp._pad_pos(x, total), nbc, cb) for x in
           (alpha.vals, beta.vals, rbase, rnext)]
    ops += [_halo_blocks(aux, nbc, cb),
            _halo_blocks(dsp._pad_pos(ptr.reshape(R, Jm, 72), total), nbc, cb)]
    live_in = jnp.pad(live.astype(jnp.int32),
                      [(0, 0), (0, nbc * cb - NB)]).reshape(R, nbc, cb, 1)
    PBH = cb * dsp._PB + dsp._HALO
    blk = lambda n: pl.BlockSpec((None, None, PBH, n),
                                 lambda r, b: (r, b, 0, 0))
    out = pl.pallas_call(
        functools.partial(dsp._dense_kernel, W=width, cb=cb),
        grid=(R, nbc),
        in_specs=[blk(width)] * 4 + [blk(8), blk(72),
                  pl.BlockSpec((None, 1, 1), lambda r, b: (r, 0, 0)),
                  pl.BlockSpec((None, 1, cb, 1), lambda r, b: (r, b, 0, 0))],
        out_specs=pl.BlockSpec((None, cb * dsp._PB, dsp.N_SLOTS),
                               lambda r, b: (r, b, 0)),
        out_shape=jax.ShapeDtypeStruct((R, nbc * cb * dsp._PB, dsp.N_SLOTS),
                                       jnp.float32),
        interpret=True,
    )(*ops, rlens[:, None, None].astype(jnp.int32), live_in)
    return np.asarray(out[:, :Jm])


@pytest.fixture(scope="module")
def three_step_case():
    """Three grid steps (cb 1, three sub-blocks), fills from the Pallas
    kernel (a framed band) and from XLA (a plain one)."""
    rng = np.random.default_rng(2828)
    case = _setup_case(rng, 150, 2, [(0, 0, 150), (1, 4, 146)])
    R = case["reads"].shape[0]
    tables = jnp.broadcast_to(case["table"][None], (R, 8, 4))
    plain = (case["reads"], case["rlens"], case["win_tpl"],
             case["win_trans"], case["wlens"], tables, case["alpha"],
             case["beta"], case["apre"], case["bsuf"], W)
    pallas_fills = fill_alpha_beta_batch(*plain[:5], W, use_pallas=True)
    framed = plain[:6] + (pallas_fills[0], pallas_fills[1],
                          pallas_fills[4], pallas_fills[5], W)
    return plain, framed


@pytest.mark.parametrize("step", [0, 1, 2], ids=["first", "middle", "last"])
def test_inplace_windows_match_halo_copies(three_step_case, step, monkeypatch):
    """Each grid step's scores from element-indexed windows of the framed
    buffers (the Pallas fill's band as written, and an XLA fill's framed
    by band_frame) equal, bit for bit, the scores of the same kernel body
    on the materialised halo'd copies it used to read -- with every
    sub-block live and with only this step's live."""
    monkeypatch.setenv("PBCCS_DENSE_CB", "1")
    dsp.dense_interior_scores_batch.clear_cache()
    plain, framed = three_step_case
    Jm = int(plain[2].shape[1])
    assert dsp._dense_grid_shape(Jm) == (1, 3)
    rows = slice(step * dsp._PB, min((step + 1) * dsp._PB, Jm))
    only = np.zeros((plain[0].shape[0], 3), bool)
    only[:, step] = True
    try:
        for args in (plain, framed):
            unframed = args[:6] + (fb.band_columns(args[6]),
                                   fb.band_columns(args[7])) + args[8:]
            for live in (np.ones_like(only), only):
                got = np.asarray(dsp.dense_interior_scores_batch(
                    *args, live=jnp.asarray(live)))
                want = _scores_on_halo_copies(unframed, jnp.asarray(live))
                assert got.shape == want.shape
                np.testing.assert_array_equal(got[:, rows], want[:, rows])
                assert np.abs(want[:, rows]).sum() > 0
    finally:
        dsp.dense_interior_scores_batch.clear_cache()


@pytest.mark.parametrize("strand", [0, 1], ids=["forward", "reverse"])
def test_edge_program_on_framed_layout_matches_oracle(strand):
    """The edge program fed the Pallas fills' framed bands and the baked
    layout (its rows fetched by the window kernel) equals
    edge_scores_fast on a near-begin and a near-end slot of every kind."""
    from pbccs_tpu.ops.mutation_score import edge_scores_fast

    rng = np.random.default_rng(28 + strand)
    case = _setup_case(rng, 60, 2, [(strand, 0, 60), (strand, 3, 57)])
    R = case["reads"].shape[0]
    tables = jnp.broadcast_to(case["table"][None], (R, 8, 4))
    five = (case["reads"], case["rlens"], case["win_tpl"],
            case["win_trans"], case["wlens"])
    alpha, beta, _, _, apre, bsuf = fill_alpha_beta_batch(
        *five, W, use_pallas=True)
    assert fb.band_lead(alpha) == fb.BAND_LEAD
    args = five + (tables, alpha, beta, apre, bsuf, W)
    layout = dsp.prepare_dense_layout(*args)
    e6 = np.asarray(dsp.edge_window_scores_batch(*args, layout=layout))

    checked = 0
    for r in range(R):
        J = int(case["wlens"][r])
        wt = case["win_tpl"][r].astype(jnp.int32)
        for row, p in ((0, 0), (1, 1), (4, J - 1), (5, J)):
            for k in (1, 6, 8):                    # sub C, ins G, del
                mtype = [0, 0, 0, 0, 1, 1, 1, 1, 2][k]
                nbase = [0, 1, 2, 3, 0, 1, 2, 3, 0][k]
                if (mtype != 1 and p >= J) or \
                        (mtype == 0 and int(wt[p]) == nbase):
                    continue
                patch = make_patches_fast(
                    wt, case["win_trans"][r], case["table"], jnp.int32(J),
                    jnp.asarray([p], jnp.int32),
                    jnp.asarray([mtype], jnp.int32),
                    jnp.asarray([nbase], jnp.int32))
                want = float(edge_scores_fast(
                    case["reads"][r].astype(jnp.int32), case["rlens"][r],
                    wt, case["win_trans"][r], case["wlens"][r],
                    BandedMatrix(alpha.vals[r], alpha.offsets[r],
                                 alpha.log_scales[r]),
                    BandedMatrix(beta.vals[r], beta.offsets[r],
                                 beta.log_scales[r]),
                    apre[r], bsuf[r], jnp.asarray([p], jnp.int32),
                    jnp.asarray([mtype], jnp.int32), patch.bases,
                    patch.trans, patch.shift)[0])
                np.testing.assert_allclose(
                    e6[r, row, k], want, rtol=2e-5, atol=2e-3,
                    err_msg=f"read {r} row {row} slot {k}")
                checked += 1
    assert checked >= 16


# --------------------------------------------------------------------------
# the score grid after the kernel: slot-major, mapped without a gather
# --------------------------------------------------------------------------

_REV_PERM = np.array([3, 2, 1, 0, 7, 6, 5, 4, 8])


def gather_map_reference(grid, strand, ts, te, jmax):
    """One read's window-frame (Jm, 9) grid on the template frame, (jmax,
    9): a NumPy transcription of the index-gather formulation the program
    ran until PR 34 (window_grid_to_template: three takes a read, the slot
    permutation a fourth), kept here as the mapping's reference."""
    jm = grid.shape[0]
    gpad = np.concatenate([grid, np.zeros((1, 9), grid.dtype)])

    def pick(g, idx):
        return g[np.where((idx >= 0) & (idx < jm), idx, jm)]

    pos = np.arange(jmax)
    if strand == 0:
        return pick(gpad, pos - ts)
    rev_g = gpad[:, _REV_PERM]
    subdel, ins = pick(rev_g, te - 1 - pos), pick(rev_g, te - pos)
    return np.concatenate([subdel[:, :4], ins[:, 4:8], subdel[:, 8:]], axis=1)


def splice_reference(grid, e6, j):
    """One read's (Jm, 9) grid with rows {0,1,2, J-2,J-1,J} overwritten by
    the (6, 9) edge scores, ins at J-2 kept (splice_edge_rows until PR 34:
    near-begin first, then the near-end rows in order)."""
    out = grid.copy()
    out[:3] = e6[:3]
    for i in range(3):
        if 0 <= j - 2 + i < grid.shape[0]:
            keep = dsp._NE_MASK9[i]
            out[j - 2 + i, keep] = e6[3 + i, keep]
    return out


def _random_windows(rng, n, jm, jmax):
    """(strand, ts, te) of n reads: random windows, then windows equal to
    the template, at its begin and at its end, shorter than the edge rows,
    and past both ends (ts < 0, te - ts over Jm), on both strands."""
    strand = rng.integers(0, 2, n)
    ts = rng.integers(0, jmax - 1, n)
    te = np.minimum(ts + rng.integers(1, jmax + 1, n), jmax)
    fixed = [(0, jmax), (0, jmax // 3), (jmax - jmax // 3, jmax), (5, 9),
             (0, 2), (jmax - 1, jmax), (-7, jmax - 11), (4, jm + 9),
             (-3, jm + 2)]
    for i, (a, b) in enumerate(fixed):
        for s in (0, 1):
            strand[2 * i + s], ts[2 * i + s], te[2 * i + s] = s, a, b
    return strand, ts, te


def totals_reference(spliced, strand, ts, te, live, base, valid):
    """(Z, jmax, 9) float32 totals of (Z*R, Jm, 9) window-frame grids, as
    the program reckoned them until PR 34 but for the order of the sum:
    each read mapped by gather_map_reference, masked where its ZMW's slot
    is valid (Z, jmax, 9), overlaps its window and the read is live, less
    its baseline, and summed over the ZMW's reads by halves of the read
    axis (the written-out order; a jnp.sum's was XLA's to choose)."""
    n, z, jmax = spliced.shape[0], valid.shape[0], valid.shape[1]
    r = n // z
    ms, me, ins = (np.asarray(a).T for a in _slot_planes(jmax))
    terms = np.zeros((n, jmax, 9), np.float32)
    for k in range(n):
        overlap = np.where(ins, (ts[k] <= me) & (ms <= te[k]),
                           (ts[k] < me) & (ms < te[k]))
        mapped = gather_map_reference(spliced[k], strand[k], ts[k], te[k],
                                      jmax)
        terms[k] = np.where(valid[k // r] & overlap & live[k],
                            mapped - base[k], np.float32(0))
    out = []
    for zmw in terms.reshape(z, r, jmax, 9):
        m = 1 << (r - 1).bit_length()
        x = np.concatenate([zmw, np.zeros((m - r, jmax, 9), np.float32)])
        while m > 1:
            m //= 2
            x = x[:m] + x[m:]
        out.append(x[0])
    return np.stack(out)


@pytest.mark.parametrize("z,r,jm,jmax", [
    (2, 12, 2304, 2304),         # 2kb-3to10x.serve-c32: 16 x 12 x 2,304
    (4, 12, 2304, 2304),         # 2kb-3to10x.batch: 32 x 12 x 2,304
    (2, 32, 576, 576),           # 500bp-30x.batch: 32 x 32 x 576
    (2, 12, 192, 256),           # a template frame longer than the windows
    (2, 12, 256, 192),           # and shorter
], ids=["serve-2x12x2304", "2kb-4x12x2304", "500bp-2x32x576",
        "jmax-over-jm", "jmax-under-jm"])
def test_slot_major_totals_match_gather_reference(z, r, jm, jmax):
    """slot_major_spliced + slot_grid_totals give, bit for bit, what the
    splice, the gather mapping, the masks and the baselines gave, at the
    cells' (Z*R, Jm) (Z reduced): first read by read (every entry a
    read's window overlaps), then as the ZMWs' totals."""
    n = z * r
    rng = np.random.default_rng(34 + n + jm)
    grid = (rng.normal(size=(n, jm, 9)) * 40).astype(np.float32)
    e6 = (rng.normal(size=(n, 6, 9)) * 40).astype(np.float32)
    strand, ts, te = _random_windows(rng, n, jm, jmax)
    wl = np.clip(te - ts, 0, jm)

    spliced = np.stack([splice_reference(grid[k], e6[k], wl[k])
                        for k in range(n)])
    grid_s = dsp.slot_major_spliced(jnp.asarray(grid), jnp.asarray(e6),
                                    jnp.asarray(wl))
    np.testing.assert_array_equal(np.asarray(grid_s),
                                  spliced.transpose(0, 2, 1))

    every = np.ones(n, bool)
    want = totals_reference(spliced, strand, ts, te, every,
                            np.zeros(n, np.float32),
                            np.ones((n, jmax, 9), bool))
    assert (want != 0).mean() > 0.3, "windows cover too little to compare"
    np.testing.assert_array_equal(
        _mapped_reads(grid_s, strand, ts, te, jmax),
        want.transpose(0, 2, 1))

    live = rng.random(n) > 0.2
    base = (rng.normal(size=n) * 40).astype(np.float32)
    valid = rng.random((z, jmax, 9)) > 0.3
    got = dsp.slot_grid_totals(
        grid_s, *map(jnp.asarray, (strand, ts, te, live, base,
                                   valid.transpose(0, 2, 1))),
        *map(jnp.asarray, _slot_planes(jmax)))
    np.testing.assert_array_equal(
        np.asarray(got).transpose(0, 2, 1),
        totals_reference(spliced, strand, ts, te, live, base, valid))


def test_mapping_lowers_to_no_gather():
    """The pass after the kernel is a turn, selects, a reversal and a lane
    rotate: its jaxpr, the kernel's included, holds no gather,
    dynamic_slice or scatter."""
    n, jm = 24, 2304
    f = lambda g, e, j, s, a, b, v: dsp.slot_grid_totals(
        dsp.slot_major_spliced(g, e, j), s, a, b, jnp.ones(n, bool),
        jnp.zeros(n, jnp.float32), v, *map(jnp.asarray, _slot_planes(jm)))
    i32 = jax.ShapeDtypeStruct((n,), jnp.int32)
    text = str(jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((n, jm, 9), jnp.float32),
        jax.ShapeDtypeStruct((n, 6, 9), jnp.float32), i32, i32, i32, i32,
        jax.ShapeDtypeStruct((2, 9, jm), jnp.bool_)))
    assert "pallas_call" in text and "roll" in text
    for op in ("gather", "dynamic_slice", "scatter"):
        assert op not in text, f"{op} in the score grid's mapping"


def test_read_reduction_is_the_same_bits_at_any_batch_shape():
    """A ZMW's totals over its reads are the same bits at Z = 4, 16 and 32,
    in whichever slot of the batch it sits, and in 12 lanes as in 32 (the
    lanes past its reads are not live and add zeros): the order is the
    kernel's own, which no batch shape, Z or layout chooses."""
    rng = np.random.default_rng(3434)
    r, jm = 12, 256
    mine = (rng.normal(size=(r, jm, 9)) * 50).astype(np.float32)
    strand, ts, te = _random_windows(rng, 18, jm, jm)
    strand, ts, te = strand[:r], ts[:r], te[:r]
    live = np.arange(r) < 10             # a 10-pass ZMW in 12 lanes
    base = (rng.normal(size=r) * 50).astype(np.float32)
    valid = rng.random((jm, 9)) > 0.2
    want = totals_reference(mine, strand, ts, te, live, base, valid[None])[0]
    # the order matters on this input: read after read gives other bits
    one = lambda k: totals_reference(mine[k:k + 1], strand[k:k + 1],
                                     ts[k:k + 1], te[k:k + 1], live[k:k + 1],
                                     base[k:k + 1], valid[None])[0]
    in_turn = one(0)
    for k in range(1, r):
        in_turn = in_turn + one(k)
    assert (in_turn != want).any()

    planes = tuple(map(jnp.asarray, _slot_planes(jm)))
    for z, slot, lanes in [(4, 0, 12), (16, 7, 12), (32, 31, 12),
                           (32, 5, 32)]:
        # a batch of other ZMWs' reads, with mine in `slot`'s first lanes
        n = z * lanes
        g = (rng.normal(size=(n, jm, 9)) * 50).astype(np.float32)
        b_strand = rng.integers(0, 2, n)
        b_ts = rng.integers(0, jm // 2, n)
        b_te = b_ts + 40
        b_live = rng.random(n) > 0.5
        b_base = (rng.normal(size=n) * 50).astype(np.float32)
        at = slot * lanes
        b_live[at:at + lanes] = False
        for whole, part in ((g, mine), (b_strand, strand), (b_ts, ts),
                            (b_te, te), (b_live, live), (b_base, base)):
            whole[at:at + r] = part
        got = dsp.slot_grid_totals(
            *map(jnp.asarray, (g.transpose(0, 2, 1), b_strand, b_ts, b_te,
                               b_live, b_base,
                               np.broadcast_to(valid.T, (z, 9, jm)))),
            *planes)
        np.testing.assert_array_equal(
            np.asarray(got)[slot].T, want, err_msg=f"Z={z} R={lanes}")
