"""Parity of the device refinement primitives with the host numpy logic
they re-express (see pbccs_tpu/parallel/device_refine.py docstring)."""

import numpy as np
import pytest

from pbccs_tpu.models.arrow import mutations as mutlib
from pbccs_tpu.parallel import device_refine as dr


def _host_candidates(tpl):
    a = mutlib.enumerate_unique_arrays(tpl)
    return set(zip(a.start.tolist(), a.mtype.tolist(), a.new_base.tolist()))


def _dev_candidates(tpl, Jmax, allowed=None):
    import jax.numpy as jnp

    padded = np.full(Jmax, 4, np.int8)
    padded[: len(tpl)] = tpl
    s, e, t, b, v = dr.slot_candidates(
        jnp.asarray(padded), jnp.int32(len(tpl)),
        None if allowed is None else jnp.asarray(allowed))
    s, e, t, b, v = (np.asarray(x) for x in (s, e, t, b, v))
    return s, e, t, b, v


def test_slot_candidates_match_host_enumeration(rng):
    for _ in range(5):
        tpl = rng.integers(0, 4, int(rng.integers(5, 60))).astype(np.int8)
        s, e, t, b, v = _dev_candidates(tpl, 64)
        dev = set(zip(s[v].tolist(), t[v].tolist(), b[v].tolist()))
        assert dev == _host_candidates(tpl)
        # ends consistent with types
        host = mutlib.enumerate_unique_arrays(tpl)
        dev_ends = {(st, mt, nb): en for st, en, mt, nb in
                    zip(s[v], e[v], t[v], b[v])}
        for st, en, mt, nb in zip(host.start, host.end, host.mtype,
                                  host.new_base):
            assert dev_ends[(int(st), int(mt), int(nb))] == int(en)


def test_slot_candidates_nearby_filter(rng):
    tpl = rng.integers(0, 4, 50).astype(np.int8)
    centers = [mutlib.Mutation(10, 11, mutlib.SUBSTITUTION, 0),
               mutlib.Mutation(30, 30, mutlib.INSERTION, 2)]
    host = mutlib.unique_nearby_arrays(tpl, centers, 5)
    want = set(zip(host.start.tolist(), host.mtype.tolist(),
                   host.new_base.tolist()))

    import jax.numpy as jnp

    fav_start = jnp.asarray([10, 30], jnp.int32)
    fav_end = jnp.asarray([11, 30], jnp.int32)
    allowed = dr.nearby_allowed(fav_start, fav_end,
                                jnp.asarray([True, True]), 5, 64)
    allowed = np.asarray(allowed) & (np.arange(64) < len(tpl))
    s, e, t, b, v = _dev_candidates(tpl, 64, allowed=allowed)
    dev = set(zip(s[v].tolist(), t[v].tolist(), b[v].tolist()))
    assert dev == want


def test_greedy_matches_best_subset(rng):
    import jax.numpy as jnp

    for trial in range(8):
        L = 60
        tpl = rng.integers(0, 4, L).astype(np.int8)
        s, e, t, b, v = _dev_candidates(tpl, 64)
        scores = rng.normal(0, 3, len(s))
        scores[~v] = -np.inf
        fav = v & (scores > 0)

        host_muts = [mutlib.Mutation(int(s[i]), int(e[i]), int(t[i]),
                                     int(b[i]), float(scores[i]))
                     for i in np.nonzero(fav)[0]]
        want = mutlib.best_subset(host_muts, 10)
        want_keys = {(m.start, m.mtype, m.new_base) for m in want}

        taken = np.asarray(dr.greedy_well_separated(
            jnp.asarray(scores, jnp.float32), jnp.asarray(s),
            jnp.asarray(fav), 10, 64))
        got_keys = {(int(s[i]), int(t[i]), int(b[i]))
                    for i in np.nonzero(taken)[0]}
        assert got_keys == want_keys, trial


@pytest.mark.slow
def test_greedy_peel_matches_scan(rng):
    """The data-parallel peeling selection equals the sequential-scan
    greedy on randomized slot grids, including adversarial cases: equal
    scores (slot tie-break), domination chains (descending staircases
    spaced under the separation), and dense favorables."""
    import jax.numpy as jnp

    for trial in range(24):
        jmax = int(rng.integers(16, 128))
        M = jmax * 9
        start = np.repeat(np.arange(jmax, dtype=np.int32), 9)
        sep = int(rng.integers(1, 14))
        kind = trial % 4
        if kind == 0:
            scores = rng.normal(0, 3, M)
        elif kind == 1:  # many exact ties
            scores = rng.integers(0, 4, M).astype(np.float64)
        elif kind == 2:  # descending staircase: worst case for peeling
            scores = np.linspace(10, 0.1, M)
        else:            # sparse favorables
            scores = np.where(rng.random(M) < 0.05, rng.normal(3, 1, M),
                              -1.0)
        fav = scores > 0
        a = jnp.asarray(scores, jnp.float32)
        st = jnp.asarray(start)
        f = jnp.asarray(fav)
        got = np.asarray(dr.greedy_well_separated(a, st, f, sep, jmax))
        want = np.asarray(dr.greedy_well_separated_scan(a, st, f, sep, jmax))
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"trial={trial} sep={sep}")
        # the position-major fast form (what the loop body runs) agrees
        posm = np.asarray(dr.greedy_well_separated_posmajor(a, f, sep, jmax))
        np.testing.assert_array_equal(posm, want,
                                      err_msg=f"posmajor trial={trial}")


def test_splice_matches_apply_mutations(rng):
    import jax.numpy as jnp

    for trial in range(8):
        L = 50
        Jmax = 64
        tpl = rng.integers(0, 4, L).astype(np.int8)
        s, e, t, b, v = _dev_candidates(tpl, Jmax)
        scores = rng.normal(0, 3, len(s))
        scores[~v] = -np.inf
        fav = v & (scores > 0)
        taken = np.asarray(dr.greedy_well_separated(
            jnp.asarray(scores, jnp.float32), jnp.asarray(s),
            jnp.asarray(fav), 10, Jmax))
        muts = [mutlib.Mutation(int(s[i]), int(e[i]), int(t[i]), int(b[i]))
                for i in np.nonzero(taken)[0]]
        if not muts:
            continue
        want_tpl = mutlib.apply_mutations(tpl, muts)
        want_mtp = mutlib.target_to_query_positions(muts, L)

        padded = np.full(Jmax, 4, np.int8)
        padded[:L] = tpl
        new_tpl, new_tlen, mtp = dr.splice_templates(
            jnp.asarray(padded), jnp.int32(L), jnp.asarray(s),
            jnp.asarray(t), jnp.asarray(b), jnp.asarray(taken))
        new_tpl, new_tlen, mtp = (np.asarray(x) for x in
                                  (new_tpl, new_tlen, mtp))
        assert new_tlen == len(want_tpl)
        np.testing.assert_array_equal(new_tpl[:new_tlen], want_tpl)
        np.testing.assert_array_equal(mtp[: L + 1], want_mtp)


def test_rc_candidates_match_host(rng):
    import jax.numpy as jnp

    tpl = rng.integers(0, 4, 40).astype(np.int8)
    s, e, t, b, v = _dev_candidates(tpl, 64)
    host = mutlib.enumerate_unique_arrays(tpl)
    host_rc = mutlib.reverse_complement_arrays(host, len(tpl))
    want = {(int(st), int(mt), int(nb)): (int(rs), int(rb))
            for st, mt, nb, rs, rb in zip(host.start, host.mtype,
                                          host.new_base, host_rc.start,
                                          host_rc.new_base)}
    rs, rb = dr.rc_candidates(jnp.asarray(s), jnp.asarray(e),
                              jnp.asarray(b), jnp.int32(len(tpl)))
    rs, rb = np.asarray(rs), np.asarray(rb)
    for i in np.nonzero(v)[0]:
        assert want[(int(s[i]), int(t[i]), int(b[i]))] == \
            (int(rs[i]), int(rb[i]))


def test_greedy_separation_zero_dedupes_per_start(rng):
    """separation=0 keeps every favorable START but at most one mutation
    per start (splice_templates' scatters silently merge same-start edits):
    best score wins, ties to the earlier slot."""
    import jax.numpy as jnp

    scores = jnp.asarray([1.0, 2.0, 3.0, 4.0, 4.0])
    start = jnp.asarray([5, 5, 6, 7, 7], jnp.int32)
    fav = jnp.asarray([True, True, False, True, True])
    taken = np.asarray(dr.greedy_well_separated(scores, start, fav, 0, 16))
    # start 5: best of (1.0, 2.0) -> slot 1; start 6: not favorable;
    # start 7: tie (4.0, 4.0) -> earlier slot 3
    np.testing.assert_array_equal(taken, [False, True, False, True, False])


@pytest.mark.slow
def test_device_loop_matches_host_loop(rng, monkeypatch):
    """End-to-end: the device-resident while_loop refinement produces
    bit-identical templates, QVs, and counters to the host loop."""
    from pbccs_tpu.models.arrow.refine import RefineOptions
    from pbccs_tpu.parallel.batch import BatchPolisher, ZmwTask
    from pbccs_tpu.simulate import simulate_zmw

    tasks = []
    for z in range(4):
        tpl, reads, strands, snr = simulate_zmw(rng, 80, 5)
        draft = tpl.copy()
        draft[40] = (draft[40] + 1) % 4
        if z == 1:
            draft = np.delete(draft, 20)
        tasks.append(ZmwTask(f"d/{z}", draft, snr, reads, strands,
                             [0] * 5, [len(draft)] * 5))
    opts = RefineOptions(max_iterations=8)

    monkeypatch.setenv("PBCCS_DEVICE_REFINE", "0")
    host = BatchPolisher(tasks)
    rh = host.refine(opts)
    qh = host.consensus_qvs()

    monkeypatch.setenv("PBCCS_DEVICE_REFINE", "1")
    dev = BatchPolisher(tasks)
    rd = dev.refine(opts)
    qd = dev.consensus_qvs()

    for z in range(4):
        assert rh[z].converged == rd[z].converged
        assert rh[z].iterations == rd[z].iterations
        assert rh[z].n_applied == rd[z].n_applied
        assert rh[z].n_tested == rd[z].n_tested
        np.testing.assert_array_equal(host.tpls[z], dev.tpls[z])
        np.testing.assert_array_equal(qh[z], qd[z])


@pytest.mark.slow
def test_device_loop_dense_matches_host_loop(rng, monkeypatch):
    """The dense-kernel scoring path (PBCCS_DENSE=1, interpret mode on
    CPU) drives the device loop to the same refinement outcome as the
    host loop: same convergence, same templates, same QVs.  Exercises the
    live-block skip (rounds > 0 restrict candidates to nearby windows,
    so most kernel cells are dead) and the window-frame edge splice."""
    from pbccs_tpu.models.arrow.refine import RefineOptions
    from pbccs_tpu.parallel.batch import BatchPolisher, ZmwTask
    from pbccs_tpu.simulate import simulate_zmw

    tasks = []
    for z in range(3):
        tpl, reads, strands, snr = simulate_zmw(rng, 70, 5)
        draft = tpl.copy()
        draft[35] = (draft[35] + 1) % 4
        if z == 1:
            draft = np.delete(draft, 2)     # near-begin edge mutation
        if z == 2:
            draft[len(draft) - 2] = (draft[len(draft) - 2] + 2) % 4  # near-end
        tasks.append(ZmwTask(f"dd/{z}", draft, snr, reads, strands,
                             [0] * 5, [len(draft)] * 5))
    opts = RefineOptions(max_iterations=8)

    monkeypatch.setenv("PBCCS_DEVICE_REFINE", "0")
    host = BatchPolisher(tasks)
    rh = host.refine(opts)
    qh = host.consensus_qvs()

    monkeypatch.setenv("PBCCS_DEVICE_REFINE", "1")
    monkeypatch.setenv("PBCCS_DENSE", "1")
    dev = BatchPolisher(tasks)
    rd = dev.refine(opts)
    qd = dev.consensus_qvs()

    for z in range(3):
        assert rh[z].converged and rd[z].converged
        np.testing.assert_array_equal(host.tpls[z], dev.tpls[z])
        np.testing.assert_array_equal(qh[z], qd[z])


@pytest.mark.slow
def test_device_loop_skip_and_empty(rng, monkeypatch):
    """skip ZMWs stay untouched and non-converged through the device loop."""
    from pbccs_tpu.models.arrow.refine import RefineOptions
    from pbccs_tpu.parallel.batch import BatchPolisher, ZmwTask
    from pbccs_tpu.simulate import simulate_zmw

    monkeypatch.setenv("PBCCS_DEVICE_REFINE", "1")
    tasks = []
    for z in range(2):
        tpl, reads, strands, snr = simulate_zmw(rng, 60, 4)
        draft = tpl.copy()
        draft[30] = (draft[30] + 1) % 4
        tasks.append(ZmwTask(f"s/{z}", draft, snr, reads, strands,
                             [0] * 4, [len(draft)] * 4))
    p = BatchPolisher(tasks)
    before = p.tpls[1].copy()
    res = p.refine(RefineOptions(max_iterations=6), skip={1})
    assert res[0].converged
    assert not res[1].converged
    assert res[1].n_tested == 0 and res[1].n_applied == 0
    np.testing.assert_array_equal(p.tpls[1], before)


@pytest.mark.slow
def test_straggler_continuation_plumbing(rng, monkeypatch):
    """The straggler early-exit path: a ZMW the loop returns unconverged
    with budget left is finished in a compact sub-polisher, its template
    and counters merge into the parent's results, its QVs come from the
    sub-polisher, and a second refine() is safe (stale-fill rebuild).

    The early exit itself needs Z>=33 (threshold Z//32), too big to
    compile in CI, so the loop's return is shimmed to mark one ZMW as an
    early-exited straggler."""
    from pbccs_tpu.models.arrow.refine import RefineOptions
    from pbccs_tpu.parallel import device_refine as dr
    from pbccs_tpu.parallel.batch import BatchPolisher, ZmwTask
    from pbccs_tpu.simulate import simulate_zmw

    monkeypatch.setenv("PBCCS_DEVICE_REFINE", "1")
    tasks = []
    for z in range(3):
        tpl, reads, strands, snr = simulate_zmw(rng, 70, 5)
        draft = tpl.copy()
        draft[35] = (draft[35] + 1) % 4
        tasks.append(ZmwTask(f"st/{z}", draft, snr, reads, strands,
                             [0] * 5, [len(draft)] * 5))

    real_loop = dr.run_refine_loop

    def shim(state, *args, **kw):
        out = real_loop(state, *args, **kw)
        import jax.numpy as jnp

        # pretend ZMW 1 exited early, unconverged with budget left
        return out._replace(
            converged=out.converged.at[1].set(False),
            done=out.done.at[1].set(False),
            iterations=out.iterations.at[1].set(1),
            overflow=jnp.asarray(False))

    monkeypatch.setattr(dr, "run_refine_loop", shim)
    p = BatchPolisher(tasks)
    res = p.refine(RefineOptions(max_iterations=6))
    monkeypatch.setattr(dr, "run_refine_loop", real_loop)

    assert p._cont.sub_polishers and 1 in p._cont.sub_polishers
    assert res[1].converged  # the sub-polisher finished it
    # the continuation carries the REMAINING budget: parent spent 1 round,
    # so total iterations can never exceed the single max_iterations bound
    assert res[1].iterations <= 6

    # reference outcome: an unshimmed polisher over the same tasks
    monkeypatch.setenv("PBCCS_DEVICE_REFINE", "0")
    want = BatchPolisher(tasks)
    want.refine(RefineOptions(max_iterations=6))
    wq = want.consensus_qvs()

    np.testing.assert_array_equal(p.tpls[1], want.tpls[1])
    q = p.consensus_qvs()
    np.testing.assert_array_equal(q[1], wq[1])
    # skipped stragglers cost no sub sweep and stay empty
    q2 = p.consensus_qvs(skip={1})
    assert len(q2[1]) == 0

    # second refine on the parent is safe after the continuation
    monkeypatch.setenv("PBCCS_DEVICE_REFINE", "1")
    res2 = p.refine(RefineOptions(max_iterations=4))
    assert all(r.converged for r in res2)
    np.testing.assert_array_equal(p.tpls[1], want.tpls[1])


def test_template_hash_distinguishes(rng):
    import jax.numpy as jnp

    tpl = rng.integers(0, 4, 40).astype(np.int8)
    pad = np.full(64, 4, np.int8)
    pad[:40] = tpl
    h0 = int(dr.template_hash(jnp.asarray(pad), jnp.int32(40)))
    # single-base change, length change, and pad-content change
    p2 = pad.copy()
    p2[17] = (p2[17] + 1) % 4
    assert int(dr.template_hash(jnp.asarray(p2), jnp.int32(40))) != h0
    assert int(dr.template_hash(jnp.asarray(pad), jnp.int32(39))) != h0
    p3 = pad.copy()
    p3[50] = 0  # beyond tlen: must not affect the hash
    assert int(dr.template_hash(jnp.asarray(p3), jnp.int32(40))) == h0


@pytest.mark.parametrize("what", ["templates", "qvs", "converged",
                                  "iterations", "n_tested", "n_applied"])
def test_loop_and_qv_sweep_give_the_parents_outputs(what, pr28_loop_run):
    """run_refine_loop and run_qv_ints end to end at one small bucket, the
    dense kernel and the Pallas fills interpreted: the templates, QVs and
    counters PR 27's tree gave for the same three ZMWs
    (tests/fixtures/pr28/refine_loop_parent.json), and a loop state that
    carries the framed bands and the four-leaf layout."""
    got, want = pr28_loop_run
    assert got[what] == want[what]


@pytest.fixture(scope="module")
def pr28_loop_run():
    import json
    import os

    from pbccs_tpu.models.arrow.refine import RefineOptions
    from pbccs_tpu.ops import fwdbwd as fb
    from pbccs_tpu.parallel.batch import BatchPolisher, ZmwTask
    from pbccs_tpu.simulate import simulate_zmw

    mp = pytest.MonkeyPatch()
    for k in ("PBCCS_DENSE", "PBCCS_PALLAS", "PBCCS_DEVICE_REFINE"):
        mp.setenv(k, "1")
    try:
        rng = np.random.default_rng(2828)
        tasks = []
        for z in range(3):
            tpl, reads, strands, snr = simulate_zmw(rng, 70, 5)
            draft = tpl.copy()
            draft[15 + 9 * z] = (draft[15 + 9 * z] + 1) % 4
            draft = np.delete(draft, 40 + z)
            tasks.append(ZmwTask(f"pr28/{z}", draft, snr, reads, strands,
                                 [0] * 5, [len(draft)] * 5))
        p = BatchPolisher(tasks)
        st = p._loop_state(set())
        assert fb.band_lead(st.alpha) == fb.band_lead(st.beta) == fb.BAND_LEAD
        assert len(st.dlayout) == 4
        assert {a.shape[2] for a in st.dlayout} == {st.alpha.vals.shape[2]}
        res = p.refine_device(RefineOptions(max_iterations=10))
        qvs = p.consensus_qvs()
    finally:
        mp.undo()
    got = {"templates": [np.asarray(t).tolist() for t in p.tpls],
           "qvs": [np.asarray(q).tolist() for q in qvs],
           "converged": [bool(r.converged) for r in res],
           "iterations": [int(r.iterations) for r in res],
           "n_tested": [int(r.n_tested) for r in res],
           "n_applied": [int(r.n_applied) for r in res]}
    path = os.path.join(os.path.dirname(__file__), "fixtures", "pr28",
                        "refine_loop_parent.json")
    with open(path) as f:
        return got, json.load(f)


# --------------------------------------------------------------------------
# PR 30: a rebuild refills the reads of the ZMWs that applied, no others
# --------------------------------------------------------------------------


@pytest.mark.parametrize("what", ["templates", "qvs", "converged",
                                  "iterations", "n_tested", "n_applied",
                                  "fill_reads"])
def test_refilling_the_needed_reads_gives_the_full_refills_outputs(
        what, ragged_loop_runs):
    """Six ZMWs of 3-10 reads in 12 read lanes whose drafts are 0-3 edits
    from the truth, so they leave the loop in different rounds: the loop
    that refills the applying ZMWs' real reads gives the templates, QVs
    and counters of the loop that refills every lane at every rebuild
    (as the loop did before PR 30), and counts the reads it filled."""
    needed, every = ragged_loop_runs
    applies = [i - c for i, c in zip(needed["iterations"],
                                     needed["converged"])]
    capacity = max(applies) * needed["lanes"]
    # the reference did refill every lane: an inert patch fails here
    assert every["fill_reads"] == (capacity, capacity)
    if what != "fill_reads":
        assert needed[what] == every[what]
        return
    assert len(set(applies)) > 1        # they leave in different rounds
    filled = sum(a * n for a, n in zip(applies, needed["n_reads"]))
    assert needed["fill_reads"] == (filled, capacity)
    assert 0 < filled < capacity


@pytest.mark.parametrize("what", ["templates", "qvs", "converged",
                                  "iterations", "n_tested", "n_applied"])
def test_ragged_loop_gives_the_parents_outputs(what, ragged_loop_runs):
    """The same six ZMWs against what PR 29's tree, whose every rebuild
    refilled every lane in one call, gave for them
    (tests/fixtures/pr30/ragged_loop_parent.json)."""
    import json
    import os

    needed, _ = ragged_loop_runs
    path = os.path.join(os.path.dirname(__file__), "fixtures", "pr30",
                        "ragged_loop_parent.json")
    with open(path) as f:
        assert needed[what] == json.load(f)[what]


@pytest.fixture(scope="module")
def ragged_loop_runs():
    import jax
    import jax.numpy as jnp

    from pbccs_tpu.models.arrow.refine import RefineOptions
    from pbccs_tpu.obs.metrics import default_registry
    from pbccs_tpu.parallel.batch import BatchPolisher, ZmwTask
    from pbccs_tpu.simulate import simulate_zmw

    def run():
        rng = np.random.default_rng(3030)
        tasks = []
        for z, (passes, edits) in enumerate([(3, 0), (10, 3), (5, 1),
                                             (8, 2), (4, 3), (7, 0)]):
            tpl, reads, strands, snr = simulate_zmw(rng, 70, passes)
            draft = tpl.copy()
            for e in range(edits):
                at = 12 + 17 * e + z
                draft[at] = (draft[at] + 1) % 4
            tasks.append(ZmwTask(f"pr30/{z}", draft, snr, reads, strands,
                                 [0] * passes, [len(draft)] * passes))
        counter = lambda kind: int(default_registry().counter(
            "ccs_refine_fill_reads_total", kind=kind).value)
        before = counter("filled"), counter("capacity")
        p = BatchPolisher(tasks, buckets=(128, 96, 12))
        assert p._R == 12
        res = p.refine_device(RefineOptions(max_iterations=10))
        qvs = p.consensus_qvs()
        return {"templates": [np.asarray(t).tolist() for t in p.tpls],
                "qvs": [np.asarray(q).tolist() for q in qvs],
                "converged": [bool(r.converged) for r in res],
                "iterations": [int(r.iterations) for r in res],
                "n_tested": [int(r.n_tested) for r in res],
                "n_applied": [int(r.n_applied) for r in res],
                "n_reads": [len(t.reads) for t in tasks],
                "lanes": p._Z * p._R,
                "fill_reads": (counter("filled") - before[0],
                               counter("capacity") - before[1])}

    mp = pytest.MonkeyPatch()
    for k in ("PBCCS_DENSE", "PBCCS_PALLAS", "PBCCS_DEVICE_REFINE"):
        mp.setenv(k, "1")
    try:
        needed = run()
        # the loop as it was: every rebuild refills every lane, the lanes
        # of the ZMWs that applied nothing and the lanes with no read too
        mp.setattr(dr, "reads_to_refill",
                   lambda applied, real_rows: jnp.ones_like(real_rows))
        jax.clear_caches()
        every = run()
    finally:
        mp.undo()
        jax.clear_caches()
    return needed, every
