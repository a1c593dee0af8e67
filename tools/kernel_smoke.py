#!/usr/bin/env python
"""Tier-1 kernel gate: the dense interior + edge mutation-scoring kernels
(ops/dense_score_pallas, interpret mode on CPU) against the float64 DENSE
oracle (ops/fwdbwd_ref) on one fixed seed, under a ~30 s budget.

Regime: band width W >= I + 1, so the banded kernel covers the whole DP
matrix and its absolute mutated-window log-likelihood must equal
`loglik_dense` of the mutated window to f32 rounding -- a ground-truth
check, not a same-code parity check.  Also pins the pre-baked layout
path (prepare_dense_layout) BITWISE against the in-graph derivation, so
a prepare-time layout bug cannot pass the gate by matching itself.

Deterministic: seed 20260729, no environment dependence beyond
JAX_PLATFORMS=cpu (tier1.sh sets it)."""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

W = 24          # band >= I + 1 for every read below (dense-cover regime)
L = 14          # window template length
SEED = 20260729


def main() -> int:
    t0 = time.perf_counter()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pbccs_tpu.models.arrow import mutations as mutlib
    from pbccs_tpu.models.arrow.params import (
        snr_to_transition_table_host,
        revcomp_padded,
        template_transition_params,
    )
    from pbccs_tpu.models.arrow.scorer import (fill_alpha_beta_batch,
                                               oriented_window)
    from pbccs_tpu.ops import dense_score_pallas as dsp
    from pbccs_tpu.ops.fwdbwd_ref import loglik_dense
    from pbccs_tpu.simulate import simulate_zmw

    rng = np.random.default_rng(SEED)
    tpl, reads, strands, snr = simulate_zmw(rng, L, 2)
    Jmax = 64
    Imax = Jmax + 32
    table = jnp.asarray(snr_to_transition_table_host(np.asarray(snr)))
    tpl_p = jnp.asarray(np.pad(tpl, (0, Jmax - L), constant_values=4))
    tlen = jnp.int32(L)
    tpl_r = revcomp_padded(tpl_p, tlen)

    windows = [(0, 0, L), (1, 0, L)]
    R = len(windows)
    reads_p = np.full((R, Imax), 4, np.int8)
    rlens = np.zeros(R, np.int32)
    st = np.zeros(R, np.int32)
    ts_a = np.zeros(R, np.int32)
    te_a = np.zeros(R, np.int32)
    for i, (strand, ts, te) in enumerate(windows):
        r = np.asarray(reads[i])[: W - 2]   # dense-cover: I <= W - 2
        reads_p[i, : len(r)] = r
        rlens[i] = len(r)
        st[i], ts_a[i], te_a[i] = strand, ts, te

    win_tpl, win_trans, wlens = jax.vmap(
        lambda s, a, b: oriented_window(s, a, b, tpl_p, tpl_r, tlen, table)
    )(jnp.asarray(st), jnp.asarray(ts_a), jnp.asarray(te_a))
    alpha, beta, _, _, apre, bsuf = fill_alpha_beta_batch(
        jnp.asarray(reads_p), jnp.asarray(rlens), win_tpl, win_trans,
        wlens, W, use_pallas=False)
    tables = jnp.broadcast_to(table[None], (R, 8, 4))
    args = (jnp.asarray(reads_p), jnp.asarray(rlens), win_tpl, win_trans,
            wlens, tables, alpha, beta, apre, bsuf, W)

    # the PRE-BAKED layout path end to end (prepare_dense_layout ->
    # kernels): matching the f64 oracle pins kernels AND baked buffers
    # in one pass.  (Bitwise prebaked==in-graph equivalence is pinned by
    # tests/test_dense_score.py::test_prepared_layout_matches_ingraph in
    # the tier-1 suite; re-deriving it here would double the trace count
    # and blow the budget.)
    layout = dsp.prepare_dense_layout(*args)
    grid = np.asarray(dsp.dense_interior_scores_batch(*args, layout=layout))
    e6 = np.asarray(dsp.edge_window_scores_batch(*args, layout=layout))

    # f64 dense oracle over every served slot of every read
    slot_mt = [0, 0, 0, 0, 1, 1, 1, 1, 2]
    slot_nb = [0, 1, 2, 3, 0, 1, 2, 3, -1]
    n_checked = 0
    worst = 0.0
    for r in range(R):
        J = int(wlens[r])
        I = int(rlens[r])
        assert W >= I + 1, "smoke regime needs a full-cover band"
        wt = np.asarray(win_tpl[r])[:J].astype(np.int8)
        read = reads_p[r, :I].astype(np.int8)

        def oracle(p, k):
            mtype, nbase = slot_mt[k], slot_nb[k]
            end = p + (0 if mtype == 1 else 1)
            mut = mutlib.Mutation(start=p, end=end, mtype=mtype,
                                  new_base=max(nbase, 0))
            mtpl = mutlib.apply_mutations(wt, [mut])
            mtr = np.asarray(template_transition_params(
                jnp.asarray(mtpl.astype(np.int32)), table,
                jnp.int32(len(mtpl))), np.float64)[: len(mtpl)]
            return loglik_dense(read, mtpl, mtr)

        def check(got, p, k, where):
            nonlocal n_checked, worst
            want = oracle(p, k)
            err = abs(got - want) / max(abs(want), 1.0)
            worst = max(worst, err)
            assert err < 5e-4, \
                f"{where} r={r} p={p} k={k}: got {got} want {want}"
            n_checked += 1

        # interior slots (kernel scope: p >= 3, end <= J - 2)
        for p in range(3, J - 2):
            for k in range(9):
                if slot_mt[k] != 1 and p + 1 > J - 2:
                    continue
                check(float(grid[r, p, k]), p, k, "interior")
        # edge rows {0,1,2} x {J-2,J-1,J}, regime rules as slot_major_spliced
        for row, p in enumerate([0, 1, 2, J - 2, J - 1, J]):
            for k in range(9):
                mtype = slot_mt[k]
                if mtype == 1:
                    if p > J or row == 3:
                        continue
                elif p >= J:
                    continue
                if p <= 2 and row >= 3:
                    continue
                check(float(e6[r, row, k]), p, k, "edge")

    dt = time.perf_counter() - t0
    assert n_checked > 150, f"too few oracle checks ({n_checked})"
    print(f"kernel smoke OK: {n_checked} slots (prebaked-layout path) "
          f"vs f64 dense oracle, worst rel err {worst:.2e}, {dt:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
