"""Quiver multi-read mutation scorer.

Parity target: the Quiver-namespace MultiReadMutationScorer (reference
ConsensusCore/include/ConsensusCore/Quiver/MultiReadMutationScorer.hpp:55-246,
src/C++/Quiver/MultiReadMutationScorer.cpp): per-read template windows on
the forward/RC template, AddRead alpha/beta mating gate, Score(mutation) =
sum over reads of LL(mutated) - LL(current), ApplyMutations with coordinate
remap.  Unlike Arrow there is no per-position transition track -- move
scores depend on the template only through base identity -- so mutation
scoring re-fills the mutated window directly (the reference's
extend+link specialization is a serial-CPU optimization; the batched
re-fill keeps every candidate on the device grid)."""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from pbccs_tpu.models.arrow import mutations as mutlib
from pbccs_tpu.models.arrow.params import revcomp
from pbccs_tpu.models.quiver.params import QuiverConfig
from pbccs_tpu.models.quiver.recursor import (
    QuiverFeatureArrays,
    feature_arrays,
    quiver_backward,
    quiver_forward,
    quiver_loglik,
    quiver_loglik_backward,
)
from pbccs_tpu.ops.fwdbwd_pallas import fills_use_pallas

from pbccs_tpu.utils import next_pow2 as _next_pow2

ADD_SUCCESS, ADD_ALPHABETAMISMATCH = 0, 1
_AB_MISMATCH_TOL = 1e-3
_MUT_CHUNK = 256

import functools


@functools.partial(jax.jit, static_argnames=("config", "width"))
def _lls_program(feats, rl, tp, tl, *, config, width):
    """(rows,) forward log-likelihoods of a flat (read, window) batch via
    the XLA recursor — ONE jitted program (eager per-op dispatch pays a
    device round trip per op; a whole polish ran minutes of pure
    dispatch latency before this was jitted)."""
    def one(feat, rlen, win, wlen):
        alpha = quiver_forward(feat, rlen, win, wlen, config, width)
        return quiver_loglik(alpha, rlen, wlen)

    return jax.vmap(one)(feats, rl, tp, tl)


@functools.partial(jax.jit, static_argnames=("config", "width"))
def _ab_program(feats, rl, tp, tl, *, config, width):
    """Batched forward+backward log-likelihoods (the AddRead mating gate's
    inputs) as one jitted program; XLA-recursor counterpart of the Pallas
    branch in _rebuild."""
    def one(feat, rlen, win, wlen):
        alpha = quiver_forward(feat, rlen, win, wlen, config, width)
        beta = quiver_backward(feat, rlen, win, wlen, config, width)
        return (quiver_loglik(alpha, rlen, wlen),
                quiver_loglik_backward(beta, wlen))

    return jax.vmap(one)(feats, rl, tp, tl)


@functools.partial(jax.jit, static_argnames=("config", "width"))
def _pallas_ab_program(feats, rl, tp, tl, *, config, width):
    """Pallas-batch AddRead fills + LLs as ONE jitted program.  Eager
    pallas_call bypasses jit executable caching AND the persistent
    compilation cache, so every process paid the full remote Mosaic
    compile again -- the quiver bench's repeated 45-minute walls."""
    from pbccs_tpu.models.quiver.pallas_fill import (
        pallas_quiver_backward_batch, pallas_quiver_forward_batch,
        quiver_loglik_batch)

    alpha = pallas_quiver_forward_batch(feats, rl, tp, tl, config, width)
    beta = pallas_quiver_backward_batch(feats, rl, tp, tl, config, width)
    ll_a = quiver_loglik_batch(alpha, rl, tl)
    jcols = jnp.arange(beta.log_scales.shape[1])[None, :]
    ll_b = (jnp.log(jnp.maximum(beta.vals[:, 0, 0], 1e-30))
            + jnp.where(jcols <= tl[:, None], beta.log_scales, 0.0
                        ).sum(axis=1))
    return ll_a, ll_b


@functools.partial(jax.jit, static_argnames=("config", "width"))
def _pallas_lls_program(feats, rl, tp, tl, *, config, width):
    """Pallas-batch forward LLs as ONE jitted program (see
    _pallas_ab_program for why jit is load-bearing here)."""
    from pbccs_tpu.models.quiver.pallas_fill import (
        pallas_quiver_forward_batch, quiver_loglik_batch)

    alpha = pallas_quiver_forward_batch(feats, rl, tp, tl, config, width)
    return quiver_loglik_batch(alpha, rl, tl)





class QuiverMultiReadScorer:
    """Per-template Quiver polishing state over QV-feature reads."""

    def __init__(self, tpl: np.ndarray, reads: Sequence, strands: Sequence[int],
                 tstarts: Sequence[int], tends: Sequence[int],
                 config: QuiverConfig | None = None):
        self.config = config or QuiverConfig()
        self.tpl = np.asarray(tpl, np.int8)
        self.n_reads = len(reads)
        self._feats = list(reads)
        self._strands = np.asarray(strands, np.int32)
        self._tstarts = np.asarray(tstarts, np.int32)
        self._tends = np.asarray(tends, np.int32)
        self._Imax = _next_pow2(max((len(f) for f in reads), default=8) + 8, 64)
        # template-axis bucket PINNED with growth headroom (one formula:
        # _jmax_bucket below): recomputing next_pow2(L) from the CURRENT
        # length minted a fresh Jmax -- and recompiled the whole
        # fill-program menu -- every time a round's accepted indels
        # crossed a pow2 boundary.  One bucket serves every rebuild and
        # mutated-window score; templates outgrowing it re-bucket (rare,
        # _rebuild).
        self._Jmax = 0      # set by _rebuild(first=True)'s bucket guard
        self._W = self.config.banding.band_width
        self._dev_feats = [feature_arrays(f, self._Imax) for f in reads]
        self._rlens = np.asarray([min(len(f), self._Imax) for f in reads], np.int32)
        self.statuses = np.zeros(self.n_reads, np.int32)
        self.active = np.zeros(self.n_reads, bool)
        self._rebuild(first=True)

    # ------------------------------------------------------------------ setup

    def _window_codes(self, r: int, tpl: np.ndarray) -> np.ndarray:
        """Read r's oriented template window of `tpl`."""
        ts, te = int(self._tstarts[r]), int(self._tends[r])
        win = tpl[ts:te]
        if self._strands[r] == 1:
            win = revcomp(win)
        return win

    def _stacked_feats(self, idx=None) -> QuiverFeatureArrays:
        feats = self._dev_feats if idx is None else \
            [self._dev_feats[i] for i in idx]
        return QuiverFeatureArrays(*(jnp.stack([getattr(f, n) for f in feats])
                                     for n in QuiverFeatureArrays._fields))

    def _jmax_bucket(self, L: int) -> int:
        """Headroom-proportional template bucket.  Shares only the headroom
        term with parallel/batch._jmax_bucket (+10 for the mutated-window
        pad); this rounds up to a power of two so the Pallas fill programs
        see a tiny shape menu, where batch pads to a multiple of 64."""
        return _next_pow2(L + max(16, L // 32) + 10, 64)

    def _rebuild(self, first: bool) -> None:
        L = len(self.tpl)
        if L + 8 > self._Jmax:   # template outgrew the bucket: re-bucket
            self._Jmax = self._jmax_bucket(L)
        Jmax = self._Jmax
        wins_np, wlens = [], []
        for r in range(self.n_reads):
            win = self._window_codes(r, self.tpl)
            wpad = np.full(Jmax, 4, np.int8)
            wpad[:len(win)] = win
            wins_np.append(wpad)
            wlens.append(len(win))
        # read axis pads to pow2 (shared contract for both fill backends)
        # so the per-ZMW pass count doesn't mint a compiled shape each
        R = self.n_reads
        Rp = _next_pow2(max(R, 1), 4)
        pad_r = ((0, Rp - R), (0, 0))
        feats = self._stacked_feats()
        feats = QuiverFeatureArrays(*(jnp.pad(t, pad_r) for t in feats))
        rl = jnp.asarray(np.pad(self._rlens, (0, Rp - R),
                                constant_values=2))
        tp = jnp.asarray(np.pad(np.stack(wins_np), pad_r,
                                constant_values=4))
        tl = jnp.asarray(np.pad(np.asarray(wlens, np.int32),
                                (0, Rp - R), constant_values=2))
        if fills_use_pallas():
            # one batched Pallas launch over the read axis (the device
            # analogue of the reference's per-read SSE recursor,
            # SseRecursor.cpp:66-130), as ONE jitted program so the
            # executable + persistent caches apply
            lls_a, lls_b = _pallas_ab_program(feats, rl, tp, tl,
                                              config=self.config,
                                              width=self._W)
        else:
            # XLA-recursor path: one jitted batched program
            lls_a, lls_b = _ab_program(feats, rl, tp, tl,
                                       config=self.config, width=self._W)
        ll_a = np.asarray(lls_a, np.float64)[:R]
        ll_b = np.asarray(lls_b, np.float64)[:R]
        self.baselines = ll_a
        denom = np.where(ll_b == 0, 1.0, ll_b)
        mated = (np.abs(1.0 - ll_a / denom) <= _AB_MISMATCH_TOL) & \
            np.isfinite(ll_a) & np.isfinite(ll_b)
        if first:
            self.active = mated.copy()
            self.statuses = np.where(mated, ADD_SUCCESS, ADD_ALPHABETAMISMATCH)
        else:
            self.active &= mated

    # ---------------------------------------------------------------- scoring

    def baseline_total(self) -> float:
        return float(self.baselines[self.active].sum())

    def _windows_for(self, tpl: np.ndarray, jmax: int):
        outs = []
        for r in range(self.n_reads):
            win = self._window_codes(r, tpl)
            wpad = np.full(jmax, 4, np.int8)
            wpad[:len(win)] = win
            outs.append((wpad, len(win)))
        return outs

    def score_mutations(self, muts: Sequence[mutlib.Mutation]) -> np.ndarray:
        """score(m) = sum over active overlapping reads of
        (LL(T+m) - LL(T)) via full banded refills of the mutated windows.

        Reads sharing an oriented window geometry (ts, te, strand) share
        the mutated windows, so windows build once per GROUP and every
        fill dispatch batches (reads-in-group x mutation-chunk) rows --
        per-read per-chunk dispatches cost a device round trip each,
        which made the per-ZMW polish dispatch-bound."""
        if not muts:
            return np.zeros(0)
        L = len(self.tpl)
        jmax = self._Jmax        # pinned bucket (see __init__)
        scores = np.zeros(len(muts))

        groups: dict[tuple[int, int, int], list[int]] = {}
        for r in range(self.n_reads):
            if self.active[r]:
                key = (int(self._tstarts[r]), int(self._tends[r]),
                       int(self._strands[r]))
                groups.setdefault(key, []).append(r)

        for (ts, te, strand), rds in groups.items():
            wins, wlens, idxs = [], [], []
            for k, m in enumerate(muts):
                overlap = (ts <= m.end) & (m.start <= te) \
                    if m.mtype == mutlib.INSERTION \
                    else (ts < m.end) & (m.start < te)
                if not overlap:
                    continue
                mt = mutlib.apply_mutations(self.tpl, [m])
                # window bounds remap: positions <= start unchanged; the
                # window end moves with the template length delta
                delta = len(mt) - L
                te_m = te + delta if m.start < te else te
                win = mt[ts:te_m]
                if strand == 1:
                    win = revcomp(win)
                wpad = np.full(jmax, 4, np.int8)
                wpad[:len(win)] = win
                wins.append(wpad)
                wlens.append(len(win))
                idxs.append(k)
            if not wins:
                continue
            lls = self._fill_lls_group(rds, np.stack(wins),
                                       np.asarray(wlens, np.int32))
            scores[np.asarray(idxs)] += (
                lls - self.baselines[np.asarray(rds)][:, None]).sum(axis=0)
        return scores

    def _fill_lls_group(self, rds: Sequence[int], wins: np.ndarray,
                        wlens: np.ndarray) -> np.ndarray:
        """(len(rds), M) absolute LLs of each read in the group against
        each mutated window: one fill dispatch per fixed-size mutation
        chunk, with (read x window) riding the batch axis.  Chunks of
        _MUT_CHUNK (+ one pow2 tail) bound the compiled-shape menu --
        an unbounded next_pow2(M) menu compiled a fresh fill program per
        distinct candidate count per round."""
        M = len(wins)
        if M > _MUT_CHUNK:
            outs = [self._fill_lls_group(rds, wins[lo: lo + _MUT_CHUNK],
                                         wlens[lo: lo + _MUT_CHUNK])
                    for lo in range(0, M, _MUT_CHUNK)]
            return np.concatenate(outs, axis=1)
        G = len(rds)
        Mpad = _next_pow2(M, 8)
        wins_p = np.concatenate(
            [wins, np.full((Mpad - M, wins.shape[1]), 4, np.int8)])
        wlens_p = np.concatenate([wlens, np.full(Mpad - M, 2, np.int32)])
        # batch rows: read-major (read g's windows at rows [g*Mpad, ...)),
        # then the TOTAL row count pads to pow2 -- G varies per ZMW with
        # the strand mix, and a (G x Mpad)-keyed shape menu compiled a
        # fresh fill program per combination
        rows = G * Mpad
        rows_p = _next_pow2(rows, 64)
        tl = jnp.asarray(np.pad(np.tile(wlens_p, G), (0, rows_p - rows),
                                constant_values=2))
        tp = jnp.asarray(np.pad(np.tile(wins_p, (G, 1)),
                                ((0, rows_p - rows), (0, 0)),
                                constant_values=4))
        feats = QuiverFeatureArrays(
            *(jnp.pad(jnp.repeat(
                jnp.stack([self._dev_feats[r][i] for r in rds]),
                Mpad, axis=0), ((0, rows_p - rows), (0, 0)))
              for i in range(len(QuiverFeatureArrays._fields))))
        rl = jnp.asarray(np.pad(
            np.repeat(self._rlens[np.asarray(rds)], Mpad),
            (0, rows_p - rows), constant_values=2))
        if fills_use_pallas():
            lls = _pallas_lls_program(feats, rl, tp, tl, config=self.config,
                                      width=self._W)
        else:
            lls = _lls_program(feats, rl, tp, tl, config=self.config,
                               width=self._W)
        return np.asarray(lls, np.float64)[:rows].reshape(G, Mpad)[:, :M]

    # ------------------------------------------------------------------- QVs

    def consensus_qvs(self) -> np.ndarray:
        """Per-position QVs via the generic single-mutation sweep
        (models.arrow.refine.consensus_qvs; reference ConsensusQVs is
        templated over both scorer families, Consensus-inl.hpp:277-297)."""
        from pbccs_tpu.models.arrow.refine import consensus_qvs

        return consensus_qvs(self)

    # --------------------------------------------------------------- mutation

    def apply_mutations(self, muts: Sequence[mutlib.Mutation]) -> None:
        if not muts:
            return
        L = len(self.tpl)
        mtp = mutlib.target_to_query_positions(muts, L)
        self.tpl = mutlib.apply_mutations(self.tpl, muts)
        self._tstarts = mtp[np.clip(self._tstarts, 0, L)].astype(np.int32)
        self._tends = mtp[np.clip(self._tends, 0, L)].astype(np.int32)
        self._rebuild(first=False)
