"""Plain float64 reference of Arrow consensus scoring for P6-C4.

What a configuration of this benchmark promises is an Arrow consensus:
a template that no single-base mutation makes more likely given the
ZMW's subreads, with per-base QVs from the likelihood ratios of those
mutations (ConsensusCore: Arrow/SimpleRecursor.cpp FillAlpha,
Consensus-inl.hpp ConsensusQVs).  This file states that directly:

* `loglik_scalar`: the pair-HMM forward recursion cell by cell, as
  written down in ConsensusCore (both ends pinned to a match, moves
  leaving template position k governed by its dinucleotide context).
* `loglik_batch`: the same recursion, the same float64, evaluated along
  anti-diagonals for many (read, template) pairs at once, inside a band
  of `2 * half + 1` cells a diagonal around the line from corner to
  corner.  The band only bounds the work: at half = 64 it is 2.7 times
  the 96 rows the program keeps at 2 kb, and the tests hold it to the
  scalar recursion.
* mutation scores by brute force: the likelihood of every mutated
  template is computed whole, with no alpha/beta link, no incremental
  update and no float32.

Nothing here comes from the program: the constants are `harness/p6c4.py`.
"""

from __future__ import annotations

import math

import numpy as np
from numpy.lib.stride_tricks import sliding_window_view

from harness import p6c4

EM_HIT = 1.0 - p6c4.PR_MISCALL
EM_MISS = p6c4.PR_MISCALL / 3.0
RESCALE_EVERY = 8
QV_MAX = 93


def loglik_scalar(read: np.ndarray, tpl: np.ndarray, table: np.ndarray) -> float:
    """log P(read | tpl), cell by cell (small inputs: the tests' anchor)."""
    trans = p6c4.transition_track(tpl, table)
    n_i, n_j = len(read), len(tpl)
    a = np.zeros((n_i + 1, n_j + 1))
    a[0, 0] = 1.0
    for j in range(1, n_j):
        for i in range(1, n_i):
            em = EM_HIT if read[i - 1] == tpl[j - 1] else EM_MISS
            m = a[i - 1, j - 1] * em
            if i == 1 and j == 1:
                score = m
            elif i != 1 and j != 1:
                score = m * trans[j - 2, p6c4.MATCH]
            else:
                score = 0.0
            if i > 1:
                ins = (trans[j - 1, p6c4.BRANCH] if read[i - 1] == tpl[j]
                       else trans[j - 1, p6c4.STICK] / 3.0)
                score += a[i - 1, j] * ins
            if j > 1:
                score += a[i, j - 1] * trans[j - 2, p6c4.DARK]
            a[i, j] = score
    last = EM_HIT if read[-1] == tpl[-1] else EM_MISS
    with np.errstate(divide="ignore"):
        return float(np.log(a[n_i - 1, n_j - 1] * last))


def _windows(arr: np.ndarray, width: int):
    return sliding_window_view(arr, width, axis=1)


def loglik_batch(reads: list, tpls: list, table: np.ndarray,
                 half: int = 64) -> np.ndarray:
    """log P(reads[b] | tpls[b]) for every pair b, float64."""
    n_b = len(reads)
    width = 2 * half + 1
    len_i = np.array([len(r) for r in reads])
    len_j = np.array([len(t) for t in tpls])
    pad = width + 4
    top = int(len_j.max()) + 1           # reversed template index: x = top - j

    def by_j(fill):
        return np.full((n_b, top + 1 + 2 * pad), fill, np.float64)

    rd = np.full((n_b, int(len_i.max()) + 2 + 2 * pad), -1, np.int8)
    t_cur, t_next = (np.full((n_b, top + 1 + 2 * pad), -2, np.int8) for _ in range(2))
    m_prev, d_prev, b_cur, s_cur = by_j(0.0), by_j(0.0), by_j(0.0), by_j(0.0)
    for b, (r, t) in enumerate(zip(reads, tpls)):
        tr = p6c4.transition_track(t, table)
        n_j = len(t)
        rd[b, pad + 1: pad + 1 + len(r)] = r             # rd[i] = read[i-1]
        j = np.arange(1, n_j)                            # columns 1..J-1
        x = pad + top - j
        t_cur[b, x] = t[j - 1]
        t_next[b, x] = t[j]
        b_cur[b, x] = tr[j - 1, p6c4.BRANCH]
        s_cur[b, x] = tr[j - 1, p6c4.STICK] / 3.0
        m_prev[b, x[1:]] = tr[j[1:] - 2, p6c4.MATCH]
        d_prev[b, x[1:]] = tr[j[1:] - 2, p6c4.DARK]
        m_prev[b, x[0]] = 1.0                            # the pinned first match
    w_rd, w_tc, w_tn = _windows(rd, width), _windows(t_cur, width), _windows(t_next, width)
    w_m, w_d, w_b, w_s = (_windows(a, width) for a in (m_prev, d_prev, b_cur, s_cur))

    slope = (len_i - len_j) / (len_i + len_j)
    rows = np.arange(n_b)
    offs = np.arange(width)
    end_d = len_i + len_j - 2

    def centre(d):
        return (d + np.rint(slope * d).astype(np.int64)) // 2

    # diagonal d holds cells (i, d - i) for i = centre(d) - half + w
    c2, c1 = centre(0), centre(1)
    prev2 = np.zeros((n_b, width + 4))
    prev1 = np.zeros((n_b, width + 4))
    prev2[rows, 2 + half - c2] = 1.0                     # cell (0, 0)
    log_scale = np.zeros(n_b)
    out = np.full(n_b, -np.inf)
    for d in range(2, int(end_d.max()) + 1):
        c = centre(d)
        lo = c - half                                    # i of window slot 0
        i = lo[:, None] + offs
        j = d - i
        ok = (i >= 1) & (i <= len_i[:, None] - 1) & (j >= 1) & (j <= len_j[:, None] - 1)
        # window starts; one clipped at an array's end holds no valid cell
        xs = np.clip(pad + top - d + lo, 0, w_tc.shape[1] - 1)
        r = w_rd[rows, np.clip(pad + lo, 0, w_rd.shape[1] - 1)]
        em = np.where(r == w_tc[rows, xs], EM_HIT, EM_MISS)
        ins = np.where(r == w_tn[rows, xs], w_b[rows, xs], w_s[rows, xs])
        w1, w2 = _windows(prev1, width), _windows(prev2, width)
        s1, s2 = c - c1, c - c2
        cur = (w2[rows, 2 + s2 - 1] * em * w_m[rows, xs]
               + w1[rows, 2 + s1 - 1] * ins
               + w1[rows, 2 + s1] * w_d[rows, xs])
        cur = np.where(ok, cur, 0.0)
        done = np.nonzero(end_d == d)[0]
        if len(done):
            cell = cur[done, len_i[done] - 1 - lo[done]]
            with np.errstate(divide="ignore"):
                out[done] = np.log(cell) + log_scale[done]
        nxt = np.zeros_like(prev1)
        nxt[:, 2:-2] = cur
        if d % RESCALE_EVERY == 0:
            m = cur.max(axis=1)
            m[m <= 0] = 1.0
            nxt /= m[:, None]
            prev1 = prev1 / m[:, None]
            log_scale += np.log(m)
        prev2, prev1, c2, c1 = prev1, nxt, c1, c
    last = np.array([EM_HIT if r[-1] == t[-1] else EM_MISS
                     for r, t in zip(reads, tpls)])
    return out + np.log(last)


def unique_mutations(tpl: np.ndarray, pos: int) -> list[tuple]:
    """The single-base mutations that start at `pos`, without the ones a
    homopolymer makes equivalent (ConsensusCore's unique enumerator):
    substitutions, insertions before `pos`, the deletion of `pos`."""
    prev = tpl[pos - 1] if pos > 0 else -1
    muts = [("sub", pos, b) for b in range(4) if b != tpl[pos]]
    muts += [("ins", pos, b) for b in range(4) if b != prev]
    if tpl[pos] != prev:
        muts.append(("del", pos, -1))
    return muts


def mutate(tpl: np.ndarray, mut: tuple) -> np.ndarray:
    kind, pos, base = mut
    if kind == "sub":
        out = tpl.copy()
        out[pos] = base
        return out
    if kind == "ins":
        return np.concatenate([tpl[:pos], [base], tpl[pos:]]).astype(np.int8)
    return np.concatenate([tpl[:pos], tpl[pos + 1:]]).astype(np.int8)


def qv_of(scores: np.ndarray) -> float:
    """QV of one position from the scores (log-likelihood ratios) of the
    mutations that start there: -10 log10(S / (1 + S)), S the sum of
    exp(score) over the mutations that lower the likelihood."""
    neg = scores[scores < 0.0]
    if not len(neg):
        return float(QV_MAX)
    log_s = float(np.logaddexp.reduce(neg))
    return -10.0 * (log_s - np.logaddexp(0.0, log_s)) / math.log(10.0)


def score_templates(reads: list, strands: list[int], tpls: list,
                    table: np.ndarray, half: int = 64) -> np.ndarray:
    """Summed log-likelihood of every template over all reads; a read of
    strand 1 is scored against the template's reverse complement."""
    pair_r, pair_t = [], []
    for t in tpls:
        rc = p6c4.revcomp(t)
        for r, s in zip(reads, strands):
            pair_r.append(r)
            pair_t.append(rc if s else t)
    ll = loglik_batch(pair_r, pair_t, table, half)
    return ll.reshape(len(tpls), len(reads)).sum(axis=1)


def edits_between(a: np.ndarray, b: np.ndarray) -> list[tuple]:
    """Single-base edits that turn `a` into `b` along one optimal
    alignment, each as a mutation of `a` alone: ("sub", p, base),
    ("del", p, -1) or ("ins", p, base) (insert before position p)."""
    n, m = len(a), len(b)
    d = np.zeros((n + 1, m + 1), np.int32)
    d[0] = np.arange(m + 1)
    idx = np.arange(m + 1)
    for i in range(1, n + 1):
        cur = np.empty(m + 1, np.int32)
        cur[0] = i
        cur[1:] = np.minimum(d[i - 1, :-1] + (b != a[i - 1]), d[i - 1, 1:] + 1)
        d[i] = np.minimum.accumulate(cur - idx) + idx
    out, i, j = [], n, m
    while i > 0 or j > 0:
        if i > 0 and j > 0 and d[i, j] == d[i - 1, j - 1] + (a[i - 1] != b[j - 1]):
            if a[i - 1] != b[j - 1]:
                out.append(("sub", i - 1, int(b[j - 1])))
            i, j = i - 1, j - 1
        elif i > 0 and d[i, j] == d[i - 1, j] + 1:
            out.append(("del", i - 1, -1))
            i -= 1
        else:
            out.append(("ins", i, int(b[j - 1])))
            j -= 1
    return out[::-1]


def check_zmw(reads: list, strands: list[int], snr, consensus: np.ndarray,
              truth: np.ndarray, positions: list[int], half: int = 64,
              toward_truth: list | None = None) -> dict:
    """What the reference says of one served consensus.

    `strands` are relative to `consensus`; `truth` is the simulated
    template in the consensus's orientation.  Returns the likelihood
    deficit against the truth (nats; <= 0 where the consensus is at least
    as likely as the template the reads came from), the reference QV at
    each sampled position and the best score any sampled mutation reaches
    (a converged consensus has none above 0).  `toward_truth` are single
    edits of the consensus taken from its alignment to the truth, returned
    each with what it gains (a consensus that refinement left early gains
    much from each)."""
    table = p6c4.transition_table(snr)
    muts = [m for p in positions for m in unique_mutations(consensus, p)]
    steps = list(toward_truth or [])
    tpls = ([consensus, truth] + [mutate(consensus, m) for m in muts]
            + [mutate(consensus, m) for m in steps])
    ll = score_templates(reads, strands, tpls, table, half)
    scores = ll[2:2 + len(muts)] - ll[0]
    gains = ll[2 + len(muts):] - ll[0]
    at = np.array([m[1] for m in muts])
    return {"ll_deficit": float(ll[1] - ll[0]),
            "qv": {int(p): qv_of(scores[at == p]) for p in positions},
            "best_mutation": float(scores.max()) if len(scores) else 0.0,
            "toward_truth": [(m, float(g)) for m, g in zip(steps, gains)]}
