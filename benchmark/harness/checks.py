"""The comparison that decides `correct`.

Three kinds of evidence, all taken after the window from what the timed
path produced, none from the program's own checks:

* the truth: every Success consensus against the template its reads were
  simulated from (either strand).  Each one is held to the rule the
  configuration states, 2 + ceil(2 (1 - pq) L) edits
  (`zmws_over_allowed_edits`, which one wrong ZMW fails); the edits of the
  whole window, a 1,000 ZMWs, are held to about three times what sound
  runs read (`edits_per_1000_zmws`: a fault that costs a few ZMWs a base
  each stays inside the rule and fails this); and one QV a base;
* the yield: no ZMW lost, failed with an exception, quarantined or served
  as a draft; at least `min_success_share` of them Success;
* the plain reference (`reference/arrow_ref.py`, float64, brute force) on
  a sample of the window's ZMWs drawn from the seed, the longest and the
  two farthest from their templates among them: no single-base mutation at sampled positions improves the served
  consensus (`best_mutation_nats`), single edits toward the true template
  do not either (`toward_truth_gain_nats_max` and `_median`: where the
  consensus differs from the truth, it does so because the reads say so),
  and the served QV at the sampled positions is the reference's (`qv_gap`).

The limits are the configuration's (`check.limits`, each set from chip
readings that PERF.md lists); a number the configuration gives no limit
is printed and not held.  Every number is printed beside its limit, in
every run.
"""

from __future__ import annotations

import concurrent.futures
import math
import multiprocessing
import os
import time

import numpy as np

from . import p6c4

END_MARGIN = 10     # bases at either end left out of the count of edits
REF_MARGIN = 25     # bases at either end where the reference is not asked


def edit_distance(a: str, b: str, anywhere: bool = False) -> int:
    """Levenshtein distance of `a` to `b`, Myers' bit-vector recurrence on
    Python ints.  With `anywhere`, the least distance of `a` to any
    substring of `b` (what lies before and after it in `b` is free)."""
    if not a:
        return 0 if anywhere else len(b)
    peq: dict[str, int] = {}
    for i, ch in enumerate(a):
        peq[ch] = peq.get(ch, 0) | (1 << i)
    m = len(a)
    mask, high = (1 << m) - 1, 1 << (m - 1)
    pv, mv, score = mask, 0, m
    best = m
    carry = 0 if anywhere else 1
    for ch in b:
        eq = peq.get(ch, 0)
        xv = eq | mv
        xh = (((eq & pv) + pv) ^ pv) | eq
        ph = mv | ~(xh | pv)
        mh = pv & xh
        if ph & high:
            score += 1
        elif mh & high:
            score -= 1
        ph = ((ph << 1) | carry) & mask
        mh = (mh << 1) & mask
        pv = (mh | ~(xv | ph)) & mask
        mv = ph & xv
        best = min(best, score)
    return best if anywhere else score


def allowed_edits(pred_acc: float, length: int) -> int:
    """Twice the expected error count (1 - pq) L, plus 2: at <= 8 passes a
    1-2 bp residual is consistent with pq ~ 0.99 (`chip_smoke.py`'s rule)."""
    return 2 + math.ceil(round(2.0 * (1.0 - pred_acc) * length, 6))


class Verdict:
    """Collects each number compared, its limit and whether it held."""

    def __init__(self):
        self.rows: list[tuple] = []

    def hold(self, name: str, value, op: str, limit) -> bool:
        ok = {"<=": value <= limit, ">=": value >= limit,
              "==": value == limit}[op]
        self.rows.append((name, value, op, limit, bool(ok)))
        return bool(ok)

    def hold_limit(self, name: str, value, limits: dict) -> None:
        """Hold `value` under the configuration's limit of that name; a
        number without one is printed and not held."""
        if name in limits:
            self.hold(name, value, "<=", limits[name])
        else:
            self.rows.append((name, value, None, None, True))

    @property
    def correct(self) -> bool:
        held = [r for r in self.rows if r[2]]
        return bool(held) and all(r[4] for r in held)

    def print(self) -> None:
        for name, value, op, limit, ok in self.rows:
            v = f"{value:.6g}" if isinstance(value, float) else str(value)
            print(f"check: {name} = {v}   " + (
                f"limit {op} {limit}   {'ok' if ok else 'FAILED'}" if op else
                "not held: this configuration gives it no limit"), flush=True)


def orient(seq: str, template: np.ndarray, bound: int) -> tuple[int, int]:
    """(edits, strand) of a consensus's interior against its template; the
    reverse strand is tried only when the forward one is over `bound`.
    The interior is the consensus without END_MARGIN bases at either end,
    placed anywhere in the template: the ends are pinned to a match and
    refinement cannot move them, so an end base the draft got wrong stays
    (3.5 % of 500 bp x 30 pass ZMWs carry one, and nothing else)."""
    inner = seq[END_MARGIN: len(seq) - END_MARGIN]
    fwd = edit_distance(inner, p6c4.decode(template), anywhere=True)
    if fwd <= bound:
        return fwd, 0
    rev = edit_distance(inner, p6c4.decode(p6c4.revcomp(template)), anywhere=True)
    return (rev, 1) if rev < fwd else (fwd, 0)


def _reference_job(job: dict) -> dict:
    from reference import arrow_ref

    tpl = job["template"]
    cons = p6c4.encode(job["seq"])
    flip = job["strand"]
    truth = p6c4.revcomp(tpl) if flip else tpl
    # the reference scores every read over the whole template; the program
    # scores it over the window its draft alignment gave it, which can stop
    # short of an end the draft got wrong.  Near the ends the two models
    # differ (500 bp, seed 2147483801, hole 1934: two inserted bases 7 and 13
    # from the end stay, on the chip and on the CPU alike, where the
    # reference reads 49.7 nats for removing one): sites and positions keep
    # REF_MARGIN away
    steps = [m for m in arrow_ref.edits_between(cons, truth)
             if REF_MARGIN <= m[1] < len(cons) - REF_MARGIN]
    rng = np.random.default_rng(job["pick"])
    if len(steps) > job["sites"]:
        steps = [steps[i] for i in sorted(rng.choice(len(steps), job["sites"],
                                                     replace=False))]
    res = arrow_ref.check_zmw(
        job["reads"], [(k % 2) ^ flip for k in range(len(job["reads"]))],
        job["snr"], cons, truth, job["positions"], job["half_band"], steps)
    served = [ord(job["qual"][p]) - 33 for p in job["positions"]]
    ref = [min(arrow_ref.QV_MAX, int(res["qv"][p])) for p in job["positions"]]
    return {"hole": job["hole"], "ll_deficit": res["ll_deficit"],
            "best_mutation": res["best_mutation"], "sites": len(steps),
            "toward_truth": res["toward_truth"], "length": len(cons),
            "qv_served": served,
            "qv_reference": ref,
            "qv_gap": max(abs(a - b) for a, b in zip(served, ref))}


def _reference_jobs(good: list, zmws: dict, strand_of: dict, edits_of: dict,
                    spec: dict, seed: int) -> list:
    """The reference's sample: Success ZMWs drawn from the seed, each with
    its sampled positions.  The one with the most read bases is always
    among them, and so are the two farthest from their templates: where a
    consensus differs from the truth, the reference says whether the reads
    support it."""
    rng = np.random.default_rng([seed, 0xC0FFEE])
    # ZMWs whose every read was used, so that the reference scores the
    # reads the program did (whatever flags the answer carries)
    full = sorted((r for r in good
                   if r["passes"] == len(zmws[r["hole"]]["reads"])),
                  key=lambda r: r["hole"]) or sorted(good, key=lambda r: r["hole"])
    n = min(spec["sample_zmws"], len(full))
    sample = []
    if n:
        longest = max(full, key=lambda r: sum(map(len, zmws[r["hole"]]["reads"])))
        far = sorted((r for r in full if edits_of[r["hole"]] and r is not longest),
                     key=lambda r: -edits_of[r["hole"]])[:min(2, n - 1)]
        sample = [longest] + far
        rest = [r for r in full if all(r is not s for s in sample)]
        sample += [rest[i] for i in rng.choice(
            len(rest), min(n - len(sample), len(rest)), replace=False)]
    jobs = []
    for r in sample:
        z = zmws[r["hole"]]
        k = min(spec["positions_per_zmw"], len(r["seq"]) - 2 * REF_MARGIN)
        jobs.append({"hole": r["hole"], "seq": r["seq"], "qual": r["qual"],
                     "template": z["template"], "reads": z["reads"],
                     "snr": z["snr"], "strand": strand_of[r["hole"]],
                     "half_band": spec["half_band"],
                     "sites": spec["sites_per_zmw"], "pick": [seed, r["hole"]],
                     "positions": sorted(int(p) for p in rng.choice(
                         np.arange(REF_MARGIN, len(r["seq"]) - REF_MARGIN), k, replace=False))})
    return jobs


def check_results(results: list[dict], zmws: dict, attempted: int,
                  spec: dict, seed: int, verdict: Verdict) -> int:
    """Hold what the window produced to the truth, the yield and the plain
    reference.  `results` has one entry for each ZMW answered: hole,
    status, and for a Success seq, qual, pq, passes, degraded.  `zmws` maps
    hole -> the generated ZMW.  Returns the number of failed ZMWs."""
    t0 = time.monotonic()
    holes = [r["hole"] for r in results]
    verdict.hold("answers_for_unknown_or_repeated_zmws",
                 len(holes) - len(set(holes) & set(zmws)), "==", 0)
    unanswered = attempted - len(set(holes))
    good = [r for r in results if r["status"] == "Success"]
    degraded = [r for r in good if r.get("degraded")]
    other = [r for r in results if r["status"] in ("Other", "error")]
    failed = unanswered + len(degraded) + len(other)
    verdict.hold("zmws_failed_lost_or_degraded", failed, "==", 0)
    verdict.hold("success_share", len(good) / max(attempted, 1), ">=",
                 spec["min_success_share"])

    lim = spec["limits"]
    worst_over, n_over, n_exact, qv_len_bad, all_edits = -10 ** 9, 0, 0, 0, 0
    strand_of, edits_of = {}, {}
    for r in good:
        tpl = zmws[r["hole"]]["template"]
        bound = allowed_edits(r["pq"], len(tpl))
        edits, strand_of[r["hole"]] = orient(r["seq"], tpl, bound)
        edits_of[r["hole"]] = edits
        worst_over = max(worst_over, edits - bound)
        n_over += edits > bound
        all_edits += edits
        n_exact += edits == 0
        qv_len_bad += len(r["qual"]) != len(r["seq"])
    # the configuration's own rule, for every ZMW; then the window's edits
    # as a whole, which sound runs hardly move and a fault spread thinly
    # over the ZMWs does
    verdict.hold_limit("zmws_over_allowed_edits", n_over, lim)
    verdict.hold_limit("edits_per_1000_zmws", 1000.0 * all_edits / max(len(good), 1), lim)
    verdict.hold("qv_strings_of_wrong_length", qv_len_bad, "==", 0)

    jobs = _reference_jobs(good, zmws, strand_of, edits_of, spec, seed)
    if jobs:
        workers = max(1, min(len(jobs), (os.cpu_count() or 2) - 1))
        with concurrent.futures.ProcessPoolExecutor(
                workers, mp_context=multiprocessing.get_context("spawn")) as pool:
            refs = list(pool.map(_reference_job, jobs))
    else:
        refs = []
    verdict.hold("reference_sample_zmws", len(refs), ">=", min(spec["sample_zmws"], 1))
    if refs:
        # over every sampled site: the largest gain is the one a half-done
        # refinement leaves behind; the middle one is steadier where the
        # largest swings (printed where the configuration does not hold it)
        gains = sorted(g for x in refs for _m, g in x["toward_truth"])
        verdict.hold_limit("toward_truth_gain_nats_max", gains[-1] if gains else 0.0, lim)
        verdict.hold_limit("toward_truth_gain_nats_median",
                           gains[len(gains) // 2] if gains else 0.0, lim)
        verdict.hold_limit("best_mutation_nats_max",
                           max(x["best_mutation"] for x in refs), lim)
        verdict.hold_limit("qv_gap_max", max(x["qv_gap"] for x in refs), lim)
    print(f"check: {all_edits} edits in {len(good)} Success ZMWs; worst ZMW "
          f"{worst_over if good else 0} edits over 2 + ceil(2 (1 - pq) L)", flush=True)
    print(f"check: {len(good)} Success of {attempted} attempted, {n_exact} exact, "
          f"{unanswered} unanswered, {len(other)} Other, {len(degraded)} degraded; "
          f"reference on {len(refs)} ZMWs x {spec['positions_per_zmw']} positions "
          f"(holes {[x['hole'] for x in refs]}), whole check "
          f"{time.monotonic() - t0:.1f} s", flush=True)
    for x in refs:
        step, gain = max(x["toward_truth"], key=lambda t: t[1], default=(None, 0.0))
        print(f"check:   hole {x['hole']}: ll_deficit {x['ll_deficit']:.4f} "
              f"best_mutation {x['best_mutation']:.4f} toward_truth_gain "
              f"{gain:.4f} over {x['sites']} "
              f"sites (best {step} of {x['length']}), qv served {x['qv_served']} "
              f"reference {x['qv_reference']}", flush=True)
    return failed
