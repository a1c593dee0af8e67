"""Traffic driver `batch_cli_ragged`: `batch_cli` on files that hold a slice
of a whole cell, with pass counts dealt from a read-length tail and the
yield of the reader's gates held to the generated truth.

Everything is `batch_cli`'s but three things.

*The making of a file.*  Where the library's `passes` is `lognormal`
(median m, sigma s, min lo, max hi), ZMW i of a file's deck of n holds
`int(clip(exp(ln m + s * Phi^-1((i + 0.5) / n)), lo, hi))` passes: the
deck is the distribution's own quantiles, shuffled from the seed over the
whole file, so every file of every seed holds the same reads (at n = 256,
median 4.9, sigma 0.7, 1..30: 1,468 reads, 62 ZMWs under 3 passes, 21
over 12, one at 30) and only their order and what they read differ.
Independent draws moved a file's reads by 10 % from seed to seed
(`batch_cli_dealt`).  A ZMW's template, SNR (drawn, a channel at a time,
across the gate) and reads are `harness/simulate.py`'s, from its own
generator.

*A question to the program before anything is made* (`setup`): the
program must name its read-lane ladder (`pbccs_tpu.parallel.batch.
lane_step`, PR 46).  A tree without it re-pins its lanes as the file goes
on, 20 -> 24 -> 28 -> 32, and polishes a file's last chunk at a Z of its
own: each a family of programs loaded inside the run, so a run does not
end inside the driver's limit.  Such a tree fails here, in seconds, with
no result line.

*The yield* (after set-up's invocations and after every window, outside
every clock): each invocation's report is held to a plain function of
what was generated.  A channel under `gates.minSnr` (as the float32 the
BAM carries) is `Below SNR threshold`, that many exactly; of the others,
fewer reads than `gates.minPasses` is `Not enough full passes`.  Those the
reader lets through polish, and polish may drop reads of a ZMW at the
mating and z-score gates: a ZMW of 3 reads that loses one, or of 4 that
loses two, falls to `Not enough full passes` too (ISSUE 46 allowed the
3-read ones only; the serial per-ZMW path, off the chip, fails the same 9
of 69 such ZMWs that one file lost on the chip, 5 of them of 4 reads:
PERF.md, section 6).
That count is held between what the reader's gates give and that plus
`MAX_FEW_AFTER`, never more than the file holds of such ZMWs.  No ZMW may
be without subreads, too short or `Exception thrown`; at most
`MAX_UNPOLISHED` a file may end `did not converge`, `below minimum
predicted accuracy` or `Too many unusable subreads` (polish outcomes no
function of the generated truth gives); every other ZMW is Success, and
the categories sum to the file.  Both allowances are twice the most a
file has read on the chip.  The line of each file names the ZMWs let
through that are not in the BAM, with their read counts, so that a
witness can be run on them off the chip.  Where a report differs, the
run fails with no result line (under a `--control` it is said, and the
run goes on: the control is there to be read by the limits).

Traffic parameters: `batch_cli`'s.
"""

from __future__ import annotations

import math
import os
import statistics

import numpy as np

from harness import bam, manifest, simulate
from harness.common import need, say

batch_cli = manifest.load_by_path("drivers", "batch_cli")

SNR_GATED = "Failed -- Below SNR threshold"
TOO_FEW = "Failed -- Not enough full passes"
UNPOLISHED = ("Failed -- CCS did not converge",
              "Failed -- CCS below minimum predicted accuracy",
              "Failed -- Too many unusable subreads")
FORBIDDEN = ("Failed -- No usable subreads", "Failed -- Insert size too small",
             "Failed -- Exception thrown")
# Allowances of the yield check, each twice the most one file has read on
# the chip (PR 46: 35 files of call A, the files of calls C and D).
# ZMWs of 3 or 4 reads that polish fails for too few passes: 0-9 a file
MAX_FEW_AFTER = 18
# ZMWs that end not converged, under the accuracy gate or with too many
# unusable reads: 0-4 a file, nearly all `did not converge`
MAX_UNPOLISHED = 8


def dealt_passes(seed: int, index: int, n: int, spec: dict) -> list[int]:
    """The pass count of each of file `index`'s n ZMWs, in file order."""
    need(spec.get("dist") == "lognormal",
         f"batch_cli_ragged deals a lognormal pass count, not {spec!r}")
    inv = statistics.NormalDist().inv_cdf
    deck = np.array([int(min(max(math.exp(
        math.log(spec["median"]) + spec["sigma"] * inv((i + 0.5) / n)),
        spec["min"]), spec["max"])) for i in range(n)])
    np.random.default_rng([seed, 0xDEA1, index]).shuffle(deck)
    return [int(k) for k in deck]


def plain_yield(zmws, gates: dict) -> dict:
    """What the reader's gates make of the generated ZMWs: the count under
    the SNR gate, the count of the others with too few reads, and of those
    let through how many hold so few reads that two dropped in polish
    leave under minPasses (`few_after`)."""
    out = {"snr": 0, "few": 0, "few_after": 0}
    for z in zmws:
        n = len(z["reads"])
        if float(np.float32(z["snr"]).min()) < gates["minSnr"]:
            out["snr"] += 1
        elif n < gates["minPasses"]:
            out["few"] += 1
        elif n - 2 < gates["minPasses"]:
            out["few_after"] += 1
    return out


class Session(batch_cli.Session):
    def __init__(self, ctx):
        super().__init__(ctx)
        self.truth_of: dict[str, dict] = {}     # input path -> hole -> ZMW
        self.invoked: list[tuple[str, str]] = []    # (input, output), unchecked

    def setup(self) -> dict:
        from pbccs_tpu.parallel import batch

        need(hasattr(batch, "lane_step"),
             "this tree's shape menu names no lane ladder "
             "(pbccs_tpu.parallel.batch.lane_step): it would re-pin its read "
             "lanes and load programs all through a ragged file")
        facts = super().setup()
        self._hold_yields()
        return facts

    def _make_file(self, seed: int, index: int) -> tuple[str, dict]:
        library = self.ctx.library
        deck = dealt_passes(seed, index, self.n, library["passes"])
        zmws = [simulate.make_zmw(seed, 0, index * self.n + i,
                                  dict(library, passes={"dist": "fixed", "value": k}))
                for i, k in enumerate(deck)]
        path = os.path.join(self.ctx.work, f"s{seed}_f{index}.subreads.bam")
        bam.write_subread_bam(path, zmws)
        self.truth_of[path] = {z["hole"]: z for z in zmws}
        return path, self.truth_of[path]

    def _invoke(self, path: str, tag: str, trace_out):
        wall, out = super()._invoke(path, tag, trace_out)
        self.invoked.append((path, out))
        return wall, out

    def window(self, seed: int, seconds: float, trace: bool):
        win = super().window(seed, seconds, trace)
        self._hold_yields()
        return win

    def repeat_check(self) -> bool:
        same = super().repeat_check()
        self._hold_yields()
        return same

    def _hold_yields(self) -> None:
        gates = self.ctx.cell.config["gates"]
        for path, out in self.invoked:
            with open(out + ".csv") as f:
                rows = {r[0]: int(r[1]) for r in
                        (line.strip().split(",") for line in f) if len(r) == 3}
            truth = self.truth_of[path]
            want = plain_yield(truth.values(), gates)
            snr, few = rows.get(SNR_GATED, 0), rows.get(TOO_FEW, 0)
            unpolished = sum(rows.get(k, 0) for k in UNPOLISHED)
            good = rows.get(batch_cli.REPORT_SUCCESS, 0)
            few_after = min(want["few_after"], MAX_FEW_AFTER)
            ok = (snr == want["snr"]
                  and want["few"] <= few <= want["few"] + few_after
                  and unpolished <= MAX_UNPOLISHED
                  and not any(rows.get(k, 0) for k in FORBIDDEN)
                  and good == len(truth) - snr - few - unpolished
                  and sum(rows.values()) == len(truth))
            in_bam = {int(r["tags"]["zm"]) for r in bam.read_bam(out)}
            fell = {hole: len(z["reads"]) for hole, z in truth.items()
                    if hole not in in_bam
                    and len(z["reads"]) >= gates["minPasses"]
                    and float(np.float32(z["snr"]).min()) >= gates["minSnr"]}
            say(f"yield: {os.path.basename(out)}: report {snr} under the SNR gate, "
                f"{few} with too few passes, {unpolished} not converged, under the "
                f"accuracy gate or with too many unusable reads, {good} Success of "
                f"{sum(rows.values())}; generated {want['snr']}, {want['few']} "
                f"(+ up to {few_after} of 3-4 reads) of {len(truth)}: "
                f"{'equal' if ok else 'DIFFERENT'}; let through and not in the "
                f"BAM, hole:reads {' '.join(f'{h}:{n}' for h, n in sorted(fell.items()))}")
            need(ok or self.ctx.control,
                 f"{out}.csv: the yield report is not the plain function of "
                 f"the generated ZMWs: {rows}")
        del self.invoked[:]
