"""Traffic driver `batch_cli_dealt`: `batch_cli` on files whose pass counts
are dealt, not drawn.

Everything is `batch_cli`'s but the making of a file: where the library's
`passes` is `uniform_int` lo..hi, a file of n ZMWs holds exactly
n / (hi - lo + 1) ZMWs at each pass count, in an order shuffled from the
seed over the whole file (a whole file, never a constant of the program
such as its 64-ZMW chunks).  Every file of every seed then holds the same
number of reads: with independent draws two seeds differed by 10 % in
reads a file, and `zmws_per_s` spread by more than a cell's bound allows
(PERF.md, section 7 of PR 26).  A ZMW's template, SNR and reads are
`harness/simulate.py`'s, from its own generator, as in `batch_cli`.

Traffic parameters: `batch_cli`'s.
"""

from __future__ import annotations

import os

import numpy as np

from harness import bam, manifest, simulate
from harness.common import need

batch_cli = manifest.load_by_path("drivers", "batch_cli")


def dealt_passes(seed: int, index: int, n: int, spec: dict) -> list[int]:
    """The pass count of each of file `index`'s n ZMWs, in file order."""
    need(spec.get("dist") == "uniform_int",
         f"batch_cli_dealt deals a uniform_int pass count, not {spec!r}")
    counts = np.arange(spec["lo"], spec["hi"] + 1)
    need(n % len(counts) == 0,
         f"a file of {n} ZMWs cannot hold as many at each of {len(counts)} pass counts")
    deck = np.repeat(counts, n // len(counts))
    np.random.default_rng([seed, 0xDEA1, index]).shuffle(deck)
    return [int(k) for k in deck]


class Session(batch_cli.Session):
    def _make_file(self, seed: int, index: int) -> tuple[str, dict]:
        library = self.ctx.library
        deck = dealt_passes(seed, index, self.n, library["passes"])
        zmws = [simulate.make_zmw(seed, 0, index * self.n + i,
                                  dict(library, passes={"dist": "fixed", "value": k}))
                for i, k in enumerate(deck)]
        path = os.path.join(self.ctx.work, f"s{seed}_f{index}.subreads.bam")
        bam.write_subread_bam(path, zmws)
        return path, {z["hole"]: z for z in zmws}
