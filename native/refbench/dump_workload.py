#!/usr/bin/env python
"""Dump a polish workload in the text format refbench.cpp consumes.

Draws pbccs_tpu.simulate.build_tasks from seed 20260729, so the
reference C++ measures the ZMWs a same-seed run of this framework
polishes.

Usage: python native/refbench/dump_workload.py [OUT.txt]
The workload is 128 ZMWs x 300 bp x 8 passes with 2 corruptions a draft.
Env knobs: REFBENCH_ITERS (default 10), REFBENCH_MIN_ZSCORE (default -5,
the reference CLI default) and REFBENCH_DRAW.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def main() -> None:
    import numpy as np

    from pbccs_tpu.models.arrow.params import decode_bases
    from pbccs_tpu.simulate import build_tasks, parse_passes

    n_zmws, tpl_len, n_passes, n_corr = 128, 300, "8", 2
    iters = int(os.environ.get("REFBENCH_ITERS", 10))
    min_z = float(os.environ.get("REFBENCH_MIN_ZSCORE", -5.0))

    out_path = sys.argv[1] if len(sys.argv) > 1 else "workload.txt"

    rng = np.random.default_rng(20260729)
    tasks, _truths = build_tasks(rng, n_zmws, tpl_len, n_passes, n_corr)
    # REFBENCH_DRAW=k dumps the k-th draw of the stream (default 1):
    # throughput is draw-invariant, accuracy is not (docs/ACCURACY.md)
    for _ in range(int(os.environ.get("REFBENCH_DRAW", 1)) - 1):
        tasks, _truths = build_tasks(rng, n_zmws, tpl_len, n_passes, n_corr)

    with open(out_path, "w") as f:
        # the CONFIG passes field is informational (per-ZMW read counts
        # ride the ZMW lines); write the range's low end as the int the
        # C++ parser expects
        f.write(f"CONFIG {n_zmws} {tpl_len} {parse_passes(n_passes)[0]} "
                f"{iters} {min_z}\n")
        for t in tasks:
            f.write(f"ZMW {t.id.replace(' ', '_')} "
                    f"{t.snr[0]} {t.snr[1]} {t.snr[2]} {t.snr[3]} "
                    f"{len(t.reads)}\n")
            f.write(f"DRAFT {decode_bases(t.tpl)}\n")
            for read, strand in zip(t.reads, t.strands):
                f.write(f"READ {strand} {decode_bases(read)}\n")
    print(f"wrote {out_path}: {n_zmws} ZMWs x L{tpl_len} x P{n_passes}",
          file=sys.stderr)


if __name__ == "__main__":
    main()
