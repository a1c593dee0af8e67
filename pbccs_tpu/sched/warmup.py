"""`ccs warmup`: precompile the polish-program menu for declared buckets.

The first polish of a bucket shape pays the XLA compile; a serving engine or a
production batch run that knows its workload geometry can pay it BEFORE
traffic instead of inside it.  Each `--bucket ZxPASSESxLEN` entry names a
compiled-shape bucket by workload geometry -- Z ZMWs per batch, PASSES
subreads per ZMW, LEN-base templates -- and warmup drives one synthetic
batch of exactly that geometry through the full polish surface
(BatchPolisher setup + refine + QV sweep + the straggler-continuation
and wide-band-retry shapes: BatchPolisher.warm_shape_set), populating
the in-process executable cache and the persistent compilation cache
(runtime/cache.py) that later processes load from.

By default each bucket warms on ONE device (the persistent cache serves
the other devices' compiles as disk hits); `--allDevices` compiles on
every visible device for fleets whose per-device executable caches must
be hot before the first request.

    ccs warmup --bucket 64x8x300 --bucket 16x3x2000 --allDevices
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from pbccs_tpu.runtime.logging import Logger, LogLevel


def parse_bucket(spec: str) -> tuple[int, int, int]:
    """'ZxPASSESxLEN' -> (n_zmws, n_passes, tpl_len)."""
    parts = spec.lower().split("x")
    if len(parts) != 3:
        raise SystemExit(
            f"--bucket {spec!r}: want ZxPASSESxLEN, e.g. 64x8x300")
    try:
        z, p, length = (int(x) for x in parts)
    except ValueError:
        raise SystemExit(
            f"--bucket {spec!r}: want ZxPASSESxLEN, e.g. 64x8x300") from None
    if min(z, p, length) < 1:
        raise SystemExit(
            f"--bucket {spec!r}: want three positive ints ZxPASSESxLEN")
    return z, p, length


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ccs warmup",
        description="Precompile the polish-program menu for declared "
                    "workload buckets (kills the cold-compile latency of "
                    "the first batch/request at each shape).")
    p.add_argument("--bucket", action="append", default=None,
                   metavar="ZxPASSESxLEN",
                   help="One compiled-shape bucket by workload geometry: "
                        "Z ZMWs per batch, PASSES subreads per ZMW, "
                        "LEN-base templates.  Repeatable.  May be "
                        "omitted when --tuneProfile supplies a "
                        "warmup_buckets menu.")
    p.add_argument("--tuneProfile", default=None, metavar="PATH|auto",
                   help="ccs-tune host profile to apply (band width, "
                        "dense blocking) so the warmed executables match "
                        "what a tuned batch/serve process will request; "
                        "its warmup_buckets menu is the default --bucket "
                        "list.  'auto' scans the profiles/ directory for "
                        "a fingerprint match.  Default: "
                        "PBCCS_TUNE_PROFILE, else no profile.")
    p.add_argument("--devices", type=int, default=0,
                   help="Devices visible to the warmed fleet (0 = all; "
                        "bounds what --allDevices compiles on). "
                        "Default = %(default)s")
    p.add_argument("--allDevices", action="store_true",
                   help="Compile every bucket on every device (default: "
                        "one device; the persistent compilation cache "
                        "serves the rest as disk hits).")
    p.add_argument("--compileCache", default=None, metavar="DIR",
                   help="Persistent XLA compilation-cache directory to "
                        "populate -- point the serve fleet's "
                        "--compileCache at the same DIR so replica "
                        "(re)starts load the warmed executables from "
                        "disk (JAX_COMPILATION_CACHE_DIR, where set, "
                        "wins over this flag; default: the "
                        "checkout-local .jax_cache).")
    p.add_argument("--logLevel", default="INFO")
    return p


def _warm_one(tasks) -> dict:
    """Full polish surface at this bucket's shapes; returns the effective
    compiled shapes (what a matching production batch will reuse)."""
    from pbccs_tpu.models.arrow.refine import RefineOptions
    from pbccs_tpu.parallel.batch import BatchPolisher

    opts = RefineOptions()
    # the bucket names its Z, as the drivers polish at theirs:
    # warm_shape_set then loads the wide-band retry's program under
    # Z = 32 too
    polisher = BatchPolisher(tasks, fixed_z=True)
    polisher.refine(opts)
    polisher.consensus_qvs()
    polisher.warm_shape_set(opts)
    return {"Z": polisher._Z, "R": polisher._R,
            "Jmax": polisher._Jmax, "Imax": polisher._Imax,
            "W": polisher._W}


_SYNTH_SEED = 20260729


def synth_chunks(n_zmws: int, n_passes: int, tpl_len: int,
                 min_passes: int = 1):
    """The bucket's geometry as ZMWs at the front door (`ccs serve
    --bucket`, which drives them through draft and polish before it is
    ready): the pass counts go PASSES, the gate's least (--minPasses),
    PASSES - 1, least + 1, .. and round again, so that even a few ZMWs
    hold both ends: the most passes set the flush's R, and the draft of
    a ZMW with few passes is the longest (a 3-pass draft of a 2 kb
    insert is 2,110-2,234 bases, a 10-pass one about 2,000) and sets its
    Jmax."""
    from pbccs_tpu.pipeline import Chunk, Subread
    from pbccs_tpu.simulate import simulate_zmw

    rng = np.random.default_rng(_SYNTH_SEED)
    lo = min(max(min_passes, 1), n_passes)
    counts = list(range(n_passes, lo - 1, -1))
    order = [c for pair in zip(counts, reversed(counts))
             for c in pair][:len(counts)]       # hi, lo, hi - 1, lo + 1, ..
    chunks = []
    for z in range(n_zmws):
        _tpl, reads, _strands, snr = simulate_zmw(
            rng, tpl_len, order[z % len(order)])
        chunks.append(Chunk(
            f"warmup/{z}",
            [Subread(f"warmup/{z}/{k}", r) for k, r in enumerate(reads)],
            snr))
    return chunks


def _synth_tasks(n_zmws: int, n_passes: int, tpl_len: int):
    from pbccs_tpu.parallel.batch import ZmwTask
    from pbccs_tpu.simulate import simulate_zmw

    rng = np.random.default_rng(_SYNTH_SEED)
    tasks = []
    for z in range(n_zmws):
        tpl, reads, strands, snr = simulate_zmw(rng, tpl_len, n_passes)
        draft = tpl.copy()
        if tpl_len > 10:  # corrupt so refinement does real mutation work
            pos = int(rng.integers(5, tpl_len - 5))
            draft[pos] = (draft[pos] + 1) % 4
        tasks.append(ZmwTask(f"warmup/{z}", draft, snr, reads, strands,
                             [0] * n_passes, [len(draft)] * n_passes))
    return tasks


def run_warmup(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    log = Logger.default(Logger(level=LogLevel.from_string(args.logLevel)))

    from pbccs_tpu.runtime import tuning

    tuning.configure(args.tuneProfile, logger=log)
    if not args.bucket:
        args.bucket = tuning.knob_str_list("warmup_buckets")
    if not args.bucket:
        raise SystemExit(
            "ccs warmup: --bucket is required (no applied tune profile "
            "supplies a warmup_buckets menu)")

    from pbccs_tpu.runtime.cache import enable_compilation_cache

    enable_compilation_cache(args.compileCache)

    import jax

    from pbccs_tpu.sched.pool import select_devices

    try:
        devices = select_devices(args.devices)
    except ValueError as e:
        raise SystemExit(f"--devices: {e}") from None
    targets = devices if args.allDevices else devices[:1]
    entries = [parse_bucket(b) for b in args.bucket]

    from pbccs_tpu.parallel.batch import effective_shapes
    from pbccs_tpu.resilience import resources

    gov = resources.default_governor()
    report = []
    for (z, passes, length) in entries:
        tasks = _synth_tasks(z, passes, length)
        imax, jmax, r, _ = effective_shapes(
            len(tasks), max(len(t.reads) for t in tasks),
            max(len(rd) for t in tasks for rd in t.reads),
            max(len(t.tpl) for t in tasks))
        bucket = resources.shape_bucket(imax, jmax, r)
        for dev in targets:
            name = f"{dev.platform}:{dev.id}"
            # the warmup menu consults the same ceilings production
            # dispatch learns: warming a Z the device cannot hold would
            # compile (and OOM) a shape no batch will ever run at
            cap = gov.cap(bucket, device=name)
            sub = tasks if cap is None else tasks[:cap]
            if len(sub) < len(tasks):
                log.warn(f"warmup: bucket {z}x{passes}x{length} clamped "
                         f"to Z={len(sub)} by the memory governor "
                         f"ceiling on {name}")
            log.info(f"warmup: bucket {z}x{passes}x{length} on {name}")
            t0 = time.monotonic()
            shapes = None
            while True:
                try:
                    with resources.device_scope(name), \
                            jax.default_device(dev):
                        shapes = _warm_one(sub)
                    break
                except Exception as e:  # noqa: BLE001 -- classified below
                    if not resources.is_capacity_error(e) or len(sub) == 1:
                        raise
                    # warmup discovers the ceiling BEFORE traffic does:
                    # record it and warm the largest Z that fits
                    ceiling = gov.record_oom(bucket, len(sub), device=name)
                    log.warn(f"warmup: {z}x{passes}x{length} OOMed at "
                             f"Z={len(sub)} on {name}; retrying at "
                             f"Z={ceiling}")
                    sub = sub[:ceiling]
            dt = time.monotonic() - t0
            entry = {"bucket": f"{z}x{passes}x{length}", "device": name,
                     "seconds": round(dt, 2), "shapes": shapes}
            if len(sub) < len(tasks):
                entry["governor_clamped_z"] = len(sub)
            report.append(entry)
            log.info(f"warmup: {entry['bucket']} on {name}: "
                     f"{dt:.1f}s, shapes {shapes}")
    print(json.dumps({"warmed": report}))
    log.flush()
    return 0


if __name__ == "__main__":
    sys.exit(run_warmup())
