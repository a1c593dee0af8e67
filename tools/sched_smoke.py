#!/usr/bin/env python
"""Scheduler smoke for the tier-1 gate: the device-fleet scheduler on 8
virtual CPU devices, asserting the dispatch contract end to end.

Legs:

  scaling   16 ZMWs in 4 chunk-batches through ScheduledPipeline over an
            8-device pool: output byte-identical to the single-device
            process_chunks driver, work actually spread over >= 2
            devices, sticky-routing metrics move
  chaos     a fault spec sickens ONE device (sched.dispatch keyed by the
            worker name, the faults.py registry): the run completes with
            ZERO lost ZMWs (outputs still byte-identical), the device is
            benched, requeues are counted
  serve     a live engine in fleet mode (ServeConfig.devices=0) with the
            same sick device: every request completes successfully, the
            engine stays up and reports the per-device breakdown

Runs on CPU in-process.  The 8-device platform must be forced BEFORE jax
initializes (same dance as tests/conftest.py), so run this as its own
process:  JAX_PLATFORMS=cpu python tools/sched_smoke.py
"""

from __future__ import annotations

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# the host refinement loop keeps the compile budget sane on CPU (the
# device-resident loop is parity-pinned against it in test_device_refine)
os.environ.setdefault("PBCCS_DEVICE_REFINE", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

sys.path.insert(0, ".")  # runnable as tools/sched_smoke.py from the repo root

from pbccs_tpu.obs.metrics import default_registry  # noqa: E402
from pbccs_tpu.pipeline import (  # noqa: E402
    Chunk,
    ConsensusSettings,
    Failure,
    Subread,
    process_chunks,
)
from pbccs_tpu.resilience import faults  # noqa: E402
from pbccs_tpu.runtime.logging import Logger, LogLevel  # noqa: E402
from pbccs_tpu.sched import (  # noqa: E402
    DevicePool,
    DevicePoolConfig,
    ScheduledPipeline,
)
from pbccs_tpu.simulate import simulate_zmw  # noqa: E402

N_ZMWS = 16
BATCH = 4


def make_workload() -> list[list[Chunk]]:
    rng = np.random.default_rng(20260803)
    chunks = []
    for i in range(N_ZMWS):
        _, reads, _, snr = simulate_zmw(rng, 60, 5)
        chunks.append(Chunk(
            f"smoke/{i}",
            [Subread(f"smoke/{i}/{k}", r) for k, r in enumerate(reads)],
            snr))
    return [chunks[i: i + BATCH] for i in range(0, N_ZMWS, BATCH)]


def outputs(tallies) -> dict[str, tuple[str, str]]:
    return {r.id: (r.sequence, r.qualities)
            for t in tallies for r in t.results}


def total(tallies) -> int:
    return sum(t.total for t in tallies)


def check(name: str, ok: bool, detail: str = "") -> None:
    print(f"  {'PASS' if ok else 'FAIL'}  {name}" +
          (f"  ({detail})" if detail else ""))
    if not ok:
        raise SystemExit(f"sched smoke failed: {name} {detail}")


def run_scheduled(batches, settings, pool) -> list:
    pipe = ScheduledPipeline(pool, settings, chunk_zmws=64, prepare_workers=2)
    emitted = list(pipe.run(
        (i, list(b), None) for i, b in enumerate(batches)))
    check("emission order == submission order",
          [i for i, _ in emitted] == list(range(len(batches))))
    return [t for _, t in emitted]


def main() -> int:
    from pbccs_tpu.runtime.cache import enable_compilation_cache

    enable_compilation_cache()
    Logger.default(Logger(level=LogLevel.ERROR))
    reg = default_registry()
    devices = jax.devices()
    check("8 virtual devices", len(devices) == 8, f"got {len(devices)}")
    batches = make_workload()
    settings = ConsensusSettings()

    print("== baseline (single-device process_chunks) ==")
    base = [process_chunks(list(b), settings) for b in batches]
    base_out = outputs(base)
    check("baseline yields successes",
          sum(t.counts[Failure.SUCCESS] for t in base) >= 12,
          f"{sum(t.counts[Failure.SUCCESS] for t in base)}/{N_ZMWS}")

    print("== scaling: ScheduledPipeline over the 8-device pool ==")
    scope = reg.scope()
    with DevicePool(devices, DevicePoolConfig(policy="sticky")) as pool:
        sched = run_scheduled(batches, settings, pool)
        st = pool.status()
    used = [d["device"] for d in st["devices"] if d["tasks_done"] > 0]
    check("output byte-identical to single-device",
          outputs(sched) == base_out)
    check("tallies match", total(sched) == total(base))
    check("work spread over >= 2 devices", len(used) >= 2, f"used={used}")
    check("sticky routing metrics moved",
          sum(scope.counters("ccs_sched_sticky_routes_total").values()) > 0)

    print("== chaos: one device benched mid-run, zero lost ZMWs ==")
    scope = reg.scope()
    with DevicePool(devices, DevicePoolConfig(policy="sticky",
                                              bench_after=1)) as pool:
        sick = pool._workers[0].name
        with faults.active(f"sched.dispatch:error~{sick}"):
            sched = run_scheduled(batches, settings, pool)
        st = pool.status()
    check("run completed with zero lost ZMWs", total(sched) == total(base),
          f"{total(sched)}/{total(base)}")
    check("surviving outputs byte-identical", outputs(sched) == base_out)
    check("sick device benched",
          scope.counter_value("ccs_sched_device_benched_total",
                              device=sick) == 1)
    check("requeues counted",
          scope.counter_value("ccs_sched_requeues_total") >= 1)
    check("no ZMW fell to Other",
          sum(t.counts[Failure.OTHER] for t in sched) == 0)

    print("== serve: fleet engine stays up through a sick device ==")
    from pbccs_tpu.pipeline import PreparedZmw
    from pbccs_tpu.serve.engine import CcsEngine, ServeConfig

    # stub polish: this leg asserts the ENGINE/pool contract (requeue,
    # bench, stay-up); consensus correctness is the scaling leg's job
    def stub_prep(chunk, _settings):
        return None, PreparedZmw(chunk, np.zeros(12, np.int8), [], 0, 0, 0.0)

    def stub_polish(preps, _settings):
        return [(Failure.SUCCESS, None) for _ in preps]

    scope = reg.scope()
    cfg = ServeConfig(max_batch=BATCH, max_wait_ms=50.0, devices=0)
    eng = CcsEngine(config=cfg, prep_fn=stub_prep, polish_fn=stub_polish)
    eng.start()
    try:
        sick = eng._pool._workers[0].name
        with faults.active(f"sched.dispatch:error~{sick}"):
            reqs = [eng.submit(c) for b in batches for c in b]
            for r in reqs:
                check(f"reply for {r.chunk.id}", r.wait(120.0))
                check(f"{r.chunk.id} completed without error",
                      r.error is None, str(r.error))
        status = eng.status()
        check("engine still answers status",
              status["engine"] == "ccs-serve")
        check("status has per-device breakdown",
              len(status["sched"]["devices"]) == 8)
    finally:
        drained = eng.close()
    check("engine drained cleanly", drained)
    check("serve leg counted requeues",
          scope.counter_value("ccs_sched_requeues_total") >= 1)

    print("sched smoke: all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
