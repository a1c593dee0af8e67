"""Pipelined batch executor: host prepare overlapped with device polish.

The offline driver's round-5 profile runs end to end at 42% of polish
throughput because the serial host-side POA draft gates the device: the
WorkQueue overlaps whole work items, but each worker still runs
prepare -> polish sequentially, so with one device the prepare of item
k+1 only overlaps the polish of item k when a second worker happens to
hold it.  This executor makes the overlap structural and fleet-wide:

    reader ──> prepare pool (N host threads: filter -> POA -> mapping)
                   │ prepared batches, keyed by compiled-shape bucket
                   ▼
               DevicePool (one executor thread per device)
                   │ per-batch outcome tallies
                   ▼
               ordered emission (results yield in submission order, so
               checkpoint journaling and output BAM order are identical
               to the single-threaded driver)

Batch composition is untouched -- the same --chunkSize groups, prepared
and polished with the same shape derivation as pipeline.process_chunks
-- so a multi-device run's output is byte-identical to the
single-device run (same bucket shapes => same compiled programs => same
arithmetic), merely reordered in time.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Iterable, Iterator

from pbccs_tpu import pipeline
from pbccs_tpu.obs import trace as obs_trace
from pbccs_tpu.obs.metrics import default_registry, log_buckets
from pbccs_tpu.runtime.logging import Logger
from pbccs_tpu.sched.pool import DevicePool

# offline-driver analogue of the serve engine's per-request stage
# histograms: per BATCH intervals through the prepare pool and the
# device fleet, so a fleet bench's latency story decomposes the same
# way a serve trace does (prepare / dispatch wait / polish)
_reg = default_registry()
_m_stages = {stage: _reg.histogram(
    "ccs_sched_stage_latency_seconds",
    "Per-batch stage intervals through the scheduled pipeline "
    "(prepare, dispatch wait, polish)",
    buckets=log_buckets(1e-4, 600.0), stage=stage)
    for stage in ("prepare", "dispatch", "polish")}
# batches the scheduled pipeline submitted to the device pool -- a
# CPU-deterministic perf-ledger counter (obs/ledger.py), distinct from
# ccs_sched_tasks_total{device} whose device attribution is
# routing-dependent
_m_batches = _reg.counter(
    "ccs_sched_batches_total",
    "Prepared batches submitted to the device pool by the scheduled "
    "pipeline")


class ScheduledPipeline:
    """Run (index, chunk-batch) work items through prepare workers and a
    DevicePool, yielding (index, ResultTally) in submission order."""

    def __init__(self, pool: DevicePool,
                 settings: "pipeline.ConsensusSettings",
                 prepare_workers: int = 2, on_error: str = "bisect",
                 max_inflight: int | None = None,
                 budget=None,
                 logger: Logger | None = None):
        self.pool = pool
        self.settings = settings
        self.prepare_workers = max(1, prepare_workers)
        self.on_error = on_error
        # bounds batches simultaneously past the reader (prepping, queued
        # on a device, or done-but-not-yet-emitted) so a fast reader
        # cannot buffer a whole cell's preps in memory
        self.max_inflight = max_inflight or (
            self.prepare_workers + pool.n_devices + 2)
        # optional resources.HostBudget (--memBudget): each batch charges
        # its marshalled-bytes estimate before the prebake builds and
        # releases when its POLISH completes -- the true lifetime of the
        # charged planes (they are garbage once the dispatch consumed
        # them), and a release point that cannot deadlock: emission is
        # strictly ordered, so a release tied to emission could wait on
        # an earlier batch whose prep is itself blocked in admit().
        # Parked results stay count-bounded by max_inflight.
        self.budget = budget
        self._log = logger or Logger.default()

    # Each input item is (index, chunks, precomputed) -- precomputed is a
    # ResultTally for work restored from a checkpoint journal (emitted in
    # order without recomputation) and None for real work.
    def run(self, items: Iterable[tuple[int, Any, Any]]
            ) -> Iterator[tuple[int, "pipeline.ResultTally"]]:
        cv = threading.Condition()
        done: dict[int, Any] = {}        # seq -> (idx, tally) | exception
        sem = threading.Semaphore(self.max_inflight)
        n_fed = [0]
        feeder_done = threading.Event()
        feeder_error: list[BaseException] = []

        def finish(seq: int, payload) -> None:
            with cv:
                done[seq] = payload
                cv.notify_all()

        def polish_done(seq, idx, tally, preps, fut, lease=None) -> None:
            # runs as a SchedFuture callback, whose exceptions the pool
            # only debug-logs: anything raising here must still finish()
            # this slot or run()'s ordered emission waits forever
            if lease is not None:
                # the polish consumed (or abandoned) the marshalled
                # planes; their budget charge ends here regardless of
                # outcome (release is idempotent)
                lease.release()
            try:
                exc = fut.exception()
                if exc is not None:
                    # the pool exhausted every healthy device on this
                    # batch: account each ZMW (logged + counted), never
                    # drop silently
                    pipeline.record_zmw_failure(
                        "sched.polish", exc, zmw=f"batch[{len(preps)}]")
                    for _ in preps:
                        tally.tally(pipeline.Failure.OTHER)
                else:
                    outcomes = fut.result()
                    if len(outcomes) != len(preps):
                        raise RuntimeError(
                            f"polish returned {len(outcomes)} outcomes "
                            f"for {len(preps)} prepared ZMWs")
                    for failure, result in outcomes:
                        tally.tally(failure)
                        if result is not None:
                            tally.results.append(result)
                finish(seq, (idx, tally))
            except BaseException as e:  # noqa: BLE001 -- surfaced in run()
                finish(seq, e)

        def prep_one(seq: int, idx: int, chunks, precomputed) -> None:
            lease = None
            t_prep0 = time.monotonic()
            try:
                if precomputed is not None:
                    finish(seq, (idx, precomputed))
                    return
                tally, preps = pipeline.prepare_batch(chunks, self.settings,
                                                      batch=idx)
                if not preps:
                    finish(seq, (idx, tally))
                    return
                (imax, jmax, r), z = pipeline._pinned_batch_shapes(
                    preps, None, 1)
                key = (jmax, imax, r, z)
                # host-budget gate (--memBudget): charge this batch's
                # marshalled-bytes estimate BEFORE building the prebake;
                # blocks (a visible resource.throttle, not a crash)
                # while other batches hold the budget, released when
                # this batch's polish completes
                if self.budget is not None:
                    from pbccs_tpu.parallel.batch import premarshal_nbytes

                    lease = self.budget.admit(
                        premarshal_nbytes((imax, jmax, r, z)),
                        site="sched.prepare", abort=stop.is_set)
                    if stop.is_set():
                        if lease is not None:
                            lease.release()
                        return
                # pre-bake the polish marshalling HERE, on the prepare
                # worker: padded numpy planes + f64 SNR tables build while
                # the device threads polish earlier batches, so
                # BatchPolisher on the executor thread adopts arrays
                # instead of marshalling.  Quiver polishes per ZMW and
                # never reads a prebake; any prebake failure falls back
                # to inline marshalling (accounted, never fatal).
                prebaked = None
                if self.settings.model != "quiver":
                    try:
                        prebaked = pipeline.prebake_polish(preps)
                    except Exception as e:  # noqa: BLE001 -- inline fallback
                        pipeline.record_zmw_failure(
                            "prepare.prebake", e,
                            zmw=f"batch[{len(preps)}]")
                settings, on_error = self.settings, self.on_error
                fleet = self.pool.n_devices > 1
                attempts = [0]
                t_submit = time.monotonic()
                _m_stages["prepare"].observe(max(t_submit - t_prep0, 0.0))

                def polish(_device):
                    # first attempt on a fleet: let a device-shaped
                    # failure (hang/XLA error) escape to the pool, which
                    # strikes/benches the sick device and requeues the
                    # WHOLE batch to a healthy one -- quarantine would
                    # otherwise bisect on the same sick device.  The
                    # requeued attempt quarantines locally as usual (a
                    # failure that followed the batch across devices is
                    # task-shaped: poison input, not hardware).
                    attempts[0] += 1
                    t_polish0 = time.monotonic()
                    if attempts[0] == 1:
                        _m_stages["dispatch"].observe(
                            max(t_polish0 - t_submit, 0.0))
                    try:
                        with obs_trace.span("polish", zmws=len(preps),
                                            batch=idx):
                            return pipeline.polish_prepared_batch(
                                preps, settings, on_error=on_error,
                                raise_device_shaped=fleet
                                and attempts[0] == 1,
                                prebaked=prebaked)
                    finally:
                        _m_stages["polish"].observe(
                            max(time.monotonic() - t_polish0, 0.0))

                from pbccs_tpu.resilience import resources

                _m_batches.inc()
                self.pool.submit(
                    key, polish, zmws=len(preps),
                    capacity_bucket=resources.shape_bucket(imax, jmax, r),
                    callback=lambda fut: polish_done(seq, idx, tally,
                                                     preps, fut, lease))
            except BaseException as e:  # noqa: BLE001 -- surfaced in run()
                # the callback never ran (pool closed, prebake blew up):
                # the budget charge must not outlive the batch (release
                # is idempotent, so a raced callback is harmless)
                if lease is not None:
                    lease.release()
                finish(seq, e)

        prep_pool = ThreadPoolExecutor(
            self.prepare_workers, thread_name_prefix="ccs-sched-prep")
        stop = threading.Event()   # consumer bailed: unwedge the feeder

        def feed() -> None:
            try:
                for idx, chunks, precomputed in items:
                    sem.acquire()
                    if stop.is_set():
                        return
                    seq = n_fed[0]
                    n_fed[0] += 1
                    prep_pool.submit(prep_one, seq, idx, chunks, precomputed)
            except BaseException as e:  # noqa: BLE001 -- surfaced in run()
                feeder_error.append(e)
            finally:
                feeder_done.set()
                with cv:
                    cv.notify_all()

        feeder = threading.Thread(target=feed, daemon=True,
                                  name="ccs-sched-feeder")
        feeder.start()
        try:
            next_seq = 0
            while True:
                with cv:
                    while next_seq not in done and not (
                            feeder_done.is_set() and next_seq >= n_fed[0]):
                        cv.wait(timeout=0.2)
                    if next_seq not in done:
                        break  # feeder finished and everything emitted
                    payload = done.pop(next_seq)
                if isinstance(payload, BaseException):
                    raise payload
                yield payload
                sem.release()
                next_seq += 1
            if feeder_error:
                raise feeder_error[0]
        finally:
            # a consumer that bailed mid-stream (journal write failed,
            # generator closed) leaves the feeder parked in sem.acquire;
            # wake it so the thread (and the input reader it holds) ends.
            # A prep worker parked in budget.admit() observes the abort
            # flag (admit polls it), so shutdown never hangs on the
            # budget; in-flight batches release their leases from the
            # polish_done callback when the pool settles their futures.
            stop.set()
            sem.release()
            feeder_done.wait(timeout=10.0)
            prep_pool.shutdown(wait=True)
