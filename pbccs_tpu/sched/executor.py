"""Pipelined batch executor: host prepare overlapped with device polish.

The one driver of the batch CLI, at every device count.  Host drafts run
ahead of the device in reading order, at ZMW granularity, and one thread
owns each device:

    reader ──> prepare pool (N host threads, FIFO: filter -> POA -> mapping)
       ▲           │ (the reader reads batch k+1 when batch k's drafts
       └───────────┤  have closed: its Python would starve them)
                   │ each batch dealt as contiguous slices of
                   │ ceil(len / N) ZMWs, so ONE batch spreads over all N
                   │ workers and batch 0 is whole after one slice's time
                   │ (a worker per whole batch finishes the first N
                   │ batches together, with the device idle until then);
                   │ the last slice to close assembles the batch
                   ▼
               DevicePool (one executor thread per device)
                   │ per-batch outcome tallies
                   ▼
               ordered emission (results yield in submission order, so
               checkpoint journaling and output BAM order are identical
               to the single-threaded driver)

Batch composition is untouched -- the same --chunkSize groups, prepared
(pipeline.prepare_batch on each slice, joined in chunk order) -- and a
batch polishes at its length class's pin in the process's shape menu
(parallel.batch.ShapeMenu: the bucket pipeline.process_chunks would pick
for it, or a neighbour across one bucket edge that an earlier batch
brought, with the same band width, so padding alone differs), so a
file's batches share one family of programs and a run's output is
byte-identical at every device and worker count, merely reordered in
time.
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


class _BatchJob:
    """One batch in the prepare pool: the (tally, preps) of each of its
    slices, joined in chunk order by whichever slice closes last."""

    def __init__(self, seq: int, idx: int, n_slices: int):
        self.seq, self.idx = seq, idx
        # fed when the batch before it has closed its drafts, so its
        # first slice starts at once: the prepare interval starts here
        self.t_fed = time.monotonic()
        self._parts: list[Any] = [None] * n_slices
        self._open = n_slices
        self._lock = threading.Lock()

    def close_slice(self, k: int, part) -> bool:
        """Book slice k's part (or the exception it died of); True for
        the slice that closes the batch."""
        with self._lock:
            self._parts[k] = part
            self._open -= 1
            return self._open == 0

    def assemble(self) -> tuple["pipeline.ResultTally", list]:
        tally, preps = pipeline.ResultTally(), []
        for part in self._parts:
            if isinstance(part, BaseException):
                raise part
            tally.merge(part[0])
            preps.extend(part[1])
        return tally, preps


class ScheduledPipeline:
    """Run (index, chunk-batch) work items through prepare workers and a
    DevicePool, yielding (index, ResultTally) in submission order."""

    def __init__(self, pool: DevicePool,
                 settings: "pipeline.ConsensusSettings", *,
                 chunk_zmws: int,
                 prepare_workers: int = 2, on_error: str = "bisect",
                 max_inflight: int | None = None,
                 budget=None,
                 logger: Logger | None = None):
        self.pool = pool
        # the most ZMWs a work item holds (the CLI's --chunkSize): a pin's
        # dispatches run at one Z reckoned from it
        # (pipeline.menu_batch_shapes)
        self.chunk_zmws = chunk_zmws
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
        # the reader and the drafts take turns: batch k+1 is read once
        # batch k's drafts have closed.  The reader is Python and holds
        # the GIL, and while it does, a draft's every return from native
        # code waits a switch interval for it: beside a reader left to
        # run through a file, a draft took 210-250 ms against 45 ms, and
        # the first batch was whole when the reading ended, after 2.8 s
        # with the device idle, not after 1 s (PR 26, on the chip's host)
        ahead = threading.Semaphore(1)
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

        def prep_slice(job: "_BatchJob", k: int, chunks) -> None:
            """One contiguous slice of a batch through the host stages;
            the slice that closes last assembles and submits the batch."""
            if stop.is_set():
                return   # the consumer bailed: nothing reads this batch
            try:
                part = pipeline.prepare_batch(chunks, self.settings,
                                              batch=job.idx)
            except BaseException as e:  # noqa: BLE001 -- surfaced in run()
                part = e
            if job.close_slice(k, part):
                ahead.release()   # drafted: read the next one
                submit_batch(job)

        def submit_batch(job: "_BatchJob") -> None:
            seq, idx = job.seq, job.idx
            lease = None
            try:
                tally, preps = job.assemble()
                if not preps:
                    finish(seq, (idx, tally))
                    return
                # the class's pin, not the batch's own bucket, and under
                # a governor's ceiling one Z for every dispatch at it:
                # one family of programs a file, loaded with its first
                # batch
                pin, z = pipeline.menu_batch_shapes(preps, self.chunk_zmws)
                imax, jmax, r = pin
                z_own = z or len(preps)
                parts = -(-len(preps) // z_own)
                key = (jmax, imax, r, z_own)
                # host-budget gate (--memBudget): charge this batch's
                # marshalled-bytes estimate BEFORE building the prebake;
                # blocks (a visible resource.throttle, not a crash)
                # while other batches hold the budget, released when
                # this batch's polish completes
                if self.budget is not None:
                    from pbccs_tpu.parallel.batch import premarshal_nbytes

                    lease = self.budget.admit(
                        premarshal_nbytes((imax, jmax, r, z_own)),
                        site="sched.prepare", abort=stop.is_set)
                    if stop.is_set():
                        if lease is not None:
                            lease.release()
                        return
                from pbccs_tpu.resilience import resources

                bucket = resources.shape_bucket(imax, jmax, r)
                # pre-bake the polish marshalling HERE, on the prepare
                # worker: padded numpy planes + f64 SNR tables build while
                # the device threads polish earlier batches, so
                # BatchPolisher on the executor thread adopts arrays
                # instead of marshalling.  Quiver polishes per ZMW and
                # never reads a prebake, and a batch over the governor's
                # ceiling is pre-split into parts that marshal their own
                # subsets; any prebake failure falls back to inline
                # marshalling (accounted, never fatal).
                prebaked = None
                if self.settings.model != "quiver" and parts == 1:
                    try:
                        prebaked = pipeline.prebake_polish(
                            preps, buckets=pin, min_z=z or 1)
                    except Exception as e:  # noqa: BLE001 -- inline fallback
                        pipeline.record_zmw_failure(
                            "prepare.prebake", e,
                            zmw=f"batch[{len(preps)}]")
                settings, on_error = self.settings, self.on_error
                fleet = self.pool.n_devices > 1
                attempts = [0]
                t_submit, submit_unix = time.monotonic(), time.time()
                _m_stages["prepare"].observe(
                    max(t_submit - job.t_fed, 0.0))

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
                        # the wait for the device as a span, from submit
                        # to where `polish` opens: long waits say the
                        # device sets the pace, none (with a starved
                        # device) says the host's drafts do.  No thread
                        # ran in it, so its CPU time is nil.
                        tracer = obs_trace.get_tracer()
                        if tracer is not None:
                            tracer.add_span(
                                "dispatch.turn_wait",
                                time.time() - submit_unix,
                                start_unix=submit_unix, zmws=len(preps),
                                batch=idx, cpu_ms=0.0)
                    try:
                        with obs_trace.span(
                                "polish", zmws=len(preps), batch=idx,
                                device=resources.current_device(),
                                lanes=r, parent_z=z_own, parts=parts):
                            return pipeline.polish_prepared_batch(
                                preps, settings, buckets=pin,
                                min_z=z or 1, fixed_z=z is not None,
                                on_error=on_error,
                                raise_device_shaped=fleet
                                and attempts[0] == 1,
                                prebaked=prebaked)
                    finally:
                        _m_stages["polish"].observe(
                            max(time.monotonic() - t_polish0, 0.0))

                _m_batches.inc()
                self.pool.submit(
                    key, polish, zmws=len(preps), capacity_bucket=bucket,
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
                it = iter(items)
                while True:
                    ahead.acquire()
                    if stop.is_set():
                        return
                    item = next(it, None)      # the read happens here
                    if item is None:
                        return
                    idx, chunks, precomputed = item
                    sem.acquire()
                    if stop.is_set():
                        return
                    seq = n_fed[0]
                    n_fed[0] += 1
                    if precomputed is not None or not chunks:
                        finish(seq, (idx, precomputed if precomputed
                                     is not None else pipeline.ResultTally()))
                        ahead.release()        # nothing of it to draft
                        continue
                    # slices sized so this ONE batch spreads over every
                    # prepare worker; the next batch is read when these
                    # have closed (`ahead`), so drafts stay in reading
                    # order and run ahead of the device
                    size = -(-len(chunks) // self.prepare_workers)
                    slices = [chunks[i: i + size]
                              for i in range(0, len(chunks), size)]
                    job = _BatchJob(seq, idx, len(slices))
                    for k, part in enumerate(slices):
                        prep_pool.submit(prep_slice, job, k, part)
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
            ahead.release()
            feeder_done.wait(timeout=10.0)
            prep_pool.shutdown(wait=True)
