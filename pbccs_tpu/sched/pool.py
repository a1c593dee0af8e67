"""Device-fleet scheduler core: one executor thread per jax.Device.

The batch CLI dispatches every polish through this pool, at one device
as at N (sched/executor.py), and a fleet `ccs serve` feeds it too.  The
sharded mesh path (parallel/mesh.py) splits ONE batch across devices --
the right shape when Z is huge; this module is the complementary shape
for the common case: many independent bucketed batches, each small
enough for one device, dispatched across the fleet so every device is
fed (Pathways-style gang dispatch at batch granularity; Orca-style
continuous batching stays in serve/batcher.py and simply feeds this
pool instead of a single executor).

Design points:

  * **One worker thread per device.**  Each task runs under
    ``jax.default_device(worker.device)`` on its worker's thread, so all
    arrays a task materializes -- a BatchPolisher's cached fills, the
    compiled-program menu -- live on that device.  The GIL is released
    for most of a polish (device execution + transfers), so W workers
    genuinely overlap W devices.
  * **Sticky bucket routing** (the default policy): compiled executables
    are cached per (program, shapes, device), so a bucket shape that
    polished on device k replays for free there and pays a (disk-cached)
    compile anywhere else.  A task's bucket key prefers a device that
    already ran that key ("home"); an idle home always wins, a busy home
    loses to the least-loaded healthy device (work-conserving: stickiness
    never leaves a device idle while work queues), which then becomes an
    additional home for the bucket.  Policies: ``sticky`` | ``least`` |
    ``roundrobin``.
  * **Device health.**  A task failure counts a strike against its
    device only when it is device-shaped -- a WatchdogTimeout (hung
    dispatch), an XLA runtime error (resilience.retry already absorbs
    transient ones inside the dispatch; RetriesExhausted counts), or an
    injected chaos fault -- AND it is the task's FIRST failure (a
    poisoned task is task-shaped and must not bench every device it
    visits; plain Python exceptions never strike).  A device-shaped
    failure requeues to a healthy device the task has not yet failed on
    (``task.excluded`` bounds the tour to the fleet size); a task-shaped
    failure gets ONE healthy-device retry, then surfaces -- touring a
    deterministic bug would cost fleet-size polish durations just to
    return the same error.  ``bench_after`` device-shaped strikes in a
    row bench the
    device: its queued tasks requeue to healthy devices and it takes no
    further work.  The LAST healthy device is never benched -- a
    degraded run beats no run.
  * **Fault site** ``sched.dispatch`` (keys: the worker name ``cpu:3``/
    ``tpu:0`` and the task key), sitting OUTSIDE the task callable: a
    chaos spec targets a *device*, exercising exactly the bench/requeue
    machinery, while poison-*ZMW* specs keep firing inside
    pipeline._guarded_dispatch as before.

Metrics (obs registry): ``ccs_sched_tasks_total{device}``,
``ccs_sched_task_failures_total{device}``, ``ccs_sched_requeues_total``,
``ccs_sched_device_benched_total{device}``,
``ccs_sched_queue_depth{device}``,
``ccs_sched_device_starved_seconds_total{device}``,
``ccs_sched_sticky_routes_total{outcome=home|spill|new}``.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
import traceback
from typing import Any, Callable, Hashable, Sequence

from pbccs_tpu.obs import trace as obs_trace
from pbccs_tpu.obs.metrics import default_registry
from pbccs_tpu.runtime.logging import Logger
from pbccs_tpu.sched.health import StickyMap

_reg = default_registry()
_m_requeues = _reg.counter(
    "ccs_sched_requeues_total",
    "Tasks re-routed to another device after a device-shaped failure")
_m_sticky = {outcome: _reg.counter(
    "ccs_sched_sticky_routes_total",
    "Sticky routing decisions by outcome", outcome=outcome)
    for outcome in ("home", "spill", "new")}


def select_devices(n: int) -> list:
    """First-n visible-device selection shared by every fleet entry point
    (batch CLI ``--devices``, ``ServeConfig.devices``, ``ccs warmup``):
    ``n == 0`` means every visible device, ``n > 0`` the first n.  A
    negative n is a usage error, never a from-the-end slice."""
    import jax

    if n < 0:
        raise ValueError(f"devices must be >= 0, got {n}")
    devs = list(jax.devices())
    if n > len(devs):
        # a silent clamp would run a "--devices 8" fleet on one device
        # at single-device throughput with nothing flagging the
        # driver/visibility misconfiguration
        Logger.default().warn(
            f"requested {n} devices but only {len(devs)} visible; "
            f"running on {len(devs)}")
    return devs[:n] if n else devs


class PoolClosed(RuntimeError):
    """submit() after close(), or a task failed by a non-waiting close."""


class NoHealthyDevice(RuntimeError):
    """A task ran out of healthy devices it has not already failed on."""


@dataclasses.dataclass(frozen=True)
class DevicePoolConfig:
    """Scheduler knobs (see module docstring for the policy they drive)."""

    policy: str = "sticky"        # sticky | least | roundrobin
    # consecutive device-shaped failures before a device is benched
    bench_after: int = 2
    # a busy home keeps a sticky task only while its depth (queued +
    # running) is <= spill_depth; 0 = work-conserving (idle homes only)
    spill_depth: int = 0

    def __post_init__(self):
        if self.policy not in ("sticky", "least", "roundrobin"):
            raise ValueError(f"unknown sched policy {self.policy!r}")
        if self.bench_after < 1:
            raise ValueError("bench_after must be >= 1")


class SchedFuture:
    """Completion handle for one submitted task (threading-based)."""

    def __init__(self, callback: Callable[["SchedFuture"], None] | None = None):
        self._done = threading.Event()
        self._result: Any = None
        self._exc: BaseException | None = None
        self._callback = callback

    def done(self) -> bool:
        return self._done.is_set()

    def wait(self, timeout: float | None = None) -> bool:
        return self._done.wait(timeout)

    def result(self, timeout: float | None = None) -> Any:
        if not self._done.wait(timeout):
            raise TimeoutError("task not complete")
        if self._exc is not None:
            raise self._exc
        return self._result

    def exception(self, timeout: float | None = None) -> BaseException | None:
        if not self._done.wait(timeout):
            raise TimeoutError("task not complete")
        return self._exc

    def _finish(self, result: Any = None,
                exc: BaseException | None = None) -> None:
        if self._done.is_set():
            return   # complete exactly once (defensive: a racing close)
        self._result, self._exc = result, exc
        self._done.set()
        if self._callback is not None:
            try:
                self._callback(self)
            except Exception as e:  # noqa: BLE001 -- a completion callback
                # must never take the worker thread down with it
                Logger.default().debug(f"sched callback failed: {e!r}")


@dataclasses.dataclass
class _Task:
    key: Hashable
    fn: Callable[[Any], Any]          # fn(jax.Device) -> result
    zmws: int
    future: SchedFuture
    excluded: set = dataclasses.field(default_factory=set)  # worker indices
    # pin=True submissions (warmup, per-device bench legs) must run on
    # THEIR device or fail loudly -- a silent requeue elsewhere would let
    # a warmup "succeed" while leaving the pinned device cold
    pinned: bool = False
    # the resources.shape_bucket this task polishes in: a capacity-shaped
    # (OOM) failure records a governor ceiling under it and requeues the
    # task to the SAME device, where the pipeline's admission pre-split
    # dispatches it in ceiling-sized parts.  None = no capacity handling
    # (the failure classifies task-shaped instead).
    capacity_bucket: Hashable | None = None
    capacity_requeues: int = 0


def starved_counter(device: str):
    """Seconds the thread that owns `device` sat with nothing to polish.
    Near zero: work was always queued when the device came free (the
    device sets the pace); large: the host's prepare does.  Booked where
    that thread waits: DevicePool._worker_loop, and on `ccs serve`'s
    one-device path, which has no pool, CcsEngine._polish_worker (from
    the first flush it took).  Each wait is also a `device.starved`
    span."""
    return _reg.counter(
        "ccs_sched_device_starved_seconds_total",
        "Seconds a device's executor sat with an empty queue between "
        "the pool's first submit and its close", device=device)


class _Worker:
    """Bookkeeping for one device executor (state guarded by pool lock)."""

    def __init__(self, index: int, device):
        self.index = index
        self.device = device
        self.name = f"{device.platform}:{device.id}"
        self.pending: collections.deque[_Task] = collections.deque()
        self.busy = False
        self.benched = False
        self.strikes = 0
        self.tasks_done = 0
        self.failures = 0
        self.thread: threading.Thread | None = None
        self.m_tasks = _reg.counter("ccs_sched_tasks_total",
                                    "Tasks completed per device",
                                    device=self.name)
        self.m_failures = _reg.counter("ccs_sched_task_failures_total",
                                       "Task attempts that raised, per device",
                                       device=self.name)
        self.m_depth = _reg.gauge("ccs_sched_queue_depth",
                                  "Queued + running tasks per device",
                                  device=self.name)
        self.m_starved = starved_counter(self.name)

    def depth(self) -> int:
        return len(self.pending) + (1 if self.busy else 0)


class DevicePool:
    """A fleet of per-device executor threads with sticky bucket routing
    and health-based benching (see module docstring)."""

    def __init__(self, devices: Sequence | None = None,
                 config: DevicePoolConfig | None = None, *,
                 logger: Logger | None = None):
        import jax

        self.config = config or DevicePoolConfig()
        self._log = logger or Logger.default()
        devices = list(devices if devices is not None else jax.devices())
        if not devices:
            raise ValueError("DevicePool needs at least one device")
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._workers = [_Worker(i, d) for i, d in enumerate(devices)]
        # bucket key -> worker indices that have run it (sticky "homes";
        # the map itself is shared with the serve router -- sched/health)
        self._sticky = StickyMap()
        self._rr = -1
        self._closed = False
        self._first_submit: float | None = None   # monotonic
        for w in self._workers:
            w.thread = threading.Thread(
                target=self._worker_loop, args=(w,), daemon=True,
                name=f"ccs-sched-{w.name}")
            w.thread.start()
        self._log.info(
            f"device pool up: {len(self._workers)} device(s) "
            f"[{', '.join(w.name for w in self._workers)}] "
            f"policy={self.config.policy}")

    @property
    def n_devices(self) -> int:
        return len(self._workers)

    def healthy_count(self) -> int:
        with self._lock:
            return sum(1 for w in self._workers if not w.benched)

    # ------------------------------------------------------------- submission

    def submit(self, key: Hashable, fn: Callable[[Any], Any], *,
               zmws: int = 1,
               callback: Callable[[SchedFuture], None] | None = None,
               worker_index: int | None = None,
               pin: bool = False,
               capacity_bucket: Hashable | None = None) -> SchedFuture:
        """Queue fn(device) on a device chosen by the routing policy.

        `key` is the sticky-routing bucket (callers pass the compiled
        shape key so a bucket's program menu stays warm on its home
        device).  `worker_index` places the task on one device; with
        `pin=True` it must also COMPLETE there -- a pinned task that
        fails surfaces its exception instead of requeueing (a per-device
        warmup that silently succeeded elsewhere would leave the pinned
        device cold while reporting success).  Without `pin`, placement
        is initial-only and failures requeue normally.  The future
        completes with fn's result, or -- after device-level requeues
        are exhausted -- its last exception.

        `capacity_bucket` (a resources.shape_bucket) opts the task into
        OOM-adaptive handling: a capacity-shaped failure records a
        MemoryGovernor ceiling for (device, bucket) and requeues to the
        SAME device -- no strike, no bench, no fleet tour -- where the
        pipeline's admission pre-split re-dispatches it in ceiling-sized
        parts (see resilience.resources)."""
        if pin and worker_index is None:
            raise ValueError("pin=True requires worker_index")
        if worker_index is not None and not (
                0 <= worker_index < len(self._workers)):
            # no negative-index wrap: a pinned task landing on the LAST
            # device via an off-by-one would "succeed" while the intended
            # device stays cold
            raise ValueError(
                f"worker_index {worker_index} out of range "
                f"[0, {len(self._workers)})")
        task = _Task(key, fn, zmws, SchedFuture(callback), pinned=pin,
                     capacity_bucket=capacity_bucket)
        with self._cv:
            if self._closed:
                raise PoolClosed("device pool is closed")
            if worker_index is not None:
                w = self._workers[worker_index]
                if w.benched:
                    raise NoHealthyDevice(f"device {w.name} is benched")
            else:
                w = self._route_locked(task)
            if self._first_submit is None:
                self._first_submit = time.monotonic()
            self._enqueue_locked(w, task)
            self._cv.notify_all()
        return task.future

    def _route_locked(self, task: _Task) -> _Worker:
        healthy = [w for w in self._workers
                   if not w.benched and w.index not in task.excluded]
        if not healthy:
            raise NoHealthyDevice(
                f"no healthy device left for bucket {task.key!r}")
        policy = self.config.policy
        if policy == "roundrobin":
            self._rr += 1
            return healthy[self._rr % len(healthy)]
        # least-loaded tie-break: fewer resident buckets first (spread the
        # compiled-program menu across the fleet), then device order
        def load(w: _Worker):
            return (w.depth(), self._sticky.resident_count(w.index),
                    w.index)

        if policy == "sticky":
            target, outcome = self._sticky.route(
                task.key, healthy, member_id=lambda w: w.index, load=load,
                depth=lambda w: w.depth(),
                spill_depth=self.config.spill_depth)
            _m_sticky[outcome].inc()
            return target
        return min(healthy, key=load)

    def _enqueue_locked(self, w: _Worker, task: _Task) -> None:
        self._sticky.note(task.key, w.index)
        w.pending.append(task)
        w.m_depth.set(w.depth())

    # ------------------------------------------------------------ worker loop

    def _worker_loop(self, w: _Worker) -> None:
        while True:
            with self._cv:
                while not w.pending and not self._closed and not w.benched:
                    t_idle = time.monotonic()
                    # `head`: a wait that began before the first submit
                    # (the counter leaves that part out, the span does not)
                    with obs_trace.span("device.starved", device=w.name,
                                        head=self._first_submit is None):
                        self._cv.wait()
                    if self._first_submit is not None:
                        w.m_starved.inc(max(0.0, time.monotonic() - max(
                            t_idle, self._first_submit)))
                if w.benched:
                    return  # _bench_locked already requeued w.pending
                if not w.pending:  # closed and drained
                    return
                task = w.pending.popleft()
                w.busy = True
                w.m_depth.set(w.depth())
            self._run_task(w, task)
            with self._cv:
                w.busy = False
                w.m_depth.set(w.depth())
                self._cv.notify_all()

    def _run_task(self, w: _Worker, task: _Task) -> None:
        import jax

        from pbccs_tpu.resilience import faults, resources

        try:
            # the device-level chaos site: keyed by WORKER name so a spec
            # can sicken one device (ZMW-poison specs live inside the
            # dispatch fn, at pipeline's polish.dispatch site); oom-kind
            # specs here model the device rejecting the batch shape.
            # device_scope tags the thread so the pipeline's governor
            # lookups/records key ceilings per THIS device.
            with resources.device_scope(w.name):
                faults.maybe_fail("sched.dispatch",
                                  keys=[w.name, str(task.key)])
                with jax.default_device(w.device):
                    result = task.fn(w.device)
        except BaseException as e:  # noqa: BLE001 -- classified below
            self._on_task_error(w, task, e)
            return
        with self._lock:
            w.strikes = 0
            w.tasks_done += 1
        w.m_tasks.inc()
        task.future._finish(result=result)

    def _on_task_error(self, w: _Worker, task: _Task,
                       exc: BaseException) -> None:
        from pbccs_tpu.resilience import faults, resources, retry, watchdog

        w.m_failures.inc()
        # CAPACITY-shaped failures (device OOM / RESOURCE_EXHAUSTED) are
        # classified FIRST: the batch SHAPE overflows the device, which
        # is neither sick hardware (striking/benching a healthy device
        # would shrink the fleet for a workload problem) nor a poison
        # input (quarantine would tour healthy ZMWs).  Record the shape
        # ceiling and requeue to the SAME device: the pipeline's
        # admission pre-split (polish_prepared_batch) dispatches the
        # requeued batch in ceiling-sized parts there.
        if (task.capacity_bucket is not None and not task.pinned
                and resources.is_capacity_error(exc)
                # halvings are bounded: each requeue lowers the ceiling,
                # so a closure that somehow ignores the governor still
                # terminates in O(log Z) requeues and surfaces
                and task.capacity_requeues <= max(1, task.zmws).bit_length()):
            resources.default_governor().record_oom(
                task.capacity_bucket, max(1, task.zmws), device=w.name)
            resources.note_oom_split()
            self._log.warn(
                f"sched: capacity failure on {w.name} (bucket "
                f"{task.key!r}, {task.zmws} ZMW(s)): "
                f"{type(exc).__name__}: {exc}; requeueing for a "
                "governor-split re-dispatch on the same device")
            with self._cv:
                task.capacity_requeues += 1
                if not self._closed and not w.benched:
                    _m_requeues.inc()
                    self._enqueue_locked(w, task)
                    self._cv.notify_all()
                    return
            # pool closed (or the device benched) under us: surface
            task.future._finish(exc=exc)
            return
        # device-shaped = the failure modes that indicate SICK HARDWARE,
        # not a bad input: a hang (WatchdogTimeout), an XLA runtime error
        # (transient ones were already retried inside the dispatch by
        # DEVICE_RETRY, so one surfacing here is persistent -- including
        # RetriesExhausted wrapping a transient that never cleared), or
        # an injected chaos fault.  Plain Python exceptions (a poison
        # input escaping quarantine, a code bug) requeue WITHOUT striking
        # the device: benching cannot fix them, and with sticky routing a
        # stream of poison requests at one home would otherwise bench
        # healthy devices one by one.
        device_shaped = (
            isinstance(exc, (watchdog.WatchdogTimeout,
                             retry.RetriesExhausted,
                             faults.InjectedFault))
            or type(exc).__name__ == "XlaRuntimeError")
        tb = "".join(traceback.format_exception(type(exc), exc,
                                                exc.__traceback__))
        self._log.warn(
            f"sched: task (bucket {task.key!r}, {task.zmws} ZMW(s)) failed "
            f"on {w.name} with {type(exc).__name__}: {exc} "
            f"[device_shaped={device_shaped}]")
        self._log.debug(f"sched: {w.name} failure traceback:\n{tb}")
        stranded: list[_Task] = []
        with self._cv:
            w.failures += 1
            # only a task's FIRST failure strikes its device: a poisoned
            # task touring the fleet (same batch failing everywhere) is
            # task-shaped, not device-shaped, and must not bench every
            # device it visits -- a sick device still accumulates strikes
            # because each NEW task fails there first
            first_failure = not task.excluded
            task.excluded.add(w.index)
            if device_shaped and first_failure and not self._closed:
                w.strikes += 1
                healthy = sum(1 for x in self._workers if not x.benched)
                if (w.strikes >= self.config.bench_after and not w.benched
                        and healthy > 1):
                    stranded = self._bench_locked(w, exc)
                elif w.strikes >= self.config.bench_after:
                    self._log.warn(
                        f"sched: {w.name} reached {w.strikes} strike(s) but "
                        "is the last healthy device; keeping it in service")
            # requeue to a healthy device this task has not failed on --
            # NEVER after close(): a drained worker may already have
            # exited its loop, so a post-close requeue would park the
            # task on a dead deque and strand its future (close()'s
            # leftover sweep only covers requeues that happen before the
            # worker joins complete).  Task-shaped failures get ONE
            # healthy-device retry, not a tour: a deterministic bug
            # re-polishing on every device would cost fleet-size polish
            # durations just to surface the same error.  Device-shaped
            # failures keep touring -- each hop is evidence against a
            # device, and benching needs it.
            # Pinned tasks never requeue: the pin IS the point.
            if self._closed or task.pinned or (
                    not device_shaped and not first_failure):
                target = None
            else:
                try:
                    target = self._route_locked(task)
                except NoHealthyDevice:
                    target = None
            if target is not None:
                _m_requeues.inc()
                self._enqueue_locked(target, task)
                self._cv.notify_all()
                self._log.warn(
                    f"sched: requeued bucket {task.key!r} "
                    f"({task.zmws} ZMW(s)) {w.name} -> {target.name}")
        # futures complete OUTSIDE the pool lock: completion callbacks run
        # arbitrary caller code (the serve engine's replies can block on a
        # slow client socket) that must never stall the scheduler
        for t in stranded:
            t.future._finish(exc=NoHealthyDevice(
                f"bucket {t.key!r}: no eligible healthy device left "
                "(failed everywhere, or pinned to a benched device)"))
        if target is None:
            # out of devices (or the pool closed): the failure is the
            # caller's (the pipeline's quarantine/tally machinery
            # accounts the ZMWs; nothing is lost silently)
            task.future._finish(exc=exc)

    def _bench_locked(self, w: _Worker,
                      exc: BaseException) -> list[_Task]:
        """Take a sick device out of service; requeue its queued tasks.
        Caller holds the lock.  Returns tasks with no healthy device left
        -- the CALLER fails their futures after releasing the lock
        (completion callbacks must never run under the pool lock)."""
        w.benched = True
        _reg.counter("ccs_sched_device_benched_total",
                     "Devices benched by repeated device-shaped failures",
                     device=w.name).inc()
        queued = list(w.pending)
        w.pending.clear()
        w.m_depth.set(0)
        self._sticky.forget_member(w.index)
        self._log.error(
            f"sched: benching device {w.name} after {w.strikes} "
            f"device-shaped failure(s) (last: {type(exc).__name__}: {exc}); "
            f"requeuing {len(queued)} queued task(s)")
        stranded: list[_Task] = []
        for task in queued:
            if task.pinned:   # pinned to this now-benched device
                stranded.append(task)
                continue
            try:
                target = self._route_locked(task)
            except NoHealthyDevice:
                stranded.append(task)
                continue
            _m_requeues.inc()
            self._enqueue_locked(target, task)
        self._cv.notify_all()
        return stranded

    # -------------------------------------------------------------- lifecycle

    def close(self, wait: bool = True, *,
              join_timeout_s: float | None = None) -> None:
        """Stop the pool.  wait=True (default) drains queued tasks first;
        wait=False fails queued tasks with PoolClosed (running tasks
        still finish -- a device program cannot be interrupted).
        join_timeout_s (None = unbounded) caps the per-worker thread join
        so an abort-path caller (the serve engine's drain-deadline
        fallback) is not held hostage by a hung device program; a capped
        join may fail still-queued tasks with PoolClosed, so the default
        stays unbounded to honor the wait=True drain contract."""
        with self._cv:
            if self._closed:
                return
            self._closed = True
            stranded: list[_Task] = []
            if not wait:
                for w in self._workers:
                    stranded.extend(w.pending)
                    w.pending.clear()
                    w.m_depth.set(w.depth())
            self._cv.notify_all()
        for task in stranded:
            task.future._finish(exc=PoolClosed("device pool closed"))
        for w in self._workers:
            if w.thread is not None:
                w.thread.join(timeout=join_timeout_s)
        # a task requeued onto a worker that had already drained and
        # exited would otherwise strand with an incomplete future
        with self._lock:
            leftovers = [t for w in self._workers for t in w.pending]
            for w in self._workers:
                w.pending.clear()
                w.m_depth.set(0)
        for task in leftovers:
            task.future._finish(exc=PoolClosed("device pool closed"))
        self._log.info("device pool down")

    def __enter__(self) -> "DevicePool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------------ intro

    def status(self) -> dict:
        """Per-device breakdown (the serve `status` verb embeds this)."""
        with self._lock:
            bucket_count = {w.index: self._sticky.resident_count(w.index)
                            for w in self._workers}
            return {
                "policy": self.config.policy,
                "devices": [{
                    "device": w.name,
                    "benched": w.benched,
                    "busy": w.busy,
                    "queued": len(w.pending),
                    "strikes": w.strikes,
                    "tasks_done": w.tasks_done,
                    "failures": w.failures,
                    "buckets": bucket_count[w.index],
                } for w in self._workers],
            }
