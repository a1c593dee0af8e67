"""Per-ZMW trace spans with wall vs device-wait attribution.

A Tracer collects a span tree per thread (filter -> draft -> polish
rounds -> emit) and exports Chrome-trace/Perfetto JSON ("traceEvents"
with complete "X" events: load chrome://tracing or ui.perfetto.dev).
Wall time is the span's duration; device-wait seconds are attributed to
the INNERMOST open span of the thread that blocked
(runtime/timing.device_fetch routes its measured blocking time here), so
a polish span decomposes into host marshalling vs device wait
(docs/DESIGN.md, "The transfer-count rule").

Tracing is OFF unless a tracer is installed (CLI --trace-out, serve
`trace` verb); the disabled fast path is one global read per span() call
(it hands back one shared no-op context manager: no Span, no generator,
no profiler annotation), cheap enough to leave the instrumentation in
the hot pipeline.

While a tracer IS installed a span also records the CPU time its thread
got (`cpu_ms`: wall minus CPU minus device wait is time the thread was
runnable but not running -- the GIL, or a core it did not get) and, when
jax is already imported, enters `jax.profiler.TraceAnnotation("ccs:" +
name)`: under --profile-dir the program's spans then sit in the
.xplane.pb host plane on the profiler's own clock, beside the device
operations.

Cross-process trace context (the fleet observability plane): a span may
carry an inbound `ctx` dict -- ``{"trace_id": ..., "span_id": ...}``,
the wire shape of serve/protocol.py's `trace` submit field -- naming the
REMOTE parent it continues.  Children inherit the trace_id through the
per-thread stack, every context-bearing span exports a process-unique
`span_id`, and tools/trace_merge.py reassembles the per-request tree
across router and replica processes from exactly these three args
(trace_id / span_id / remote_parent).  Export metadata carries a
wall-clock origin so the merger can rebase each process's perf_counter
timeline onto one axis.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time
import uuid
from typing import Any, Iterator


def new_trace_id() -> str:
    """A fresh 16-hex-char request trace id (minted at the first tier
    that sees the request: client, or the router edge)."""
    return uuid.uuid4().hex[:16]


class Span:
    """One finished-or-open span; nesting is per-thread."""

    __slots__ = ("name", "args", "tid", "t0", "t1", "device_wait_s",
                 "cpu_s", "parent", "index", "trace_id", "remote_parent",
                 "sid", "open")

    def __init__(self, name: str, args: dict[str, Any], tid: int,
                 t0: float, parent: "Span | None", index: int,
                 trace_id: str | None = None,
                 remote_parent: str | None = None,
                 sid: str | None = None):
        self.name = name
        self.args = args
        self.tid = tid
        self.t0 = t0
        self.t1 = t0
        self.device_wait_s = 0.0
        self.cpu_s: float | None = None   # thread CPU time; None = not taken
        self.parent = parent
        self.index = index
        self.trace_id = trace_id
        self.remote_parent = remote_parent
        self.sid = sid          # explicit span id (router retro-spans)
        self.open = True

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0


class Tracer:
    """Collects spans; thread-safe; export once at the end of a capture.

    `max_spans` bounds the capture: a serve-side capture left running by
    a vanished client must not grow at traffic rate until the OOM killer
    ends the engine.  Past the cap new spans are counted (dropped_spans,
    surfaced in the export) but not recorded."""

    def __init__(self, max_spans: int = 200_000, tag: str | None = None):
        self.t_origin = time.perf_counter()
        # wall-clock anchor of the perf_counter origin: trace_merge
        # rebases per-process timelines onto one axis with it
        self.t_origin_unix = time.time()
        self.max_spans = max_spans
        # process tag: makes exported span_ids unique across the fleet's
        # processes so cross-process parent links cannot collide; the
        # random suffix matters because replicas span HOSTS (host:port
        # addressing) and bare pids collide across machines
        self.tag = tag or f"p{os.getpid():x}-{uuid.uuid4().hex[:6]}"
        self.dropped_spans = 0
        self._lock = threading.Lock()
        self._spans: list[Span] = []
        self._local = threading.local()

    # ------------------------------------------------------------- spans

    def _stack(self) -> list[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @contextlib.contextmanager
    def span(self, name: str, ctx: dict | None = None,
             **args) -> Iterator[Span | None]:
        """Record one span.  `ctx` is an inbound cross-process trace
        context ({"trace_id", "span_id"}): the span adopts its trace_id
        and records its span_id as the REMOTE parent; without ctx the
        trace_id is inherited from the enclosing span (if any)."""
        stack = self._stack()
        parent = stack[-1] if stack else None
        trace_id = remote_parent = None
        if ctx:
            trace_id = ctx.get("trace_id")
            remote_parent = ctx.get("span_id")
        elif parent is not None:
            trace_id = parent.trace_id
        with self._lock:
            if len(self._spans) >= self.max_spans:
                self.dropped_spans += 1
                sp = None
            else:
                index = len(self._spans)
                sp = Span(name, args, threading.get_ident() & 0xFFFFFFFF,
                          time.perf_counter(), parent, index,
                          trace_id=trace_id, remote_parent=remote_parent)
                self._spans.append(sp)
        if sp is None:
            yield None
            return
        stack.append(sp)
        # the profiler's clock: a no-op unless a jax.profiler capture is
        # live.  jax is never imported from here (`ccs --help` and the
        # pure-host tests stay off the backend).
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        note = (profiler.TraceAnnotation("ccs:" + name)
                if profiler is not None else contextlib.nullcontext())
        cpu0 = time.thread_time()
        try:
            with note:
                yield sp
        finally:
            sp.cpu_s = time.thread_time() - cpu0
            sp.t1 = time.perf_counter()
            sp.open = False
            stack.pop()

    def add_span(self, name: str, duration_s: float, *,
                 start_unix: float | None = None,
                 ctx: dict | None = None, span_id: str | None = None,
                 **args) -> Span | None:
        """Record a RETROACTIVE closed span (the router's per-request
        span: its lifetime is only known at completion).  It ends now
        unless `start_unix` (wall clock, seconds since the epoch) says
        where it began: then it sits where it happened, under the span
        the calling thread has open (a compile reported by jax after the
        fact, runtime/cache.py).  `span_id` pins the exported id so the
        forwarding tier could name this span as the remote parent BEFORE
        it was recorded."""
        duration_s = max(duration_s, 0.0)
        parent = None
        if start_unix is None:
            t0 = time.perf_counter() - duration_s
        else:
            t0 = self.t_origin + (start_unix - self.t_origin_unix)
            parent = self.current_span()
        trace_id = remote_parent = None
        if ctx:
            trace_id = ctx.get("trace_id")
            remote_parent = ctx.get("span_id")
        with self._lock:
            if len(self._spans) >= self.max_spans:
                self.dropped_spans += 1
                return None
            sp = Span(name, args, threading.get_ident() & 0xFFFFFFFF,
                      t0, parent, len(self._spans),
                      trace_id=trace_id, remote_parent=remote_parent,
                      sid=span_id)
            sp.t1 = t0 + duration_s
            sp.open = False
            self._spans.append(sp)
        return sp

    # ------------------------------------------------------------ context

    def span_id_of(self, sp: Span) -> str:
        """The span's fleet-unique exported id."""
        return sp.sid if sp.sid is not None else f"{self.tag}-{sp.index}"

    def context_of(self, sp: Span) -> dict | None:
        """The wire trace context continuing this span on the next hop
        (None when the span belongs to no trace)."""
        if sp.trace_id is None:
            return None
        return {"trace_id": sp.trace_id, "span_id": self.span_id_of(sp)}

    def current_span(self) -> Span | None:
        stack = self._stack()
        return stack[-1] if stack else None

    def add_device_wait(self, dt: float) -> None:
        """Attribute dt blocking seconds to the calling thread's innermost
        open span (no-op when the thread is not inside a span)."""
        stack = self._stack()
        if stack:
            stack[-1].device_wait_s += dt

    # ------------------------------------------------------------ reading

    def finished_spans(self) -> list[Span]:
        """Snapshot of spans recorded so far.  Open spans are included
        with `open` still True and t1 frozen at their start; the Chrome
        export tags them (args.open) and measures them to the capture
        instant so a mid-flight capture never renders zero-duration
        lies."""
        with self._lock:
            return list(self._spans)

    def to_chrome(self) -> dict[str, Any]:
        """Chrome-trace JSON object.  ts/dur are microseconds from the
        tracer's origin; device-wait attribution and the parent span index
        ride in args (the span TREE survives the round trip).  Spans
        still OPEN at capture time are tagged args.open=true with their
        duration measured up to the capture instant -- a mid-flight
        capture renders them honestly instead of as zero-duration lies.
        The `meta` block (dropped/open counts, process tag, wall-clock
        origin) is what tools/trace_merge.py keys the multi-process
        merge on."""
        now = time.perf_counter()
        open_spans = 0
        events = []
        for sp in self.finished_spans():
            args = dict(sp.args)
            args["device_wait_ms"] = round(sp.device_wait_s * 1e3, 3)
            if sp.cpu_s is not None:
                args["cpu_ms"] = round(sp.cpu_s * 1e3, 3)
            if sp.parent is not None:
                args["parent"] = sp.parent.index
            if sp.trace_id is not None:
                args["trace_id"] = sp.trace_id
                args["span_id"] = self.span_id_of(sp)
            elif sp.sid is not None:
                args["span_id"] = sp.sid
            if sp.remote_parent is not None:
                args["remote_parent"] = sp.remote_parent
            t1 = sp.t1
            if sp.open:
                open_spans += 1
                args["open"] = True
                t1 = max(now, sp.t0)
            events.append({
                "name": sp.name,
                "cat": "ccs",
                "ph": "X",
                "pid": 0,
                "tid": sp.tid,
                "ts": round((sp.t0 - self.t_origin) * 1e6, 1),
                "dur": round((t1 - sp.t0) * 1e6, 1),
                "id": sp.index,
                "args": args,
            })
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "meta": {"process": self.tag,
                         "origin_unix": self.t_origin_unix,
                         "dropped_spans": self.dropped_spans,
                         "open_spans": open_spans}}

    def write_json(self, path: str) -> None:
        # atomic publish (ccs-analyze ATM001): a truncated trace JSON is
        # unreadable by the Chrome viewer, so never leave a torn one
        from pbccs_tpu.resilience.resources import atomic_output

        with atomic_output(path, "trace") as f:
            json.dump(self.to_chrome(), f)


def span_tree(chrome: dict[str, Any]) -> dict[int | None, list[dict]]:
    """Rebuild parent -> children from an exported Chrome-trace object
    (the inverse of Tracer.to_chrome; trace smoke + round-trip tests)."""
    tree: dict[int | None, list[dict]] = {}
    for ev in chrome.get("traceEvents", []):
        tree.setdefault(ev.get("args", {}).get("parent"), []).append(ev)
    return tree


# ------------------------------------------------------------- global hook

_tracer: Tracer | None = None
_tracer_lock = threading.Lock()


def get_tracer() -> Tracer | None:
    return _tracer


def set_tracer(tracer: Tracer | None) -> Tracer | None:
    """Install (or clear) the process-wide tracer; returns the previous
    one so nested captures can restore it."""
    global _tracer
    with _tracer_lock:
        prev, _tracer = _tracer, tracer
    return prev


def install_tracer(tracer: Tracer) -> bool:
    """Compare-and-swap install: succeeds only when no capture is live.
    Concurrent owners (CLI --trace-out, serve trace verb) must use this,
    not set_tracer, so one cannot silently hijack the other's capture."""
    global _tracer
    with _tracer_lock:
        if _tracer is not None:
            return False
        _tracer = tracer
        return True


def clear_tracer(expected: Tracer) -> bool:
    """Compare-and-swap clear: uninstalls only if `expected` is still the
    live tracer (never tears down someone else's capture)."""
    global _tracer
    with _tracer_lock:
        if _tracer is not expected:
            return False
        _tracer = None
        return True


class _NoSpan:
    """What span() hands back while tracing is off: enters to None."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False


_NO_SPAN = _NoSpan()


def span(name: str, ctx: dict | None = None, **args):
    """Record a span on the installed tracer (a context manager yielding
    the Span, or None past the cap); no-op (one global read, one shared
    object) when tracing is off.  `ctx` carries an inbound cross-process
    trace context (see Tracer.span)."""
    t = _tracer
    if t is None:
        return _NO_SPAN
    return t.span(name, ctx=ctx, **args)


def add_device_wait(dt: float) -> None:
    t = _tracer
    if t is not None:
        t.add_device_wait(dt)


def current_context() -> dict | None:
    """Wire trace context of the calling thread's innermost open span on
    the installed tracer (None when tracing is off or the span carries
    no trace id) -- what a client attaches to an outbound submit."""
    t = _tracer
    if t is None:
        return None
    sp = t.current_span()
    if sp is None:
        return None
    return t.context_of(sp)
