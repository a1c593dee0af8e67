"""What every traffic driver shares: the run's context, the work
directory, device facts, the program's environment."""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import time

from . import manifest


def say(msg: str) -> None:
    print(msg, flush=True)


class BenchFailure(Exception):
    """The run cannot produce a result: exit non-zero, print no last line."""


def need(ok: bool, what: str) -> None:
    if not ok:
        raise BenchFailure(what)


@dataclasses.dataclass
class Context:
    cell: manifest.Cell
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    control: dict | None
    t_process: float                       # time.monotonic() at process start
    setup_parts: dict = dataclasses.field(default_factory=dict)

    @property
    def work(self) -> str:
        return os.path.join(manifest.ROOT, ".bench_work", self.cell.name)

    def fresh_work(self) -> str:
        shutil.rmtree(self.work, ignore_errors=True)
        os.makedirs(self.work)
        return self.work

    def param(self, key: str, default=None):
        """A traffic parameter; `--rehearse` takes the file's `rehearse`
        override where it has one."""
        t = self.cell.traffic
        if self.rehearse and key in t.get("rehearse", {}):
            return t["rehearse"][key]
        return t.get(key, default)

    @property
    def library(self) -> dict:
        lib = dict(self.cell.config["library"])
        if self.rehearse:
            lib.update(self.cell.config.get("rehearse_library", {}))
        return lib

    @property
    def check(self) -> dict:
        """The configuration's `check` block; `--rehearse` takes the limits
        of `rehearse_check`, read at the rehearsal's sizes."""
        spec = dict(self.cell.config["check"])
        if self.rehearse:
            spec.update(self.cell.config.get("rehearse_check", {}))
        return spec

    def timed(self, part: str):
        return _Timed(self.setup_parts, part)


class _Timed:
    def __init__(self, parts: dict, part: str):
        self.parts, self.part = parts, part

    def __enter__(self):
        self.t0 = time.monotonic()

    def __exit__(self, *exc):
        self.parts[self.part] = (self.parts.get(self.part, 0.0)
                                 + time.monotonic() - self.t0)


def program_env(ctx: Context) -> dict:
    """Environment changes for the program: `--rehearse` holds JAX to the
    CPU with both kernels interpreted.  Nothing else is set."""
    env = {}
    if ctx.rehearse:
        env.update(JAX_PLATFORMS="cpu", PBCCS_PALLAS="1", PBCCS_DENSE="1")
        if ctx.cell.chips > 1:
            env["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                f" --xla_force_host_platform_device_count={ctx.cell.chips}").strip()
    return env


def device_facts() -> dict:
    """Platform, kind, count and kernel modes as JAX and the program
    report them, from inside the process that holds the chip."""
    import jax

    from pbccs_tpu import native
    from pbccs_tpu.ops import dense_score_pallas, fwdbwd_pallas

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "jax": jax.__version__,
            "cache_dir": jax.config.jax_compilation_cache_dir,
            "native_library": bool(native.available()),
            "fills_use_pallas": bool(fwdbwd_pallas.fills_use_pallas()),
            "dense_score_enabled": bool(dense_score_pallas.dense_score_enabled()),
            "interpreted": bool(fwdbwd_pallas._interpret()
                                or dense_score_pallas._interpret())}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device of this process."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def check_device(facts: dict, ctx: Context) -> None:
    say("device: " + json.dumps(facts))
    if not ctx.rehearse:
        need(facts["platform"] == "tpu",
             f"no TPU: jax reports platform {facts['platform']!r}")
        need(not facts["interpreted"], "a Pallas kernel is in interpret mode")
        need(facts["count"] >= ctx.cell.chips,
             f"the cell asks for {ctx.cell.chips} chips, jax sees {facts['count']}")
    need(facts["native_library"], "the native host library is not loaded")
    need(facts["fills_use_pallas"], "the Pallas fill kernel is off")
    need(facts["dense_score_enabled"], "the dense scoring kernel is off")


def spans_on_wall_clock(doc: dict | None) -> list:
    """Chrome events of one span capture (`--trace-out`), `ts` moved from
    the tracer's origin onto the wall clock (us since the epoch), so that
    captures and the device trace share an axis."""
    if not doc:
        return []
    origin_us = doc["meta"]["origin_unix"] * 1e6
    for ev in doc["traceEvents"]:
        ev["ts"] += origin_us
    return doc["traceEvents"]


def start_device_trace(out_dir: str) -> float:
    """Start jax.profiler into `out_dir` without its Python tracer (which
    slows the host it measures); returns the wall clock at the start."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    t0 = time.time()
    jax.profiler.start_trace(out_dir, profiler_options=options)
    return t0


@dataclasses.dataclass
class Window:
    """What one measured window produced, in the form run.py reduces."""
    attempted: int
    results: list                  # one dict for each ZMW answered
    zmws: dict                     # hole -> generated ZMW
    end_to_end: dict               # metric name -> value (host clock)
    notes: list                    # lines for the log
    counters: object = None        # prom.Counters over the window
    spans: list = dataclasses.field(default_factory=list)    # chrome events, wall-clock us
    xplane: str | None = None      # path of the device trace
    trace_wall: tuple | None = None     # (unix start, unix end) of the device trace
    traced_zmws: int = 0           # ZMWs of the invocations the spans cover
