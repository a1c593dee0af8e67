"""Traffic driver `batch_cli`: the batch CLI on subread BAM files.

`pbccs_tpu.cli.run` is called in this process, as `ccs OUT.bam IN.bam`
would run it, once for warm-up and then back to back in the window, each
time on another file of ZMWs drawn from the seed.  The clock of an
invocation closes when `cli.run` returns: BAM and report are on disk.

Warm-up is whole invocations on files 0, 1, .. until one loads and compiles
no program (at most `warmup_files_max`).  The window's files are
`warmup_files_max`, .. whatever warm-up took, so a seed always times the
same files.

Traffic parameters: `zmws_per_file`, `window_files` (the most files a
window may use), `warmup_files_max`, `cli_args` (flags beyond the defaults,
e.g. `--devices 4`),
`trace` {`start_s`, `seconds`}: where in the window's second invocation the
device trace is taken.
"""

from __future__ import annotations

import glob
import json
import os
import threading
import time

from harness import arith, bam, common, prom, simulate
from harness.common import Context, Window, need, program_env, say

REPORT_SUCCESS = "Success -- CCS generated"
REPORT_OTHER = "Failed -- Exception thrown"


class Session:
    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.n = int(ctx.param("zmws_per_file"))
        self.cli_args = list(ctx.param("cli_args", []))
        if ctx.control:
            self.cli_args += ctx.control.get("cli_args", [])

    # ------------------------------------------------------------ set-up
    def _make_file(self, seed: int, index: int) -> tuple[str, dict]:
        """File `index` of a seed: ZMWs index*n .. index*n + n - 1."""
        zmws = simulate.make_zmws(seed, 0, index * self.n, self.n, self.ctx.library)
        path = os.path.join(self.ctx.work, f"s{seed}_f{index}.subreads.bam")
        bam.write_subread_bam(path, zmws)
        return path, {z["hole"]: z for z in zmws}

    def _files(self, seed: int, first: int, count: int) -> list:
        return [self._make_file(seed, k) for k in range(first, first + count)]

    def setup(self) -> dict:
        ctx = self.ctx
        os.environ.update(program_env(ctx))
        ctx.fresh_work()
        self.warm_max = int(ctx.param("warmup_files_max"))
        with ctx.timed("data_s"):
            self.files = self._files(ctx.seed, self.warm_max,
                                     int(ctx.param("window_files")))
        with ctx.timed("import_s"):
            import jax  # noqa: F401

            from pbccs_tpu import cli, native
            from pbccs_tpu.obs.metrics import default_registry
            from pbccs_tpu.runtime.cache import enable_compilation_cache
        self.cli, self.registry = cli, default_registry()
        with ctx.timed("native_library_s"):
            native.available()
        with ctx.timed("device_init_s"):
            enable_compilation_cache()
            facts = common.device_facts()
        common.check_device(facts, ctx)
        for k in range(self.warm_max):
            with ctx.timed("data_s"):
                self.warm_file = self._make_file(ctx.seed, k)[0]
            before = self._counters()
            with ctx.timed("warmup_invocations_s"):
                wall, self.warm_out = self._invoke(self.warm_file, f"warmup{k}", None)
            moved = prom.Counters(before, self._counters())
            say(f"setup: warm-up invocation {k}: {wall:.3f} s; {moved.programs_text()}")
            if sum(moved.programs()) == 0:
                break
        else:
            say(f"setup: {self.warm_max} warm-up invocations and the last still "
                "loaded a program")
        return facts

    def _counters(self) -> dict:
        return prom.parse(self.registry.render_prometheus())

    def _invoke(self, path: str, tag: str, trace_out: str | None):
        out = os.path.join(self.ctx.work, f"{tag}.ccs.bam")
        argv = [out, path, "--reportFile", out + ".csv", "--logFile", out + ".log"]
        if trace_out:
            argv += ["--trace-out", trace_out]
        t0 = time.monotonic()
        rc = self.cli.run(argv + self.cli_args)
        wall = time.monotonic() - t0
        need(rc == 0, f"{tag}: the batch CLI returned {rc}; see {out}.log")
        return wall, out

    # ------------------------------------------------------------ window
    def window(self, seed: int, seconds: float, trace: bool) -> Window:
        ctx = self.ctx
        files = (self.files if seed == ctx.seed
                 else self._files(seed, self.warm_max, int(ctx.param("window_files"))))
        min_files = min(2, len(files))
        before = self._counters()
        walls, outs, spans, zmws = [], [], [], {}
        xplane = trace_wall = None
        tracer = None
        t_begin = time.monotonic()
        for k, (path, truth) in enumerate(files):
            left = seconds - (time.monotonic() - t_begin)
            if k >= min_files and left < walls[-1]:
                break
            span_file = os.path.join(ctx.work, f"spans{k}.json") if trace else None
            if trace and k == min(1, len(files) - 1):
                tracer = _DeviceTrace(os.path.join(ctx.work, "xplane"),
                                      **ctx.param("trace"))
                tracer.start()
            wall, out = self._invoke(path, f"s{seed}_w{k}", span_file)
            walls.append(wall)
            outs.append((out, truth))
            zmws.update(truth)
            if span_file:
                with open(span_file) as f:
                    spans += common.spans_on_wall_clock(json.load(f))
        if tracer:
            xplane, trace_wall = tracer.finish()
        after = self._counters()
        n_done = len(walls) * self.n
        results, notes = [], []
        for out, truth in outs:
            results += _read_results(out, truth, notes)
        notes.append(f"window: {len(walls)} invocations of {self.n} ZMWs, walls "
                     + ", ".join(f"{w:.3f}" for w in walls) + " s")
        return Window(attempted=n_done, results=results, zmws=zmws,
                      end_to_end={"zmws_per_s": arith.rate(n_done, sum(walls))},
                      notes=notes, counters=prom.Counters(before, after),
                      spans=spans, xplane=xplane, trace_wall=trace_wall,
                      traced_zmws=n_done)

    def repeat_check(self) -> bool:
        """The last warm-up file again: the same input gives the same bytes."""
        _wall, again = self._invoke(self.warm_file, "warmup_again", None)
        with open(self.warm_out, "rb") as a, open(again, "rb") as b:
            return a.read() == b.read()

    def memory_peak_bytes(self) -> int:
        return common.memory_peak_bytes()

    def close(self) -> None:
        pass


def _read_results(out: str, truth: dict, notes: list) -> list:
    """One entry for each ZMW the report counts: Success entries carry the
    BAM record, the others only the report's category."""
    with open(out + ".csv") as f:
        rows = {r[0]: int(r[1]) for r in
                (line.strip().split(",") for line in f) if len(r) == 3}
    recs = bam.read_bam(out)
    need(len(recs) == rows.get(REPORT_SUCCESS, 0),
         f"{out}: {len(recs)} records for {rows.get(REPORT_SUCCESS, 0)} successes")
    gated = {k: v for k, v in rows.items() if v and k != REPORT_SUCCESS}
    if gated:
        notes.append(f"yield: {os.path.basename(out)}: {json.dumps(gated)}")
    results = [{"hole": int(r["tags"]["zm"]), "status": "Success",
                "seq": r["seq"], "qual": r["qual"], "pq": float(r["tags"]["pq"]),
                "passes": int(r["tags"]["np"]), "degraded": "df" in r["tags"]}
               for r in recs]
    # the report has no hole numbers: gated ZMWs are the holes without a record
    missing = iter(sorted(set(truth) - {r["hole"] for r in results}))
    for status, count in gated.items():
        for _ in range(count):
            hole = next(missing, None)
            if hole is not None:
                results.append({"hole": hole, "status":
                                "Other" if status == REPORT_OTHER else status})
    return results


class _DeviceTrace:
    """jax.profiler around a few seconds of the window, from a thread of
    its own: `start_s` after the invocation began, for `seconds`."""

    def __init__(self, out_dir: str, start_s: float, seconds: float):
        self.out_dir, self.start_s, self.seconds = out_dir, start_s, seconds
        self.wall = None
        self.error = None
        self.thread = threading.Thread(target=self._run, daemon=True)

    def start(self) -> None:
        self.thread.start()

    def _run(self) -> None:
        import jax

        try:
            time.sleep(self.start_s)
            t0 = common.start_device_trace(self.out_dir)
            time.sleep(self.seconds)
            jax.profiler.stop_trace()
            self.wall = (t0, time.time())
        except Exception as e:  # noqa: BLE001 -- reported by finish()
            self.error = e

    def finish(self):
        self.thread.join()
        need(self.error is None, f"device trace failed: {self.error!r}")
        found = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                          recursive=True)
        need(bool(found), "the profiler wrote no .xplane.pb")
        return max(found, key=os.path.getmtime), self.wall
