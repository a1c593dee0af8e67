"""Traffic driver `serve_closed`: a closed loop of sessions on `ccs serve`.

`pbccs_tpu.serve.server.run_serve` is started in a thread of this process,
as `ccs serve <serve_args>` would run it (the device trace, the registry
and peak memory are then the server's), and one child process (this file
run as a script; it imports neither jax nor the program) holds `sessions`
TCP connections to it, each with exactly one ZMW in flight and no think
time: a session sends its next ZMW when the last one's result line
arrives.  The child speaks the documented NDJSON protocol with a client of
the benchmark's own, makes the ZMWs (decks of `deck_zmws` with as many at
each pass count, shuffled from the seed: `batch_cli_dealt.dealt_passes`),
and reads every latency on its own clock, from a submit's send to its
result line.  `time.monotonic()` is one clock for both processes.

Set-up: the child makes the pool while the server loads its programs
(`serve_args` declares the deployment, `--bucket`); the driver asks
`status` and fails before a ZMW is sent if it names no warmed shape set;
then waves of `wave_zmws` ZMWs (deck 0) go through the sessions, uncounted,
until a wave loads no program, `waves_max` at most, and it fails if the
last one still did.  Window: the loop starts at deck 1 and `seconds` are
counted from that instant (the pipeline's ramp, about one flush's latency
with nothing answered, is inside them): `zmws_per_s` is the ZMWs answered
inside them over their length, gated ZMWs too.  Then the sessions stop
sending, what is in flight drains uncounted, and every ZMW sent is checked.
A pool that runs out fails the run: nothing is sent twice.  While a window
runs, `_StallWatch` holds the process to its own clock: ticks that stop for
more than a second leave every thread's stack on stderr.

Traffic parameters: `sessions`, `serve_args`, `deck_zmws`, `pool_decks`,
`wave_zmws`, `waves_max`, `ready_timeout_s`, `client_cpus` (cores
the child is pinned to, off the server's, where the machine has eight or
more), `trace` {`start_s`, `seconds`}: where in the window the device
trace is taken.  `guarantees` and `assumed` are for the reader.
"""

from __future__ import annotations

import faulthandler
import itertools
import json
import os
import pickle
import socket
import statistics
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":               # the child: benchmark/ on the path
    sys.path.insert(0, os.path.dirname(HERE))

import numpy as np  # noqa: E402

from harness import common, manifest, prom, simulate  # noqa: E402
from harness.common import BenchFailure, Context, Window, need, program_env, say  # noqa: E402

PROGRAM_LOAD = "ccs_program_load_seconds_total"
SHAPE_SETS = "ccs_polish_shape_sets_total"
ANSWER = ("sequence", "qual", "predicted_accuracy", "status")


# ===================================================================== parent
class Session:
    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.serve_args = list(ctx.param("serve_args"))
        if ctx.control:
            self.serve_args += ctx.control.get("cli_args", [])
        self.deck = int(ctx.param("deck_zmws"))
        self.child = None
        self.control = None
        self.server = None
        self.server_stop = threading.Event()     # what SIGTERM sets in `ccs serve`
        self.warm_records = []
        self.warm_spans = []

    # ------------------------------------------------------------ set-up
    def setup(self) -> dict:
        ctx = self.ctx
        os.environ.update(program_env(ctx))
        ctx.fresh_work()
        client_cpus = self._split_cpus(int(ctx.param("client_cpus", 0)))
        self._start_child(client_cpus)
        self._tell({"cmd": "pool", "seed": ctx.seed,
                    "decks": int(ctx.param("pool_decks"))})
        with ctx.timed("import_s"):
            import jax  # noqa: F401

            from pbccs_tpu import native
            from pbccs_tpu.obs import trace as obs_trace
            from pbccs_tpu.obs.metrics import default_registry
            from pbccs_tpu.runtime.cache import enable_compilation_cache
            from pbccs_tpu.serve import server
        self.registry = default_registry()
        with ctx.timed("native_library_s"):
            native.available()
        with ctx.timed("device_init_s"):
            enable_compilation_cache()
            facts = common.device_facts()
        common.check_device(facts, ctx)

        capture = None
        if ctx.trace:        # `serve.warm` closes before any verb can ask
            capture = obs_trace.Tracer()
            need(obs_trace.install_tracer(capture), "a span capture is already installed")
        with ctx.timed("serve_ready_s"):
            try:
                port = self._start_server(server.run_serve)
            finally:
                if capture is not None:
                    obs_trace.clear_tracer(capture)
        if capture is not None:
            self.warm_spans = [e for e in common.spans_on_wall_clock(capture.to_chrome())
                               if e["name"] == "serve.warm"]
        self.control = _Control("127.0.0.1", port)
        status = self.control.call("status")
        warmed = status.get("warmed") or []
        sets = sum(len(w.get("shape_sets", [])) for w in warmed)
        say(f"setup: the server is ready on port {port}; status names {sets} warmed "
            f"shape set(s): {json.dumps(warmed)}")
        if ctx.control:
            say("setup: CONTROL: the warmed-set and warm-wave checks are skipped")
        else:
            need(sets > 0, "`status` names no warmed shape set: this `ccs serve` "
                 "loads its programs inside traffic, and a window cannot hold it")
        with ctx.timed("data_s"):
            pool = self._reply()
        say(f"setup: pool of {pool['zmws']} ZMWs made by the client in {pool['seconds']:.3f} s")
        self.pools = {ctx.seed: pool["path"]}
        self._ask({"cmd": "connect", "host": "127.0.0.1", "port": port,
                   "sessions": int(ctx.param("sessions"))})
        n_wave, waves_max = int(ctx.param("wave_zmws")), int(ctx.param("waves_max"))
        need(n_wave * waves_max <= self.deck, "the warm waves do not fit deck 0")
        for k in range(waves_max):
            before = self._counters()
            with ctx.timed("warm_waves_s"):
                got = self._ask({"cmd": "wave", "seed": ctx.seed,
                                 "indices": list(range(k * n_wave, (k + 1) * n_wave)),
                                 "out": os.path.join(ctx.work, f"wave{k}.json")})
            moved = prom.Counters(before, self._counters())
            loaded = any((*moved.programs(), moved.moved(SHAPE_SETS),
                          moved.moved(PROGRAM_LOAD)))
            say(f"setup: warm wave {k}: {n_wave} ZMWs in {got['seconds']:.3f} s; "
                f"{moved.programs_text()}, shape sets {moved.moved(SHAPE_SETS):.0f}, "
                f"program load {moved.moved(PROGRAM_LOAD):.3f} s")
            self.warm_records += _load(got["out"])
            if not loaded:
                break
        else:
            need(bool(ctx.control), f"{waves_max} warm waves and the last still loaded a "
                 "program: the server's warm-up does not cover its traffic")
        return facts

    def _split_cpus(self, want: int) -> list:
        """The child's cores, taken off this process's (the server's)
        before any thread exists; none on a small machine."""
        cores = sorted(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity") else []
        if not want or len(cores) < 8:
            return []
        os.sched_setaffinity(0, cores[:-want])
        say(f"setup: server on cores {cores[:-want]}, client on {cores[-want:]}")
        return cores[-want:]

    def _start_child(self, cpus: list) -> None:
        spec = {"library": self.ctx.library, "deck_zmws": self.deck,
                "work": self.ctx.work, "cpus": cpus}
        self.child = subprocess.Popen(
            [sys.executable, "-u", os.path.abspath(__file__), json.dumps(spec)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)

    def _start_server(self, run_serve) -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        argv = ["--host", "127.0.0.1", "--port", str(port)] + self.serve_args
        say("setup: ccs serve " + " ".join(argv))
        self.server = threading.Thread(target=run_serve, args=(argv, self.server_stop),
                                       daemon=True, name="bench-ccs-serve")
        self.server.start()
        give_up = time.monotonic() + float(self.ctx.param("ready_timeout_s"))
        while time.monotonic() < give_up:
            need(self.server.is_alive(), "`ccs serve " + " ".join(self.serve_args)
                 + "` exited before it was ready")
            try:
                socket.create_connection(("127.0.0.1", port), timeout=1.0).close()
                return port
            except OSError:
                time.sleep(0.2)
        raise BenchFailure("`ccs serve` was not ready in time")

    def _counters(self) -> dict:
        return prom.parse(self.registry.render_prometheus())

    # ----------------------------------------------------- child's pipe
    def _tell(self, msg: dict) -> None:
        try:
            self.child.stdin.write(json.dumps(msg) + "\n")
            self.child.stdin.flush()
        except OSError as e:
            raise BenchFailure(f"the client process is gone: {e}") from None

    def _reply(self) -> dict:
        line = self.child.stdout.readline()
        need(bool(line), f"the client process ended (exit {self.child.poll()})")
        msg = json.loads(line)
        need("error" not in msg, f"client: {msg.get('error')}")
        return msg

    def _ask(self, msg: dict) -> dict:
        self._tell(msg)
        return self._reply()

    # ------------------------------------------------------------ window
    def window(self, seed: int, seconds: float, trace: bool) -> Window:
        ctx = self.ctx
        if seed not in self.pools:
            got = self._ask({"cmd": "pool", "seed": seed,
                             "decks": int(ctx.param("pool_decks"))})
            self.pools[seed] = got["path"]
        out = os.path.join(ctx.work, f"loop_s{seed}.json")
        tracer = None
        if trace:
            need(self.control.call("trace", action="start").get("state") == "started",
                 "the server would not start a span capture")
            batch_cli = manifest.load_by_path("drivers", "batch_cli")
            tracer = batch_cli._DeviceTrace(os.path.join(ctx.work, "xplane"),
                                            **ctx.param("trace"))
            tracer.start()
        before = self._counters()
        with _StallWatch() as watch:
            t0 = time.monotonic()
            self._ask({"cmd": "loop", "seed": seed, "first": self.deck, "out": out})
            time.sleep(max(0.0, t0 + seconds - time.monotonic()))
            t1 = time.monotonic()
        after = self._counters()
        spans = []
        if trace:
            spans = common.spans_on_wall_clock(
                self.control.call("trace", action="stop").get("trace"))
        done = self._ask({"cmd": "stop"})
        xplane = trace_wall = None
        if tracer:
            xplane, trace_wall = tracer.finish()
        # (a control that skips the polish answers faster than any pool lasts)
        need(not done["exhausted"] or bool(ctx.control),
             f"the pool of {done['sent']} ZMWs ran out inside the window: nothing is "
             "sent twice, give the traffic file more decks")
        records = _load(out)
        need(len(records) == done["sent"], f"{done['sent']} ZMWs sent, {len(records)} answered")
        with open(self.pools[seed], "rb") as f:
            zmws = {z["hole"]: z for z in pickle.load(f)}
        inside = [r for r in records if t0 <= r["t_recv"] < t1]
        lat = sorted((r["t_recv"] - r["t_send"]) * 1e3 for r in inside)
        need(len(lat) >= 2 or bool(ctx.control),
             f"{len(lat)} ZMWs answered inside the window")
        lat = lat or [0.0]
        # the readers' own arithmetic, on the client's samples
        quantile = manifest.load_by_path("metrics", "serve_latency_p50_ms").of
        p50, p95 = quantile(lat, 0.50), quantile(lat, 0.95)
        notes = [f"window: {len(inside)} ZMWs answered inside {t1 - t0:.3f} s by "
                 f"{ctx.param('sessions')} sessions ({done['sent']} sent from the loop's "
                 f"start to the drain's end); client-side latency p50 {p50:.3f} ms, "
                 f"p95 {p95:.3f} ms, mean {statistics.fmean(lat):.3f} ms"]
        served = sorted(e["dur"] / 1e3 for e in spans if e["name"] == "serve.request")
        # where a low rate came from: a silence of seconds that this process's
        # ticks overran and the client's did not is a thread here holding the
        # GIL (its stack is on stderr); one that both overran is the machine's
        stamps = [t0] + sorted(r["t_recv"] for r in inside) + [t1]
        notes.append(f"window: longest silence between two answers "
                     f"{max(b - a for a, b in zip(stamps, stamps[1:])):.3f} s; the harness's "
                     f"sleep overran {t1 - t0 - seconds:.3f} s, its {_StallWatch.TICK_S * 1e3:.0f} "
                     f"ms ticks {watch.worst_s:.3f} s at most, the client's own 50 ms ticks "
                     f"{done['stall_s']:.3f} s (from the loop's start)")
        if served:
            s50, s95 = quantile(served, 0.50), quantile(served, 0.95)
            notes.append(f"window: {len(served)} `serve.request` spans: p50 {s50:.3f} ms, "
                         f"p95 {s95:.3f} ms")
            for name, client, server in (("p50", p50, s50), ("p95", p95, s95)):
                need(abs(client - server) <= 5.0 + 0.02 * client,
                     f"latency {name}: the client reads {client:.3f} ms, the server's "
                     f"`serve.request` spans {server:.3f}: more than 5 ms + 2 % apart")
        flushes = [e["args"] for e in spans if e["name"] == "serve.flush"]
        if flushes:
            held = sorted(f["zmws"] for f in flushes)
            notes.append(
                f"window: {len(flushes)} flushes ("
                + ", ".join(f"{k} {sum(f['reason'] == k for f in flushes)}"
                            for k in sorted({f["reason"] for f in flushes}))
                + f"), ZMWs held min {held[0]}, median {held[len(held) // 2]}, max "
                f"{held[-1]}, at (Z, R, Jmax) "
                + str(sorted({(f["z"], f["r"], f["jmax"]) for f in flushes})))
        if seed == ctx.seed:          # every ZMW ever sent is checked
            records = self.warm_records + records
            self.warm_records = []
        notes += _reply_notes(records)
        return Window(attempted=len(records), results=[_result(r) for r in records],
                      zmws=zmws,
                      end_to_end={"zmws_per_s": len(inside) / (t1 - t0)},
                      notes=notes, counters=prom.Counters(before, after),
                      spans=spans + self.warm_spans, xplane=xplane,
                      trace_wall=trace_wall, traced_zmws=len(inside))

    def repeat_check(self) -> bool:
        """The first warm wave again, in another order: the same sequence,
        QV string, predicted accuracy and status for every ZMW, whatever
        flush it now shares with whom."""
        n = int(self.ctx.param("wave_zmws"))
        first = {r["hole"]: r for r in _load(os.path.join(self.ctx.work, "wave0.json"))}
        order = [int(i) for i in np.random.default_rng([self.ctx.seed, 0x5E2E]).permutation(n)]
        got = self._ask({"cmd": "wave", "seed": self.ctx.seed, "indices": order,
                         "out": os.path.join(self.ctx.work, "wave0_again.json")})
        again = {r["hole"]: r for r in _load(got["out"])}
        differ = [h for h in first if [first[h]["reply"].get(k) for k in ANSWER]
                  != [again.get(h, {"reply": {}})["reply"].get(k) for k in ANSWER]]
        say(f"repeat: {len(first)} ZMWs sent again in another order, "
            f"{len(differ)} answers differ" + (f": holes {differ[:8]}" if differ else ""))
        return not differ and len(again) == len(first)

    def memory_peak_bytes(self) -> int:
        return common.memory_peak_bytes()

    def close(self) -> None:
        if self.control:
            self.control.close()
        if self.child and self.child.poll() is None:
            try:
                self._tell({"cmd": "quit"})
                self.child.wait(timeout=10)
            except (BenchFailure, subprocess.TimeoutExpired):
                self.child.kill()
                self.child.wait()
        if self.server is not None:       # drain as a TERM would: nothing is in flight
            self.server_stop.set()
            self.server.join(timeout=30.0)


class _StallWatch:
    """This process's own clock while a window runs.  A thread ticks every
    TICK_S and re-arms `faulthandler`'s watchdog each time; that watchdog is
    a C thread and needs no GIL, so ticks that stop for more than STALL_S (a
    thread of the server holding the GIL in a call that does not release it,
    or the machine) leave every thread's stack on stderr WHILE the stall
    lasts.  `worst_s`: the longest a tick overran."""

    TICK_S, STALL_S = 0.25, 1.0

    def __init__(self):
        self.worst_s = 0.0
        self._done = threading.Event()
        self._thread = threading.Thread(target=self._tick, daemon=True,
                                        name="bench-stall-watch")

    def _tick(self) -> None:
        while not self._done.is_set():
            faulthandler.dump_traceback_later(self.TICK_S + self.STALL_S, file=sys.stderr)
            t = time.monotonic()
            self._done.wait(self.TICK_S)
            self.worst_s = max(self.worst_s, time.monotonic() - t - self.TICK_S)
        faulthandler.cancel_dump_traceback_later()

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._done.set()
        self._thread.join()


def _load(path: str) -> list:
    with open(path) as f:
        return json.load(f)


def _result(rec: dict) -> dict:
    """A reply in the form `checks.check_results` reads: a result line with
    its yield status, `draft_only` as `degraded`; an error reply (or a
    session the server closed) as an `error` entry."""
    reply = rec["reply"]
    if reply.get("type") != "result":
        return {"hole": rec["hole"], "status": "error"}
    out = {"hole": rec["hole"], "status": reply["status"]}
    if "sequence" in reply:
        out.update(seq=reply["sequence"], qual=reply["qual"],
                   pq=float(reply["predicted_accuracy"]),
                   passes=int(reply["num_passes"]),
                   degraded=bool(reply.get("draft_only")))
    return out


def _reply_notes(records: list) -> list:
    kinds = {}
    for r in records:
        reply = r["reply"]
        kind = (reply.get("status") if reply.get("type") == "result"
                else f"{reply.get('type')}:{reply.get('code', reply.get('reason'))}")
        if reply.get("draft_only"):
            kind += " (draft_only)"
        kinds[kind] = kinds.get(kind, 0) + 1
    return ["yield: " + json.dumps(dict(sorted(kinds.items())))]


class _Control:
    """The driver's own session for the `status` and `trace` verbs."""

    def __init__(self, host: str, port: int):
        self.sock = socket.create_connection((host, port), timeout=120.0)
        self.lines = self.sock.makefile("rb")
        self.ids = itertools.count(1)

    def call(self, verb: str, **fields) -> dict:
        rid = f"bench-{next(self.ids)}"
        self.sock.sendall(json.dumps({"verb": verb, "id": rid, **fields}).encode() + b"\n")
        while True:
            line = self.lines.readline()
            need(bool(line), f"the server closed the control session during `{verb}`")
            msg = json.loads(line)
            if msg.get("id") == rid:
                need(msg.get("type") != "error", f"`{verb}`: {msg}")
                return msg

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:
            pass


# ====================================================================== child
class _Client:
    """The client process: pools of ZMWs as NDJSON frames, and `sessions`
    connections that each keep one ZMW in flight."""

    def __init__(self, spec: dict):
        self.spec = spec
        self.pools = {}            # seed -> list of (hole, frame)
        self.socks = []
        self.stop = threading.Event()
        self.lock = threading.Lock()

    def pool(self, seed: int, decks: int) -> dict:
        t0 = time.monotonic()
        dealt = manifest.load_by_path("drivers", "batch_cli_dealt")
        library, n = self.spec["library"], self.spec["deck_zmws"]
        zmws = []
        for d in range(decks):
            for i, k in enumerate(dealt.dealt_passes(seed, d, n, library["passes"])):
                zmws.append(simulate.make_zmw(
                    seed, 0, d * n + i, dict(library, passes={"dist": "fixed", "value": k})))
        path = os.path.join(self.spec["work"], f"pool_s{seed}.pkl")
        with open(path, "wb") as f:
            pickle.dump(zmws, f)
        self.pools[seed] = [(z["hole"], _frame(z)) for z in zmws]
        return {"zmws": len(zmws), "path": path, "seconds": time.monotonic() - t0}

    def connect(self, host: str, port: int, sessions: int) -> dict:
        for _ in range(sessions):
            s = socket.create_connection((host, port), timeout=600.0)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self.socks.append((s, s.makefile("rb")))
        return {"sessions": len(self.socks)}

    def run(self, seed: int, indices, out: str, until_stopped: bool):
        """Closed loop over `indices` of the seed's pool: each session
        takes the next one when its last is answered.  Returns what the
        reply line carries once every session is idle."""
        pool = self.pools[seed]
        todo = iter(indices)
        records, state = [], {"exhausted": False, "sent": 0}
        self.stop.clear()

        def take():
            with self.lock:
                if self.stop.is_set():
                    return None
                k = next(todo, None)
                if k is None:
                    state["exhausted"] = until_stopped
                else:
                    state["sent"] += 1
                return k

        def session(sock, lines):
            while (k := take()) is not None:
                hole, frame = pool[k]
                t_send = time.monotonic()
                reply = {"type": "lost"}
                try:
                    sock.sendall(frame)
                    while line := lines.readline():
                        reply = json.loads(line)
                        if reply.get("id") == f"z{hole}" or reply.get("type") == "closed":
                            break
                except OSError as e:
                    reply = {"type": "lost", "code": repr(e)}
                rec = {"hole": hole, "t_send": t_send, "t_recv": time.monotonic(),
                       "reply": reply}
                with self.lock:
                    records.append(rec)
                if reply.get("type") in ("closed", "lost"):
                    return

        def ticker():
            """This process's own clock: the longest a 50 ms sleep overran.
            A client that stalls when the server does points at the machine."""
            while not idle.is_set():
                t = time.monotonic()
                time.sleep(0.05)
                state["stall_s"] = max(state["stall_s"], time.monotonic() - t - 0.05)

        idle, state["stall_s"] = threading.Event(), 0.0
        threads = [threading.Thread(target=session, args=s, daemon=True) for s in self.socks]
        t0 = time.monotonic()
        for t in threads + [threading.Thread(target=ticker, daemon=True)]:
            t.start()

        def finish() -> dict:
            for t in threads:
                t.join()
            idle.set()
            with open(out, "w") as f:
                json.dump(records, f)
            return {"sent": state["sent"], "out": out, "exhausted": state["exhausted"],
                    "seconds": time.monotonic() - t0, "stall_s": state["stall_s"]}

        return finish


_LETTERS = np.frombuffer(b"ACGT", "S1")


def _frame(z: dict) -> bytes:
    """One `submit` frame: the ZMW as the subread BAM of the batch cells
    names and describes it (float32 SNR, read accuracy 0.85, both
    adapters seen)."""
    name, reads, start = f"{simulate.MOVIE}/{z['hole']}", [], 0
    for r in z["reads"]:
        reads.append({"id": f"{name}/{start}_{start + len(r)}",
                      "seq": _LETTERS[r].tobytes().decode(),
                      "flags": 3, "accuracy": float(np.float32(0.85))})
        start += len(r) + 50
    zmw = {"id": name, "snr": [float(np.float32(s)) for s in z["snr"]], "reads": reads}
    return json.dumps({"verb": "submit", "id": f"z{z['hole']}", "zmw": zmw},
                      separators=(",", ":")).encode() + b"\n"


def client_main(spec: dict) -> int:
    if spec["cpus"]:
        os.sched_setaffinity(0, spec["cpus"])
    client, finish = _Client(spec), None
    for line in sys.stdin:
        msg = json.loads(line)
        try:
            cmd = msg.pop("cmd")
            if cmd == "quit":
                break
            if cmd == "pool":
                reply = client.pool(**msg)
            elif cmd == "connect":
                reply = client.connect(**msg)
            elif cmd == "wave":
                reply = client.run(msg["seed"], msg["indices"], msg["out"], False)()
            elif cmd == "loop":
                pool = client.pools[msg["seed"]]
                finish = client.run(msg["seed"], range(msg["first"], len(pool)),
                                    msg["out"], True)
                reply = {"t_start": time.monotonic()}
            elif cmd == "stop":
                client.stop.set()
                reply = finish()
            else:
                reply = {"error": f"unknown command {cmd!r}"}
        except Exception as e:  # noqa: BLE001 -- the parent reports it and fails
            reply = {"error": repr(e)}
        print(json.dumps(reply), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(client_main(json.loads(sys.argv[1])))
