"""The trace sites of the batch path: the spans and counters that say why
the chip idles and where set-up goes (obs/trace.py, runtime/cache.py,
obs/flight.py, pipeline.py, cli.py).

CPU only: the tests assert which spans exist, how they nest and which
counters move -- never how long anything takes on a device.
"""

import json
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from pbccs_tpu import pipeline
from pbccs_tpu.obs import flight as obs_flight
from pbccs_tpu.obs import trace as obs_trace
from pbccs_tpu.obs.metrics import default_registry
from pbccs_tpu.runtime import cache as runtime_cache
from tests.test_cli import make_zmw_records

LOAD_SECONDS = "ccs_program_load_seconds_total"
SLOT_ROUNDS = "ccs_refine_slot_rounds_total"


@pytest.fixture
def tracer():
    """A tracer installed process-wide for the test, then cleared."""
    t = obs_trace.Tracer()
    prev = obs_trace.set_tracer(t)
    try:
        yield t
    finally:
        obs_trace.set_tracer(prev)


def events_by_name(chrome: dict) -> dict[str, list[dict]]:
    out: dict[str, list[dict]] = {}
    for ev in chrome["traceEvents"]:
        out.setdefault(ev["name"], []).append(ev)
    return out


# ------------------------------------------------------------ the tracer off


class CountingAnnotation:
    made = 0

    def __init__(self, name):
        type(self).made += 1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_without_a_tracer_span_makes_nothing(monkeypatch):
    """The disabled path is one global read: the same shared object every
    time, no Span, no profiler annotation."""
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountingAnnotation)
    monkeypatch.setattr(CountingAnnotation, "made", 0)
    prev = obs_trace.set_tracer(None)
    try:
        a = obs_trace.span("polish.refine", zmws=3)
        b = obs_trace.span("draft")
        assert a is b
        with a as sp:
            assert sp is None
        assert CountingAnnotation.made == 0
        t = obs_trace.Tracer()
        obs_trace.set_tracer(t)
        with obs_trace.span("polish.refine", zmws=3) as sp:
            assert sp is not None
        assert CountingAnnotation.made == 1
    finally:
        obs_trace.set_tracer(prev)


# -------------------------------------------------------- the profiler's clock


def test_spans_sit_in_the_profilers_host_plane(tracer, tmp_path):
    """With a tracer installed and jax.profiler capturing, a span is also
    a `ccs:` TraceAnnotation in the .xplane.pb: one clock for the program's
    spans and the device's operations."""
    from pbccs_tpu.obs import profiling

    with profiling.profile_capture(str(tmp_path)):
        with obs_trace.span("polish.refine", zmws=2):
            jnp.arange(8).sum().block_until_ready()
    found = list(tmp_path.rglob("*.xplane.pb"))
    assert found, "the profiler wrote no .xplane.pb"
    data = jax.profiler.ProfileData.from_file(str(found[0]))
    host = [p for p in data.planes if p.name.startswith("/host:")]
    names = {e.name for p in host for line in p.lines for e in line.events}
    assert "ccs:polish.refine" in names
    # the capture ran without the profiler's Python tracer, whose events
    # are named `$file:line function`
    assert not [n for n in names if n.startswith("$")]


# --------------------------------------------------------- program-load phases


def phase_seconds() -> dict[str, float]:
    reg = default_registry()
    return {phase: reg.counter(LOAD_SECONDS, phase=phase).value
            for phase in ("trace", "lower", "compile", "cache_read")}


def test_a_compile_yields_program_spans_and_moves_every_phase(tracer):
    """One fresh jit: trace, lower and compile each leave a span named for
    the function and move their phase; a second load of the same program
    from the persistent cache moves cache_read.  Nested events (the jnp ops
    traced inside) are not booked twice."""
    runtime_cache._install_cache_metrics()
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        salt = time.time_ns() % 1_000_003      # a program no run cached

        def ccs_trace_site_probe(x):
            return jnp.sin(x) * salt + jnp.cos(x)

        before = phase_seconds()
        t0 = time.perf_counter()
        jax.jit(ccs_trace_site_probe)(jnp.ones(7)).block_until_ready()
        wall = time.perf_counter() - t0
        first = phase_seconds()
        jax.clear_caches()                      # the next call reads the disk
        jax.jit(ccs_trace_site_probe)(jnp.ones(7)).block_until_ready()
        after = phase_seconds()
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_secs)
    for phase in ("trace", "lower", "compile"):
        assert first[phase] > before[phase], phase
    assert after["cache_read"] > before["cache_read"]
    # outermost events only: the phases of one compile fit in its wall
    assert sum(first[p] - before[p]
               for p in ("trace", "lower", "compile")) <= wall
    by_name = events_by_name(tracer.to_chrome())
    for name in ("program.trace", "program.lower", "program.compile"):
        mine = [e for e in by_name.get(name, [])
                if "ccs_trace_site_probe" in e["args"]["fun"]]
        assert mine, f"no {name} span names the probe"
    # sin, cos, multiply, add were traced inside the probe: no span each
    assert not [e for e in by_name["program.trace"]
                if e["args"]["fun"] in ("sin", "cos")]


# ------------------------------------------------------------- slot occupancy


def test_record_round_moves_both_slot_round_series():
    reg = default_registry()
    live = reg.counter(SLOT_ROUNDS, kind="live")
    capacity = reg.counter(SLOT_ROUNDS, kind="capacity")
    live0, cap0 = live.value, capacity.value
    rec = obs_flight.FlightRecorder()
    rec.record_round("t", 0, live=5, n_zmws=6, z=8)
    rec.record_round("t", 1, live=2, n_zmws=6, z=8, source="device")
    assert live.value - live0 == 7
    assert capacity.value - cap0 == 16


# ------------------------------------------------------------- the device turn


def test_turn_wait_covers_the_time_another_batch_holds_the_turn(
        tracer, monkeypatch):
    """Two workers, one device turn: the second's `dispatch.turn_wait`
    spans the first's `polish`, and its own `polish` opens after it."""
    holding = threading.Event()

    def stub_prepare(chunks, settings, **span_args):
        return pipeline.ResultTally(), list(chunks)

    def stub_polish(preps, settings, **kw):
        holding.set()
        time.sleep(0.2)
        return [(pipeline.Failure.OTHER, None) for _ in preps]

    monkeypatch.setattr(pipeline, "prepare_batch", stub_prepare)
    monkeypatch.setattr(pipeline, "polish_prepared_batch", stub_polish)
    first = threading.Thread(target=pipeline.process_chunks, args=(["a"],))
    second = threading.Thread(target=pipeline.process_chunks,
                              args=(["b", "c"],))
    first.start()
    assert holding.wait(10.0)
    second.start()
    first.join(10.0)
    second.join(10.0)
    assert not first.is_alive() and not second.is_alive()

    by_name = events_by_name(tracer.to_chrome())
    waits = {e["args"]["zmws"]: e for e in by_name["dispatch.turn_wait"]}
    polishes = {e["args"]["zmws"]: e for e in by_name["polish"]}
    assert waits[1]["dur"] < 50_000             # the turn was free
    held_until = polishes[1]["ts"] + polishes[1]["dur"]
    wait_end = waits[2]["ts"] + waits[2]["dur"]
    assert waits[2]["dur"] > 100_000
    assert wait_end >= held_until               # it waited the holder out
    assert polishes[2]["ts"] >= wait_end        # and only then polished
    assert waits[2]["args"]["cpu_ms"] < 50.0    # waiting is not work


def test_scheduled_turn_wait_runs_from_submit_to_the_polish_opening(
        tracer, monkeypatch):
    """The scheduled driver, one device: the second batch is submitted
    while the first polishes, and its `dispatch.turn_wait` starts at its
    submit (where its last `prepare` closed), ends where its `polish`
    opens, carries `zmws` and `batch`, and costs no CPU."""
    from pbccs_tpu.sched import DevicePool
    from pbccs_tpu.sched.executor import ScheduledPipeline

    def stub_prepare(chunks, settings, **span_args):
        with obs_trace.span("prepare", zmws=len(chunks), **span_args):
            return pipeline.ResultTally(), list(chunks)

    def stub_polish(preps, settings, **kw):
        time.sleep(0.3)
        return [(pipeline.Failure.OTHER, None) for _ in preps]

    monkeypatch.setattr(pipeline, "prepare_batch", stub_prepare)
    monkeypatch.setattr(pipeline, "polish_prepared_batch", stub_polish)
    monkeypatch.setattr(pipeline, "menu_batch_shapes",
                        lambda preps, full_zmws: ((8, 8, 4), None))
    monkeypatch.setattr(pipeline, "prebake_polish", lambda preps, **kw: None)
    with DevicePool(jax.devices()[:1]) as pool:
        pipe = ScheduledPipeline(pool, pipeline.ConsensusSettings(), chunk_zmws=64,
                                 prepare_workers=2)
        emitted = list(pipe.run([(0, ["a"], None), (1, ["b", "c"], None)]))
    assert [idx for idx, _tally in emitted] == [0, 1]

    by_name = events_by_name(tracer.to_chrome())
    waits = {e["args"]["batch"]: e for e in by_name["dispatch.turn_wait"]}
    polishes = {e["args"]["batch"]: e for e in by_name["polish"]}
    prepares = {e["args"]["batch"]: e for e in by_name["prepare"]}
    assert {b: e["args"]["zmws"] for b, e in waits.items()} == {0: 1, 1: 2}
    assert waits[0]["dur"] < 100_000            # the device was free
    for b in (0, 1):
        wait_end = waits[b]["ts"] + waits[b]["dur"]
        prepared = prepares[b]["ts"] + prepares[b]["dur"]
        assert 0 <= waits[b]["ts"] - prepared < 100_000   # from the submit
        assert abs(polishes[b]["ts"] - wait_end) < 20_000  # to the polish
        assert waits[b]["args"]["cpu_ms"] == 0.0
    assert waits[1]["dur"] > 150_000            # it waited the holder out
    assert (waits[1]["ts"] + waits[1]["dur"]
            >= polishes[0]["ts"] + polishes[0]["dur"] - 20_000)


# --------------------------------------------- the thread that owns the chip

STARVED = "ccs_sched_device_starved_seconds_total"


def starved_seconds(device: str) -> float:
    return default_registry().counter(STARVED, device=device).value


def assert_spans_match_the_counter(starved: list[dict], moved: float) -> None:
    """The non-`head` waits are what the counter counts: 1 % + 5 ms."""
    counted = sum(e["dur"] for e in starved if not e["args"]["head"]) / 1e6
    assert abs(counted - moved) <= 0.01 * moved + 0.005, (counted, moved)


def test_device_starved_spans_the_pool_workers_waits_as_the_counter_counts(
        tracer):
    """One device: a wait before the first submit (`head`: the counter
    leaves it out), one between two tasks, one up to the close.  Each is
    a `device.starved` span on the worker's thread that carries the
    worker's name, and the non-`head` ones sum to the counter's movement."""
    from pbccs_tpu.sched import DevicePool

    device = jax.devices()[0]
    name = f"{device.platform}:{device.id}"
    before = starved_seconds(name)
    ran_on = []

    def task(_device):
        ran_on.append(threading.get_ident() & 0xFFFFFFFF)
        time.sleep(0.05)

    with DevicePool([device]) as pool:
        time.sleep(0.15)
        pool.submit("k", task).result(10.0)
        time.sleep(0.25)
        pool.submit("k", task).result(10.0)
        time.sleep(0.1)
    moved = starved_seconds(name) - before
    starved = events_by_name(tracer.to_chrome())["device.starved"]
    assert all(e["args"]["device"] == name for e in starved)
    assert {e["tid"] for e in starved} == set(ran_on)     # the owner thread
    assert not any(e["args"].get("open") for e in starved)
    assert [e["args"]["head"] for e in starved][0] is True
    heads = [e for e in starved if e["args"]["head"]]
    assert len(heads) == 1 and heads[0]["dur"] >= 140_000
    assert sum(e["dur"] for e in starved if not e["args"]["head"]) >= 340_000
    assert_spans_match_the_counter(starved, moved)
    for e in starved:
        assert e["args"]["cpu_ms"] < 20.0              # waiting is not work


class _ServedStub:
    """A CcsEngine whose drafts and polishes are stubs: the engine's own
    threads, queues and spans, no device work."""

    def __init__(self, **cfg):
        import numpy as np

        from pbccs_tpu.serve.engine import CcsEngine, ServeConfig

        self.polished_on = []

        def prep(chunk, settings):
            return None, pipeline.PreparedZmw(
                chunk, np.zeros(64, np.int8), [], len(chunk.reads), 0, 0.0)

        def polish(preps, settings):
            self.polished_on.append(threading.get_ident() & 0xFFFFFFFF)
            time.sleep(0.05)
            return [(pipeline.Failure.OTHER, None) for _ in preps]

        self.engine = CcsEngine(
            config=ServeConfig(max_batch=2, max_wait_ms=10.0, **cfg),
            prep_fn=prep, polish_fn=polish)

    @staticmethod
    def chunk(k: int):
        import numpy as np

        seq = np.arange(20, dtype=np.int8) % 4
        return pipeline.Chunk(f"m/{k}", [pipeline.Subread(f"m/{k}/0", seq)],
                              np.full(4, 8.0))

    def serve(self, waves: int = 2, gap_s: float = 0.2) -> None:
        with self.engine as eng:
            for wave in range(waves):
                reqs = [eng.submit(self.chunk(2 * wave + i)) for i in range(2)]
                assert all(r.wait(10.0) for r in reqs)
                time.sleep(gap_s)


def test_the_served_path_books_its_waits_at_one_device(tracer):
    """`--devices 1`: the polish executor's waits on its queue are
    `device.starved` spans (the one before its first flush `head`), the
    counter of the pool's name moves by the non-`head` ones, and the
    executor only polishes: a flush's requests are completed under
    `serve.complete` on the completion thread, which says how long the
    hand-off waited (`queued_ms`)."""
    device = jax.devices()[0]
    name = f"{device.platform}:{device.id}"
    before = starved_seconds(name)
    stub = _ServedStub()
    stub.serve()
    moved = starved_seconds(name) - before
    by_name = events_by_name(tracer.to_chrome())
    starved, completes = by_name["device.starved"], by_name["serve.complete"]
    assert len(stub.polished_on) == 2 and len(set(stub.polished_on)) == 1
    assert {e["tid"] for e in starved} == set(stub.polished_on)
    assert all(e["args"]["device"] == name for e in starved)
    assert [e["args"]["head"] for e in starved] == [True, False, False]
    assert moved >= 0.15                               # the gap after a wave
    assert_spans_match_the_counter(starved, moved)
    assert [(e["args"]["zmws"], e["args"]["flush"]) for e in completes] \
        == [(2, 1), (2, 2)]
    # one thread completes, and it is not the one that polished
    assert len({e["tid"] for e in completes}) == 1
    assert not {e["tid"] for e in completes} & set(stub.polished_on)
    assert all(0.0 <= e["args"]["queued_ms"] < 50.0 for e in completes)
    # polish, wait: nothing between them on the owner thread's timeline
    for done, wait in zip(by_name["serve.polish"], starved[1:]):
        assert 0 <= wait["ts"] - (done["ts"] + done["dur"]) < 20_000
    assert "parent" not in completes[0]["args"]


def test_the_served_path_with_a_pool_leaves_the_waits_to_the_pool(tracer):
    """`--devices 2`: no polish executor of the engine's own, so every
    `device.starved` span and every counted second is the pool's (one
    site a path), and `serve.complete` runs on the completion thread,
    where no device waits for it."""
    devices = jax.devices()[:2]
    names = [f"{d.platform}:{d.id}" for d in devices]
    before = [starved_seconds(n) for n in names]
    stub = _ServedStub(devices=2)
    stub.serve()
    moved = sum(starved_seconds(n) - b for n, b in zip(names, before))
    by_name = events_by_name(tracer.to_chrome())
    starved, completes = by_name["device.starved"], by_name["serve.complete"]
    assert {e["args"]["device"] for e in starved} <= set(names)
    assert_spans_match_the_counter(starved, moved)
    pool_threads = {e["tid"] for e in starved}
    assert set(stub.polished_on) <= pool_threads
    assert len(completes) == 2
    assert not {e["tid"] for e in completes} & pool_threads


@pytest.mark.parametrize("devices", [1, 2])
def test_the_next_flush_polishes_while_the_last_one_completes(tracer, devices):
    """Two flushes queued, replies that take 0.2 s each: the thread that
    owns the device hands the first flush off and opens the second one's
    polish while the first one's `serve.complete` is still open, at one
    device as with a pool."""
    stub = _ServedStub(devices=devices)
    with stub.engine as eng:
        reqs = [eng.submit(stub.chunk(k), callback=lambda _r: time.sleep(0.2))
                for k in range(4)]
        assert all(r.wait(10.0) for r in reqs)
    by_name = events_by_name(tracer.to_chrome())
    polish = {e["args"]["flush"]: e for e in by_name["serve.polish"]}
    first, second = sorted(by_name["serve.complete"], key=lambda e: e["ts"])
    assert {first["args"]["flush"], second["args"]["flush"]} == set(polish) \
        == {1, 2}
    assert first["dur"] >= 380_000                     # two replies of 0.2 s
    late = polish[second["args"]["flush"]]
    assert late["ts"] < first["ts"] + first["dur"]
    assert late["tid"] != first["tid"] == second["tid"]
    # that flush ended under the first one's replies, and its hand-off waited
    assert second["args"]["queued_ms"] >= 100.0 > first["args"]["queued_ms"]


def test_polish_names_the_device_it_runs_on(tracer, monkeypatch):
    """Both `polish` sites carry `device=` as the pool names it: the
    scheduled driver's from the pool's scope, the engine's pinned polish
    from the process's first device."""
    from pbccs_tpu.sched import DevicePool
    from pbccs_tpu.sched.executor import ScheduledPipeline
    from pbccs_tpu.serve import engine as serve_engine

    def stub_polish(preps, settings, **kw):
        return [(pipeline.Failure.OTHER, None) for _ in preps]

    monkeypatch.setattr(pipeline, "prepare_batch",
                        lambda chunks, settings, **kw:
                        (pipeline.ResultTally(), list(chunks)))
    monkeypatch.setattr(pipeline, "polish_prepared_batch", stub_polish)
    monkeypatch.setattr(serve_engine, "polish_prepared_batch", stub_polish)
    monkeypatch.setattr(pipeline, "menu_batch_shapes",
                        lambda preps, full_zmws: ((8, 8, 4), None))
    monkeypatch.setattr(serve_engine, "menu_pin", lambda preps: (8, 8, 4))
    monkeypatch.setattr(pipeline, "prebake_polish", lambda preps, **kw: None)
    device = jax.devices()[1]
    with DevicePool([device]) as pool:
        pipe = ScheduledPipeline(pool, pipeline.ConsensusSettings(), chunk_zmws=64,
                                 prepare_workers=1)
        assert [idx for idx, _t in pipe.run([(0, ["a"], None)])] == [0]
    serve_engine._polish_shape_pinned(["a", "b"], pipeline.ConsensusSettings())
    scheduled, served = events_by_name(tracer.to_chrome())["polish"]
    assert scheduled["args"]["device"] == f"{device.platform}:{device.id}"
    assert (scheduled["args"]["zmws"], scheduled["args"]["batch"]) == (1, 0)
    first = jax.devices()[0]
    assert served["args"]["device"] == f"{first.platform}:{first.id}"
    assert served["args"]["zmws"] == 2


def test_the_owner_threads_sites_make_nothing_without_a_tracer(monkeypatch):
    """Tracing off: a pool wait, a served wait and completion, and a
    polish each get the one shared no-op back (one global read a site)."""
    from pbccs_tpu.sched import DevicePool

    handed = []
    real = obs_trace.span

    def spy(name, ctx=None, **args):
        got = real(name, ctx=ctx, **args)
        handed.append((name, got))
        return got

    prev = obs_trace.set_tracer(None)
    monkeypatch.setattr(obs_trace, "span", spy)
    try:
        with DevicePool(jax.devices()[:1]) as pool:
            pool.submit("k", lambda _device: None).result(10.0)
        _ServedStub().serve(waves=1, gap_s=0.0)
    finally:
        obs_trace.set_tracer(prev)
    assert {"device.starved", "serve.complete"} <= {n for n, _ in handed}
    assert all(got is obs_trace._NO_SPAN for _n, got in handed)


def test_polish_wide_covers_the_wide_band_retry_under_polish(
        tracer, rng, monkeypatch):
    """A batch in which one ZMW's read fails the alpha/beta mating at the
    narrow band and mates at twice the band: the retry's z-scores, refine
    and QV sweep run under `polish.wide`, a child of `polish` between
    `polish.refine` and `polish.qv`, and polish's parts then cover it
    (a batch without a retry has no such span: the CLI test below)."""
    import pbccs_tpu.parallel.batch as batchmod
    from pbccs_tpu.models.arrow.scorer import ADD_ALPHABETAMISMATCH
    from pbccs_tpu.simulate import simulate_zmw

    chunks = []
    for z in range(2):
        _tpl, reads, _strands, snr = simulate_zmw(rng, 60, 4)
        chunks.append(pipeline.Chunk(
            f"rb/{z}", [pipeline.Subread(f"rb/{z}/{i}", r)
                        for i, r in enumerate(reads)], snr))
    built = []

    class DropAtTheNarrowBand(batchmod.BatchPolisher):
        def __init__(self, tasks, **kw):
            super().__init__(tasks, **kw)
            built.append(self._W)
            if len(built) == 1:
                for z, t in enumerate(tasks):
                    if t.id == "rb/1":
                        self.statuses[z, len(t.reads) - 1] = \
                            ADD_ALPHABETAMISMATCH
                        self.active[z, len(t.reads) - 1] = False

    monkeypatch.setattr(batchmod, "BatchPolisher", DropAtTheNarrowBand)
    tally = pipeline.process_chunks(chunks)
    assert tally.counts[pipeline.Failure.SUCCESS] == 2
    assert len(built) == 2 and built[1] == 2 * built[0]

    chrome = tracer.to_chrome()
    by_name = events_by_name(chrome)
    (polish,), (wide,) = by_name["polish"], by_name["polish.wide"]
    assert wide["args"]["parent"] == polish["id"]
    assert wide["args"]["zmws"] == 1
    parts = [e for e in chrome["traceEvents"]
             if e["args"].get("parent") == polish["id"]]
    assert [e["name"] for e in parts] == [
        "polish.setup", "polish.gates", "polish.refine", "polish.wide",
        "polish.qv", "polish.finish"]
    assert sum(e["dur"] for e in parts) / polish["dur"] > 0.98


# --------------------------------------------------------------- the batch CLI


def write_subread_bam(rng, path: str, holes) -> None:
    from pbccs_tpu.io.bam import (BamHeader, BamRecord, BamWriter,
                                  ReadGroupInfo, make_read_group_id)

    movie = "m140905_042212_sidney_c100564852550000001823085912221377_s1_X0"
    header = BamHeader(read_groups=[
        ReadGroupInfo(movie, "SUBREAD", binding_kit="100356300",
                      sequencing_kit="100356200",
                      basecaller_version="2.3.0")])
    rg_id = make_read_group_id(movie, "SUBREAD")
    with BamWriter(path, header) as bw:
        for hole in holes:
            _, recs, snr = make_zmw_records(rng, movie, hole, tpl_len=60,
                                            n_passes=4)
            for name, seq in recs:
                bw.write(BamRecord(name=name, seq=seq, tags={
                    "RG": rg_id, "zm": hole, "cx": 3, "rq": 0.85,
                    "sn": [float(s) for s in snr]}))


def test_trace_out_of_a_batch_cli_run_has_the_spans_of_its_path(
        rng, tmp_path):
    """`ccs OUT IN --trace-out F` on four simulated ZMWs in two batches:
    every span the scheduled driver's path reaches is in the file, one
    batch's read, prepare slices, turn wait and polish carry its `batch`
    index, and polish's five parts hang under `polish`."""
    from pbccs_tpu.cli import run

    in_bam = str(tmp_path / "subreads.bam")
    write_subread_bam(rng, in_bam, holes=(11, 12, 13, 14))
    out_bam, trace_out = str(tmp_path / "out.bam"), str(tmp_path / "t.json")
    rc = run([out_bam, in_bam, "--reportFile", str(tmp_path / "r.csv"),
              "--numThreads", "2", "--chunkSize", "2", "--logLevel", "WARN",
              "--trace-out", trace_out])
    assert rc == 0
    with open(trace_out) as f:
        chrome = json.load(f)
    assert "droppedSpans" not in chrome
    assert chrome["meta"]["dropped_spans"] == 0
    by_name = events_by_name(chrome)
    by_id = {e["id"]: e for e in chrome["traceEvents"]}

    reached = {"run", "read", "prepare", "filter", "draft",
               "draft.poa", "draft.map", "dispatch.turn_wait", "polish",
               "polish.setup", "polish.gates", "polish.refine", "polish.qv",
               "polish.finish", "emit", "device.starved"}
    assert reached <= set(by_name), reached - set(by_name)
    # the pool's thread waits and polishes under one name for its device
    assert ({e["args"]["device"] for e in by_name["device.starved"]}
            == {e["args"]["device"] for e in by_name["polish"]})
    assert ({e["tid"] for e in by_name["device.starved"]}
            == {e["tid"] for e in by_name["polish"]})

    (run_ev,) = by_name["run"]
    assert run_ev["args"]["zmws"] == 4 and run_ev["args"]["threads"] == 2
    assert run_ev["args"]["chunk_size"] == 2
    assert run_ev["args"]["devices"] == 1 and run_ev["args"]["cpus"] >= 1
    # two batches and the end of the input: three reads, on the feeder's
    # thread (no parent), the end of the input with no batch to name
    assert sorted((e["args"]["zmws"], e["args"].get("batch"))
                  for e in by_name["read"]) == [(0, None), (2, 0), (2, 1)]
    assert not any("parent" in e["args"] for e in by_name["read"])
    assert "batch" not in by_name               # `batch=` ties, no span

    def children(ev):
        return [e for e in chrome["traceEvents"]
                if e["args"].get("parent") == ev["id"]]

    for idx in (0, 1):
        # two prepare workers: a batch of two is dealt as two slices
        slices = [e for e in by_name["prepare"] if e["args"]["batch"] == idx]
        assert [e["args"]["zmws"] for e in slices] == [1, 1]
        (wait,) = [e for e in by_name["dispatch.turn_wait"]
                   if e["args"]["batch"] == idx]
        (polish,) = [e for e in by_name["polish"]
                     if e["args"]["batch"] == idx]
        assert wait["args"]["zmws"] == polish["args"]["zmws"] == 2
        assert wait["ts"] >= max(e["ts"] + e["dur"] for e in slices)
        assert abs(polish["ts"] - (wait["ts"] + wait["dur"])) < 20_000
        assert [e["name"] for e in children(polish)] == [
            "polish.setup", "polish.gates", "polish.refine", "polish.qv",
            "polish.finish"]
    for ev in by_name["filter"] + by_name["draft"]:
        assert by_id[ev["args"]["parent"]]["name"] == "prepare"
    for ev in by_name["draft.poa"] + by_name["draft.map"]:
        assert by_id[ev["args"]["parent"]]["name"] == "draft"
    for ev in chrome["traceEvents"]:
        if not ev["name"].startswith("program."):   # told of after the fact
            assert 0.0 <= ev["args"]["cpu_ms"] <= ev["dur"] / 1e3 + 1.0, ev
    # any program this run brought up says where, under the span it ran in
    for ev in by_name.get("program.compile", []):
        assert ev["args"]["fun"] and "parent" in ev["args"]


# ------------------------------------------------------ a crash names itself

CRASHING_CHILD = """
import os, signal, sys, threading, time

def crash(*args, **kwargs):
    threading.Thread(target=time.sleep, args=(30,), daemon=True,
                     name="bystander").start()
    time.sleep(0.2)
    os.kill(os.getpid(), signal.SIGSEGV)
    time.sleep(30)

if sys.argv[1] == "batch":
    from pbccs_tpu import cli
    cli._run_pipeline = crash              # past the flags, where work starts
    sys.exit(cli.run([sys.argv[2] + "/out.bam", sys.argv[0],
                      "--reportFile", sys.argv[2] + "/report.csv"]))
from pbccs_tpu.serve import server
server.load_edge_config = crash            # the first thing after the flags
sys.exit(server.run_serve(["--port", "0"]))
"""


@pytest.mark.parametrize("entry", ["batch", "serve"])
def test_a_crash_leaves_every_threads_stack_on_stderr(entry, tmp_path):
    """`ccs` and `ccs serve` arm faulthandler where they start: a child
    that takes a SIGSEGV dies of it (exit 139 to a shell) and says first
    where each of its threads was."""
    import os
    import signal
    import subprocess
    import sys

    script = tmp_path / "crashing_child.py"
    script.write_text(CRASHING_CHILD)
    root = os.path.join(os.path.dirname(__file__), os.pardir)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.abspath(root))
    done = subprocess.run([sys.executable, str(script), entry, str(tmp_path)],
                          env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode == -signal.SIGSEGV, done.stderr[-2000:]
    assert "Fatal Python error: Segmentation fault" in done.stderr
    assert "Current thread" in done.stderr and " in crash" in done.stderr
    # the bystander's stack is there too: all threads, not the one that died
    assert done.stderr.count("most recent call first") >= 2


# ------------------------------------------------------- tools/trace_cover.py


def test_trace_cover_reads_coverage_and_pairs_annotations_by_duration():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "trace_cover", os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools", "trace_cover.py"))
    cover = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cover)

    def ev(i, name, ts_s, dur_s, parent=None, batch=None, tid=1):
        args = {} if parent is None else {"parent": parent}
        if batch is not None:
            args["batch"] = batch
        return {"id": i, "name": name, "ts": ts_s * 1e6, "dur": dur_s * 1e6,
                "tid": tid, "args": args}

    # batch 0: two overlapping prepare slices, half a second of joining
    # before the submit, the wait, the polish; batch 1 never polished
    events = [ev(0, "read", 0.0, 0.1, batch=0),        # not a batch part
              ev(1, "prepare", 0.0, 4.0, batch=0),
              ev(8, "prepare", 1.0, 2.5, batch=0),
              ev(2, "dispatch.turn_wait", 4.5, 3.0, batch=0),
              ev(3, "polish", 7.5, 2.5, batch=0),
              ev(4, "polish.setup", 7.5, 1.0, 3),
              ev(5, "polish.refine", 8.5, 1.0, 3),
              ev(6, "polish.round", 8.5, 1.0, 5),      # not polish's child
              ev(9, "prepare", 3.0, 4.0, batch=1),
              ev(7, "polish", 20.0, 2.0)]              # no batch, no parts
    assert cover.batch_coverage(events) == [0.95]
    assert cover.coverage(events, "polish", cover.POLISH_PARTS) == [0.8, 0.0]
    # two polish annotations: each span pairs with the one of its length;
    # `prepare` began before the capture and is not paired with the
    # `prepare` of a later batch
    notes = [("polish", 1000.0 + 7.50002, 2.49999),
             ("polish", 1000.0 + 20.00004, 1.99999),
             ("prepare", 1000.0 + 0.9, 4.0)]
    skews = cover.annotation_skews(events, 1000.0, notes)
    assert sorted(round(s * 1e6) for s in skews) == [20, 40]
    # the wide-band retry is one of polish's parts
    events += [ev(10, "polish.wide", 9.5, 0.25, 3)]
    assert cover.coverage(events, "polish", cover.POLISH_PARTS) == [0.9, 0.0]
    # the owner thread, from its first polish (7.5) to its last closing (22):
    # two polishes (4.5 s), a wait of 8 s clipped at neither end; a
    # completion (the completion thread's, whatever its tid says), a head
    # wait before the first polish and another thread's wait count for nothing
    events += [ev(11, "device.starved", 10.0, 8.0),
               ev(12, "serve.complete", 17.5, 1.0),
               ev(13, "device.starved", 0.0, 7.5),
               ev(14, "device.starved", 18.5, 1.5, tid=2)]
    (share,) = cover.owner_coverage(events)
    assert share == pytest.approx((4.5 + 8.0) / 14.5)
