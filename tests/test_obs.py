"""Observability layer: metrics registry, trace spans, export surfaces.

Covers the tentpole contracts: histogram bucketing edge cases, concurrent
counter increments, measurement-scope isolation (the timing.reset()
replacement), span-tree nesting + Chrome-trace export round trip with
device-wait attribution, Prometheus text rendering, and a serve-session
test that scrapes the `metrics` verb and asserts stage counters advance.
"""

import json
import threading

import numpy as np
import pytest

from pbccs_tpu.obs import metrics as obs_metrics
from pbccs_tpu.obs import trace as obs_trace
from pbccs_tpu.obs.metrics import MetricsRegistry, log_buckets
from pbccs_tpu.runtime import timing


# ---------------------------------------------------------------- metrics


class TestCounters:
    def test_inc_and_negative_rejected(self):
        reg = MetricsRegistry()
        c = reg.counter("t_total")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_get_or_create_by_name_and_labels(self):
        reg = MetricsRegistry()
        a = reg.counter("t_total", stage="draft")
        b = reg.counter("t_total", stage="draft")
        c = reg.counter("t_total", stage="polish")
        assert a is b and a is not c

    def test_kind_collision_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_concurrent_increments_exact(self):
        """8 threads x 5000 increments must lose nothing."""
        reg = MetricsRegistry()
        c = reg.counter("t_total")
        g = reg.gauge("t_gauge")
        n, per = 8, 5000

        def worker():
            for _ in range(per):
                c.inc()
                g.inc()

        threads = [threading.Thread(target=worker) for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == n * per
        assert g.value == n * per


class TestHistogram:
    def test_log_buckets(self):
        b = log_buckets(1.0, 100.0, 10.0)
        assert b == (1.0, 10.0, 100.0)
        with pytest.raises(ValueError):
            log_buckets(0.0, 1.0)
        with pytest.raises(ValueError):
            log_buckets(1.0, 2.0, 1.0)

    def test_bucketing_edges(self):
        """A value exactly on a bound lands in that bound's bucket
        (Prometheus le semantics); below-first and above-last land in the
        first and +Inf buckets."""
        reg = MetricsRegistry()
        h = reg.histogram("h", buckets=(1.0, 10.0, 100.0))
        for v in (0.5, 1.0, 1.0000001, 10.0, 99.0, 100.0, 1e9):
            h.observe(v)
        counts, s, n = h.snapshot()
        # bucket semantics: <=1, <=10, <=100, +Inf
        assert counts == (2, 2, 2, 1)
        assert n == 7
        assert s == pytest.approx(0.5 + 1 + 1.0000001 + 10 + 99 + 100 + 1e9)

    def test_prometheus_cumulative_rendering(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_seconds", "latency", buckets=(1.0, 10.0))
        for v in (0.5, 5.0, 50.0):
            h.observe(v)
        text = reg.render_prometheus()
        assert '# TYPE lat_seconds histogram' in text
        assert 'lat_seconds_bucket{le="1"} 1' in text
        assert 'lat_seconds_bucket{le="10"} 2' in text
        assert 'lat_seconds_bucket{le="+Inf"} 3' in text
        assert 'lat_seconds_count 3' in text

    def test_concurrent_observes_exact(self):
        reg = MetricsRegistry()
        h = reg.histogram("h", buckets=(0.5,))
        threads = [threading.Thread(
            target=lambda: [h.observe(1.0) for _ in range(2000)])
            for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        counts, s, n = h.snapshot()
        assert n == 12000 and counts == (0, 12000) and s == 12000.0


class TestScopes:
    def test_scope_reports_deltas_only(self):
        reg = MetricsRegistry()
        c = reg.counter("t_total")
        c.inc(10)
        scope = reg.scope()
        c.inc(5)
        assert scope.counter_value("t_total") == 5.0

    def test_concurrent_scopes_do_not_clobber(self):
        """The satellite contract: two measurement windows over one
        registry are independent -- no global reset."""
        reg = MetricsRegistry()
        c = reg.counter("t_total")
        bench = reg.scope()
        c.inc(3)
        engine = reg.scope()     # opened later: sees only what follows
        c.inc(4)
        assert bench.counter_value("t_total") == 7.0
        assert engine.counter_value("t_total") == 4.0
        # opening yet another scope (the old reset()) changes neither
        reg.scope()
        assert bench.counter_value("t_total") == 7.0
        assert engine.counter_value("t_total") == 4.0

    def test_histogram_delta(self):
        reg = MetricsRegistry()
        h = reg.histogram("h", buckets=(1.0,))
        h.observe(0.5)
        scope = reg.scope()
        h.observe(0.5)
        h.observe(2.0)
        counts, s, n = scope.delta()[("h", ())]
        assert counts == (1, 1) and n == 2 and s == 2.5

    def test_timing_shim_windows(self):
        """timing.reset() only moves the module window; an explicit
        window is unaffected (bench vs live engine isolation)."""
        win = timing.window()
        timing.add_stage("test_obs_stage", 1.0)
        timing.reset()          # module window restarts ...
        assert timing.stage_seconds().get("test_obs_stage") is None
        # ... but the explicit window still sees the pre-reset second
        assert timing.stage_seconds(win)["test_obs_stage"] == \
            pytest.approx(1.0)


# ------------------------------------------------------------------ trace


class TestTrace:
    def test_span_tree_nesting_and_round_trip(self):
        tracer = obs_trace.Tracer()
        with tracer.span("polish", zmws=2):
            with tracer.span("polish.round", round=0):
                pass
            with tracer.span("polish.round", round=1):
                pass
        chrome = json.loads(json.dumps(tracer.to_chrome()))  # wire trip
        events = chrome["traceEvents"]
        assert [e["name"] for e in events] == \
            ["polish", "polish.round", "polish.round"]
        tree = obs_trace.span_tree(chrome)
        roots = tree[None]
        assert len(roots) == 1 and roots[0]["name"] == "polish"
        children = tree[roots[0]["id"]]
        assert [c["args"]["round"] for c in children] == [0, 1]
        # children are contained in the parent's [ts, ts+dur]
        for c in children:
            assert c["ts"] >= roots[0]["ts"]
            assert c["ts"] + c["dur"] <= \
                roots[0]["ts"] + roots[0]["dur"] + 1e-6

    def test_device_wait_attribution(self):
        """timing.device_fetch inside a span attributes its blocking time
        to the innermost open span."""
        tracer = obs_trace.Tracer()
        prev = obs_trace.set_tracer(tracer)
        try:
            with obs_trace.span("polish"):
                with obs_trace.span("polish.round", round=0):
                    timing.device_fetch(np.arange(4))
        finally:
            obs_trace.set_tracer(prev)
        spans = {s.name: s for s in tracer.finished_spans()}
        assert spans["polish.round"].device_wait_s >= 0.0
        ev = [e for e in tracer.to_chrome()["traceEvents"]
              if e["name"] == "polish.round"][0]
        assert "device_wait_ms" in ev["args"]

    def test_disabled_tracer_is_noop(self):
        prev = obs_trace.set_tracer(None)
        try:
            with obs_trace.span("x") as sp:
                assert sp is None
            obs_trace.add_device_wait(1.0)  # must not raise
        finally:
            obs_trace.set_tracer(prev)

    def test_span_cap_bounds_capture(self):
        """A capture left running must not grow unboundedly: past
        max_spans new spans are dropped and counted."""
        tracer = obs_trace.Tracer(max_spans=3)
        for i in range(5):
            with tracer.span("s", i=i) as sp:
                assert (sp is not None) == (i < 3)
        assert len(tracer.finished_spans()) == 3
        chrome = tracer.to_chrome()
        assert chrome["meta"]["dropped_spans"] == 2

    def test_span_records_cpu_time_at_most_wall(self):
        """cpu_ms rides beside device_wait_ms: a busy span reads CPU
        close to wall, a sleeping one close to none, neither above."""
        import time

        tracer = obs_trace.Tracer()
        with tracer.span("busy"):
            t_end = time.perf_counter() + 0.05
            while time.perf_counter() < t_end:
                pass
        with tracer.span("asleep"):
            time.sleep(0.05)
        with tracer.span("open"):
            events = {e["name"]: e for e in
                      tracer.to_chrome()["traceEvents"]}
        for name in ("busy", "asleep"):
            ev = events[name]
            # thread_time and perf_counter are two clocks: a tick of slack
            assert 0.0 <= ev["args"]["cpu_ms"] <= ev["dur"] / 1e3 + 1.0
        assert events["busy"]["args"]["cpu_ms"] > 25.0
        assert events["asleep"]["args"]["cpu_ms"] < 25.0
        # an open span's CPU time is its thread's to read, not the
        # exporter's: left out, not reported as nothing
        assert "cpu_ms" not in events["open"]["args"]

    def test_add_span_with_a_start_lands_where_it_is_told(self):
        """A retroactive span with `start_unix` sits at that wall-clock
        time on the tracer's axis, under the span its thread has open;
        without one it still ends now, with no parent."""
        import time

        tracer = obs_trace.Tracer()
        with tracer.span("polish.setup") as outer:
            t_started = time.time()
            time.sleep(0.03)
            told = tracer.add_span("program.compile", 0.01,
                                   start_unix=t_started, fun="jit(f)")
            now = tracer.add_span("router.request", 0.01)
        assert told.parent is outer and now.parent is None
        chrome = tracer.to_chrome()
        ev = {e["name"]: e for e in chrome["traceEvents"]}
        origin = chrome["meta"]["origin_unix"]
        at = origin + ev["program.compile"]["ts"] / 1e6
        assert at == pytest.approx(t_started, abs=0.005)
        assert ev["program.compile"]["dur"] == pytest.approx(10_000, rel=0.01)
        assert ev["program.compile"]["args"]["parent"] == \
            ev["polish.setup"]["id"]
        assert ev["program.compile"]["args"]["fun"] == "jit(f)"
        # it began ~30 ms before the span that ended "now" did
        assert ev["router.request"]["ts"] - ev["program.compile"]["ts"] \
            > 15_000

    def test_install_and_clear_are_cas(self):
        """install_tracer refuses to hijack a live capture; clear_tracer
        only uninstalls its own."""
        prev = obs_trace.set_tracer(None)
        try:
            a, b = obs_trace.Tracer(), obs_trace.Tracer()
            assert obs_trace.install_tracer(a)
            assert not obs_trace.install_tracer(b)   # a's capture survives
            assert not obs_trace.clear_tracer(b)     # b can't clear a's
            assert obs_trace.get_tracer() is a
            assert obs_trace.clear_tracer(a)
            assert obs_trace.get_tracer() is None
        finally:
            obs_trace.set_tracer(prev)

    def test_spans_across_threads_keep_separate_stacks(self):
        tracer = obs_trace.Tracer()

        def worker(i):
            with tracer.span("w", i=i):
                pass

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        spans = tracer.finished_spans()
        assert len(spans) == 4
        assert all(s.parent is None for s in spans)  # no cross-thread nest


# ------------------------------------------------------- serve integration


class TestServeMetrics:
    def test_metrics_verb_scrape_advances(self):
        """A serve session scrapes the `metrics` verb before and after a
        submit: admission and stage counters must advance, and the body
        must be valid Prometheus text."""
        from pbccs_tpu.serve.client import CcsClient
        from pbccs_tpu.serve.server import CcsServer
        from tests.test_serve import stub_engine

        def scrape(body: str) -> dict[str, float]:
            out = {}
            for line in body.splitlines():
                if line and not line.startswith("#"):
                    name, _, v = line.rpartition(" ")
                    out[name] = float(v)
            return out

        eng = stub_engine(max_batch=2, max_wait_ms=50.0).start()
        srv = CcsServer(eng, port=0).start()
        try:
            with CcsClient(srv.host, srv.port) as cli:
                before = scrape(cli.metrics())
                assert "ccs_serve_admitted_total" in before
                for i in range(3):
                    msg = cli.submit(f"m/{i}", ["ACGTACGT"] * 4) \
                        .reply(timeout=10.0)
                    assert msg["status"] == "Success"
                after = scrape(cli.metrics())
                assert after["ccs_serve_admitted_total"] >= \
                    before["ccs_serve_admitted_total"] + 3
                assert after["ccs_serve_completed_total"] >= \
                    before["ccs_serve_completed_total"] + 3
                stage_key = 'ccs_stage_seconds_total{stage="serve.prep"}'
                assert after[stage_key] > before.get(stage_key, 0.0)
                lat = 'ccs_serve_request_latency_seconds_count'
                assert after[lat] >= before.get(lat, 0.0) + 3
                # flush accounting: the three submits flushed at least one
                # fill batch (max_batch=2) and one deadline batch
                flushes = [k for k in after if
                           k.startswith("ccs_serve_flushes_total")]
                assert sum(after[k] for k in flushes) >= \
                    sum(before.get(k, 0.0) for k in flushes) + 2
                # status carries the /metrics-style snapshot
                st = cli.status()
                assert "ccs_serve_admitted_total" in st["metrics"]
        finally:
            srv.shutdown()
            eng.close()

    def test_trace_verb_capture_round_trip(self):
        from pbccs_tpu.serve.client import CcsClient
        from pbccs_tpu.serve.server import CcsServer
        from tests.test_serve import stub_engine

        eng = stub_engine(max_batch=1, max_wait_ms=50.0).start()
        srv = CcsServer(eng, port=0).start()
        try:
            with CcsClient(srv.host, srv.port) as cli:
                assert cli.trace("stop")["state"] == "not_running"
                assert cli.trace("start")["state"] == "started"
                assert cli.trace("start")["state"] == "already_running"
                msg = cli.submit("m/1", ["ACGTACGT"] * 4).reply(timeout=10.0)
                assert msg["status"] == "Success"
                reply = cli.trace("stop")
                assert reply["state"] == "stopped"
                names = {e["name"]
                         for e in reply["trace"]["traceEvents"]}
                assert "serve.prep" in names and "serve.polish" in names
        finally:
            srv.shutdown()
            eng.close()
            assert obs_trace.get_tracer() is None  # capture never leaks

    def test_trace_bad_action_is_structured_error(self):
        from pbccs_tpu.serve.client import CcsClient, ServeError
        from pbccs_tpu.serve.server import CcsServer
        from tests.test_serve import stub_engine

        eng = stub_engine().start()
        srv = CcsServer(eng, port=0).start()
        try:
            with CcsClient(srv.host, srv.port) as cli:
                with pytest.raises(ServeError) as ei:
                    cli.trace("frobnicate")
                assert ei.value.code == "bad_request"
        finally:
            srv.shutdown()
            eng.close()


# ------------------------------------------------------------- summary/CLI


class TestSummaryAndRegistry:
    def test_summary_table_from_scope(self):
        reg = MetricsRegistry()
        scope = reg.scope()
        reg.counter("ccs_demo_total", stage="x").inc(2)
        reg.histogram("ccs_demo_seconds", buckets=(1.0,)).observe(0.5)
        table = reg.summary_table(scope)
        assert "ccs_demo_total{stage=x}" in table
        assert "n=1" in table

    def test_default_registry_is_shared(self):
        assert obs_metrics.default_registry() is \
            obs_metrics.default_registry()

    def test_prometheus_escaping(self):
        reg = MetricsRegistry()
        reg.counter("c_total", stage='we"ird\n').inc()
        text = reg.render_prometheus()
        assert 'stage="we\\"ird\\n"' in text


# ------------------------------------------- fleet observability plane


class TestTraceContext:
    def test_ctx_span_exports_trace_identity(self):
        tracer = obs_trace.Tracer(tag="t1")
        with tracer.span("serve.prep",
                         ctx={"trace_id": "abc", "span_id": "rt-q1"}):
            with tracer.span("inner"):
                pass
        events = {e["name"]: e for e in
                  tracer.to_chrome()["traceEvents"]}
        prep = events["serve.prep"]["args"]
        assert prep["trace_id"] == "abc"
        assert prep["remote_parent"] == "rt-q1"
        assert prep["span_id"] == "t1-0"
        # children INHERIT the trace id through the thread stack
        inner = events["inner"]["args"]
        assert inner["trace_id"] == "abc"
        assert "remote_parent" not in inner

    def test_add_span_pins_explicit_span_id(self):
        tracer = obs_trace.Tracer()
        sp = tracer.add_span("router.request", 0.25,
                             ctx={"trace_id": "abc", "span_id": "cl-0"},
                             span_id="rt-q9", replica="r1")
        assert sp is not None and not sp.open
        ev = tracer.to_chrome()["traceEvents"][0]
        assert ev["args"]["span_id"] == "rt-q9"
        assert ev["args"]["remote_parent"] == "cl-0"
        assert ev["dur"] == pytest.approx(250_000, rel=0.05)

    def test_current_context_round_trip(self):
        tracer = obs_trace.Tracer(tag="cli")
        prev = obs_trace.set_tracer(None)
        try:
            assert obs_trace.install_tracer(tracer)
            assert obs_trace.current_context() is None  # not in a span
            with obs_trace.span("load", ctx={"trace_id": "t",
                                             "span_id": None}):
                ctx = obs_trace.current_context()
                assert ctx == {"trace_id": "t", "span_id": "cli-0"}
            assert obs_trace.clear_tracer(tracer)
        finally:
            obs_trace.set_tracer(prev)

    def test_open_spans_tagged_not_zero_duration(self):
        """Satellite contract: a mid-flight capture tags still-open
        spans open=true with duration measured to the capture instant,
        and the export metadata surfaces dropped/open counts."""
        import time as _time

        tracer = obs_trace.Tracer(max_spans=2)
        with tracer.span("outer"):
            _time.sleep(0.01)
            chrome = tracer.to_chrome()       # captured mid-flight
        with tracer.span("later"):
            pass
        with tracer.span("past-cap"):
            pass
        ev = chrome["traceEvents"][0]
        assert ev["args"]["open"] is True
        assert ev["dur"] >= 10_000            # >= the 10 ms slept, in us
        assert chrome["meta"]["open_spans"] == 1
        final = tracer.to_chrome()
        assert "open" not in final["traceEvents"][0]["args"]
        assert final["meta"]["dropped_spans"] == 1
        assert final["meta"]["open_spans"] == 0
        assert "origin_unix" in final["meta"]


class TestSeriesCap:
    def test_cap_drops_new_label_sets_and_counts(self):
        reg = MetricsRegistry(max_series_per_name=2)
        a = reg.counter("ccs_x_total", peer="a")
        b = reg.counter("ccs_x_total", peer="b")
        c = reg.counter("ccs_x_total", peer="c")   # past the cap
        d = reg.counter("ccs_x_total", peer="d")
        for m in (a, b, c, d):
            m.inc()
        text = reg.render_prometheus()
        assert 'ccs_x_total{peer="a"}' in text
        assert 'ccs_x_total{peer="b"}' in text
        assert 'peer="c"' not in text and 'peer="d"' not in text
        assert ('ccs_metrics_series_dropped_total{metric="ccs_x_total"}'
                ' 2') in text
        # existing series keep working past the cap
        assert reg.counter("ccs_x_total", peer="a") is a
        # a dropped label set counts ONCE and hands back the SAME
        # cached detached instrument on every later lookup (no
        # per-update allocation, no runaway drop counter)
        again = reg.counter("ccs_x_total", peer="c")
        assert again is c
        again.inc()
        text2 = reg.render_prometheus()
        assert ('ccs_metrics_series_dropped_total{metric="ccs_x_total"}'
                ' 2') in text2
        with pytest.raises(TypeError):
            reg.gauge("ccs_x_total", peer="c")   # kind mismatch holds

    def test_dropped_instrument_is_usable_but_detached(self):
        reg = MetricsRegistry(max_series_per_name=1)
        reg.histogram("h_seconds", buckets=(1.0,), peer="a")
        ghost = reg.histogram("h_seconds", buckets=(1.0,), peer="b")
        ghost.observe(0.5)   # must not raise
        assert ghost.count == 1
        assert ('h_seconds', (("peer", "b"),)) not in reg.snapshot()

    def test_set_series_cap_validates(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.set_series_cap(0)
        reg.set_series_cap(3)


class TestFederationHelpers:
    def test_relabel_injects_into_all_sample_forms(self):
        from pbccs_tpu.obs.metrics import relabel_exposition

        body = ('# TYPE a_total counter\n'
                'a_total 3\n'
                'a_total{x="1"} 4\n'
                'h_bucket{le="+Inf"} 7\n')
        out = relabel_exposition(body, replica="r:1")
        assert 'a_total{replica="r:1"} 3' in out
        assert 'a_total{x="1",replica="r:1"} 4' in out
        assert 'h_bucket{le="+Inf",replica="r:1"} 7' in out
        assert '# TYPE a_total counter' in out

    def test_merge_groups_by_name_with_one_type_line(self):
        from pbccs_tpu.obs.metrics import merge_expositions

        merged = merge_expositions([
            "# TYPE a_total counter\na_total 1\n",
            '# TYPE a_total counter\na_total{replica="x"} 2\n',
        ])
        assert merged.count("# TYPE a_total counter") == 1
        assert "a_total 1" in merged
        assert 'a_total{replica="x"} 2' in merged

    def test_histogram_quantile(self):
        from pbccs_tpu.obs.metrics import histogram_quantile

        bounds = (0.1, 0.2, 0.4)
        assert histogram_quantile((10, 0, 0, 0), bounds, 0.99) == 0.1
        assert histogram_quantile((50, 49, 1, 0), bounds, 0.99) == 0.2
        assert histogram_quantile((0, 0, 0, 5), bounds, 0.5) == 0.4
        import math
        assert math.isnan(histogram_quantile((0, 0, 0, 0), bounds, 0.5))

    def test_histogram_quantile_edge_shapes(self):
        """Empty/all-zero counts, a single bucket, and degenerate
        no-finite-bounds layouts answer (NaN or a bound), never raise."""
        import math

        from pbccs_tpu.obs.metrics import histogram_quantile

        # empty layouts: no counts at all / no finite bounds
        assert math.isnan(histogram_quantile((), (), 0.5))
        assert math.isnan(histogram_quantile((5,), (), 0.9))
        # all-zero counts at every width
        assert math.isnan(histogram_quantile((0,), (), 0.5))
        assert math.isnan(histogram_quantile((0, 0), (1.0,), 0.5))
        # a single bucket: everything lands on its one bound
        assert histogram_quantile((3, 0), (1.0,), 0.01) == 1.0
        assert histogram_quantile((3, 0), (1.0,), 0.99) == 1.0
        # overflow-only observations report the last finite bound
        assert histogram_quantile((0, 7), (1.0,), 0.5) == 1.0
        # q=0 and q=1 extremes stay in range
        assert histogram_quantile((1, 1, 0), (0.1, 0.2), 0.0) == 0.1
        assert histogram_quantile((1, 1, 0), (0.1, 0.2), 1.0) == 0.2

    def test_hostile_label_values_roundtrip_federation(self):
        """Label values containing backslash, quote, newline, and a
        literal `}` must survive render -> relabel -> merge -> parse
        without corrupting the exposition (the values the fleet mints
        from network identity are not this hostile; a chaos test's
        are)."""
        from pbccs_tpu.obs.metrics import (MetricsRegistry,
                                           merge_expositions,
                                           parse_exposition,
                                           relabel_exposition)

        hostile = 'a\\b"c}d\ne'
        reg = MetricsRegistry()
        reg.counter("ccs_hostile_total", "t", path=hostile).inc(3)
        body = reg.render_prometheus()
        relabeled = relabel_exposition(body, replica="r:1")
        merged = merge_expositions([relabeled])
        parsed = parse_exposition(merged)
        key = ("ccs_hostile_total",
               (("path", hostile), ("replica", "r:1")))
        assert parsed[key] == 3.0
        # the relabel actually landed (a corrupted line would have been
        # passed through unlabeled)
        assert all("replica" in dict(labels)
                   for (_n, labels) in parsed)

    def test_relabel_escapes_injected_label_value(self):
        from pbccs_tpu.obs.metrics import (parse_exposition,
                                           relabel_exposition)

        out = relabel_exposition("a_total 1\n", replica='x"y\\z')
        assert parse_exposition(out)[
            ("a_total", (("replica", 'x"y\\z'),))] == 1.0

    def test_merge_empty_and_comment_only_parts(self):
        from pbccs_tpu.obs.metrics import merge_expositions

        assert merge_expositions([]) == ""
        assert merge_expositions(["", "# HELP x_total h\n"]) == ""
        merged = merge_expositions(["", "# TYPE a_total counter\n"
                                        "a_total 1\n"])
        assert "a_total 1" in merged


class TestHttpExposition:
    """obs/httpexp.py error paths: 404 on unknown paths, a scrape
    racing server shutdown degrades to a connection error (never a
    handler traceback), and /healthz tracks the health callback
    through an engine drain."""

    @staticmethod
    def _stop(server):
        # shutdown() only stops serve_forever; server_close() releases
        # the listening socket so later connects fail fast and tests
        # don't leak fds for the process lifetime
        server.shutdown()
        server.server_close()

    @staticmethod
    def _get(port, path, timeout=5.0):
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=timeout)
        try:
            conn.request("GET", path)
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def test_unknown_path_is_404(self):
        from pbccs_tpu.obs.httpexp import start_metrics_http

        server = start_metrics_http(lambda: "x 1\n")
        try:
            status, body = self._get(server.server_port, "/nope")
            assert status == 404 and b"not found" in body
            status, _ = self._get(server.server_port,
                                  "/metrics/../../etc/passwd")
            assert status == 404
        finally:
            self._stop(server)

    def test_render_error_is_500_and_server_survives(self):
        from pbccs_tpu.obs.httpexp import start_metrics_http

        calls = [0]

        def render():
            calls[0] += 1
            if calls[0] == 1:
                raise RuntimeError("boom")
            return "ok_total 1\n"

        server = start_metrics_http(render)
        try:
            status, body = self._get(server.server_port, "/metrics")
            assert status == 500 and b"boom" in body
            status, body = self._get(server.server_port, "/metrics")
            assert status == 200 and b"ok_total" in body
        finally:
            self._stop(server)

    def test_healthz_tracks_health_callback(self):
        from pbccs_tpu.obs.httpexp import start_metrics_http

        healthy = [True]
        server = start_metrics_http(lambda: "x 1\n",
                                    health=lambda: healthy[0])
        try:
            status, body = self._get(server.server_port, "/healthz")
            assert status == 200 and body == b"ok\n"
            healthy[0] = False
            status, body = self._get(server.server_port, "/healthz")
            assert status == 503 and body == b"draining\n"
            # a RAISING health callback reads as unhealthy, not a 500
            server2 = start_metrics_http(
                lambda: "x 1\n",
                health=lambda: (_ for _ in ()).throw(RuntimeError()))
            try:
                status, _ = self._get(server2.server_port, "/healthz")
                assert status == 503
            finally:
                self._stop(server2)
        finally:
            self._stop(server)

    def test_healthz_accurate_during_engine_drain(self):
        import numpy as np

        from pbccs_tpu.obs.httpexp import start_metrics_http
        from pbccs_tpu.pipeline import Failure, PreparedZmw
        from pbccs_tpu.serve.engine import CcsEngine, ServeConfig

        eng = CcsEngine(
            config=ServeConfig(max_batch=1, max_wait_ms=20.0),
            prep_fn=lambda c, s: (None, PreparedZmw(
                c, np.zeros(8, np.int8), [], 1, 0, 0.0)),
            polish_fn=lambda p, s: [(Failure.SUCCESS, None)
                                    for _ in p]).start()
        server = start_metrics_http(eng.metrics_text,
                                    health=eng.accepting)
        try:
            assert self._get(server.server_port, "/healthz")[0] == 200
            eng.close()   # drain begins: accepting flips false
            assert self._get(server.server_port, "/healthz")[0] == 503
        finally:
            self._stop(server)

    def test_scrape_racing_shutdown_degrades(self):
        """Scrapes fired while the server shuts down either answer or
        fail THEIR socket; none leaves the server wedged and the port
        is dead afterwards."""
        import threading

        from pbccs_tpu.obs.httpexp import start_metrics_http

        server = start_metrics_http(lambda: "x 1\n" * 200)
        port = server.server_port
        outcomes = []

        def scrape():
            try:
                outcomes.append(self._get(port, "/metrics",
                                          timeout=2.0)[0])
            except Exception:  # noqa: BLE001 -- any transport-level
                # failure (reset, torn reply, timeout) is the expected
                # degradation; a traceback OUT of the server is not
                outcomes.append("conn_error")

        threads = [threading.Thread(target=scrape) for _ in range(8)]
        for i, t in enumerate(threads):
            t.start()
            if i == 3:
                self._stop(server)
        for t in threads:
            t.join(timeout=5.0)
        assert len(outcomes) == 8
        assert all(o in (200, "conn_error") for o in outcomes), outcomes
        import pytest as _pytest
        with _pytest.raises(OSError):
            self._get(port, "/metrics", timeout=1.0)


class TestFlightRecorder:
    def test_ring_bounds_and_gauges(self):
        from pbccs_tpu.obs import flight

        rec = flight.FlightRecorder(capacity=4)
        for i in range(10):
            rec.record_round("b0", i, live=8 - i if i < 8 else 0,
                             n_zmws=8, z=16)
        snap = rec.snapshot()
        assert len(snap) == 4                  # ring stays bounded
        assert snap[-1]["round"] == 9
        assert snap[-1]["padding_waste"] == 0.5
        reg = obs_metrics.default_registry()
        snapshot = reg.snapshot()
        key = ("ccs_refine_padding_waste", ())
        assert key in snapshot

    def test_dump_logs_and_keeps(self):
        from pbccs_tpu.obs import flight

        rec = flight.FlightRecorder(capacity=8)
        rec.record_round("b1", 0, 4, 4, 8)

        class FakeLog:
            def __init__(self):
                self.lines = []

            def warn(self, msg):
                self.lines.append(msg)

        log = FakeLog()
        out = rec.dump("test-reason", log)
        assert len(out) == 1
        assert log.lines and "test-reason" in log.lines[0]
        assert rec.snapshot()                  # keep=True by default


class TestStageHistogramsAndSlo:
    def test_stage_latency_and_slo_counters_advance(self):
        """A served request leaves per-stage samples and, with a tiny
        --sloP99Ms, a burn-rate violation; the status verb carries the
        slo block."""
        from pbccs_tpu.serve.client import CcsClient
        from pbccs_tpu.serve.server import CcsServer
        from tests.test_serve import stub_engine

        reg = obs_metrics.default_registry()
        scope = reg.scope()
        eng = stub_engine(max_batch=1, max_wait_ms=20.0)
        # impossible objective: every request violates
        object.__setattr__(eng.config, "slo_p99_ms", 1e-6)
        eng.start()
        srv = CcsServer(eng, port=0).start()
        try:
            with CcsClient(srv.host, srv.port) as cli:
                msg = cli.submit("m/1", ["ACGTACGT"] * 4).reply(10.0)
                assert msg["status"] == "Success"
                st = cli.status()
                assert st["slo"]["enabled"] is True
                assert st["slo"]["target_p99_ms"] == 1e-6
        finally:
            srv.shutdown()
            eng.close()
        delta = scope.delta()
        stages = {k[1][0][1] for k, v in delta.items()
                  if k[0] == "ccs_serve_stage_latency_seconds"
                  and v[2] > 0}
        assert {"admission", "prepare", "queue", "dispatch", "polish",
                "emit"} <= stages
        assert scope.counter_value("ccs_slo_requests_total") >= 1
        assert scope.counter_value("ccs_slo_violations_total") >= 1
