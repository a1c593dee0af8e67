"""The device's idle time split by the program's spans (`harness/
idle_by_span.py`) and its ten readers, on a hand-made device trace (three
operations, two gaps) and hand-made spans: which class an idle instant goes
to, that the classes sum to `device_idle_share`, that a capture of a program
without the sites reads nothing and an empty class reads 0.0."""

import pytest

from harness import idle_by_span, manifest, prom, xplane
from harness.reduce import ReaderInput

T0 = 1_790_000_000.0
SHARES = ["idle_setup_share", "idle_refine_share", "idle_polish_rest_share",
          "idle_complete_share", "idle_starved_share", "idle_between_runs_share",
          "idle_unexplained_share"]
PER_ZMW = ["device_starved_ms_per_zmw", "polish_wide_ms_per_zmw",
           "serve_complete_ms_per_zmw"]
NEW = SHARES + PER_ZMW
ALL = ["500bp-30x.batch", "2kb-3to10x.batch", "2kb-3to10x.serve-c32"]
CELLS = dict.fromkeys(NEW, ALL)
CELLS.update(idle_complete_share=ALL[2:], serve_complete_ms_per_zmw=ALL[2:],
             idle_between_runs_share=ALL[:2])


def trace(ops=((0.0, 2.0), (3.0, 2.0), (8.0, 2.0)), window_s=10.0, start=T0):
    """One chip, busy 0-2, 3-5 and 8-10 s of a 10 s capture: idle 2-3 and 5-8."""
    chip = xplane.Chip("/device:TPU:0",
                       [xplane.Op("fusion", a, d, "%fusion.1 = f32[8] fusion()")
                        for a, d in ops])
    return xplane.Trace([chip], start, window_s)


def span(name: str, start_s: float, end_s: float, **args) -> dict:
    return {"name": name, "ts": (T0 + start_s) * 1e6, "dur": (end_s - start_s) * 1e6,
            "args": dict(args, device_wait_ms=0.0)}


def read(name: str, spans, tr="default", zmws=100):
    inp = ReaderInput(prom.Counters({}, {}), list(spans),
                      trace() if tr == "default" else tr, zmws, "TPU v5 lite", {}, None)
    return manifest.load_by_path("metrics", name).read(inp)


# the first gap, 2-3 s, holds a polish and what follows it; the second, 5-8 s,
# a starved wait, the end of one invocation and the start of the next
SPANS = [span("run", 0.0, 6.0), span("run", 7.0, 10.0),
         span("polish", 1.5, 2.9, zmws=32, batch=0, device="tpu:0"),
         span("polish.setup", 2.0, 2.4), span("polish.refine", 2.2, 2.7),
         span("polish.refine.straggler", 2.5, 2.7),
         span("serve.complete", 2.85, 2.95, zmws=32, flush=1),
         span("device.starved", 2.95, 3.2, device="tpu:0", head=False),
         span("device.starved", 5.0, 5.5, device="tpu:0", head=False),
         span("draft.poa", 0.0, 10.0)]           # a prepare worker's: no class
FOUND = {"idle_setup_share": 4.0,         # 2.0-2.4
         "idle_refine_share": 3.0,        # 2.4-2.7: setup had 2.2-2.4 first
         "idle_polish_rest_share": 2.0,   # 2.7-2.9
         "idle_complete_share": 0.5,      # 2.9-2.95: polish had 2.85-2.9
         "idle_starved_share": 5.5,       # 2.95-3.0 and 5.0-5.5
         "idle_between_runs_share": 10.0,  # 6-7
         "idle_unexplained_share": 15.0}   # 5.5-6 and 7-8, inside a run
PARENT_SPANS = [s for s in SPANS if s["name"] not in
                ("device.starved", "serve.complete", "polish.wide")]


@pytest.mark.parametrize("name", SHARES)
def test_an_idle_instant_goes_to_the_first_class_that_holds_it(name):
    assert read(name, SPANS) == pytest.approx(FOUND[name], abs=1e-4)


def test_the_seven_classes_sum_to_device_idle_share():
    total = sum(read(name, SPANS) for name in SHARES)
    assert total == pytest.approx(read("device_idle_share", SPANS), abs=1e-4)
    assert total == pytest.approx(40.0, abs=1e-4)


def test_a_capture_with_no_run_span_has_no_between_runs_class():
    served = [s for s in SPANS if s["name"] != "run"]
    assert read("idle_between_runs_share", served) is None
    assert idle_by_span.split(trace(), served)["between_runs"] is None
    assert read("idle_unexplained_share", served) == pytest.approx(25.0, abs=1e-4)
    rest = [n for n in SHARES if n != "idle_between_runs_share"]
    assert sum(read(n, served) for n in rest) == pytest.approx(40.0, abs=1e-4)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_sites_reads_nothing(name):
    assert read(name, PARENT_SPANS) is None
    assert read(name, []) is None


@pytest.mark.parametrize("name", SHARES)
def test_a_capture_that_cannot_be_placed_on_the_wall_clock_reads_nothing(name):
    assert read(name, SPANS, tr=trace(start=None)) is None
    assert read(name, SPANS, tr=None) is None


@pytest.mark.parametrize("name", [n for n in NEW if n not in
                                  ("idle_starved_share", "device_starved_ms_per_zmw")])
def test_an_empty_class_reads_zero(name):
    """One starved wait while the device is busy, and a `run` over all of
    the capture: every site is there, nothing idle lies in any class."""
    spans = [span("run", -1.0, 11.0), span("device.starved", 0.5, 0.6, head=True)]
    want = 40.0 if name == "idle_unexplained_share" else 0.0
    assert read(name, spans) == pytest.approx(want, abs=1e-4)
    assert isinstance(read(name, spans), float)


def test_the_per_zmw_readers_sum_their_spans_over_the_windows_zmws():
    spans = SPANS + [span("polish.wide", 2.7, 2.8, zmws=2),
                     span("device.starved", -3.0, -1.0, device="tpu:0", head=True)]
    # 0.25 + 0.5 s and the 2 s head wait of an invocation before the capture
    assert read("device_starved_ms_per_zmw", spans, zmws=50) == pytest.approx(55.0)
    assert read("polish_wide_ms_per_zmw", spans, zmws=50) == pytest.approx(2.0)
    assert read("serve_complete_ms_per_zmw", spans, zmws=50) == pytest.approx(2.0)
    assert read("device_starved_ms_per_zmw", spans, zmws=0) is None
    # whole-window readers need no device trace
    assert read("polish_wide_ms_per_zmw", spans, tr=None, zmws=50) == pytest.approx(2.0)


def test_an_open_span_counts_up_to_the_capture_instant():
    """The served path's last wait is still open when the `trace` verb
    stops the capture: the tracer exports it measured to that instant."""
    spans = [span("device.starved", 5.0, 8.0, device="tpu:0", head=False, open=True)]
    assert read("idle_starved_share", spans) == pytest.approx(30.0, abs=1e-4)


def test_interval_arithmetic():
    ibs = idle_by_span
    assert ibs.merged([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [(0, 2), (3, 4)]
    assert ibs.overlap([(0, 2), (3, 6)], [(1, 4), (5, 9)]) == [(1, 2), (3, 4), (5, 6)]
    assert ibs.overlap([(0, 1)], []) == []


def test_the_entries_are_appended_in_order_with_their_cells():
    doc = manifest.load()
    assert [m["name"] for m in doc["per_layer"]][-len(NEW):] == NEW
    for m in doc["per_layer"][-len(NEW):]:
        assert m["workloads"] == CELLS[m["name"]], m["name"]
        assert m["better"] == "lower" and m["moves"] == "zmws_per_s"
        if m["name"] in SHARES:
            assert (m["unit"], m["source"], m["layer"]) == ("%", "device_trace", "device")
        else:
            assert (m["unit"], m["source"]) == ("ms/zmw", "program_span")
    layers = {m["name"]: m["layer"] for m in doc["per_layer"]}
    assert [layers[n] for n in PER_ZMW] == ["dispatch", "refine loop", "serve"]
    for m in doc["per_layer"]:
        manifest.load_by_path("metrics", m["name"])      # every entry has its reader
