"""A shape set's first polish loads its straggler continuation's programs
and its wide-band retry's too (`polish.warm`, BatchPolisher.warm_shape_set),
so a later batch that leaves a straggler or fails a mating stops nothing.

CPU only: two batches of 32 ZMWs x 60 bp through `ccs` (the least Z with a
straggler early exit), with the host refinement loop of tests/conftest.py.
The tests assert which spans exist and which counters move.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

from pbccs_tpu import cli
from pbccs_tpu.models.arrow.refine import RefineOptions
from pbccs_tpu.parallel import batch as pbatch
from pbccs_tpu.simulate import simulate_zmw
from tests.test_ragged_file import (LOAD_SECONDS, SHAPE_SETS, counter_total,
                                    write_subread_bam)

_spec = importlib.util.spec_from_file_location(
    "trace_cover", os.path.join(os.path.dirname(__file__), os.pardir,
                                "tools", "trace_cover.py"))
trace_cover = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(trace_cover)


@pytest.fixture(scope="module")
def two_batches_of_32(tmp_path_factory):
    """`ccs --chunkSize 32 --trace-out` on 64 ZMWs.  After its own refine,
    every full-size polisher past the first is made to leave ZMW 0 behind
    and to retry two ZMWs at the wide band: it builds the continuation's
    sub-polisher and the retry's and runs both, and what that moved is
    noted."""
    tmp = tmp_path_factory.mktemp("shape_sets")
    rng = np.random.default_rng(20260929)
    zmws = []
    for hole in range(1, 65):
        _tpl, reads, _strands, snr = simulate_zmw(rng, 60, 3)
        zmws.append((hole, reads, snr))
    in_bam = str(tmp / "subreads.bam")
    write_subread_bam(in_bam, zmws)

    parents, continuations = [], []
    refine = pbatch.BatchPolisher.refine

    def refine_then_leave_one_behind(self, opts=None, skip=None, budget=None):
        out = refine(self, opts, skip, budget)
        if self._Z == 32:
            parents.append((self.first_of_shape_set,
                            counter_total(SHAPE_SETS)))
            if len(parents) > 1:
                before = (counter_total(LOAD_SECONDS),
                          counter_total(SHAPE_SETS))
                sub = self._straggler_sub([0])
                refine(sub, opts or RefineOptions())
                sub.consensus_qvs()
                wide, = self.wide_band_subs(self._row_tasks([1, 2], "wide"))
                wide.statuses
                refine(wide, opts or RefineOptions())
                wide.consensus_qvs()
                continuations.append((
                    sub.first_of_shape_set or wide.first_of_shape_set,
                    (sub._Imax, sub._Jmax, sub._R), sub._Z, before,
                    (counter_total(LOAD_SECONDS), counter_total(SHAPE_SETS))))
        return out

    pbatch.shape_menu.reset_for_tests()
    with pbatch._shape_sets_lock:       # sets an earlier test file of this
        pbatch._shape_sets_seen.clear()  # worker's process built count anew
    sets_before = counter_total(SHAPE_SETS)
    pbatch.BatchPolisher.refine = refine_then_leave_one_behind
    try:
        out, trace_out = str(tmp / "out.bam"), str(tmp / "t.json")
        assert cli.run([out, in_bam, "--reportFile", out + ".csv",
                        "--numThreads", "2", "--chunkSize", "32",
                        "--logLevel", "WARN", "--trace-out", trace_out]) == 0
    finally:
        pbatch.BatchPolisher.refine = refine
    with open(trace_out) as f:
        chrome = json.load(f)
    return (chrome, parents, continuations,
            counter_total(SHAPE_SETS) - sets_before)


def test_the_continuations_programs_come_with_the_first_batch(
        two_batches_of_32):
    """The first batch brings three shape sets, its own, its
    continuation's and its wide-band retry's; the second batch's
    continuation and retry are no new sets and load nothing."""
    _chrome, parents, continuations, new_sets = two_batches_of_32
    assert [first for first, _sets in parents] == [True, False]
    assert new_sets == 3
    (first_of_set, buckets, z, before, after), = continuations
    assert first_of_set is False and z == 4
    assert buckets == (128, 128, 4)             # the parent's pin
    assert after == before                      # nothing traced or loaded


def test_polish_warm_opens_once_a_pin_and_polish_stays_covered(
        two_batches_of_32):
    chrome, _parents, _continuations, _new_sets = two_batches_of_32
    events = chrome["traceEvents"]
    by_id = {e["id"]: e for e in events}
    (warm,) = [e for e in events if e["name"] == "polish.warm"]
    assert {k: warm["args"][k] for k in ("imax", "jmax", "r", "z")} == {
        "imax": 128, "jmax": 128, "r": 4, "z": 4}
    polish = by_id[warm["args"]["parent"]]
    assert polish["name"] == "polish" and polish["args"]["batch"] == 0
    # every program this run brought up came up inside the first polish
    t_closed = polish["ts"] + polish["dur"]
    late = [e for e in events if e["name"].startswith("program.")
            and e["ts"] > t_closed]
    assert late == []
    # tools/trace_cover.py: a polish is still covered by its parts
    covered = trace_cover.coverage(events, "polish", trace_cover.POLISH_PARTS)
    assert len(covered) == 2 and min(covered) > 0.97
    without = [p for p in trace_cover.POLISH_PARTS if p != "polish.warm"]
    assert min(trace_cover.coverage(events, "polish", without)) < 0.9
    assert min(trace_cover.batch_coverage(events)) > 0.9
