"""`correct` has to come out false when it should.

These drive a whole run of `run.py` in this process, at `--rehearse` size on
the CPU (the harness's look for a chip is the one thing `--rehearse` skips),
through the batch CLI: set-up, warm-up, window, output check.

* a sound run is correct;
* a control (controls/*.json, the program's own degraded path switched on)
  is not;
* with the timed path broken underneath -- an answer altered where it is
  produced -- it is not.

They take about half a minute each: the program compiles for the CPU.
The limits are the configuration's `rehearse_check`, read at these sizes.
On the chip the control ran at the cell's own size; PERF.md has the
readings.
"""

import json
import os

import numpy as np
import pytest

import run as bench_run

CELL = "500bp-30x.batch"


@pytest.fixture(autouse=True)
def cpu_speed(monkeypatch):
    # the device-resident refine loop takes minutes to compile for the CPU
    monkeypatch.setenv("PBCCS_DEVICE_REFINE", "0")
    before = dict(os.environ)
    yield
    for k in set(os.environ) - set(before):      # what program_env() set
        del os.environ[k]
    from pbccs_tpu.resilience import faults

    faults.install(None)                         # a control's --faults stay armed


def one_run(capsys, *extra):
    rc = bench_run.run(bench_run.parse_args(
        ["--workload", CELL, "--seed", "11", "--seconds", "1", "--trace", "0",
         "--rehearse", *extra]))
    out = capsys.readouterr().out
    last = json.loads(out.strip().splitlines()[-1])
    rows = {line.split()[1]: line for line in out.splitlines()
            if line.startswith("check: ") and " limit " in line}
    return rc, last, rows


def test_a_sound_run_is_correct(capsys):
    rc, last, rows = one_run(capsys)
    assert rc == 0 and last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == 16 and last["rehearsal"] is True
    # a CPU run reports no number under a metric's name, and no platform check
    assert last["metrics"] == {} and last["device"]["platform"] == "cpu"
    assert "platform_is_tpu_and_kernels_compiled" not in rows
    assert len(rows) >= 9 and all(line.endswith("ok") for line in rows.values())


def test_the_draft_only_control_is_not_correct(capsys):
    rc, last, rows = one_run(capsys, "--control", "draft-only")
    assert rc == 1 and last["correct"] is False and last["failed"] == 16
    assert rows["zmws_failed_lost_or_degraded"].endswith("FAILED")
    # and not by the flag alone: the reference finds steps toward the truth
    # that refinement would have taken, and QVs that are not its own
    assert rows["toward_truth_gain_nats_max"].endswith("FAILED")
    assert rows["qv_gap_max"].endswith("FAILED")


def _break(monkeypatch, how):
    from pbccs_tpu import pipeline

    real = pipeline._finish_zmw

    def broken(prep, settings, tpl, qvs, refine, *rest):
        if how == "qv_inflated":
            qvs = np.asarray(qvs) + 20
        elif how == "one_base_in_every_zmw":
            tpl = np.array(tpl, copy=True)
            tpl[40] = (tpl[40] + 1) % 4
            tpl[80] = (tpl[80] + 1) % 4
        elif how == "one_zmw_of_the_batch" and str(prep.chunk.id).endswith("/21"):
            # one slot of one batch (hole 21 is in the window's first file):
            # eight bases of a single consensus
            tpl = np.array(tpl, copy=True)
            tpl[15::12] = (tpl[15::12] + 1) % 4
        return real(prep, settings, tpl, qvs, refine, *rest)

    monkeypatch.setattr(pipeline, "_finish_zmw", broken)


@pytest.mark.parametrize("how,number", [
    ("qv_inflated", "qv_gap_max"),
    ("one_base_in_every_zmw", "edits_per_1000_zmws"),
    ("one_zmw_of_the_batch", "zmws_over_allowed_edits"),
])
def test_an_answer_altered_where_it_is_produced_is_not_correct(capsys, monkeypatch,
                                                               how, number):
    _break(monkeypatch, how)
    rc, last, rows = one_run(capsys)
    assert rc == 1 and last["correct"] is False
    assert rows[number].endswith("FAILED")
    if how == "one_zmw_of_the_batch":
        # and the reference, which always samples the ZMW farthest from its
        # template, finds that the reads do not support what was served
        assert rows["zmws_over_allowed_edits"].split()[3] == "1"
        assert rows["toward_truth_gain_nats_max"].endswith("FAILED")
