"""A ZMW whose refinement rounds repeat stops when that is shown, where
the device loop has no straggler exit (parallel/device_refine.py
`CycleWatch`): not converged, as at the round budget, and without holding
the other slots of its dispatch until then.  CPU, 80 bp, one shape."""

import numpy as np
import pytest

from pbccs_tpu.models.arrow import refine as refine_mod
from pbccs_tpu.models.arrow.refine import RefineOptions
from pbccs_tpu.obs.metrics import default_registry
from pbccs_tpu.parallel import batch as pbatch
from pbccs_tpu.parallel import device_refine as dr
from pbccs_tpu.simulate import simulate_zmw

BUDGET = 17     # a round budget no other test's loop is traced at


@pytest.fixture
def ping_pong(monkeypatch):
    """A favorability floor under zero: a mutation that loses up to 3 nats
    reads favorable and so does its reversal, the ping-pong that f32 noise
    gives a long template on the chip."""
    monkeypatch.setattr(refine_mod, "FAVORABILITY_NOISE_FLOOR", -0.003)
    pbatch._favorability_eps.clear_cache()
    yield
    pbatch._favorability_eps.clear_cache()


def test_a_periodic_zmw_stops_early_and_the_rest_are_untouched(
        ping_pong, monkeypatch):
    rng = np.random.default_rng(7)
    tasks = []
    for z in range(4):
        tpl, reads, strands, snr = simulate_zmw(rng, 80, 5)
        draft = tpl.copy()
        draft[40] = (draft[40] + 1) % 4
        tasks.append(pbatch.ZmwTask(f"c/{z}", draft, snr, reads, strands,
                                    [0] * 5, [len(draft)] * 5))
    opts = RefineOptions(max_iterations=BUDGET)
    monkeypatch.setenv("PBCCS_DEVICE_REFINE", "0")
    host = pbatch.BatchPolisher(tasks)
    at_budget = host.refine(opts)
    cyclers = [z for z, r in enumerate(at_budget) if not r.converged]
    assert 1 <= len(cyclers) < len(tasks)
    assert all(at_budget[z].iterations == BUDGET for z in cyclers)

    scope = default_registry().scope()
    monkeypatch.setenv("PBCCS_DEVICE_REFINE", "1")
    dev = pbatch.BatchPolisher(tasks)
    assert dev._loop_state().cycle is not None       # Z = 4: no early exit
    stopped = dev.refine(opts)
    for z, (want, got) in enumerate(zip(at_budget, stopped)):
        assert got.converged == want.converged
        if z in cyclers:
            assert got.iterations < BUDGET // 2
        else:
            assert (got.iterations, got.n_applied, got.n_tested) == (
                want.iterations, want.n_applied, want.n_tested)
            np.testing.assert_array_equal(dev.tpls[z], host.tpls[z])
    moved = {dict(k)["kind"]: v for k, v in scope.counters(
        "ccs_refine_cycle_stops_total").items()}
    assert moved["zmws"] == len(cyclers)
    assert moved["rounds_spared"] == sum(
        BUDGET - stopped[z].iterations for z in cyclers)


def test_a_loop_with_a_straggler_exit_carries_no_watch():
    assert dr.straggler_exit_zmws(16) == 0 and dr.straggler_exit_zmws(32) == 1
    rng = np.random.default_rng(8)
    tpl, reads, strands, snr = simulate_zmw(rng, 40, 3)
    task = pbatch.ZmwTask("c/w", tpl, snr, reads, strands, [0] * 3,
                          [len(tpl)] * 3)
    assert pbatch.BatchPolisher([task], min_z=32)._loop_state().cycle is None
