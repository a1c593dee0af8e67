"""Test configuration: force an 8-device virtual CPU mesh so sharding tests
run anywhere and deterministically; the chip run is chip_smoke.py.

The override is unconditional: on a machine with one chip JAX would
otherwise use it, which would break multi-device mesh tests."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# If jax was imported before this file, the env override above was
# captured too late: force the config directly before any backend
# initializes.
import jax

jax.config.update("jax_platforms", "cpu")

# The device-resident refinement loop compiles one lax.while_loop program
# per (Z, R, Jmax, opts) shape; across the suite's many shapes that is
# minutes of XLA time testing nothing new.  The host loop (the behavior
# the device loop is parity-pinned against in test_device_refine.py) runs
# by default; device-loop tests opt back in per-test.
os.environ.setdefault("PBCCS_DEVICE_REFINE", "0")

# persistent compilation cache: the batched polish programs take minutes to
# compile on CPU; cached executables make repeat test runs fast
from pbccs_tpu.runtime.cache import enable_compilation_cache  # noqa: E402

enable_compilation_cache()

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(20260729)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Drop compiled executables and tracing caches after each test module.

    The full suite compiles hundreds of distinct program shapes; letting
    them accumulate in one process degrades dispatch and tracing until the
    heavy tail tests crawl (observed: a test that takes 70 s alone taking
    5-10x longer at the end of the suite).  The persistent compilation
    cache makes any cross-module recompiles cheap disk loads."""
    yield
    jax.clear_caches()
