"""Opt-in jax.profiler capture (the CLI's --profile-dir hook).

Kept separate from metrics/trace because it is the one observability
surface that touches jax: importing it must stay lazy (inside the
context manager) so `ccs --help` and the pure-host tests never pay a
backend import, and a jax without profiler support (or a capture that
fails mid-run) degrades to a logged warning, never a crashed pipeline.
"""

from __future__ import annotations

import contextlib
from typing import Iterator


@contextlib.contextmanager
def profile_capture(profile_dir: str | None) -> Iterator[None]:
    """Capture a jax.profiler trace of the enclosed block into
    profile_dir (TensorBoard/XProf format).  No-op when profile_dir is
    falsy; never raises on profiler failure."""
    if not profile_dir:
        yield
        return
    started = False
    try:
        import jax

        # without the profiler's Python tracer: it slows the host it
        # measures, and the program's own spans are in the capture as
        # `ccs:` annotations (obs/trace.py)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(profile_dir, profiler_options=options)
        started = True
    except Exception as e:  # noqa: BLE001 -- observability must not kill work
        from pbccs_tpu.runtime.logging import Logger

        Logger.default().warn(f"jax profiler capture unavailable: {e!r}")
    try:
        yield
    finally:
        if started:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001
                from pbccs_tpu.runtime.logging import Logger

                Logger.default().warn(f"jax profiler stop failed: {e!r}")
