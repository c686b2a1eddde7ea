"""Device-level profiling: ``jax.profiler`` traces, host annotations and
kernel scopes, layered on the request-level perf registry.

The reference instruments the host path only — named start/stop timers
aggregated to min/max/avg/p95/p99 (reference pkg/utils/perf.go:168-210),
exposed at GET /api/perf/stats (reference pkg/api/router.go:104). On TPU
that misses where the time actually goes: host wall-clock around a dispatch
measures the *enqueue*, not the device, because XLA execution is async.
This module adds the device-side views SURVEY §5 calls for:

1. **Traces** — ``trace()`` wraps a region in a ``jax.profiler`` capture
   (TensorBoard/xprof format: per-op device timelines, HLO, memory). Opt-in
   via ``OPSAGENT_PROFILE_DIR`` or an explicit ``logdir``; no-op otherwise,
   so production serving pays nothing.
2. **Names on the trace** — ``annotate()`` names host regions inside an
   active trace (free when no trace is running; ``obs.phase`` builds the
   tick phases on it), and ``scoped()`` puts a function's device
   operations under a ``jax.named_scope`` (metadata only: the compiled
   program is the same), so the trace's reduction finds each kernel by
   the program's own name.

Per-step device times with no trace at all come from the step clock
(``obs.StepClock``, ``opsagent_step_device_seconds``).
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Callable, Iterator

import jax

from .logger import get_logger

log = get_logger("profiling")

_ENV_DIR = "OPSAGENT_PROFILE_DIR"


def profile_dir() -> str | None:
    """The configured trace directory, or None when tracing is off."""
    return os.environ.get(_ENV_DIR) or None


@contextlib.contextmanager
def trace(logdir: str | None = None) -> Iterator[None]:
    """Capture a ``jax.profiler`` trace of the enclosed region into
    ``logdir`` (or ``$OPSAGENT_PROFILE_DIR``). No-op when neither is set.

    The capture includes device timelines for every XLA program launched
    inside the region — the tool for answering "where do the ms/step go"
    that host timers cannot (they only see the async enqueue).
    """
    logdir = logdir or profile_dir()
    if not logdir:
        yield
        return
    jax.profiler.start_trace(logdir)
    log.info(f"jax.profiler trace started -> {logdir}")
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        log.info(f"jax.profiler trace written -> {logdir}")


def annotate(name: str) -> contextlib.AbstractContextManager:
    """Name a host region on the profiler timeline (TraceAnnotation).
    Free when no trace is active; safe to leave in the hot path."""
    return jax.profiler.TraceAnnotation(name)


def scoped(name: str) -> Callable:
    """Decorator: trace the function under ``jax.named_scope(name)``, so
    every device operation it emits carries ``name`` on its ``op_name``
    path. ``name`` is one of ``models.llama.SCOPES``. The scope is entered
    at each call (not bound at decoration), which is what lets a test
    compile the same program without scopes."""
    def decorate(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return wrapper
    return decorate


MAX_CAPTURE_SECONDS = 120.0


def timed_capture(seconds: float, logdir: str | None = None) -> str:
    """Capture a ``jax.profiler`` device trace of the NEXT ``seconds`` of
    whatever the process is doing — the on-demand form behind
    ``POST /api/debug/profile?seconds=N``: live traffic keeps flowing
    while the capture runs, so the trace shows the real serving mix
    (dispatch composition, compiles, host gaps) instead of a synthetic
    bench loop. Blocking: run from a worker thread, never the event loop.

    Raises ``ValueError`` for a silly duration, ``RuntimeError`` when no
    trace directory is configured (``--profile-dir`` /
    ``$OPSAGENT_PROFILE_DIR`` — operator-configured only, so a network
    client cannot mint an arbitrary-filesystem-write primitive), and
    whatever ``jax.profiler.start_trace`` raises when a capture is
    already running (the caller maps that to 409)."""
    if not 0 < seconds <= MAX_CAPTURE_SECONDS:
        raise ValueError(
            f"seconds must be in (0, {MAX_CAPTURE_SECONDS:.0f}], "
            f"got {seconds}"
        )
    logdir = logdir or profile_dir()
    if not logdir:
        raise RuntimeError(
            "profiling not enabled: start the server with --profile-dir "
            "(or set OPSAGENT_PROFILE_DIR)"
        )
    import time

    jax.profiler.start_trace(logdir)
    log.info(f"on-demand profile capture started ({seconds}s) -> {logdir}")
    try:
        time.sleep(seconds)
    finally:
        jax.profiler.stop_trace()
        log.info(f"on-demand profile capture written -> {logdir}")
    return logdir


def save_device_memory_profile(path: str) -> None:
    """Dump the current device memory profile (pprof format) — which
    buffers hold HBM right now. Pairs with the allocator's page
    accounting for leak hunts."""
    jax.profiler.save_device_memory_profile(path)
