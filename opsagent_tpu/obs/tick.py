"""Tick phases and the step clock: how the thread that drives the engine
spends a tick, and how long each dispatched step ran on the device, both
without a profiler.

``phase(name, **ids)`` is span and counter together, on one clock: it
reads ``time.perf_counter()`` on entry and exit, holds a
``TraceAnnotation("engine.<name>", **ids)`` open between them (free when no
capture runs; on the device trace's clock when one does), and adds the
elapsed seconds to ``opsagent_tick_phase_seconds_total{phase}``. Phases
nest by suspension, not by inclusion: entering one pauses the enclosing
phase (its seconds stop, its annotation closes) and leaving resumes it, so
a thread is in exactly one phase at a time and the phases' seconds add up
to the wall time between the outermost entry and exit.

``StepClock`` turns the pulls the engine makes anyway into device step
times: at each pull the host learns when a step finished.
"""

from __future__ import annotations

import threading
import time

# obs/__init__ imports this module after it has made its instruments.
from . import STEP_DEVICE_SECONDS, STEP_LATE_PULLS, TICK_PHASE_SECONDS

_local = threading.local()
_annotation = None


def _trace_annotation():
    # jax is imported at first use: obs is also imported by processes
    # (router, agent CLI) that never touch a device.
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation


class phase:
    """``with obs.phase("wait", tick=7): ...`` — see the module docstring.
    ``name`` is one of ``obs.TICK_PHASES``."""

    __slots__ = ("name", "ids", "_outer", "_t0", "_ann")

    def __init__(self, name: str, **ids):
        self.name = name
        self.ids = ids

    def _open(self, now: float) -> None:
        self._t0 = now
        self._ann = _trace_annotation()("engine." + self.name, **self.ids)
        self._ann.__enter__()

    def _pause(self, now: float) -> None:
        self._ann.__exit__(None, None, None)
        TICK_PHASE_SECONDS.inc(now - self._t0, phase=self.name)

    def __enter__(self) -> "phase":
        now = time.perf_counter()
        self._outer = getattr(_local, "top", None)
        if self._outer is not None:
            self._outer._pause(now)
        _local.top = self
        self._open(now)
        return self

    def __exit__(self, *exc) -> None:
        now = time.perf_counter()
        self._pause(now)
        _local.top = self._outer
        if self._outer is not None:
            self._outer._open(now)


class StepClock:
    """Device time of each step from the times of the pulls.

    The device runs steps one after another in enqueue order, so step k
    ran from ``max(ready_{k-1}, enqueued_k)`` to ``ready_k``. The host
    learns ``ready_k`` exactly when its pull of step k had to wait (the
    step was still running when the host arrived). A pull that found its
    result ready only bounds it (the device had been waiting for the
    host), so it gives no sample and counts as late; so does a step whose
    start is unknown because the step before it was pulled late while
    this one was already enqueued, or was never pulled at all (a prefill
    chunk that does not finish its prompt).

    One clock per engine; every method runs under the engine's lock."""

    def __init__(self, clock=time.perf_counter):
        self._clock = clock
        self._seq = 0          # steps enqueued so far
        self._pulled = 0       # the newest step whose pull has come
        self._ready = 0.0      # when that pull returned ...
        self._exact = True     # ... and whether it had waited for the step

    def enqueue(self) -> tuple[int, float]:
        """Stamp a step just before its dispatch: (ticket, enqueue time)."""
        self._seq += 1
        return self._seq, self._clock()

    def pulled(
        self, program: str, bucket: int, ticket: tuple[int, float],
        waited: bool,
    ) -> None:
        """The pull of the step ``ticket`` has returned; ``waited`` says
        whether the step was still running when the host arrived."""
        now = self._clock()
        seq, enqueued = ticket
        start_known = seq == self._pulled + 1 and (
            self._exact or enqueued >= self._ready
        )
        if waited and start_known:
            STEP_DEVICE_SECONDS.observe(
                now - max(self._ready, enqueued),
                program=program, bucket=str(bucket),
            )
        else:
            STEP_LATE_PULLS.inc(program=program)
        self._pulled, self._ready, self._exact = seq, now, waited
