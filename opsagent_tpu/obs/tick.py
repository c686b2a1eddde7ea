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
to the wall time between the outermost entry and exit. The seconds reach
the counters when the thread's outermost phase ends.

A phase has parts, one level down: ``phase(name, part="match")`` is the
thread in ``engine.<name>.<part>`` (the enclosing ``engine.<name>`` closes
and re-opens around it, like any nested phase), and its seconds go to the
phase's counter as before AND to ``opsagent_tick_part_seconds_total{phase,
part}``. What a phase spends under no part is its ``other`` (the phase's
counter less its parts'). A part opens a few times a tick or once an
admission; a cost that recurs a row or a token is timed by its caller and
handed to ``add_part`` (counter only, no annotation), which takes the
seconds out of the part open around it, so the parts of a phase partition
it and no second counts twice.

``StepClock`` turns the pulls the engine makes anyway into device step
times: at each pull the host learns when a step finished.
"""

from __future__ import annotations

import threading
import time

# obs/__init__ imports this module after it has made its instruments.
from . import (
    STEP_DEVICE_SECONDS, STEP_LATE_PULLS, TICK_PART_SECONDS,
    TICK_PHASE_SECONDS,
)

# The phases in which the thread works (not blocked on the device, not
# waiting for a request): what opsagent_tick_host_work_seconds adds up.
WORK_PHASES = frozenset(("admit", "plan", "dispatch", "commit", "reap"))

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
    """``with obs.phase("wait", tick=7): ...`` and ``with obs.phase("admit",
    part="match"): ...`` — see the module docstring. ``name`` is one of
    ``obs.TICK_PHASES``."""

    __slots__ = (
        "name", "part", "ids", "_label", "_outer", "_t0", "_ann", "_sums")

    def __init__(self, name: str, part: str | None = None, **ids):
        self.name = name
        self.part = part
        self.ids = ids
        self._label = (
            "engine." + name if part is None else f"engine.{name}.{part}")
        self._sums: dict[str, float] | None = None

    def _open(self, now: float) -> None:
        self._t0 = now
        self._ann = _trace_annotation()(self._label, **self.ids)
        self._ann.__enter__()

    def _pause(self, now: float) -> None:
        """End a stretch: its seconds go to the thread's own sums, which
        reach the counters when its outermost phase ends (a lock and a
        label check a stretch would be a third of what a part costs)."""
        self._ann.__exit__(None, None, None)
        spent = now - self._t0
        held = _local.held
        name = self.name
        held[name] = held.get(name, 0.0) + spent
        if self._sums:
            # what add_part was handed while this stretch was open
            for part, seconds in self._sums.items():
                key = (name, part)
                held[key] = held.get(key, 0.0) + seconds
                spent -= seconds
            self._sums = None
        if self.part is not None:
            key = (name, self.part)
            held[key] = held.get(key, 0.0) + max(0.0, spent)

    def __enter__(self) -> "phase":
        now = time.perf_counter()
        self._outer = getattr(_local, "top", None)
        if self._outer is not None:
            self._outer._pause(now)
        elif not hasattr(_local, "held"):
            _local.held = {}
        _local.top = self
        self._open(now)
        return self

    def __exit__(self, *exc) -> None:
        now = time.perf_counter()
        self._pause(now)
        _local.top = self._outer
        if self._outer is not None:
            self._outer._open(now)
        else:
            _flush()


def _flush() -> None:
    """The thread's sums into the counters: as its outermost phase ends, so
    a scrape misses at most the outermost phase in progress."""
    held = getattr(_local, "held", None)
    if not held:
        return
    work = 0.0
    for key, seconds in held.items():
        if type(key) is str:
            TICK_PHASE_SECONDS.inc(seconds, phase=key)
            if key in WORK_PHASES:
                work += seconds
        else:
            TICK_PART_SECONDS.inc(seconds, phase=key[0], part=key[1])
    held.clear()
    _local.work = getattr(_local, "work", 0.0) + work


def add_part(name: str, part: str, seconds: float) -> None:
    """``seconds`` of the phase ``name`` were spent in ``part``: a cost
    that recurs a row or a token, timed by the caller. Counter only, no
    annotation. While the thread is in a phase the seconds are held until
    that stretch ends (one counter add a part) and come out of the part
    it is; they are counted under the phase the thread IS in, which is
    ``name`` wherever the callers are placed as meant, so that no phase's
    parts can add up to more than the phase."""
    top = getattr(_local, "top", None)
    if top is None:
        TICK_PART_SECONDS.inc(seconds, phase=name, part=part)
        return
    if top._sums is None:
        top._sums = {}
    top._sums[part] = top._sums.get(part, 0.0) + seconds


def take_host_work() -> float:
    """The seconds this thread spent in the work phases since it last
    asked: one tick's host work, where the scheduler counts a tick."""
    spent = getattr(_local, "work", 0.0)
    _local.work = 0.0
    return spent


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

    def enqueue(self, width: str = "") -> tuple[int, float, str]:
        """Stamp a step just before its dispatch: (ticket, enqueue time,
        ``width``). ``width`` is the rows the dense segments of a mixed
        program run over in this tick (``Engine._step_rows``, as
        ``opsagent_mixed_dispatch_width_total`` counts it), empty for
        programs that are not mixed; the pull labels its sample with it."""
        self._seq += 1
        return self._seq, self._clock(), width

    def pulled(
        self, program: str, bucket: int, ticket: tuple[int, float, str],
        waited: bool,
    ) -> None:
        """The pull of the step ``ticket`` has returned; ``waited`` says
        whether the step was still running when the host arrived."""
        now = self._clock()
        seq, enqueued, width = ticket
        start_known = seq == self._pulled + 1 and (
            self._exact or enqueued >= self._ready
        )
        if waited and start_known:
            STEP_DEVICE_SECONDS.observe(
                now - max(self._ready, enqueued),
                program=program, bucket=str(bucket), width=width,
            )
        else:
            STEP_LATE_PULLS.inc(program=program)
        self._pulled, self._ready, self._exact = seq, now, waited
