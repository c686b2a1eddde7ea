"""Share of the token pulls that gave the step clock no sample: the result was
ready when the host arrived (the device had been waiting for the host), or
the step's start was not known. At 0 the host keeps ahead of the device; as
it rises the host sets the pace, and ``step.mixed_ms_mean`` keeps fewer and
lighter steps.

Layer: engine step (obs/tick.py ``StepClock.pulled``, called from
serving/engine.py ``Engine._pull``). Source: the window's delta of the
EXISTING ``opsagent_step_late_pulls_total`` over itself plus
``opsagent_step_device_seconds_count``, every program: the share PERF.md has
been quoting from logs; the parent commit reads the same number.
Moves: tpot_p50_ms.
"""
from benchmarks.client import delta


def read(ctx: dict):
    late = delta(ctx["before"], ctx["after"], "opsagent_step_late_pulls_total")
    sampled = delta(
        ctx["before"], ctx["after"], "opsagent_step_device_seconds_count")
    if late + sampled <= 0:
        return None
    return 100.0 * late / (late + sampled)
