"""Host work of one scheduler tick spent in the phase ``dispatch``: placing the host arrays on the device and calling the jitted step program (the call returns when the step is enqueued).
With its three siblings and ``reap`` it adds up to ``engine.host_work_ms_mean``;
these four are the phase means that the host-work reader has only logged.

Layer: engine step (serving/scheduler.py, engine.py, async_runtime.py;
``obs.phase``, span ``engine.dispatch`` and the spans of its parts
``engine.dispatch.<part>``). Source: the window's delta of the EXISTING
``opsagent_tick_phase_seconds_total{phase="dispatch"}`` over that of
``opsagent_ticks_total``: whole window, tracing on or off; the parent commit
reads the same number. Moves: tpot_p50_ms.
"""
from benchmarks import host_parts


def read(ctx: dict):
    return host_parts.phase_ms(ctx, "dispatch")
