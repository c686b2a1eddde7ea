"""Host work of one scheduler tick spent in the phase ``admit``: draining the request queue and every ``begin_request`` attempt (the trie match, the page allocation, registering the sequence).
With its three siblings and ``reap`` it adds up to ``engine.host_work_ms_mean``;
these four are the phase means that the host-work reader has only logged.

Layer: engine step (serving/scheduler.py, engine.py, async_runtime.py;
``obs.phase``, span ``engine.admit`` and the spans of its parts
``engine.admit.<part>``). Source: the window's delta of the EXISTING
``opsagent_tick_phase_seconds_total{phase="admit"}`` over that of
``opsagent_ticks_total``: whole window, tracing on or off; the parent commit
reads the same number. Moves: tpot_p50_ms.
"""
from benchmarks import host_parts


def read(ctx: dict):
    return host_parts.phase_ms(ctx, "admit")
