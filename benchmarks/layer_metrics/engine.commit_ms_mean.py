"""Host work of one scheduler tick spent in the phase ``commit``: folding a pulled step's tokens into host state: accept, finish rules, stream callbacks, the stop strings' scan.
With its three siblings and ``reap`` it adds up to ``engine.host_work_ms_mean``;
these four are the phase means that the host-work reader has only logged.

Layer: engine step (serving/scheduler.py, engine.py, async_runtime.py;
``obs.phase``, span ``engine.commit`` and the spans of its parts
``engine.commit.<part>``). Source: the window's delta of the EXISTING
``opsagent_tick_phase_seconds_total{phase="commit"}`` over that of
``opsagent_ticks_total``: whole window, tracing on or off; the parent commit
reads the same number. Moves: tpot_p50_ms.
"""
from benchmarks import host_parts


def read(ctx: dict):
    return host_parts.phase_ms(ctx, "commit")
