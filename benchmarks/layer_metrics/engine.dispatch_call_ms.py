"""Host work of one scheduler tick spent in the part ``call`` of the phase
``dispatch``: the jitted call alone: flattening the parameter tree and the donated cache tree, the executable's launch; it returns when the step is enqueued, and the step annotations (``engine.mixed_step_async`` ...) lie inside it.

Layer: engine step (serving/async_runtime.py ``_enqueue``, and every dispatch of serving/engine.py; ``obs.phase("dispatch", part="call")``, span
``engine.dispatch.call`` on the trace's clock). Source: the window's delta of
``opsagent_tick_part_seconds_total{phase="dispatch",part="call"}`` over that
of ``opsagent_ticks_total``: whole window, tracing on or off. A program
without the family (the parent commit) gives nothing to read.
Moves: tpot_p50_ms.
"""
from benchmarks import host_parts


def read(ctx: dict):
    return host_parts.part_ms(ctx, "dispatch", "call")
