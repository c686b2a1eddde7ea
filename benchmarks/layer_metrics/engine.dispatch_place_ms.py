"""Host work of one scheduler tick spent in the part ``place`` of the phase
``dispatch``: turning the host arrays into device arrays (the ``jnp.asarray`` placements, the key split, the FSM tables).

Layer: engine step (serving/async_runtime.py ``_enqueue``, and every dispatch of serving/engine.py; ``obs.phase("dispatch", part="place")``, span
``engine.dispatch.place`` on the trace's clock). Source: the window's delta of
``opsagent_tick_part_seconds_total{phase="dispatch",part="place"}`` over that
of ``opsagent_ticks_total``: whole window, tracing on or off. A program
without the family (the parent commit) gives nothing to read.
Moves: tpot_p50_ms.
"""
from benchmarks import host_parts


def read(ctx: dict):
    return host_parts.part_ms(ctx, "dispatch", "place")
