"""Host work of one scheduler tick spent in the part ``arrays`` of the phase
``plan``: building the step program's host arguments (the token, start, length, emit, table and sampling arrays, a row at a time; FSM seating and walks), less the allocator's share, which is summed into ``pages``.

Layer: engine step (serving/async_runtime.py ``_arrays``, and the same seam of ``step_mixed``, ``prefill_batch`` and ``step_block`` in serving/engine.py; ``obs.phase("plan", part="arrays")``, span
``engine.plan.arrays`` on the trace's clock). Source: the window's delta of
``opsagent_tick_part_seconds_total{phase="plan",part="arrays"}`` over that
of ``opsagent_ticks_total``: whole window, tracing on or off. A program
without the family (the parent commit) gives nothing to read.
Moves: tpot_p50_ms.
"""
from benchmarks import host_parts


def read(ctx: dict):
    return host_parts.part_ms(ctx, "plan", "arrays")
