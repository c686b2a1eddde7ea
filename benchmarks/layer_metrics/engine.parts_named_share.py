"""How much of a tick's host work has a name: 1 - (the seconds the work phases
spent under no part, their ``other``) / (the work phases' seconds). Prints the
whole part table of the window to the run's log, in seconds and in
milliseconds a tick, as the host-work reader prints the phases.

Layer: engine step (every ``obs.phase(name, part=...)`` of
serving/scheduler.py, engine.py and async_runtime.py; spans
``engine.<phase>.<part>``). Source: the window's deltas of
``opsagent_tick_part_seconds_total{phase,part}`` against
``opsagent_tick_phase_seconds_total{phase}`` over ``admit``, ``plan``,
``dispatch``, ``commit`` and ``reap``. A program without the parts' family
gives nothing to read. Moves: tpot_p50_ms.
"""
import json

from benchmarks import host_parts


def read(ctx: dict):
    parts = host_parts.table(ctx)
    if parts is None:
        return None
    n = host_parts.ticks(ctx)
    per_tick = {
        phase: {part: round(s / n * 1e3, 4) for part, s in rows.items()}
        for phase, rows in parts.items()} if n > 0 else None
    print(f"[bench] tick parts over the window, seconds: {json.dumps(parts)}",
          flush=True)
    print(f"[bench] tick parts, ms a tick over {n:.0f} ticks: "
          f"{json.dumps(per_tick)}", flush=True)
    return host_parts.named_share(ctx)
