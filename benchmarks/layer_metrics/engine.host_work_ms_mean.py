"""Host work of one scheduler tick: the seconds the scheduler thread spent
admitting, planning, dispatching, committing and reaping, over the ticks
that had work. What is left of a tick is ``wait`` (blocked on a device
array) and, with nothing running, ``idle``. This is the host's share of a
tick that ``engine.host_gap_mean_ms`` was read for and does not give (that
one is dispatch to dispatch, the device's step included).

Layer: engine step (serving/scheduler.py, engine.py, async_runtime.py;
``obs.phase``). Source: the window's delta of
``opsagent_tick_phase_seconds_total`` over the phases ``admit``, ``plan``,
``dispatch``, ``commit`` and ``reap``, over that of ``opsagent_ticks_total``.
Moves: tpot_p50_ms.
"""
import json

from benchmarks.client import delta

FAMILY = "opsagent_tick_phase_seconds_total"
WORK = ("admit", "plan", "dispatch", "commit", "reap")


def read(ctx: dict):
    ticks = delta(ctx["before"], ctx["after"], "opsagent_ticks_total")
    if ticks <= 0:
        return None
    phases = {
        p: delta(ctx["before"], ctx["after"], FAMILY, phase=p)
        for p in (*WORK, "wait", "idle")
    }
    # The scheduler thread is always in exactly one phase: the run's log
    # shows how close their sum comes to the seconds between the scrapes.
    print(f"[bench] tick phases over the window: {json.dumps(phases)}; sum "
          f"{sum(phases.values()):.3f}s of {ctx['counts']['window_s']:.3f}s, "
          f"{ticks:.0f} ticks", flush=True)
    return sum(phases[p] for p in WORK) / ticks * 1e3
