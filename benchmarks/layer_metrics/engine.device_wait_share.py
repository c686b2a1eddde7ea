"""Share of the scheduler thread's busy time spent blocked on a device
array (the token pulls). High means the device is the limit and the host
keeps ahead of it; as steps get shorter it falls, and at 0 every pull finds
its result ready (``opsagent_step_late_pulls_total``): the device waits for
the host.

Layer: engine step (serving/async_runtime.py, engine.py; ``obs.phase``).
Source: the window's delta of ``opsagent_tick_phase_seconds_total``: the
phase ``wait`` over all phases but ``idle``. Moves: tpot_p50_ms.
"""
from benchmarks.client import delta

FAMILY = "opsagent_tick_phase_seconds_total"


def read(ctx: dict):
    busy = (delta(ctx["before"], ctx["after"], FAMILY)
            - delta(ctx["before"], ctx["after"], FAMILY, phase="idle"))
    if busy <= 0:
        return None
    return 100.0 * delta(ctx["before"], ctx["after"], FAMILY, phase="wait") / busy
