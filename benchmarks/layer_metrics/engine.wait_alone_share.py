"""Share of the seconds the scheduler thread was blocked on the device in which
NOTHING was enqueued behind the step it pulled: the device then idles from the
end of that step until the next dispatch (after a drain of the async
pipeline, a fall-back to the block pipeline, the first dispatch after an idle
wait). The rest of ``wait`` is ``pipelined``: a younger step keeps the device
busy while the host folds the pulled one.

Layer: engine step (serving/engine.py ``Engine._pull``: the caller says
whether its pipeline holds a younger step; spans ``engine.wait.alone`` and
``engine.wait.pipelined``). Source: the window's delta of
``opsagent_tick_part_seconds_total{phase="wait"}``: ``alone`` over both parts.
A program without the family gives nothing to read. Moves: tpot_p50_ms.
"""
from benchmarks.client import delta

FAMILY = "opsagent_tick_part_seconds_total"


def read(ctx: dict):
    if FAMILY not in ctx["after"]:
        return None
    waited = delta(ctx["before"], ctx["after"], FAMILY, phase="wait")
    if waited <= 0:
        return None
    alone = delta(ctx["before"], ctx["after"], FAMILY, phase="wait",
                  part="alone")
    return 100.0 * alone / waited
