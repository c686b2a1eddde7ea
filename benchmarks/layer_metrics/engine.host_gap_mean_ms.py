"""Mean host time between two back-to-back mixed dispatches.

Layer: engine step (serving/engine.py, async_runtime.py). Source: the
program's histogram ``opsagent_step_host_gap_seconds``, ``_sum`` over
``_count`` of the window's delta. Moves: tpot_p50_ms.
"""
from benchmarks.client import delta

FAMILY = "opsagent_step_host_gap_seconds"


def read(ctx: dict):
    n = delta(ctx["before"], ctx["after"], FAMILY + "_count")
    if n <= 0:
        return None
    return delta(ctx["before"], ctx["after"], FAMILY + "_sum") / n * 1e3
