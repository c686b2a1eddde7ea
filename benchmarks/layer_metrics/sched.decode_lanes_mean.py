"""Mean number of decode rows a mixed dispatch carried.

Layer: scheduler (serving/scheduler.py). Source: the program's histogram
``opsagent_mixed_dispatch_decode_lanes``, ``_sum`` over ``_count`` of the
window's delta. Moves: out_tokens_per_s.
"""
from benchmarks.client import delta

FAMILY = "opsagent_mixed_dispatch_decode_lanes"


def read(ctx: dict):
    n = delta(ctx["before"], ctx["after"], FAMILY + "_count")
    if n <= 0:
        return None
    return delta(ctx["before"], ctx["after"], FAMILY + "_sum") / n
