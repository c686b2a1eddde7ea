"""Mean time a request waited in the scheduler's queue before admission.

Layer: scheduler (serving/scheduler.py). Source: the program's histogram
``opsagent_queue_wait_seconds``, ``_sum`` over ``_count`` of the window's
delta (the buckets are too coarse for a quantile). Moves: out_tokens_per_s.
"""
from benchmarks.client import delta

FAMILY = "opsagent_queue_wait_seconds"


def read(ctx: dict):
    n = delta(ctx["before"], ctx["after"], FAMILY + "_count")
    if n <= 0:
        return None
    return delta(ctx["before"], ctx["after"], FAMILY + "_sum") / n * 1e3
