"""Scheduler-thread time of ONE ``begin_request`` attempt: the trie match, the
page allocation, a state snapshot's restore dispatch, registering the
sequence. An attempt that ends in ``OutOfPages`` is made again on a later
tick with the whole match repeated, and shows as attempts, not as one long
wait; the attempts by outcome go to the run's log.

Layer: scheduler (serving/scheduler.py ``_try_admit``, which stamps each
attempt; serving/engine.py ``begin_request`` under it, in the spans
``engine.admit.<part>``). Source: the window's delta of the histogram
``opsagent_admission_seconds``, ``_sum`` over ``_count``, every outcome. A
program without the family gives nothing to read. Moves: tpot_p50_ms.
"""
import json

from benchmarks.client import delta

FAMILY = "opsagent_admission_seconds"


def read(ctx: dict):
    n = delta(ctx["before"], ctx["after"], FAMILY + "_count")
    if n <= 0:
        return None
    by_outcome = {}
    for labels, _ in ctx["after"].get(FAMILY + "_count", []):
        k = delta(ctx["before"], ctx["after"], FAMILY + "_count", **labels)
        s = delta(ctx["before"], ctx["after"], FAMILY + "_sum", **labels)
        by_outcome[labels["outcome"]] = [k, s / k * 1e3 if k else None]
    print("[bench] admission attempts over the window, [count, mean ms] by "
          f"outcome: {json.dumps(by_outcome)}", flush=True)
    return delta(ctx["before"], ctx["after"], FAMILY + "_sum") / n * 1e3
