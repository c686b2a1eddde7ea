#!/usr/bin/env python3
"""What one ``obs.phase`` part costs the thread that opens it, here.

    python3 scripts/phase_cost.py            # on the chip: chiprun -- python3 scripts/phase_cost.py

Times, in a tight loop on this machine's host: a counter add, a
``TraceAnnotation`` alone, a part nested in its phase (two suspensions: the
phase's annotation closes and re-opens around the part's) and an
``obs.add_part``; first with no capture running, then inside a
``jax.profiler`` capture (what a ``--trace 1`` run pays for its 3 s span).
One JSON line, microseconds a call. The engine opens 25-35 parts a tick.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import timeit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from opsagent_tpu import obs  # noqa: E402

N = 20000


def us(fn) -> float:
    return round(timeit.timeit(fn, number=N) / N * 1e6, 3)


def annotation():
    with jax.profiler.TraceAnnotation("engine.cost", tick=1):
        pass


def part():
    with obs.phase("plan", part="cost"):
        pass


def measure() -> dict:
    with obs.phase("plan"):
        nested = us(part)
    return {
        "counter_add": us(lambda: obs.TICKS.inc(0.0)),
        "annotation": us(annotation),
        "nested_part": nested,
        "add_part": us(lambda: obs.add_part("plan", "cost", 0.0)),
    }


def main() -> int:
    out = {"platform": jax.devices()[0].platform, "off": measure()}
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            out["capturing"] = measure()
        finally:
            jax.profiler.stop_trace()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
