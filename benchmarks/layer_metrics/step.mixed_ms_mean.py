"""Device time of one mixed prefill+decode step over the whole window, with
no profiler: the program's step clock learns at each token pull when the
step finished, and samples ``ready_k - max(ready_k-1, enqueued_k)`` where
the pull had to wait for the step.

Layer: model step (models/llama.py through serving/async_runtime.py).
Source: the program's histogram ``opsagent_step_device_seconds`` with
``program="mixed"`` (every chunk bucket), ``_sum`` over ``_count`` of the
window's delta. Moves: tpot_p50_ms.
"""
from benchmarks.client import delta

FAMILY = "opsagent_step_device_seconds"


def read(ctx: dict):
    n = delta(ctx["before"], ctx["after"], FAMILY + "_count", program="mixed")
    late = delta(ctx["before"], ctx["after"], "opsagent_step_late_pulls_total")
    every = delta(ctx["before"], ctx["after"], FAMILY + "_count")
    print(f"[bench] step clock over the window: {every:.0f} samples "
          f"({n:.0f} mixed), {late:.0f} late pulls", flush=True)
    if n <= 0:
        return None
    total = delta(ctx["before"], ctx["after"], FAMILY + "_sum", program="mixed")
    return total / n * 1e3
