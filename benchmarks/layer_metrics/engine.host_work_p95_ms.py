"""The 95th percentile of ONE tick's host work: a mean of 25 ms hides the ticks
that leave the device idle, and the tail is what a cut of the host's work has
to shorten.

Layer: engine step (serving/scheduler.py ``_loop``: observed where
``opsagent_ticks_total`` is counted, from ``obs.take_host_work()``, the work
phases' seconds since the last tick). Source: the window's delta of the
buckets of ``opsagent_tick_host_work_seconds`` (bounds every 5 ms to 100 ms,
then 150, 250, 500, 1000), linear inside the bucket. A program without the
family gives nothing to read. Moves: tpot_p50_ms.
"""
from benchmarks import host_parts

FAMILY = "opsagent_tick_host_work_seconds"


def read(ctx: dict):
    p95 = host_parts.histogram_quantile(ctx, FAMILY, 0.95)
    if p95 is None:
        return None
    p50 = host_parts.histogram_quantile(ctx, FAMILY, 0.50)
    p99 = host_parts.histogram_quantile(ctx, FAMILY, 0.99)
    print(f"[bench] one tick's host work: p50 {p50 * 1e3:.2f} ms, p95 "
          f"{p95 * 1e3:.2f} ms, p99 {p99 * 1e3:.2f} ms", flush=True)
    return p95 * 1e3
