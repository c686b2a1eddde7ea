"""Device time of one mixed step, re-weighted by what every dispatch ran: the sum
over widths of (that width's share of ALL the window's mixed dispatches) x
(the mean of the step clock's samples at that width). The step clock keeps
only the steps whose pull waited for the device; where the host sets the
pace those are the light ticks, and ``step.mixed_ms_mean`` reads under the
truth. The width (the rows the dense segments ran: 128 or 256 in today's
cells) is what mostly prices a step, every dispatch is counted by it, and
each width's sample coverage goes to the run's log. A width that ran in
under 1 % of the dispatches and left no sample is left out (the weights of
the others are scaled up); any other width without a sample gives no number,
and the log says which.

Layer: model step (models/llama.py through serving/async_runtime.py;
obs/tick.py ``StepClock``: the ticket carries the width from
``Engine._count_step_tokens`` to the pull). Source: the window's deltas of
``opsagent_mixed_dispatch_width_total{width}`` and of the histogram
``opsagent_step_device_seconds{width}``, ``_sum`` over ``_count`` a width. A
program whose step clock has no ``width`` label (the parent commit) gives
nothing to read. Moves: tpot_p50_ms.
"""
import json

from benchmarks.client import delta

DISPATCHES = "opsagent_mixed_dispatch_width_total"
CLOCK = "opsagent_step_device_seconds"
NEGLIGIBLE = 0.01


def read(ctx: dict):
    before, after = ctx["before"], ctx["after"]
    if not any("width" in ls for ls, _ in after.get(CLOCK + "_count", [])):
        return None
    ran = {ls["width"]: delta(before, after, DISPATCHES, **ls)
           for ls, _ in after.get(DISPATCHES, [])}
    every = sum(ran.values())
    if every <= 0:
        return None
    rows, mean_ms, weight = {}, 0.0, 0.0
    missing = []
    for width, n in sorted(ran.items()):
        if n <= 0:
            continue
        k = delta(before, after, CLOCK + "_count", width=width)
        ms = (delta(before, after, CLOCK + "_sum", width=width) / k * 1e3
              if k > 0 else None)
        rows[width] = {"dispatches": n, "samples": k, "mean_ms": ms}
        if ms is None:
            if n / every >= NEGLIGIBLE:
                missing.append(width)
            continue
        mean_ms += n / every * ms
        weight += n / every
    print("[bench] mixed steps by width over the window: "
          f"{json.dumps(rows)}", flush=True)
    if missing or weight <= 0:
        print(f"[bench] no step-clock sample at width {missing}: "
              "step.mixed_ms_by_width gives no number", flush=True)
        return None
    return mean_ms / weight
