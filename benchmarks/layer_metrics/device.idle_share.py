"""Share of the traced span in which no operation ran on the device.

An idle device stretches every token's time in every cell, so the reading
is tied to the metric that every cell reports.

Layer: device. Source: the device trace: one minus the union of the
operation intervals over the span. Moves: tpot_p50_ms.
"""


def read(ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace["devices"] or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
