"""Share of the device's operation time spent moving data into another
layout: copy, transpose, bitcast and reshape operations under XLA's own
names, or events the trace puts in its data-formatting category.

Layer: kernels (ops/attention.py and what XLA makes of it). Source: the
device trace, over the sum of all operation time. Moves: tpot_p50_ms.
"""


def read(ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace["devices"] or trace["op_sum_s"] <= 0:
        return None
    return 100.0 * trace["relayout_s"] / trace["op_sum_s"]
