"""Device time of one pass of the model.

Layer: model step (models/llama.py). Source: the device trace: seconds in
which an operation ran on the device inside the traced span, over the
model passes dispatched in it (``trace_reduce.model_passes``: a mixed
step, a fast-forward step or a prefill chunk is one pass, a fused decode
block is ``decode_block`` passes). It moves when a pass gets faster, not
when the mix of ticks does. Moves: tpot_p50_ms.
"""
from benchmarks import trace_reduce


def read(ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    passes = trace_reduce.model_passes(
        trace["annotations"], ctx["config"]["engine"]["decode_block"])
    if not passes:
        return None
    return trace["busy_s"] / passes * 1e3
