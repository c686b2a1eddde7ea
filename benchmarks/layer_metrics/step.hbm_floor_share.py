"""How close a pass of the model comes to the least time its bytes allow.

Layer: model step (models/llama.py). Source: the benchmark's own byte
function (``bytes_model.step_floor_bytes``: every weight once, the resident
keys and values once) at the cell's shapes and the traced span's mean
resident tokens as the client counts them, over the device's published
bytes per second, over the device time of a pass (as
``step.device_ms_mean``: a fused decode block reads the weights once for
each of its passes, and is charged so). The bound is bytes: at these batch
sizes a pass's floor is memory traffic, not arithmetic. It is a share of a
floor for the whole pass, not a kernel's roofline share.
Moves: tpot_p50_ms.
"""
from benchmarks import bytes_model, trace_reduce


def read(ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    passes = trace_reduce.model_passes(
        trace["annotations"], ctx["config"]["engine"]["decode_block"])
    if not passes:
        return None
    pass_s = trace["busy_s"] / passes
    peak = bytes_model.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    floor = bytes_model.step_floor_bytes(
        ctx["config"], trace["resident_tokens"])
    return 100.0 * (floor / peak) / pass_s
