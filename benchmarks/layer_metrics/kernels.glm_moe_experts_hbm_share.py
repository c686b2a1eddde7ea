"""How close GLM-4.7-Flash's routed experts' matmuls come to the least time their bytes allow.

Layer: kernels (models/llama.py ``_moe_share``). Source: the benchmark's own
byte function (``families/glm4_moe_lite.py`` ``moe_experts_floor_bytes``:
the three int8 matrices and scales of each expert that had work, once) at
the window's mean of distinct experts touched in a layer's pass (the deltas
of ``opsagent_moe_share_total{what="experts_touched"}`` over
``{what="moe_layer_passes"}``, the program's own count on the device), times
the layers THAT HAVE EXPERTS (``families/glm4_moe_lite.py`` ``moe_layers``:
11 of the 12, the first is dense; the Solar twin multiplies by every layer,
so this reader does not call it), over the device's published bytes per
second, over the device time a pass spends under ``moe_experts`` in the
traced span. The bound is bytes; a true floor (an expert with work is read
at least once), so it cannot pass 100%. Moves: tpot_p50_ms.
"""
from benchmarks import bytes_model, scope_reduce
from benchmarks.client import delta
from benchmarks.loading import load_family

SHARE = "opsagent_moe_share_total"


def read(ctx: dict):
    family = load_family(ctx["config"])
    if not hasattr(family, "moe_layers"):
        return None
    try:
        ms = scope_reduce.scope_ms_per_pass(ctx, "moe_experts")
    except KeyError:
        return None
    passes = delta(ctx["before"], ctx["after"], SHARE, what="moe_layer_passes")
    if not ms or passes <= 0:
        return None
    touched = delta(ctx["before"], ctx["after"], SHARE, what="experts_touched") / passes
    peak = bytes_model.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    floor = family.moe_layers(ctx["config"]) * family.moe_experts_floor_bytes(
        ctx["config"], touched)
    return 100.0 * (floor / peak) / (ms * 1e-3)
