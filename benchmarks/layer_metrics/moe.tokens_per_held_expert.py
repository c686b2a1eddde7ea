"""Assignments that land on one held expert in one layer's pass: how full
the expert matmuls run at this chip's share.

Layer: model step (models/llama.py ``_moe_share``). Source: the window's
deltas of ``opsagent_moe_share_total{what="landed"}`` (token-expert
assignments to experts held here, counted on the device) over
``{what="moe_layer_passes"}``, over the experts held
(``n_routed_experts`` of the configuration's file). Moves: tpot_p50_ms.
"""
from benchmarks.client import delta

SHARE = "opsagent_moe_share_total"


def read(ctx: dict):
    passes = delta(ctx["before"], ctx["after"], SHARE, what="moe_layer_passes")
    if passes <= 0:
        return None
    landed = delta(ctx["before"], ctx["after"], SHARE, what="landed")
    return landed / passes / ctx["config"]["n_routed_experts"]
