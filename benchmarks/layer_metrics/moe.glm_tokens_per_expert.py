"""Assignments that land on one of GLM-4.7-Flash's 64 experts in one layer's pass: how full the expert matmuls run with every expert held.

Layer: model step (models/llama.py ``_moe_share``). Source: the window's
deltas of ``opsagent_moe_share_total{what="landed"}`` (token-expert
assignments, counted on the device) over ``{what="moe_layer_passes"}``,
over the experts held (``n_routed_experts`` of the configuration's file),
read as its Solar twin ``moe.tokens_per_held_expert`` reads it, whose reader
this file calls: the twin's list of cells cannot be edited by the PR that
added this one. A program whose cache keeps no such counters (the parent's,
for a model without recurrent state) gives nothing to read.
Moves: tpot_p50_ms.
"""
from benchmarks.loading import load_module


def read(ctx: dict):
    return load_module("layer_metrics", "moe.tokens_per_held_expert").read(ctx)
