"""Device time of one model pass spent in GLM-4.7-Flash's router: float32 sigmoid scores over 64 experts, the top-4 of score plus selection bias, the renormalised weights, and the sort and scatter that plan the dispatch (``moe_router``).

Layer: kernels (models/llama.py ``_route``, ``_moe_share`` and what XLA
makes of them). Source: the device trace, read as its Solar twin
``kernels.moe_router_ms`` reads it (own time of each operation under the
scope, over the model passes of the traced span), whose reader this file
calls: the scope is the same, the cell and the shapes are not, and the
twin's list of cells cannot be edited by the PR that added this one. A
program without the scope gives nothing to read. Moves: tpot_p50_ms.
"""
from benchmarks.loading import load_module


def read(ctx: dict):
    return load_module("layer_metrics", "kernels.moe_router_ms").read(ctx)
