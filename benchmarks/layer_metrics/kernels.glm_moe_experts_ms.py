"""Device time of one model pass spent in GLM-4.7-Flash's routed experts, all 64 of a layer held: the loop over the blocks of sorted assignments, three int8 matmuls a block, and the weighted gather back (``moe_experts``).

Layer: kernels (models/llama.py ``_moe_share`` and what XLA makes of it).
Source: the device trace, read as its Solar twin ``kernels.moe_experts_ms``
reads it (own time of each operation under the scope, over the model passes
of the traced span), whose reader this file calls: the scope is the same,
the cell and the shapes are not (64 experts of 2048 x 1536, top-4, behind
one dense layer), and the twin's list of cells cannot be edited by the PR
that added this one. A program without the scope gives nothing to read.
Moves: tpot_p50_ms.
"""
from benchmarks.loading import load_module


def read(ctx: dict):
    return load_module("layer_metrics", "kernels.moe_experts_ms").read(ctx)
