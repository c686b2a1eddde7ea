"""Device time of one model pass spent in the gated delta-rule update of Olmo-Hybrid's 24 linear-attention layers: decay (one number a head), the chunk form's triangular system or the one-token recurrence, read-out (``lin_scan``).

Layer: kernels (ops/linear_attention.py, models/llama.py ``_linear_mixer``
and what XLA makes of them). Source: the device trace, read as its Solar
twin ``kernels.lin_scan_ms`` reads it (own time of each operation under the scope, over
the model passes of the traced span), whose reader this file calls: the
scope is the same, the cell and the shapes are not (30 heads of 96 x 192,
24 layers deep), and the twin's list of cells cannot be edited by the PR
that added this one. A program without the scope gives nothing to read.
Moves: tpot_p50_ms.
"""
from benchmarks.loading import load_module


def read(ctx: dict):
    return load_module("layer_metrics", "kernels.lin_scan_ms").read(ctx)
