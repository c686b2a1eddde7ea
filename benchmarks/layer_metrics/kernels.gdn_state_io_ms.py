"""Device time of one model pass spent in moving Olmo-Hybrid's recurrent state: the read of each row's slot (held as 4,320 rows of 128 a layer, re-tiled to ``[30, 96, 192]`` for the update and back) and the scatter back, snapshot copies inside a step, the ``state_copy`` program of a restore (``state_io``).

Layer: kernels (ops/linear_attention.py, models/llama.py ``_linear_mixer``
and what XLA makes of them). Source: the device trace, read as its Solar
twin ``kernels.state_io_ms`` reads it (own time of each operation under the scope, over
the model passes of the traced span), whose reader this file calls: the
scope is the same, the cell and the shapes are not (30 heads of 96 x 192,
24 layers deep), and the twin's list of cells cannot be edited by the PR
that added this one. A program without the scope gives nothing to read.
Moves: tpot_p50_ms.
"""
from benchmarks.loading import load_module


def read(ctx: dict):
    return load_module("layer_metrics", "kernels.state_io_ms").read(ctx)
