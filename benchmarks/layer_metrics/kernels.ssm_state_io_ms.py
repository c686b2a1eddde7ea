"""Device time of one model pass spent in moving Jamba's recurrent state: the read of each row's slot (``[16, 5120]`` float32 and a conv tail of 3 x 5120 a layer, 26 layers), the scatter back to the live and the snapshot slot, and the ``state_copy`` program of a restore (``state_io``).

Layer: kernels (models/llama.py ``_mamba_mixer``, ``copy_state_slots`` and
what XLA makes of them). Source: the device trace, read as
``kernels.state_io_ms`` reads it (own time of each operation under the scope,
over the model passes of the traced span), whose reader this file calls: the
scope is the same, the cell and the shapes are not, and that metric's list of
cells cannot be edited by the PR that added this one. A program without the
scope gives nothing to read. Moves: tpot_p50_ms.
"""
from benchmarks.loading import load_module


def read(ctx: dict):
    return load_module("layer_metrics", "kernels.state_io_ms").read(ctx)
