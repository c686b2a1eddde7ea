"""How close Olmo-Hybrid's state update comes to the least time its bytes
allow: the share of its roofline for whatever implements the scan.

Layer: kernels (ops/linear_attention.py). Source: the family's own byte
function (``families/olmo_hybrid.py`` ``lin_scan_floor_bytes``: each running
row's float32 state, 30 x 96 x 192, and conv tail read and written once in
each of the 24 linear layers) at the window's mean decode rows a pass, over
the device's published bytes per second, over the device time a pass spends
under ``lin_scan`` and ``state_io`` in the traced span: read as its Solar twin
``kernels.lin_scan_hbm_share`` reads it, whose reader this file calls (the
twin asks the cell's family for the floor, so the bytes are this model's).
Prefill rows are left out of the rows, so the floor is low rather than high:
a true floor, bound by bytes, that cannot pass 100%. Moves: tpot_p50_ms.
"""
from benchmarks.loading import load_module


def read(ctx: dict):
    return load_module("layer_metrics", "kernels.lin_scan_hbm_share").read(ctx)
