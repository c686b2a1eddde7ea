"""How close Jamba's selective scan comes to the least time its bytes allow:
the share of its roofline for whatever implements the scan.

Layer: kernels (ops/selective_scan.py). Source: the family's own byte
function (``families/jamba.py`` ``ssm_scan_floor_bytes``: each running row's
float32 state, 16 x 5120, and conv tail read and written once in each of the
26 Mamba layers, 2 x rows x 358,400 B x 26) at the window's mean decode rows
a pass (the delta of ``opsagent_mixed_dispatch_decode_lanes``; prefill rows
are left out, so the floor is low rather than high), over the device's
published bytes per second, over the device time a pass spends under
``ssm_scan`` and ``state_io`` in the traced span. The bound is bytes; a true
floor, the same whatever implements the scan, so it cannot pass 100%. A
program without the scopes or a family without the byte function gives
nothing to read. Moves: tpot_p50_ms.
"""
from benchmarks import bytes_model, scope_reduce
from benchmarks.client import delta
from benchmarks.loading import load_family

LANES = "opsagent_mixed_dispatch_decode_lanes"


def read(ctx: dict):
    family = load_family(ctx["config"])
    if not hasattr(family, "ssm_scan_floor_bytes"):
        return None
    try:
        ms = scope_reduce.scope_ms_per_pass(ctx, "ssm_scan", "state_io")
    except KeyError:
        return None
    n = delta(ctx["before"], ctx["after"], LANES + "_count")
    if not ms or n <= 0:
        return None
    rows = delta(ctx["before"], ctx["after"], LANES + "_sum") / n
    peak = bytes_model.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    floor = family.ssm_scan_floor_bytes(ctx["config"], rows)
    return 100.0 * (floor / peak) / (ms * 1e-3)
