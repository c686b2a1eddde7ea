"""How close the attention over the latent pages comes to the least time its bytes allow.

Layer: kernels (whatever reads the paged latent: ``ops/attention.py``'s
gather today). Source: the benchmark's own byte function
(``families/glm4_moe_lite.py`` ``mla_attn_floor_bytes``: the latent of the
rows' LIVE context tokens, 1,152 B a token a layer at bfloat16, read once in
every layer) at the window's mean live context tokens a pass (the deltas of
``opsagent_attn_context_tokens_total{what="live"}``, counted at dispatch,
over the model passes dispatched, ``opsagent_decode_dispatches_total`` with a
fused block counting as its ``decode_block`` passes), over the device's published bytes per second, over
the device time a pass spends under ``attn_core`` and ``kv_gather`` in the
traced span: whatever implements the attention, so that a later kernel is
read by the same yardstick. The bound is bytes; a true floor (a live
token's latent is read at least once a layer), so it cannot pass 100%. A
program without the counter (the parent's) gives nothing to read.
Moves: tpot_p50_ms.
"""
from benchmarks import bytes_model, scope_reduce
from benchmarks.client import delta
from benchmarks.loading import load_family

CONTEXT = "opsagent_attn_context_tokens_total"
DISPATCHES = "opsagent_decode_dispatches_total"


def passes(ctx: dict) -> float:
    """Model passes dispatched in the window: one a dispatch, and
    ``decode_block`` for each fused block."""
    block = ctx["config"]["engine"]["decode_block"]
    return (delta(ctx["before"], ctx["after"], DISPATCHES)
            + (block - 1) * delta(
                ctx["before"], ctx["after"], DISPATCHES, kind="block"))


def read(ctx: dict):
    family = load_family(ctx["config"])
    if not hasattr(family, "mla_attn_floor_bytes"):
        return None
    try:
        ms = scope_reduce.scope_ms_per_pass(ctx, "attn_core", "kv_gather")
    except KeyError:
        return None
    n = passes(ctx)
    live = delta(ctx["before"], ctx["after"], CONTEXT, what="live")
    if not ms or n <= 0 or live <= 0:
        return None
    peak = bytes_model.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    floor = family.mla_attn_floor_bytes(ctx["config"], live / n)
    return 100.0 * (floor / peak) / (ms * 1e-3)
