"""Share of Jamba's prompt tokens served from a restored state snapshot (9.3
MB a slot, and the pages behind it), not prefilled.

Layer: prefix trie and pages (serving/kvcache.py). Source: the window's
delta of ``opsagent_state_restored_tokens_total`` over that of
``opsagent_state_prompt_tokens_total``, read as its Solar twin
``state.snapshot_hit_share`` reads it, whose reader this file calls. A
program whose model keeps no such state counts neither, and gives nothing
to read. Moves: tpot_p50_ms.
"""
from benchmarks.loading import load_module


def read(ctx: dict):
    return load_module("layer_metrics", "state.snapshot_hit_share").read(ctx)
