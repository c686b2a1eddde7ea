"""Share of prompt tokens served from a restored state snapshot (and the
pages behind it), not prefilled.

Layer: prefix trie and pages (serving/kvcache.py: the trie node that ends a
page chain keeps a snapshot of every linear-attention layer's state). Source:
the window's delta of ``opsagent_state_restored_tokens_total`` over that of
``opsagent_state_prompt_tokens_total``. A program whose model keeps no such
state counts neither, and gives nothing to read. Moves: tpot_p50_ms.
"""
from benchmarks.client import delta


def read(ctx: dict):
    prompt = delta(ctx["before"], ctx["after"],
                   "opsagent_state_prompt_tokens_total")
    if prompt <= 0:
        return None
    hit = delta(ctx["before"], ctx["after"],
                "opsagent_state_restored_tokens_total")
    return 100.0 * hit / prompt
