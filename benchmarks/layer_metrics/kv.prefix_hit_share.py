"""Share of prompt tokens served from the prefix trie, not prefilled.

Layer: prefix trie and pages (serving/kvcache.py). Source: the window's
delta of ``opsagent_prefix_hit_tokens_total`` over that plus
``opsagent_prefill_tokens_total``. Moves: out_tokens_per_s.
"""
from benchmarks.client import delta


def read(ctx: dict):
    hit = delta(ctx["before"], ctx["after"], "opsagent_prefix_hit_tokens_total")
    filled = delta(ctx["before"], ctx["after"], "opsagent_prefill_tokens_total")
    if hit + filled <= 0:
        return None
    return 100.0 * hit / (hit + filled)
