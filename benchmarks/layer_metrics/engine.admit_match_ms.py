"""Host work of one scheduler tick spent in the part ``match`` of the phase
``admit``: the prefix trie's match of an admitted prompt (a hash a page, then the walk for the deepest node that holds a state snapshot).

Layer: engine step (serving/engine.py ``begin_request``, serving/kvcache.py ``match_prefix`` / ``match_prefix_state``; ``obs.phase("admit", part="match")``, span
``engine.admit.match`` on the trace's clock). Source: the window's delta of
``opsagent_tick_part_seconds_total{phase="admit",part="match"}`` over that
of ``opsagent_ticks_total``: whole window, tracing on or off. A program
without the family (the parent commit) gives nothing to read.
Moves: tpot_p50_ms.
"""
from benchmarks import host_parts


def read(ctx: dict):
    return host_parts.part_ms(ctx, "admit", "match")
