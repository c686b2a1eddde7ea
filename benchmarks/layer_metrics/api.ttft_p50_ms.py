"""The median time to first token as the client saw it: the nearest-rank
50th percentile over the first tokens that arrived in the window. It is not
an end-to-end metric yet because no bound of at most 10% carries it: a
window holds one turn of each session, some 36 first tokens that leave the
engine on its ticks, so the median reads the lump that holds the middle
sample (runs agree within 0.2% inside a lump and jump 3-12% when one sample
changes lumps), and the mean over the same samples spreads by 5% from seed
to seed (PERF.md section 2). Recorded without a bound, in the cells that
give it twenty samples or more.

Layer: OpenAI surface (serving/api.py, the streamed response). Source: the
load generator's clock. Moves: out_tokens_per_s (the loop is closed: a
session's next tokens wait for its first).
"""

MIN_SAMPLES = 20


def read(ctx: dict):
    if ctx["counts"]["ttft_samples"] < MIN_SAMPLES:
        return None
    return ctx["client"].get("ttft_p50_ms")
