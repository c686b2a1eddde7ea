"""The tail of the time to first token as the client saw it: the
nearest-rank 95th percentile over the first tokens that arrived in the
window. With some forty of them in a window it is the 38th of 40 and
spreads by 8% from run to run, more than a bound of at most 10% can carry,
so it is recorded here without a bound, in the cells that give it twenty
samples or more.

Layer: OpenAI surface (serving/api.py, the streamed response). Source: the
load generator's clock. Moves: out_tokens_per_s.
"""

MIN_SAMPLES = 20


def read(ctx: dict):
    if ctx["counts"]["ttft_samples"] < MIN_SAMPLES:
        return None
    return ctx["client"].get("ttft_p95_ms")
