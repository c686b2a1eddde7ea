"""Output tokens per decode-carrying dispatch (fast-forwarded runs and
fused decode blocks raise it above the rows in a step, and pull a request's
time per token under the time of a step).

Layer: engine step and constrained decode (serving/engine.py,
constrained.py). Source: the window's delta of
``opsagent_decode_tokens_total`` over that of
``opsagent_decode_dispatches_total`` (all kinds). Moves: tpot_p50_ms.
"""
from benchmarks.client import delta


def read(ctx: dict):
    n = delta(ctx["before"], ctx["after"], "opsagent_decode_dispatches_total")
    if n <= 0:
        return None
    return delta(ctx["before"], ctx["after"], "opsagent_decode_tokens_total") / n
