"""Scheduler ticks a token takes, over the requests that finished in the window:
the ticks between a request's first and last token over its tokens less one.
1 where every tick gives each row one token; under 1 where a dispatch carries
more than one token of a row (grammar fast-forward appends, fused decode
blocks of ``decode_block`` passes); over 1 where rows are starved or ticks
fall back. ``tpot`` is about this times the tick (``engine.host_gap_mean_ms``
where every tick is a mixed dispatch) plus what the hand-off adds
(``api.emit_lag_mean_ms``), so a median that moves against its tick can be
put down to one of the three.

Layer: scheduler (serving/scheduler.py ``_reap`` adds a finished request's
ticks and tokens; serving/engine.py ``_accept_token`` stamps each token with
``Engine.sched_tick``). Source: the window's delta of
``opsagent_request_decode_ticks_total`` over that of
``opsagent_request_decode_tokens_total``. A program without the counters
gives nothing to read. Moves: tpot_p50_ms.
"""
from benchmarks.client import delta


def read(ctx: dict):
    tokens = delta(
        ctx["before"], ctx["after"], "opsagent_request_decode_tokens_total")
    if tokens <= 0:
        return None
    return delta(ctx["before"], ctx["after"],
                 "opsagent_request_decode_ticks_total") / tokens
