"""What the observability that is always on costs a tick: the seconds of the
``account`` part of every phase (``admit``, ``plan``, ``commit``, ``reap``):
counters, histograms, the attribution ledger's sums, flight events, span
children, the occupancy gauges; nothing a served token depends on.

Layer: engine step (serving/async_runtime.py ``_account_dispatch`` and
``_commit``, serving/engine.py ``_accept_token`` / ``_first_token_obs`` /
``finish``, serving/scheduler.py; spans ``engine.<phase>.account``, and
``obs.add_part`` where the cost recurs a row or a token). Source: the
window's delta of ``opsagent_tick_part_seconds_total{part="account"}``, every
phase, over that of ``opsagent_ticks_total``. A program without the family
gives nothing to read. Moves: tpot_p50_ms.
"""
from benchmarks import host_parts


def read(ctx: dict):
    return host_parts.account_ms(ctx)
