"""The host's side of a tick from the program's counters, over the whole
window (the two scrapes around it), with no profiler: what the readers
``engine.<phase>_ms_mean``, ``engine.<phase>_<part>_ms``,
``engine.account_ms``, ``engine.parts_named_share`` and
``engine.host_work_p95_ms`` under ``layer_metrics/`` share.

``opsagent_tick_phase_seconds_total{phase}`` holds the scheduler thread's
seconds by phase (it is always in exactly one), and
``opsagent_tick_part_seconds_total{phase,part}`` the seconds of a phase by
the named part of it (``obs.phase(name, part=...)``: the thread is then in
the span ``engine.<phase>.<part>`` on the trace's clock). What a phase
holds beyond its parts is its ``other``. A program from before the parts
has no such family: every function here then answers ``None``.
"""

from __future__ import annotations

from benchmarks.client import delta

PHASES = "opsagent_tick_phase_seconds_total"
PARTS = "opsagent_tick_part_seconds_total"
TICKS = "opsagent_ticks_total"
WORK = ("admit", "plan", "dispatch", "commit", "reap")


def ticks(ctx: dict) -> float:
    return delta(ctx["before"], ctx["after"], TICKS)


def phase_ms(ctx: dict, phase: str):
    """Milliseconds a tick of the phase's seconds."""
    n = ticks(ctx)
    if n <= 0 or PHASES not in ctx["after"]:
        return None
    return delta(ctx["before"], ctx["after"], PHASES, phase=phase) / n * 1e3


def part_ms(ctx: dict, phase: str, part: str):
    """Milliseconds a tick of one part's seconds; ``None`` without the
    family (the parent commit), 0.0 where the part never opened."""
    n = ticks(ctx)
    if n <= 0 or PARTS not in ctx["after"]:
        return None
    return (delta(ctx["before"], ctx["after"], PARTS, phase=phase, part=part)
            / n * 1e3)


def table(ctx: dict):
    """{phase: {part: seconds, ..., "other": seconds}} over the window,
    every phase the scrape names; ``None`` without the parts' family."""
    if PARTS not in ctx["after"]:
        return None
    out: dict[str, dict[str, float]] = {}
    for labels, _ in ctx["after"][PARTS]:
        out.setdefault(labels["phase"], {})[labels["part"]] = delta(
            ctx["before"], ctx["after"], PARTS, **labels)
    for labels, _ in ctx["after"].get(PHASES, []):
        phase = labels["phase"]
        parts = out.setdefault(phase, {})
        whole = delta(ctx["before"], ctx["after"], PHASES, phase=phase)
        parts["other"] = whole - sum(
            v for k, v in parts.items() if k != "other")
    return out


def account_ms(ctx: dict):
    """Milliseconds a tick in the ``account`` part of every phase: what
    only observes (counters, histograms, flight events, span children)."""
    n = ticks(ctx)
    if n <= 0 or PARTS not in ctx["after"]:
        return None
    return delta(ctx["before"], ctx["after"], PARTS, part="account") / n * 1e3


def named_share(ctx: dict):
    """Per cent of the work phases' seconds that lie under a named part."""
    parts = table(ctx)
    if parts is None:
        return None
    work = sum(delta(ctx["before"], ctx["after"], PHASES, phase=p)
               for p in WORK)
    if work <= 0:
        return None
    other = sum(parts.get(p, {}).get("other", 0.0) for p in WORK)
    return 100.0 * (1.0 - other / work)


def histogram_quantile(ctx: dict, family: str, q: float, **labels):
    """The ``q`` quantile of a histogram's observations in the window, from
    the delta of its cumulative buckets, linear inside the bucket it falls
    in (the first bucket starts at 0; the overflow answers the last
    bound). ``None`` without an observation."""
    name = family + "_bucket"
    bounds = sorted(
        {ls["le"] for ls, _ in ctx["after"].get(name, [])
         if all(ls.get(k) == v for k, v in labels.items())},
        key=float)      # "+Inf" parses as a float
    total = delta(ctx["before"], ctx["after"], family + "_count", **labels)
    if total <= 0 or not bounds:
        return None
    rank, lo, below = q * total, 0.0, 0.0
    for le in bounds:
        upto = delta(ctx["before"], ctx["after"], name, le=le, **labels)
        if upto >= rank:
            if le == "+Inf":
                return lo
            inside = upto - below
            share = (rank - below) / inside if inside > 0 else 1.0
            return lo + (float(le) - lo) * share
        lo, below = float(le), upto
    return lo
