#!/usr/bin/env python
"""Headline benchmark: paged-decode throughput (tokens/sec/chip).

Runs the serving engine's continuous-batching decode loop at steady state and
reports aggregate decode tokens/sec divided by chip count — the north-star
serving metric from BASELINE.json (target: 2000 tok/s/chip, Llama-3-8B class,
v5e). Prints ONE JSON line on stdout:

    {"metric": "...", "value": N, "unit": "tok/s/chip", "vs_baseline": N}

A plain `python bench.py` orchestrates up to fifteen stages in isolated
subprocesses under one wall-clock budget (OPSAGENT_BENCH_BUDGET, default
850 s). It measures a chip: the orchestrating parent never imports JAX
(each stage child is the one process that holds the chip while it
runs), the first stage refuses to run off a TPU, and with no chip the
run exits non-zero without printing a row. Stages: the default preset
first (bench-1b), then the bench-8b int8 headline, its int4,
int8-KV-pages, and combined int4+int8-KV variants (the fastest 8B
variant becomes the headline), the BASELINE config-5 concurrent-sessions
run, the sessions-mixed A/B (mixed prefill+decode batching on vs. off on
the same workload), the sessions-async A/B (one-step-lookahead async
mixed ticks, async_depth 2 vs. 1, reporting tok/s and host-gap p50 for
both phases plus an identical-output check), the sessions-offload A/B
(hierarchical KV: host-RAM offload tier off vs. on under page pressure),
the fleet-affinity A/B (two engine replicas behind the fleet router:
prefix-affinity + sticky placement vs stateless least-loaded, reporting
re-prefill-avoided tokens and p50 TTFT per phase),
the agent-turns stage (north-star p50 TTFT per tool-call turn), and a
cold-restart TTFT probe against the stage-1-primed compilation cache.
EVERY result line is printed
and flushed the moment it exists (the driver kills this process at an
unknown wall clock; an already-earned number must survive), and a
combined headline line is printed last. Every row names the device it
ran on (``extra.platform`` / ``device_kind`` / ``device_count``).

Model/batch are overridable via env (OPSAGENT_BENCH_MODEL,
OPSAGENT_BENCH_BATCH, OPSAGENT_BENCH_STEPS), which runs that single
configuration inline. A single configuration with the model NAMED may
run on the CPU as a check of the harness (the tier-1 tests do, at
tiny-test): its rows say ``platform: cpu`` and carry the unit
``tok/s (cpu)`` where a chip run says ``tok/s/chip`` — a CPU number is
never written under a device unit. OPSAGENT_BENCH_MODE=sessions switches to the
BASELINE config-5 scenario: ``batch`` concurrent client sessions
submitting chat completions through the full stack (OpenAI translation
-> scheduler admission -> chunked prefill -> pipelined decode),
reporting aggregate tok/s/chip and the p50 TTFT clients observed.
OPSAGENT_BENCH_MODE=sessions-mixed runs that same workload TWICE against
one engine — mixed prefill+decode batching on, then off — and reports
both (the one-weight-stream-per-tick delta); OPSAGENT_BENCH_MIXED=0
pins the split tick for any other mode.
OPSAGENT_BENCH_MODE=sessions-async runs the workload twice with the
one-step-lookahead async mixed pipeline on (async_depth=2), then with
synchronous ticks (depth=1), same prompt seeds — reporting tok/s,
host-gap p50, and overlapped-commit counts for both phases plus a
byte-identical-output verdict; OPSAGENT_BENCH_ASYNC=<depth> pins the
depth for any other mode.
OPSAGENT_BENCH_MODE=sessions-ffwd runs the sessions workload with every
completion constrained to a JSON schema, twice — grammar fast-forward
on (forced-token runs splice into the KV without forward passes), then
off — same prompt seeds, reporting tok/s, the forced-token fraction,
and skipped dispatches per phase plus a byte-identical-output verdict.
OPSAGENT_BENCH_MODE=fleet-affinity runs the sessions workload over
OPSAGENT_BENCH_REPLICAS (default 2) in-process engine replicas behind
the fleet router, twice — prefix-affinity + sticky placement on, then
stateless least-loaded — reporting p50 TTFT and re-prefill-avoided
tokens for both phases in one JSON line.
OPSAGENT_BENCH_MODE=fleet-chaos runs that fleet workload twice more —
seeded faults off, then on (serving/faults: mid-SSE disconnects at
fixed hit counts) — reporting failed requests (must stay 0: router
failover absorbs the deaths), failovers, shed count, and the p99 TTFT
delta containment costs, in one JSON line.
OPSAGENT_BENCH_MODE=fleet-journey runs the streamed fleet workload with
request journeys on vs off (the obs-overhead A/B) plus one stitched-
timeline smoke: a request forced through mid-SSE failover + peer
fault-in must yield ONE router timeline with lanes from both replicas,
failover/fault_in windows, >= 95% coverage, monotonic segments.
``--perf-gate`` (or OPSAGENT_BENCH_PERF_GATE=1) compares the
orchestrated run's result lines against the committed
BENCH_r*_local.jsonl baseline after the headline is printed and exits 4
on regression — the --slo-strict twin for perf (see
scripts/perf_gate.py / `opsagent perf-check` for the standalone gate).
OPSAGENT_BENCH_MODE=agent runs the north-star agent shape instead:
multi-turn ReAct sessions (observation-as-user-message, full-history
resend) with the prefix cache on, reporting p50 client TTFT per
tool-call turn and the prefix-hit rate.
OPSAGENT_BENCH_MODE=agent-conveyor trains the tiny BPE agent
in-process (seconds on CPU), serves the checkpoint, and runs the
scripted tool episode with conveyor mid-decode tool launches on vs off
— p50 episode wall, overlap seconds banked behind decode, early-launch
count, the byte-identical-transcript verdict, and the
zero-post-warmup-compiles invariant for both phases.
OPSAGENT_BENCH_MODE=cold-start runs the snapshot/restore A/B
(serving/snapshot): fresh-init request-ready vs Engine.from_snapshot
request-ready against empty compile caches, with byte-identical greedy
outputs and the zero-post-warmup-compiles invariant checked on the
restored engine.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from opsagent_tpu.utils.perf import get_perf_stats

# jax is imported inside the functions a stage child runs, never at module
# level: the orchestrating parent must not initialise a backend — a
# process that has touched JAX holds the chip, and its stage children
# would then fail or hang.

BASELINE_TOK_S_PER_CHIP = 2000.0  # BASELINE.json north_star decode target

# The north-star target is defined for an 8B-class model on real TPU
# hardware (BASELINE.md). A ratio against it is only meaningful for that
# class on that platform; everything else reports vs_baseline: null.
BASELINE_CLASS_MODELS = ("bench-8b", "llama-3-8b-instruct")


def vs_baseline(tok_s_chip: float, model: str, platform: str) -> float | None:
    """Ratio vs the BASELINE.md north star, or None when the ratio would
    be meaningless (platform is not tpu, or the model is not 8B-class)."""
    if platform != "tpu" or model not in BASELINE_CLASS_MODELS:
        return None
    return round(tok_s_chip / BASELINE_TOK_S_PER_CHIP, 3)


CHIP_RATE_UNIT = "tok/s/chip"
CPU_RATE_UNIT = "tok/s (cpu)"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def emit(row: dict) -> None:
    """Print one result row, naming the device it ran on. Off the chip
    the per-chip rate unit becomes ``tok/s (cpu)``: such a row is a check
    of the harness (counts, identical outputs), never a device number."""
    import jax

    dev = jax.devices()[0]
    extra = row.setdefault("extra", {})
    extra["platform"] = dev.platform
    extra["device_kind"] = dev.device_kind
    extra["device_count"] = len(jax.devices())
    if dev.platform != "tpu" and row.get("unit") == CHIP_RATE_UNIT:
        row["unit"] = CPU_RATE_UNIT
    print(json.dumps(row), flush=True)


def log_perf_table() -> None:
    """Per-phase engine series (prefill chunks, decode dispatches, ttft
    — count/avg/p95/p99/max) to stderr: on chip this lands in
    session.log and localizes first-call overhead (e.g. the r04 ~2 s
    first-request TTFT) without a second instrumented run."""
    log(get_perf_stats().format_table())


def metrics_snapshot() -> dict:
    """Compact dump of the obs registry (the same samples a GET /metrics
    scrape would expose: TTFT/ITL histogram count+sum, decode-token and
    dispatch counters, KV-page gauges), folded into every bench JSON line
    so BENCH_*.json records engine telemetry alongside the latency
    numbers."""
    try:
        from opsagent_tpu.obs import metrics_snapshot as snap

        return snap()
    except Exception:  # noqa: BLE001 - telemetry must never sink a bench
        return {}


def _tick_phases(snap0: dict, snap1: dict) -> tuple[float, float]:
    """(host work per tick in ms, share of the non-idle time spent waiting
    for the device) between two ``metrics_snapshot()``s, from the tick
    phase counter (opsagent_tick_phase_seconds_total, obs.phase)."""
    def delta(key: str) -> float:
        return float(snap1.get(key, 0.0)) - float(snap0.get(key, 0.0))

    def phase(name: str) -> float:
        return delta(f'opsagent_tick_phase_seconds_total{{phase="{name}"}}')

    work = sum(
        phase(p) for p in ("admit", "plan", "dispatch", "commit", "reap")
    )
    wait = phase("wait")
    ticks = delta("opsagent_ticks_total")
    return (
        work / ticks * 1e3 if ticks else 0.0,
        wait / (work + wait) if work + wait else 0.0,
    )


def attribution_snapshot() -> dict:
    """The goodput ledger's roofline snapshot (obs/attribution.py):
    modeled bytes by kind, MFU / HBM-utilization over the rate window,
    and the measured-vs-modeled drift EMA — folded into every result
    line so a BENCH artifact carries its own attribution."""
    try:
        from opsagent_tpu.obs import attribution

        return attribution.snapshot()
    except Exception:  # noqa: BLE001 - telemetry must never sink a bench
        return {}


def slo_verdicts() -> dict:
    """The declared-SLO verdicts (obs.slo) over this run's histograms —
    the same evaluation ``GET /api/slo`` serves and ``opsagent
    slo-check --bench`` reads back out of the BENCH JSON."""
    try:
        from opsagent_tpu.obs import slo

        return slo.evaluate()
    except Exception:  # noqa: BLE001 - telemetry must never sink a bench
        return {}


def slo_strict() -> bool:
    return (
        "--slo-strict" in sys.argv[1:]
        or os.environ.get("OPSAGENT_BENCH_SLO_STRICT", "") not in ("", "0")
    )


def perf_gate_enabled() -> bool:
    """``--perf-gate`` (or OPSAGENT_BENCH_PERF_GATE=1): after the
    headline line is printed, compare this run's result lines against
    the committed BENCH_r*_local.jsonl baseline (the slo-strict twin for
    perf regressions; orchestrator-level, since the comparison spans
    stages)."""
    return (
        "--perf-gate" in sys.argv[1:]
        or os.environ.get("OPSAGENT_BENCH_PERF_GATE", "") not in ("", "0")
    )


def exit_if_perf_regression(rows: list) -> None:
    """Under ``--perf-gate``, a regression vs the committed baseline
    fails the orchestrator with exit 4 (distinct from --slo-strict's 3).
    Called AFTER every result line is printed, so no number is ever lost
    to the verdict; exits only on a CONFIRMED regression — disjoint
    metric sets (e.g. a cpu fallback run vs a tpu baseline) pass with a
    note, because absence of evidence is the budget's business."""
    if not perf_gate_enabled():
        return
    try:
        from opsagent_tpu.cli.perfcheck import (
            compare, default_baseline, format_report, load_rows,
        )
    except Exception as e:  # noqa: BLE001
        log(f"bench: --perf-gate unavailable: {e}")
        return
    baseline = default_baseline()
    if not baseline:
        log("bench: --perf-gate: no committed baseline jsonl; skipping")
        return
    report = compare(
        [r for r in rows if r is not None], load_rows(baseline)
    )
    log(f"bench: --perf-gate vs {os.path.basename(baseline)}:")
    log(format_report(report))
    if report["pass"] is False:
        sys.exit(4)


def exit_if_slo_breach(slo: dict) -> None:
    """Under ``--slo-strict`` (or OPSAGENT_BENCH_SLO_STRICT=1), a
    breached declared SLO fails the bench process — the CI-gate form of
    the watchdog. Called AFTER the result line is printed, so the number
    is never lost to the verdict."""
    if not slo_strict():
        return
    failed = [
        v["name"] for v in (slo or {}).get("slos", [])
        if v.get("pass") is False
    ]
    if failed:
        log(f"bench: --slo-strict: SLO breach: {', '.join(failed)}")
        sys.exit(3)


def main() -> None:
    # Plain `python bench.py` orchestrates the presets in subprocesses
    # (guaranteed-fast number first, headline after, sessions last, all
    # under one wall-clock budget). Explicit OPSAGENT_BENCH_MODEL/MODE
    # requests — and orchestrator children — run a single config inline.
    if slo_strict():
        # Children are spawned without argv: carry the flag in the env so
        # every stage applies the same gate.
        os.environ["OPSAGENT_BENCH_SLO_STRICT"] = "1"
    if (
        os.environ.get("_OPSAGENT_BENCH_CHILD")
        or os.environ.get("OPSAGENT_BENCH_MODEL")
        or os.environ.get("OPSAGENT_BENCH_MODE")
    ):
        run_single()
    else:
        run_orchestrated()


def _run_child_rows(
    env_extra: dict, timeout_s: float, tag: str
) -> list[dict]:
    """Run one bench preset in a subprocess; return EVERY parsed JSON
    row it printed, in print order. Multi-row stages (the ragged sweep
    prints one row per cell) need all of them; single-row stages take
    the last via ``_run_child``.

    Subprocess isolation means a hang or OOM in one preset cannot take
    down the other's already-collected result, and each child in turn is
    the one process holding the chip. The child's
    stderr is INHERITED (progress streams to the driver's tail in real
    time); stdout is captured on a reader thread so a timeout kill still
    yields any JSON the child managed to print."""
    import subprocess
    import threading

    if timeout_s < 60:
        log(f"bench[{tag}]: skipped ({timeout_s:.0f}s left is too little)")
        return []
    env = dict(os.environ, _OPSAGENT_BENCH_CHILD="1")
    for k, v in env_extra.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    proc = subprocess.Popen(
        [sys.executable, "-u", os.path.abspath(__file__)],
        stdout=subprocess.PIPE, stderr=None, text=True, env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    lines: list[str] = []

    def _read() -> None:
        for line in proc.stdout:
            lines.append(line)

    reader = threading.Thread(target=_read, daemon=True)
    reader.start()
    try:
        proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        log(f"bench[{tag}]: TIMED OUT after {timeout_s:.0f}s, killing")
        proc.kill()
        proc.wait()
    reader.join(timeout=10)
    rows: list[dict] = []
    for line in lines:
        try:
            parsed = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(parsed, dict) and "metric" in parsed:
            rows.append(parsed)
    if not rows:
        log(f"bench[{tag}]: no JSON result (rc={proc.returncode})")
    return rows


def _run_child(env_extra: dict, timeout_s: float, tag: str) -> dict | None:
    """Single-row form of ``_run_child_rows``: the LAST parsed row is
    the stage's result (children print their summary line last — the
    same contract the driver applies to the orchestrator itself)."""
    rows = _run_child_rows(env_extra, timeout_s, tag)
    return rows[-1] if rows else None


def run_orchestrated() -> None:
    """Budgeted multi-preset run. The contract with the driver (which
    kills the whole process group at an unknown wall clock) is: flush
    every result line the moment it exists, so a later kill can never
    erase an already-earned number, and print the headline line LAST so
    the driver's last-JSON-line parse picks it up.

    Order: default preset (bench-1b; refuses to run off a TPU, and with
    it the whole run), then the bench-8b int8 headline and its int4,
    int8-KV, and combined int4+int8-KV variants, the BASELINE config-5
    concurrent-sessions run, the sessions-mixed A/B, the agent-turns
    stage, the ragged sweep and the cold-restart TTFT probe last; the
    later stages only start if the remaining budget plausibly covers them.
    Mode env vars are stripped from stages they don't belong to, so an
    operator-set OPSAGENT_BENCH_MODE cannot contaminate the baseline
    stages."""
    budget = float(os.environ.get("OPSAGENT_BENCH_BUDGET", "850"))
    t_start = time.perf_counter()

    def remaining() -> float:
        return budget - (time.perf_counter() - t_start)

    # None-valued entries REMOVE inherited vars (see _run_child): an
    # operator-exported mode var must not contaminate the stages it
    # doesn't belong to.
    base = {
        "OPSAGENT_BENCH_MODE": None,
        "OPSAGENT_BENCH_QUANT": None,
        "OPSAGENT_BENCH_KV": None,
        "OPSAGENT_BENCH_MIXED": None,
        "OPSAGENT_BENCH_ASYNC": None,
        # An operator-exported fault spec must never contaminate the
        # perf stages; the fleet-chaos stage pins its own spec in-process.
        "OPSAGENT_FAULTS": None,
    }

    def stage(env_extra: dict, min_remaining: float, tag: str,
              cap: float | None = None) -> dict | None:
        """One budget-gated preset: run, flush its line immediately."""
        if remaining() <= min_remaining:
            log(f"bench: skipping {tag} ({remaining():.0f}s left)")
            return None
        timeout_s = remaining() - 10
        if cap is not None:
            timeout_s = min(cap, timeout_s)
        r = _run_child({**base, **env_extra}, timeout_s, tag)
        if r is not None:
            print(json.dumps(r), flush=True)
        return r

    def stage_rows(env_extra: dict, min_remaining: float, tag: str,
                   cap: float | None = None) -> list[dict]:
        """Multi-row stage: flush EVERY row the child earned, in order,
        the moment the child exits (the sweep's per-cell rows are each a
        first-class perf-gate series — losing all-but-last would reduce
        the sweep to a single backend's number)."""
        if remaining() <= min_remaining:
            log(f"bench: skipping {tag} ({remaining():.0f}s left)")
            return []
        timeout_s = remaining() - 10
        if cap is not None:
            timeout_s = min(cap, timeout_s)
        rows = _run_child_rows({**base, **env_extra}, timeout_s, tag)
        for r in rows:
            print(json.dumps(r), flush=True)
        return rows

    stage1_cap = float(os.environ.get("OPSAGENT_BENCH_STAGE1_CAP", "390"))
    # The first stage decides whether there is a chip to measure: its
    # child refuses to run off a TPU (run_single), and a hang at backend
    # start-up is killed at the stage cap. Either way there is no row and
    # no fallback — the run fails.
    r1 = stage({}, 0, "default", cap=stage1_cap)
    if r1 is None:
        log("bench: the device stage produced no row (no TPU, or it "
            "failed or hung): nothing measured, no row printed")
        sys.exit(1)
    headline = r1
    r8b = stage({"OPSAGENT_BENCH_MODEL": "bench-8b"}, 420, "8b")
    if r8b is not None:
        headline = r8b
    # int4 variant of the headline: weight streaming halves again vs
    # int8, so if decode is weight-bound this stage should show it (and
    # if not, the delta localizes the bottleneck to KV/attention/host).
    r8b4 = stage(
        {"OPSAGENT_BENCH_MODEL": "bench-8b",
         "OPSAGENT_BENCH_QUANT": "int4"},
        330, "8b-int4",
    ) if r8b is not None else None
    if r8b4 is not None and r8b4["value"] > headline["value"]:
        headline = r8b4
    # int8 KV pages on the int8-weight headline: halves the KV-read term
    # the roofline blames for most of the non-weight step time. Promoted
    # to headline if faster, same promote-if-faster flow as int4.
    r8bkv = stage(
        {"OPSAGENT_BENCH_MODEL": "bench-8b",
         "OPSAGENT_BENCH_KV": "int8"},
        330, "8b-kv-int8",
    ) if r8b is not None else None
    if r8bkv is not None and r8bkv["value"] > headline["value"]:
        headline = r8bkv
    # Both levers compose (weight stream and KV reads are additive HBM
    # terms): measure int4 weights + int8 KV together when each stage
    # produced a number, and promote if fastest.
    r8b4kv = stage(
        {"OPSAGENT_BENCH_MODEL": "bench-8b",
         "OPSAGENT_BENCH_QUANT": "int4",
         "OPSAGENT_BENCH_KV": "int8"},
        330, "8b-int4-kv-int8",
    ) if r8b4 is not None and r8bkv is not None else None
    if r8b4kv is not None and r8b4kv["value"] > headline["value"]:
        headline = r8b4kv
    rsess = stage(
        {"OPSAGENT_BENCH_MODE": "sessions",
         "OPSAGENT_BENCH_MODEL": "bench-1b"},
        240, "sessions",
    )
    # Mixed-batching A/B on the sessions workload: the same config-5
    # scenario run with the unified mixed prefill+decode tick and with
    # the split tick in ONE child, so the one-weight-stream-per-tick
    # delta (tok/s and p50 TTFT) lands as a first-class BENCH artifact.
    rsessmix = stage(
        {"OPSAGENT_BENCH_MODE": "sessions-mixed",
         "OPSAGENT_BENCH_MODEL": "bench-1b"},
        240, "sessions-mixed",
    )
    # Async-tick A/B on the same workload: one-step-lookahead mixed
    # pipeline (depth=2) vs synchronous ticks (depth=1) in one child —
    # tok/s + host-gap p50 for both phases, plus the identical-output
    # verdict that proves the lookahead changes WHEN host work happens,
    # never WHAT gets generated.
    rsessasync = stage(
        {"OPSAGENT_BENCH_MODE": "sessions-async",
         "OPSAGENT_BENCH_MODEL": "bench-1b"},
        240, "sessions-async",
    )
    # Grammar fast-forward A/B: every completion schema-constrained,
    # forced-token runs spliced without forward passes (on) vs paying a
    # dispatch per token (off) — tok/s, forced-token fraction, skipped
    # dispatches, and the byte-identical-output verdict.
    rsessffwd = stage(
        {"OPSAGENT_BENCH_MODE": "sessions-ffwd",
         "OPSAGENT_BENCH_MODEL": "bench-1b"},
        240, "sessions-ffwd",
    )
    # Hierarchical-KV A/B on the same workload under page pressure:
    # offload tier off vs on (host-pool spill/park/restore) in one child.
    rsessoff = stage(
        {"OPSAGENT_BENCH_MODE": "sessions-offload",
         "OPSAGENT_BENCH_MODEL": "bench-1b"},
        240, "sessions-offload",
    )
    # Fleet-affinity A/B: the sessions workload over TWO in-process
    # engine replicas behind the FleetRouter — prefix-affinity + sticky
    # placement (comebacks restore from the owning replica's host pool)
    # vs stateless least-loaded placement (comebacks usually re-prefill
    # on the wrong replica). The decision numbers for ROADMAP item 3's
    # fleet front-end.
    rfleet = stage(
        {"OPSAGENT_BENCH_MODE": "fleet-affinity",
         "OPSAGENT_BENCH_MODEL": "bench-1b"},
        240, "fleet-affinity",
    )
    # Failure-containment A/B: the same fleet workload with seeded faults
    # OFF then ON (mid-SSE disconnects + connect failures). The chaos
    # phase must complete with ZERO failed requests — failovers absorb
    # the injected deaths; what it costs is the reported p99 TTFT delta.
    rchaos = stage(
        {"OPSAGENT_BENCH_MODE": "fleet-chaos",
         "OPSAGENT_BENCH_MODEL": "bench-1b"},
        230, "fleet-chaos",
    )
    # Fleet-global KV A/B: page directory + peer fault-in ON vs OFF over
    # the same forced-misroute session workload. The ON phase must land
    # second turns on a non-owning replica (and a freshly promoted
    # standby) through the wire-restore path with byte-identical greedy
    # output; the reported delta is re-prefill work avoided.
    rfgkv = stage(
        {"OPSAGENT_BENCH_MODE": "fleet-global-kv",
         "OPSAGENT_BENCH_MODEL": "bench-1b"},
        240, "fleet-global-kv",
    )
    # Fleet-journey obs-overhead A/B + stitched-timeline smoke: request
    # journeys (ID stamping + participants map + hop metrics) on vs off
    # on the streamed fleet workload, plus one forced failover+fault-in
    # request whose router timeline must stitch lanes from BOTH replicas
    # at >= 95% coverage. The reported value is the overhead percent
    # cross-replica tracing costs the request plane.
    rjourney = stage(
        {"OPSAGENT_BENCH_MODE": "fleet-journey",
         "OPSAGENT_BENCH_MODEL": "bench-1b"},
        230, "fleet-journey",
    )
    # Telemetry-history overhead A/B + downsample-tier proof: the
    # background sampler at 10x its production rate on vs off on the
    # sessions workload (byte-identical outputs, <= 2% tok/s), plus the
    # synthetic 90-min clock walk proving the 1s/10s/60s tiers and the
    # ring byte bound.
    robsh = stage(
        {"OPSAGENT_BENCH_MODE": "obs-history",
         "OPSAGENT_BENCH_MODEL": "bench-1b"},
        230, "obs-history",
    )
    # The literal north-star metric (BASELINE: p50 TTFT per tool-call
    # turn): multi-turn ReAct-shaped sessions with the prefix cache on.
    # Reports ms, not tok/s — never a headline candidate; folded into
    # extra below.
    ragent = stage(
        {"OPSAGENT_BENCH_MODE": "agent",
         "OPSAGENT_BENCH_MODEL": "bench-1b"},
        220, "agent-turns",
    )
    # Conveyor tool-overlap A/B: the trained tiny agent's scripted tool
    # episodes with early mid-decode tool launches on vs off — p50
    # episode wall, overlap banked per turn, early-launch count, and the
    # byte-identical-transcript verdict. Trains its own checkpoint
    # in-process.
    rconvey = stage(
        {"OPSAGENT_BENCH_MODE": "agent-conveyor"},
        200, "agent-conveyor", cap=300.0,
    )
    # Ragged sweep (ISSUE 15): the MIXED hot path (step_mixed →
    # paged_ragged_attention_auto) timed per KV dtype × weight quant ×
    # weight stream on the bench-8b shape under the attention reader the
    # engine chooses, one tok/s/chip row per cell with self-describing
    # resolved-impl extras. Last row is the child's best-cell summary —
    # promote-if-faster like the int4 stage.
    sweep_rows = stage_rows(
        {"OPSAGENT_BENCH_MODE": "ragged-sweep",
         "OPSAGENT_BENCH_MODEL": "bench-8b"},
        320, "ragged-sweep",
    ) if r8b is not None else []
    rsweep = sweep_rows[-1] if sweep_rows else None
    if rsweep is not None and rsweep["value"] > headline["value"]:
        headline = rsweep
    # Cold-restart TTFT proof (VERDICT r03 #9): stage 1 primed the
    # persistent compilation cache; this fresh process re-inits the same
    # preset, so its init_s/warmup_s/first_ttft_ms ARE the
    # cold-process-warm-cache restart numbers against the p50 < 500 ms
    # target. Short decode: only the startup path matters here.
    rcold = stage(
        {"OPSAGENT_BENCH_MODEL": "bench-1b",
         "OPSAGENT_BENCH_STEPS": "64"},
        120, "cold-restart",
    )
    # Cold-start A/B (ROADMAP item 4): fresh-init vs snapshot-restore
    # request-ready time in one child, both against empty compile caches
    # (the restore's cache holds only what the snapshot packaged), with
    # byte-identical greedy outputs and zero post-warmup compiles
    # asserted on the restored engine. The acceptance bar is restore
    # <= 0.5x fresh.
    rcoldstart = stage(
        {"OPSAGENT_BENCH_MODE": "cold-start",
         "OPSAGENT_BENCH_MODEL": "bench-1b",
         "OPSAGENT_BENCH_STEPS": "64"},
        150, "cold-start",
    )

    # Combined headline, printed last: the driver records one parsed line.
    extra = dict(headline.get("extra", {}))
    if headline is not r1:
        extra["bench_1b_tok_s_chip"] = r1["value"]
    if r8b is not None and headline is not r8b:
        extra["bench_8b_int8_tok_s_chip"] = r8b["value"]
    if r8b4 is not None and headline is not r8b4:
        extra["bench_8b_int4_tok_s_chip"] = r8b4["value"]
    if r8bkv is not None and headline is not r8bkv:
        extra["bench_8b_kv_int8_tok_s_chip"] = r8bkv["value"]
    if r8b4kv is not None and headline is not r8b4kv:
        extra["bench_8b_int4_kv_int8_tok_s_chip"] = r8b4kv["value"]
    if rsess is not None:
        extra["sessions_tok_s_chip"] = rsess["value"]
        extra["sessions_p50_ttft_ms"] = rsess.get("extra", {}).get(
            "p50_ttft_ms"
        )
    if rsessmix is not None:
        me = rsessmix.get("extra", {})
        extra["sessions_mixed_tok_s_chip"] = rsessmix["value"]
        extra["sessions_mixed_p50_ttft_ms"] = me.get("p50_ttft_ms")
        extra["sessions_split_tok_s_chip"] = me.get("split_tok_s_chip")
        extra["sessions_split_p50_ttft_ms"] = me.get("split_p50_ttft_ms")
    if rsessasync is not None:
        ae = rsessasync.get("extra", {})
        extra["sessions_async_tok_s_chip"] = rsessasync["value"]
        extra["sessions_async_host_work_ms"] = ae.get("host_work_ms")
        extra["sessions_async_sync_tok_s_chip"] = ae.get("sync_tok_s_chip")
        extra["sessions_async_sync_host_work_ms"] = ae.get(
            "sync_host_work_ms"
        )
        extra["sessions_async_outputs_identical"] = ae.get(
            "outputs_identical"
        )
    if rsessffwd is not None:
        fwe = rsessffwd.get("extra", {})
        extra["sessions_ffwd_tok_s_chip"] = rsessffwd["value"]
        extra["sessions_ffwd_forced_fraction"] = fwe.get("forced_fraction")
        extra["sessions_ffwd_skipped_dispatches"] = fwe.get(
            "skipped_dispatches"
        )
        extra["sessions_ffwd_off_tok_s_chip"] = fwe.get("off_tok_s_chip")
        extra["sessions_ffwd_outputs_identical"] = fwe.get(
            "outputs_identical"
        )
    if rsessoff is not None:
        oe = rsessoff.get("extra", {})
        extra["sessions_offload_tok_s_chip"] = rsessoff["value"]
        extra["sessions_offload_admission_wait_p50_ms"] = oe.get(
            "admission_wait_p50_ms"
        )
        extra["sessions_offload_off_admission_wait_p50_ms"] = oe.get(
            "off_admission_wait_p50_ms"
        )
        extra["sessions_offload_reprefill_avoided_tokens"] = oe.get(
            "reprefill_avoided_tokens"
        )
    if rfleet is not None:
        fe = rfleet.get("extra", {})
        extra["fleet_affinity_tok_s_chip"] = rfleet["value"]
        extra["fleet_affinity_p50_ttft_ms"] = fe.get("p50_ttft_ms")
        extra["fleet_affinity_reprefill_avoided_tokens"] = fe.get(
            "reprefill_avoided_tokens"
        )
        extra["fleet_off_p50_ttft_ms"] = fe.get("off_p50_ttft_ms")
        extra["fleet_off_reprefill_avoided_tokens"] = fe.get(
            "off_reprefill_avoided_tokens"
        )
    if rchaos is not None:
        che = rchaos.get("extra", {})
        extra["fleet_chaos_failed_requests"] = che.get("failed_requests")
        extra["fleet_chaos_failovers"] = che.get("failovers")
        extra["fleet_chaos_shed"] = che.get("shed")
        extra["fleet_chaos_p99_ttft_ms"] = che.get("p99_ttft_ms")
        extra["fleet_chaos_off_p99_ttft_ms"] = che.get("off_p99_ttft_ms")
        extra["fleet_chaos_outputs_identical"] = che.get(
            "outputs_identical"
        )
    if rjourney is not None:
        je = rjourney.get("extra", {})
        extra["fleet_journey_overhead_pct"] = rjourney["value"]
        extra["fleet_journey_on_tok_s"] = je.get("journeys_on_tok_s")
        extra["fleet_journey_off_tok_s"] = je.get("journeys_off_tok_s")
        extra["fleet_journey_smoke_ok"] = je.get("smoke_ok")
        extra["fleet_journey_smoke_coverage"] = je.get("smoke_coverage")
    if robsh is not None:
        he = robsh.get("extra", {})
        extra["obs_history_overhead_pct"] = robsh["value"]
        extra["obs_history_on_tok_s_chip"] = he.get(
            "sampler_on_tok_s_chip"
        )
        extra["obs_history_off_tok_s_chip"] = he.get(
            "sampler_off_tok_s_chip"
        )
        extra["obs_history_outputs_identical"] = he.get(
            "outputs_identical"
        )
        extra["obs_history_tiers_ok"] = (he.get("tiers") or {}).get("ok")
    if rfgkv is not None:
        ge = rfgkv.get("extra", {})
        extra["fleet_global_kv_remote_hit_pages"] = ge.get(
            "remote_hit_pages"
        )
        extra["fleet_global_kv_reprefill_avoided_tokens"] = ge.get(
            "reprefill_avoided_tokens"
        )
        extra["fleet_global_kv_outputs_identical"] = ge.get(
            "outputs_identical"
        )
        extra["fleet_global_kv_standby_identical"] = ge.get(
            "standby_identical"
        )
        extra["fleet_global_kv_p50_moved_ms"] = ge.get("p50_moved_ms")
        extra["fleet_global_kv_off_p50_moved_ms"] = ge.get(
            "off_p50_moved_ms"
        )
        extra["fleet_global_kv_fallbacks"] = ge.get("fallbacks")
    if ragent is not None:
        ae = ragent.get("extra", {})
        extra["agent_turn_p50_ttft_ms"] = ragent["value"]
        extra["agent_turn1_p50_ttft_ms"] = ae.get("turn1_p50_ttft_ms")
        extra["agent_prefix_hit_rate"] = ae.get("prefix_hit_rate")
    if rconvey is not None:
        ve = rconvey.get("extra", {})
        extra["agent_conveyor_p50_ms"] = rconvey["value"]
        extra["agent_conveyor_off_p50_ms"] = ve.get("off_p50_ms")
        extra["agent_conveyor_overlap_ms_per_turn"] = ve.get(
            "overlap_ms_per_turn"
        )
        extra["agent_conveyor_early_launches"] = ve.get("early_launches")
        extra["agent_conveyor_outputs_identical"] = ve.get(
            "outputs_identical"
        )
    if rsweep is not None:
        se = rsweep.get("extra", {})
        if headline is not rsweep:
            extra["ragged_sweep_best_tok_s_chip"] = rsweep["value"]
        extra["ragged_sweep_best_cell"] = se.get("best_cell")
        extra["ragged_sweep_outputs_identical"] = se.get(
            "outputs_identical"
        )
        extra["ragged_sweep_cells"] = se.get("cells")
    if rcold is not None:
        ce = rcold.get("extra", {})
        extra["cold_restart_first_ttft_ms"] = ce.get("first_ttft_ms")
        extra["cold_restart_init_s"] = ce.get("init_s")
        extra["cold_restart_warmup_s"] = ce.get("warmup_s")
    if rcoldstart is not None:
        cse = rcoldstart.get("extra", {})
        extra["cold_start_fresh_request_ready_s"] = cse.get(
            "fresh_request_ready_s"
        )
        extra["cold_start_restore_request_ready_s"] = cse.get(
            "restore_request_ready_s"
        )
        extra["cold_start_speedup_ratio"] = cse.get("speedup_ratio")
        extra["cold_start_outputs_identical"] = cse.get(
            "outputs_identical"
        )
        extra["cold_start_post_warmup_compiles"] = cse.get(
            "post_warmup_compiles"
        )
    out = dict(headline, extra=extra)
    print(json.dumps(out), flush=True)
    # The children already gated themselves; re-check the headline's
    # folded verdicts so the ORCHESTRATOR's exit code is the CI signal.
    exit_if_slo_breach(extra.get("slo") or {})
    # Perf-regression gate LAST (exit 4): every earned number is already
    # printed, so the verdict can never eat a result line.
    exit_if_perf_regression([
        r1, r8b, r8b4, r8bkv, r8b4kv, rsess, rsessmix, rsessasync,
        rsessoff, rfleet, rchaos, rfgkv, ragent, rconvey,
        rcold, rcoldstart, robsh, *sweep_rows,
    ])


def run_single() -> None:
    import jax
    import jax.numpy as jnp

    log("bench: acquiring device (backend init)")
    dev = jax.devices()[0]
    platform = dev.platform
    n_chips = len(jax.devices())
    log(f"bench: device ready ({platform}, {dev.device_kind}, x{n_chips})")
    on_tpu = platform == "tpu"
    mode = os.environ.get("OPSAGENT_BENCH_MODE", "")
    # Nothing is swapped by platform: sizes default to the device preset
    # whatever the backend, and a run that does not NAME its model is the
    # device stage, which refuses to run off the chip. (agent-conveyor
    # trains and names its own tiny model.)
    if (
        not on_tpu and mode != "agent-conveyor"
        and not os.environ.get("OPSAGENT_BENCH_MODEL")
    ):
        log(f"bench: this stage measures a TPU and found {platform}: "
            "refusing to run (name OPSAGENT_BENCH_MODEL=tiny-test for a "
            "harness check on the CPU)")
        sys.exit(3)
    model = os.environ.get("OPSAGENT_BENCH_MODEL", "bench-1b")
    batch = int(os.environ.get("OPSAGENT_BENCH_BATCH", "32"))
    steps = int(os.environ.get("OPSAGENT_BENCH_STEPS", "512"))
    prompt_len = int(os.environ.get("OPSAGENT_BENCH_PROMPT", "128"))
    # bf16 is the chip's compute dtype; the CPU harness check runs f32
    # (every row carries ``extra.dtype`` via impl_info).
    dtype = jnp.bfloat16 if on_tpu else jnp.float32

    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams

    log(f"bench: platform={platform} chips={n_chips} model={model} "
        f"batch={batch} steps={steps}")

    # bench-8b: 16 GB of bf16 weights do not fit the 16 GB chip — serve
    # weight-only int8 (8 GB + scales), which also halves the
    # weight-streaming time that bounds decode.
    quantize = os.environ.get(
        "OPSAGENT_BENCH_QUANT", "int8" if model == "bench-8b" else ""
    )
    # Large pages (fewer gather/grid steps per decode) and a page budget of
    # 128 prompt + 512 generated + slack for the decode pipeline's lookahead
    # (decode_block x (pipeline_depth + 1) tokens are pre-booked).
    if mode == "agent-conveyor":
        # Trains its own tiny checkpoint and builds its own engine (BPE
        # tokenizer, trained weights) — intercept before the shared
        # construction below.
        run_agent_conveyor(platform, n_chips)
        return
    if mode == "ragged-sweep":
        # Builds one engine per (KV dtype x weight quant x weight
        # stream) cell with its own geometry — intercept before the shared
        # construction below.
        run_ragged_sweep(platform, n_chips, model, batch, steps,
                         prompt_len)
        return
    # Mixed prefill+decode batching (EngineConfig.mixed_batching):
    # OPSAGENT_BENCH_MIXED=0 pins the split prefill/decode tick; the
    # sessions-mixed stage measures both in one child.
    mixed_on = os.environ.get("OPSAGENT_BENCH_MIXED", "") != "0"
    # One-step-lookahead async mixed ticks (EngineConfig.async_depth):
    # OPSAGENT_BENCH_ASYNC pins a depth; the sessions-mixed A/B forces
    # synchronous ticks — its question is one-weight-stream-per-tick,
    # not the lookahead (the sessions-async stage owns that A/B), and
    # pinning keeps its split phase an apples-to-apples comparison.
    async_depth = int(os.environ.get("OPSAGENT_BENCH_ASYNC", "2") or 2)
    if mode == "sessions-mixed":
        async_depth = 1
    kv_quantize = os.environ.get("OPSAGENT_BENCH_KV", "")
    # Page geometry, overridable for on-chip sweeps: the XLA gather reads
    # the FULL page-table capacity (max_pages x page_size) per step
    # regardless of resident tokens, so capacity directly scales the
    # KV-read term the roofline blames; the Pallas kernels read only
    # resident pages. OPSAGENT_BENCH_PAGE/OPSAGENT_BENCH_MAXPAGES let a
    # sweep probe that tradeoff without code edits.
    page_size = int(os.environ.get("OPSAGENT_BENCH_PAGE", "64"))
    decode_block = int(os.environ.get("OPSAGENT_BENCH_BLOCK", "32"))
    if mode == "agent":
        # The agent history grows by ~(generated + observation) tokens
        # per turn; size the per-seq page budget for the FINAL turn's
        # full history (plus decode lookahead), not the linear-decode
        # shape. Estimate CONSERVATIVELY in byte-tokenizer terms (the
        # bench presets' worst case: a "w1234" word is ~6-7 tokens, and
        # chat-template framing adds ~100+ per message): measured actuals
        # at the defaults are ~336 initial + ~378/turn; these bounds give
        # ~486 + ~480/turn, so late turns can never hit OutOfPages and
        # silently drop the slowest histories out of the reported p50.
        agent_turns = int(os.environ.get("OPSAGENT_BENCH_TURNS", "4"))
        agent_gen = max(16, steps // 8)
        est_history = (
            150 + 7 * (16 + prompt_len // 4)
            + agent_turns * (agent_gen + 7 * 48 + 80)
        )
        # Fold in the decode lookahead the fail-fast guard below adds to
        # `need` (decode_block x (pipeline_depth + 1); 4x bounds any
        # pipeline_depth <= 3), so the auto-sized geometry can never fail
        # its own guard at a swept decode_block/page_size.
        default_maxpages = (
            -(-(est_history + decode_block * 4) // page_size) + 4
        )
    else:
        default_maxpages = 12
    max_pages = int(
        os.environ.get("OPSAGENT_BENCH_MAXPAGES", str(default_maxpages))
    )
    num_pages = max(512 * 64 // page_size, batch * max_pages)
    if mode == "sessions-offload":
        # The offload A/B only measures anything under HBM PRESSURE: size
        # the page pool so the sessions' grown histories cannot all stay
        # trie-resident — the off phase re-prefills evicted content, the
        # on phase restores it from the host pool.
        num_pages = max(int(batch * max_pages * 0.6), max_pages * 2)
    cfg = EngineConfig(
        model=model,
        dtype=dtype,
        max_batch_size=batch,
        num_pages=num_pages,
        page_size=page_size,
        max_pages_per_seq=max_pages,
        prefill_buckets=(prompt_len,),
        quantize=quantize,
        kv_quantize=kv_quantize,
        decode_block=decode_block,
        mixed_batching=mixed_on,
        async_depth=async_depth,
        offload=(mode in ("sessions-offload", "fleet-affinity",
                          "fleet-chaos", "fleet-global-kv",
                          "fleet-journey", "audit-fanout")),
    )
    # Fail fast on undersized sweep points: OutOfPages mid-window would
    # force-finish sequences ('length') and quietly deflate the metric.
    # Lookahead slack from the EFFECTIVE config, so a changed
    # pipeline_depth default cannot silently undersize the guard.
    lookahead = cfg.decode_block * (cfg.pipeline_depth + 1)
    # The linear-decode guard: prompt + steps tokens per sequence. Agent
    # mode's per-seq need is the history estimate already folded into
    # default_maxpages above (and its per-turn generation is short).
    need = (
        prompt_len + steps + lookahead if mode != "agent"
        else est_history + lookahead
    )
    if cfg.page_size * cfg.max_pages_per_seq < need:
        raise SystemExit(
            f"bench: page geometry {cfg.page_size}x{cfg.max_pages_per_seq} "
            f"holds {cfg.page_size * cfg.max_pages_per_seq} tokens < "
            f"{need} needed (prompt {prompt_len} + steps {steps} + "
            f"lookahead {lookahead}); raise OPSAGENT_BENCH_MAXPAGES or "
            f"lower OPSAGENT_BENCH_STEPS"
        )
    if mode == "cold-start":
        # Builds its own engines (fresh then restored) — intercept before
        # the shared construction below.
        run_cold_start(cfg, model, batch, steps, prompt_len, platform,
                       n_chips, quantize)
        return
    t0 = time.perf_counter()
    eng = Engine(cfg)
    init_s = time.perf_counter() - t0
    log(f"bench: engine init (weights+shard) {init_s:.1f}s")
    # Only compile the programs this bench dispatches ("bench"/"sessions"
    # warmup levels): full warmup's program cross-product is what timed
    # out the round-2 driver gate. The agent mode drives the same
    # full-stack path as sessions (scheduler admission -> chunked prefill
    # -> pipelined decode), so it shares that warmup level.
    t0 = time.perf_counter()
    if mode in ("sessions", "agent", "sessions-mixed", "sessions-offload",
                "sessions-async", "sessions-ffwd", "fleet-affinity",
                "fleet-chaos", "fleet-global-kv", "fleet-journey",
                "audit-fanout", "obs-history"):
        level = "sessions"
    else:
        level = "bench"
    warmup_s = eng.warmup(level)
    log(f"bench: warmup {warmup_s:.1f}s "
        f"(persistent cache makes repeat runs fast)")

    if mode == "sessions":
        run_sessions(eng, model, batch, steps, prompt_len, platform,
                     n_chips, quantize, init_s, warmup_s)
        return
    if mode == "sessions-mixed":
        run_sessions_mixed(eng, model, batch, steps, prompt_len, platform,
                           n_chips, quantize, init_s, warmup_s)
        return
    if mode == "sessions-async":
        run_sessions_async(eng, model, batch, steps, prompt_len, platform,
                           n_chips, quantize, init_s, warmup_s)
        return
    if mode == "sessions-ffwd":
        run_sessions_ffwd(eng, model, batch, steps, prompt_len, platform,
                          n_chips, quantize, init_s, warmup_s)
        return
    if mode == "sessions-offload":
        run_sessions_offload(eng, model, batch, steps, prompt_len, platform,
                             n_chips, quantize, init_s, warmup_s)
        return
    if mode == "fleet-affinity":
        run_fleet_affinity(eng, cfg, model, batch, steps, prompt_len,
                           platform, n_chips, quantize, init_s, warmup_s)
        return
    if mode == "fleet-chaos":
        run_fleet_chaos(eng, cfg, model, batch, steps, prompt_len,
                        platform, n_chips, quantize, init_s, warmup_s)
        return
    if mode == "fleet-global-kv":
        run_fleet_global_kv(eng, cfg, model, batch, steps, prompt_len,
                            platform, n_chips, quantize, init_s, warmup_s)
        return
    if mode == "fleet-journey":
        run_fleet_journey(eng, cfg, model, batch, steps, prompt_len,
                          platform, n_chips, quantize, init_s, warmup_s)
        return
    if mode == "audit-fanout":
        run_audit_fanout(eng, cfg, model, batch, steps, prompt_len,
                         platform, n_chips, quantize, init_s, warmup_s)
        return
    if mode == "obs-history":
        run_obs_history(eng, model, batch, steps, prompt_len, platform,
                        n_chips, quantize, init_s, warmup_s)
        return
    if mode == "agent":
        # turns/gen_tokens are THE values the page-budget guard above was
        # sized from — passed through, never recomputed, so the guard and
        # the workload cannot desynchronize.
        run_agent_turns(eng, model, batch, prompt_len, platform,
                        n_chips, quantize, init_s, warmup_s,
                        turns=agent_turns, gen_tokens=agent_gen)
        return

    rng = np.random.default_rng(0)
    vocab = eng.model_cfg.vocab_size
    sampling = SamplingParams(temperature=0.0, max_tokens=10**9)

    # Admit a full batch. With the warmed engine the FIRST admission is
    # compile-free — its TTFT is the honest cold-request number.
    t0 = time.perf_counter()
    ids = []
    ttfts = []
    for i in range(batch):
        prompt = rng.integers(1, vocab, size=prompt_len).tolist()
        t1 = time.perf_counter()
        sid = eng.add_request(prompt, sampling)
        ttfts.append(time.perf_counter() - t1)
        ids.append(sid)
    log(f"bench: admitted {batch} reqs in {time.perf_counter() - t0:.1f}s; "
        f"first-request TTFT {ttfts[0]*1e3:.0f} ms (warmed, no compile)")

    # Warm up decode (compilation + cache donation settle), then drain the
    # pipeline so warmup tokens don't leak into the timed window.
    eng.step_block(ids)
    eng.drain()

    # Steady-state decode: `steps` tokens per sequence, block dispatches.
    # The final drain pulls the last in-flight blocks so `produced` counts
    # exactly the tokens whose compute falls inside dt.
    # OPSAGENT_PROFILE_DIR=<dir> captures a jax.profiler device trace of
    # exactly the timed window (open in TensorBoard to see where the
    # ms/step go); a no-op otherwise.
    from opsagent_tpu.utils.profiling import trace

    block = eng.cfg.decode_block
    produced = 0
    with trace():
        # Clock inside the trace context: start_trace/stop_trace overhead
        # (trace serialization takes seconds) must not deflate the number.
        t0 = time.perf_counter()
        for _ in range(max(1, steps // block)):
            out = eng.step_block(ids)
            produced += sum(len(v) for v in out.values())
        produced += sum(len(v) for v in eng.drain().values())
        dt = time.perf_counter() - t0

    tok_s = produced / dt
    tok_s_chip = tok_s / n_chips
    # Post-warmup TTFT (compile-free) from the later admissions.
    p50_ttft_ms = float(np.median(ttfts[1:]) * 1e3) if len(ttfts) > 1 else 0.0

    log(f"bench: {produced} tokens in {dt:.2f}s -> {tok_s:.0f} tok/s total, "
        f"{tok_s_chip:.0f} tok/s/chip; p50 TTFT {p50_ttft_ms:.0f} ms")

    log_perf_table()

    qtag = f",{quantize}" if quantize else ""
    if kv_quantize:
        qtag += f",kv-{kv_quantize}"
    emit({
        "metric": f"paged_decode_throughput[{model}{qtag},B={batch},{platform}]",
        "value": round(tok_s_chip, 1),
        "unit": "tok/s/chip",
        "vs_baseline": vs_baseline(tok_s_chip, model, platform),
        "extra": {
            "total_tok_s": round(tok_s, 1),
            "p50_ttft_ms": round(p50_ttft_ms, 1),
            "first_ttft_ms": round(ttfts[0] * 1e3, 1),
            "init_s": round(init_s, 1),
            "warmup_s": round(warmup_s, 1),
            "chips": n_chips,
            "platform": platform,
            **eng.impl_info(),
            "paged_backend": eng.kernels.attn,
            "decode_block": eng.cfg.decode_block,
            "page_size": eng.cfg.page_size,
            "metrics": metrics_snapshot(),
            "attribution": attribution_snapshot(),
            "slo": slo_verdicts(),
        },
    })
    exit_if_slo_breach(slo_verdicts())


def run_cold_start(cfg, model, batch, steps, prompt_len, platform,
                   n_chips, quantize) -> None:
    """Cold-start A/B (ROADMAP item 4): fresh-init request-ready time vs
    snapshot-restore request-ready time in one child, greedy outputs
    verified byte-identical across the two engines.

    Phase 1 builds + warms an engine against the persistent compile
    cache as it finds it (``compile_cache_entries_before`` in the row: 0
    is the honest first-boot cost), drives a short greedy decode, then
    snapshots it. ``jax.clear_caches()`` drops the in-process executable
    caches before phase 2, so the restore cannot coast on them: phase 2
    restores against the same ONE cache location
    (``engine.compile_cache_dir()`` — nothing here points JAX anywhere
    else), which then holds what phase 1 compiled, i.e. what the
    snapshot packaged — what a scale-out replica on a new host has after
    pre-seeding."""
    import gc
    import shutil
    import tempfile

    import jax

    from opsagent_tpu import obs
    from opsagent_tpu.serving.engine import Engine
    from opsagent_tpu.serving.sampler import SamplingParams

    work = tempfile.mkdtemp(prefix="opsagent-coldstart-")
    snapdir = os.path.join(work, "snapshot")
    # Every warmed program must land in the persistent cache for the
    # snapshot to package it — drop the min-compile-time floor.
    os.environ["OPSAGENT_COMPILE_CACHE_MIN_S"] = "0"

    t0 = time.perf_counter()
    eng = Engine(cfg)
    eng.warmup("bench")
    fresh_s = time.perf_counter() - t0
    log(f"bench: fresh init -> request-ready {fresh_s:.1f}s")
    cache_dir = eng.init_stats["compile_cache_dir"]
    entries_before = eng.init_stats["compile_cache_entries_at_start"]

    rng = np.random.default_rng(0)
    vocab = eng.model_cfg.vocab_size
    prompts = [rng.integers(1, vocab, size=prompt_len).tolist()
               for _ in range(batch)]
    sampling = SamplingParams(temperature=0.0, max_tokens=steps)
    fresh_out = eng.generate(prompts, sampling)

    man = eng.snapshot(snapdir)
    del eng
    gc.collect()
    jax.clear_caches()

    t0 = time.perf_counter()
    eng2 = Engine.from_snapshot(snapdir, warmup="bench")
    restore_s = time.perf_counter() - t0
    preseeded = eng2.init_stats.get("compile_cache_preseeded", 0)
    log(f"bench: snapshot restore -> request-ready {restore_s:.1f}s "
        f"({preseeded} compile-cache entries pre-seeded)")

    gauge0 = obs.POST_WARMUP_COMPILES.value()
    restore_out = eng2.generate(prompts, sampling)
    post_compiles = obs.POST_WARMUP_COMPILES.value() - gauge0
    identical = fresh_out == restore_out
    speedup = fresh_s / restore_s if restore_s > 0 else 0.0
    log(f"bench: cold-start speedup {speedup:.1f}x, outputs identical: "
        f"{identical}, post-warmup compiles on restore: {post_compiles}")

    qtag = f",{quantize}" if quantize else ""
    if cfg.kv_quantize:
        qtag += f",kv-{cfg.kv_quantize}"
    emit({
        "metric": f"cold_start_request_ready[{model}{qtag},{platform}]",
        "value": round(restore_s, 2),
        "unit": "request_ready_s",
        "extra": {
            "fresh_request_ready_s": round(fresh_s, 2),
            "restore_request_ready_s": round(restore_s, 2),
            "speedup_ratio": round(speedup, 2),
            "outputs_identical": identical,
            "post_warmup_compiles": post_compiles,
            "restore_weights_load_s": eng2.init_stats.get("weights_load_s"),
            "restore_warmup_s": eng2.init_stats.get("warmup_s"),
            "compile_cache_preseeded": preseeded,
            "compile_cache_dir": cache_dir,
            "compile_cache_entries_before": entries_before,
            "snapshot_leaves": len(man["leaves"]),
            "snapshot_compile_cache_entries":
                man["compile_cache"]["entries"],
            "snapshot_fingerprint": man["fingerprint"],
            "chips": n_chips,
            "platform": platform,
        },
    })
    shutil.rmtree(work, ignore_errors=True)


def run_ragged_sweep(platform, n_chips, model, batch, steps,
                     prompt_len) -> None:
    """Ragged sweep (ROADMAP item 1): time the MIXED hot path — sync
    ``step_mixed`` ticks, the program serving actually runs — across
    KV page dtype x weight quant x weight-stream cells on one model
    shape, one self-describing tok/s/chip row per cell. Every cell runs
    the attention reader its engine chooses
    (``ops.kernels.paged_attention_backend``; the row names it). The
    weight-stream axis needs quantized weights, so it adds one
    pallas-dma prefetch cell per quantized weight mode, each beside the
    xla weight-stream cell that anchors its byte-identity check.

    Each cell builds its own engine (the quant modes are
    engine-construction inputs), warms exactly the mixed program family
    ("bench-mixed" level), admits ``batch`` identical greedy prompts
    through chunked mixed admission, then times ``steps`` decode-only
    mixed ticks. Within a (weight, KV) group the xla weight-stream cell
    is the oracle: the prefetch cell's full greedy token streams must be
    byte-identical, and that verdict rides each row's extra. Off-chip
    the weight-stream kernel runs in interpret mode (no Mosaic on CPU),
    which is exactly what the CI smoke exercises.

    Rows are flushed the moment they exist (driver-kill contract), and
    the LAST line is a copy of the best cell with the per-cell values
    folded into extra — the orchestrator's promote-if-faster input."""
    import gc

    import jax.numpy as jnp

    from opsagent_tpu import obs
    from opsagent_tpu.serving.engine import (
        BackendRefused, Engine, EngineConfig,
    )
    from opsagent_tpu.serving.sampler import SamplingParams

    on_tpu = platform == "tpu"
    budget = float(os.environ.get(
        "OPSAGENT_BENCH_SWEEP_BUDGET", "600" if on_tpu else "240"
    ))
    t_start = time.perf_counter()
    if not on_tpu:
        # No Mosaic off-chip: run the weight-stream cell in interpret
        # mode so the full chain (engine gate -> Kernels.weights ->
        # quant-matmul kernel) still executes end to end on CPU.
        os.environ["OPSAGENT_PALLAS_INTERPRET"] = "1"
    kv_modes = ("", "int8")
    # Off-chip cells keep fp32 weights: the question CPU answers is
    # dispatch-equivalence, not throughput, and weight quant doubles the
    # cell count without touching the mixed path under test.
    weight_modes = ("int8", "int4") if on_tpu else ("",)
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    steps = min(steps, 256)
    chunk = 64 if on_tpu else 16
    buckets = tuple(sorted({4, chunk}))
    page_size = int(os.environ.get("OPSAGENT_BENCH_PAGE", "64"))
    # +1 page of slack over prompt+generated: the settle tick plus the
    # decode rows' one-token booking must never hit OutOfPages (which
    # would truncate rows and quietly deflate the number).
    max_pages = -(-(prompt_len + steps + 2) // page_size) + 1
    num_pages = max(batch * max_pages, 64)
    sampling = SamplingParams(temperature=0.0, max_tokens=10**9)

    cells = [
        (wq, kv, "xla", False)
        for wq in weight_modes for kv in kv_modes
    ]
    # Weight-stream axis: one pallas-dma prefetch cell per quantized
    # weight mode (plain KV — the weight path is the axis under test).
    # The prefetch kernel is single-shard for now, so these cells pin
    # tp=1 and bring their OWN tp=1 xla oracle: greedy byte
    # identity is only meaningful against the same reduction layout, and
    # the baseline grid above runs on every chip.
    ws_weights = ("int8", "int4") if on_tpu else ("int8",)
    for wq in ws_weights:
        cells.append((wq, "", "xla", True))
        cells.append((wq, "", "pallas-dma", True))
    rows: list[dict] = []
    skipped: dict[str, str] = {}
    oracle: dict[tuple, list[list[int]]] = {}
    groups_ok: dict[tuple, bool] = {}
    for wq, kv, ws, single in cells:
        label = f"{wq or 'bf16'}/kv-{kv or 'bf16'}"
        if single:
            label += f"/ws-{ws}"
        elapsed = time.perf_counter() - t_start
        if rows and elapsed > budget:
            log(f"bench[ragged-sweep]: {elapsed:.0f}s > {budget:.0f}s "
                f"budget; dropping {label} and later cells")
            break
        cfg = EngineConfig(
            model=model,
            dtype=dtype,
            tp=1 if single else 0,
            max_batch_size=batch,
            num_pages=num_pages,
            page_size=page_size,
            max_pages_per_seq=max_pages,
            prefill_buckets=(prompt_len,),
            quantize=wq,
            kv_quantize=kv,
            weight_stream=ws,
            mixed_batching=True,
            async_depth=1,
            mixed_buckets=buckets,
        )
        try:
            eng = Engine(cfg)
        except BackendRefused as e:
            # A weight stream the engine refuses at this model's shapes
            # is skipped BY NAME with the reason — it never runs as xla
            # under the kernel's label.
            skipped[label] = str(e)
            log(f"bench[ragged-sweep]: skipping cell {label}: {e}")
            continue
        warmup_s = eng.warmup("bench-mixed")
        compiles0 = obs.POST_WARMUP_COMPILES.value()
        rng = np.random.default_rng(0)
        vocab = eng.model_cfg.vocab_size
        ids = [
            eng.begin_request(
                rng.integers(1, vocab, size=prompt_len).tolist(), sampling
            )
            for _ in range(batch)
        ]
        while eng._prefilling:
            chunks = {}
            for sid in list(eng._prefilling):
                done, total = eng.prefill_progress(sid)
                chunks[sid] = min(chunk, total - done)
            eng.step_mixed([], chunks)
        # One settle tick outside the window (donation/layout settle),
        # then `steps` timed decode-only mixed ticks — every tick is ONE
        # dispatch advancing all `batch` lanes through the cell's kernel.
        eng.step_mixed(ids, {})
        produced = 0
        t0 = time.perf_counter()
        for _ in range(steps):
            out, _ = eng.step_mixed(ids, {})
            produced += sum(len(v) for v in out.values())
        dt = time.perf_counter() - t0
        post_compiles = int(obs.POST_WARMUP_COMPILES.value() - compiles0)
        tok_s = produced / dt
        cell_chips = 1 if single else n_chips
        tok_s_chip = tok_s / cell_chips
        outputs = [list(eng.sequences[s].tokens) for s in ids]
        # tp=1 weight-stream cells form their own oracle group: greedy
        # byte identity only holds within one reduction layout.
        group = (wq, kv, single)
        if ws == "xla":
            oracle[group] = outputs
            identical = True
        else:
            identical = outputs == oracle.get(group)
        groups_ok[group] = groups_ok.get(group, True) and identical
        info = eng.impl_info()
        # ws lands in the metric only for the single-shard weight-stream
        # cells (oracle + prefetch), so every pre-existing cell keeps its
        # baseline-comparable metric name.
        ws_tag = f",ws-{ws}" if single else ""
        row = {
            "metric": (
                f"mixed_ragged_throughput[{model},{wq or 'bf16'},"
                f"kv-{kv or 'bf16'},{info['attn_impl']}{ws_tag},"
                f"B={batch},{platform}]"
            ),
            "value": round(tok_s_chip, 1),
            "unit": "tok/s/chip",
            "vs_baseline": None,
            "extra": {
                "total_tok_s": round(tok_s, 1),
                "requested_weight_stream": ws,
                **info,
                "outputs_identical": identical,
                "post_warmup_compiles": post_compiles,
                "warmup_s": round(warmup_s, 1),
                "steps": steps,
                "interpret": not on_tpu,
                "paged_backend": info["attn_impl"],
                "chips": cell_chips,
                "platform": platform,
            },
        }
        emit(row)
        rows.append(row)
        log(f"bench[ragged-sweep/{label}]: resolved={info['attn_impl']} "
            f"ws={info['weight_stream']} {tok_s_chip:.0f} tok/s/chip, "
            f"identical={identical}, post-warmup compiles {post_compiles}")
        for sid in ids:
            eng.finish(sid)
        del eng
        gc.collect()
    if not rows:
        raise SystemExit("bench[ragged-sweep]: no cell produced a number")
    # Best-cell summary LAST: the orchestrator's last-JSON-line parse
    # (and promote-if-faster fold) reads this row.
    best = max(rows, key=lambda r: r["value"])
    summary = dict(best, extra=dict(best["extra"]))
    summary["extra"].update({
        "best_cell": best["metric"],
        "cells": len(rows),
        "skipped_cells": skipped,
        "outputs_identical": all(groups_ok.values()),
        "cell_tok_s_chip": {r["metric"]: r["value"] for r in rows},
    })
    emit(summary)


def run_sessions(eng, model, batch, steps, prompt_len, platform, n_chips,
                 quantize, init_s, warmup_s) -> None:
    """BASELINE config 5: ``batch`` concurrent sessions through the FULL
    stack — OpenAI chat translation (templates, usage accounting) ->
    scheduler admission -> chunked prefill -> pipelined decode — each
    generating ``steps // 8`` tokens per round for several rounds in the
    agent-loop shape (re-send the grown history, so the prefix cache
    carries earlier rounds' KV)."""
    import threading

    from opsagent_tpu.serving.api import ServingStack

    stack = ServingStack(eng)
    gen_tokens = max(16, steps // 8)
    rounds = 3
    results: list[dict] = []
    lock = threading.Lock()

    def session(sid: int) -> None:
        # Chat history grows across rounds like a real agent loop — each
        # round re-sends the whole conversation, so the prefix cache
        # carries the earlier rounds' KV. Per-session generator: numpy
        # Generators are not thread-safe, and distinct seeds keep prompts
        # distinct so cross-session prefix hits can't inflate the number.
        rng = np.random.default_rng(1000 + sid)
        words = [f"w{rng.integers(0, 9999)}" for _ in range(prompt_len // 2)]
        messages = [
            {"role": "system", "content": "bench session"},
            {"role": "user", "content": " ".join(words)},
        ]
        for r in range(rounds):
            t0 = time.perf_counter()
            try:
                resp = stack.chat_completion({
                    "messages": messages,
                    "max_tokens": gen_tokens,
                    "temperature": 0.0,
                })
            except Exception as e:  # noqa: BLE001
                with lock:
                    results.append({"err": str(e)})
                return
            dt = time.perf_counter() - t0
            msg = resp["choices"][0]["message"]
            messages.append(
                {"role": "assistant", "content": msg.get("content") or ""}
            )
            messages.append({"role": "user", "content": f"continue {r}"})
            with lock:
                results.append({
                    "tokens": resp["usage"]["completion_tokens"], "wall": dt,
                })

    t0 = time.perf_counter()
    threads = [
        threading.Thread(target=session, args=(i,)) for i in range(batch)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0

    errs = [r for r in results if "err" in r]
    ok = [r for r in results if "tokens" in r]
    produced = sum(r["tokens"] for r in ok)
    tok_s_chip = produced / wall / n_chips
    stats = get_perf_stats().get_stats()
    ttft = stats.get("engine.ttft", {})
    log(f"bench[sessions]: {batch} sessions x {rounds} rounds, "
        f"{produced} tokens in {wall:.2f}s -> {tok_s_chip:.0f} tok/s/chip; "
        f"p50 TTFT {ttft.get('p50', 0):.0f} ms; errors={len(errs)}")
    qtag = f",{quantize}" if quantize else ""
    emit({
        "metric": f"concurrent_sessions[{model}{qtag},N={batch},{platform}]",
        "value": round(tok_s_chip, 1),
        "unit": "tok/s/chip",
        "vs_baseline": vs_baseline(tok_s_chip, model, platform),
        "extra": {
            "sessions": batch,
            "rounds": rounds,
            "p50_ttft_ms": round(float(ttft.get("p50", 0)), 1),
            "p99_ttft_ms": round(float(ttft.get("p99", 0)), 1),
            "errors": len(errs),
            "init_s": round(init_s, 1),
            "warmup_s": round(warmup_s, 1),
            "chips": n_chips,
            "platform": platform,
            **eng.impl_info(),
            "paged_backend": eng.kernels.attn,
            "metrics": metrics_snapshot(),
            "attribution": attribution_snapshot(),
            "slo": slo_verdicts(),
        },
    })
    log_perf_table()
    stack.close()
    exit_if_slo_breach(slo_verdicts())


def _drive_sessions_streaming(stack, batch, rounds, gen_tokens, prompt_len,
                              seed_base: int, park: bool = False,
                              extra_body: dict | None = None) -> dict:
    """Run ``batch`` concurrent multi-round chat sessions with STREAMING
    completions, measuring client-observed TTFT per round (first yielded
    chunk, error-checked). Returns {produced, wall, ttfts, errors, texts}
    — self-contained client-side measurement, so two phases in one
    process cannot contaminate each other through global perf-stat
    snapshots; ``texts`` maps (session, round) to the full completion
    text (the sessions-async A/B's identical-output check).
    ``park=True`` parks each session's KV to the host tier between rounds
    (ServingStack.park — the tool-execution window of a real agent
    turn)."""
    import threading

    results: list[dict] = []
    errors: list[str] = []
    texts: dict[tuple[int, int], str] = {}
    lock = threading.Lock()

    def session(sid: int) -> None:
        rng = np.random.default_rng(seed_base + sid)
        words = [f"w{rng.integers(0, 9999)}" for _ in range(prompt_len // 2)]
        messages = [
            {"role": "system", "content": "bench session"},
            {"role": "user", "content": " ".join(words)},
        ]
        for r in range(rounds):
            if park and r:
                # The inter-round gap is where a real agent blocks on its
                # tool subprocess: hand the HBM back for other sessions'
                # admissions; this round's admission restores the chain.
                stack.park(messages)
            t0 = time.perf_counter()
            try:
                gen = stack.chat_completion_stream({
                    "messages": messages,
                    "max_tokens": gen_tokens,
                    "temperature": 0.0,
                    "stream": True,
                    **(extra_body or {}),
                })
                first = next(gen)
                if "error" in first:
                    raise RuntimeError(first["error"]["message"])
                ttft = time.perf_counter() - t0
                parts: list[str] = []
                n_tok = 0
                for ch in gen:
                    if "error" in ch:
                        raise RuntimeError(ch["error"]["message"])
                    delta = ch["choices"][0]["delta"]
                    if delta.get("content"):
                        parts.append(delta["content"])
                        n_tok += 1
            except Exception as e:  # noqa: BLE001
                with lock:
                    errors.append(f"round {r + 1}: {e}")
                return
            messages.append(
                {"role": "assistant", "content": "".join(parts)}
            )
            messages.append({"role": "user", "content": f"continue {r}"})
            with lock:
                results.append({"ttft": ttft, "tokens": n_tok})
                texts[(sid, r)] = "".join(parts)

    t0 = time.perf_counter()
    threads = [
        threading.Thread(target=session, args=(i,)) for i in range(batch)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {
        "produced": sum(r["tokens"] for r in results),
        "wall": time.perf_counter() - t0,
        "ttfts": [r["ttft"] for r in results],
        "errors": errors,
        "texts": texts,
    }


def run_sessions_mixed(eng, model, batch, steps, prompt_len, platform,
                       n_chips, quantize, init_s, warmup_s) -> None:
    """The mixed-batching A/B stage: the BASELINE config-5 concurrent-
    sessions workload run TWICE against the same engine — once with the
    unified mixed prefill+decode tick (one weight stream per tick), once
    with the split prefill-then-decode tick — so the delta is a
    first-class BENCH artifact, not a cross-round comparison. Distinct
    prompt seeds per phase keep phase 2 from riding phase 1's prefix
    cache. Reports the mixed numbers as the headline value with the split
    phase in extra."""
    from opsagent_tpu.serving.api import ServingStack

    gen_tokens = max(16, steps // 8)
    rounds = 3
    phases: dict[str, dict] = {}
    for tag, flag, seed in (("mixed", True, 5000), ("split", False, 9000)):
        eng.cfg.mixed_batching = flag
        stack = ServingStack(eng)
        try:
            phases[tag] = _drive_sessions_streaming(
                stack, batch, rounds, gen_tokens, prompt_len, seed
            )
        finally:
            stack.close()
        r = phases[tag]
        p50 = float(np.median(r["ttfts"]) * 1e3) if r["ttfts"] else 0.0
        r["p50_ttft_ms"] = p50
        r["p99_ttft_ms"] = (
            float(np.percentile(r["ttfts"], 99) * 1e3) if r["ttfts"] else 0.0
        )
        r["tok_s_chip"] = r["produced"] / max(1e-9, r["wall"]) / n_chips
        log(f"bench[sessions-mixed/{tag}]: {batch} sessions x {rounds} "
            f"rounds, {r['produced']} tokens in {r['wall']:.2f}s -> "
            f"{r['tok_s_chip']:.0f} tok/s/chip; p50 TTFT {p50:.0f} ms; "
            f"errors={len(r['errors'])}")
    mixed, split = phases["mixed"], phases["split"]
    qtag = f",{quantize}" if quantize else ""
    emit({
        "metric": f"sessions_mixed[{model}{qtag},N={batch},{platform}]",
        "value": round(mixed["tok_s_chip"], 1),
        "unit": "tok/s/chip",
        "vs_baseline": vs_baseline(mixed["tok_s_chip"], model, platform),
        "extra": {
            "sessions": batch,
            "rounds": rounds,
            "p50_ttft_ms": round(mixed["p50_ttft_ms"], 1),
            "p99_ttft_ms": round(mixed["p99_ttft_ms"], 1),
            "split_tok_s_chip": round(split["tok_s_chip"], 1),
            "split_p50_ttft_ms": round(split["p50_ttft_ms"], 1),
            "split_p99_ttft_ms": round(split["p99_ttft_ms"], 1),
            "ttft_delta_ms": round(
                split["p50_ttft_ms"] - mixed["p50_ttft_ms"], 1
            ),
            "tok_s_chip_delta": round(
                mixed["tok_s_chip"] - split["tok_s_chip"], 1
            ),
            "errors": len(mixed["errors"]) + len(split["errors"]),
            "init_s": round(init_s, 1),
            "warmup_s": round(warmup_s, 1),
            "chips": n_chips,
            "platform": platform,
            **eng.impl_info(),
            "paged_backend": eng.kernels.attn,
            "metrics": metrics_snapshot(),
            "attribution": attribution_snapshot(),
            "slo": slo_verdicts(),
        },
    })
    log_perf_table()
    exit_if_slo_breach(slo_verdicts())


def run_sessions_async(eng, model, batch, steps, prompt_len, platform,
                       n_chips, quantize, init_s, warmup_s) -> None:
    """The async-tick A/B stage: the concurrent-sessions workload run
    TWICE against the same engine — first with the one-step-lookahead
    async mixed pipeline (async_depth=2: tick t+1 dispatches before tick
    t's tokens are pulled, host post-processing overlaps device compute),
    then with synchronous ticks (depth=1, today's behavior). SAME prompt
    seeds both phases: byte-identical output text is part of the async
    contract (the lookahead changes WHEN host work happens, never WHAT
    gets generated), and running the sync phase second hands IT the
    prefix-cache advantage — a handicap against the async phase's tok/s,
    so an async win here is conservative. Decision numbers per phase:
    tok/s/chip, p50 TTFT, host work per tick and the share of the loop's
    time spent blocked on the device (the tick phase counter: a sync tick
    is work + a whole step of waiting, an async tick hides one in the
    other, so the overlap shows as a lower wait share), and the
    overlapped-commit count proving host work actually ran while a newer
    dispatch was in flight."""
    from opsagent_tpu.serving.api import ServingStack

    gen_tokens = max(16, steps // 8)
    rounds = 3
    phases: dict[str, dict] = {}
    for tag, depth in (("async", 2), ("sync", 1)):
        eng.cfg.async_depth = depth
        get_perf_stats().reset()
        snap0 = metrics_snapshot()
        stack = ServingStack(eng)
        try:
            phases[tag] = _drive_sessions_streaming(
                stack, batch, rounds, gen_tokens, prompt_len, 4000
            )
        finally:
            stack.close()
        r = phases[tag]
        r["p50_ttft_ms"] = (
            float(np.median(r["ttfts"]) * 1e3) if r["ttfts"] else 0.0
        )
        r["tok_s_chip"] = r["produced"] / max(1e-9, r["wall"]) / n_chips
        snap1 = metrics_snapshot()
        r["host_work_ms"], r["device_wait_share"] = _tick_phases(
            snap0, snap1
        )
        r["overlapped_commits"] = int(
            snap1.get("opsagent_async_overlapped_commits_total", 0)
            - snap0.get("opsagent_async_overlapped_commits_total", 0)
        )
        r["async_commits"] = int(
            snap1.get("opsagent_async_commits_total", 0)
            - snap0.get("opsagent_async_commits_total", 0)
        )
        log(f"bench[sessions-async/{tag}]: {batch} sessions x {rounds} "
            f"rounds, {r['produced']} tokens in {r['wall']:.2f}s -> "
            f"{r['tok_s_chip']:.0f} tok/s/chip; p50 TTFT "
            f"{r['p50_ttft_ms']:.0f} ms; host work "
            f"{r['host_work_ms']:.2f} ms/tick, device wait share "
            f"{r['device_wait_share']:.2f}; overlapped commits "
            f"{r['overlapped_commits']}; errors={len(r['errors'])}")
    a, s = phases["async"], phases["sync"]
    identical = a["texts"] == s["texts"] and not a["errors"] and not s["errors"]
    qtag = f",{quantize}" if quantize else ""
    emit({
        "metric": f"sessions_async[{model}{qtag},N={batch},{platform}]",
        "value": round(a["tok_s_chip"], 1),
        "unit": "tok/s/chip",
        "vs_baseline": vs_baseline(a["tok_s_chip"], model, platform),
        "extra": {
            "sessions": batch,
            "rounds": rounds,
            "p50_ttft_ms": round(a["p50_ttft_ms"], 1),
            "host_work_ms": round(a["host_work_ms"], 3),
            "device_wait_share": round(a["device_wait_share"], 3),
            "overlapped_commits": a["overlapped_commits"],
            "async_commits": a["async_commits"],
            "sync_tok_s_chip": round(s["tok_s_chip"], 1),
            "sync_p50_ttft_ms": round(s["p50_ttft_ms"], 1),
            "sync_host_work_ms": round(s["host_work_ms"], 3),
            "sync_device_wait_share": round(s["device_wait_share"], 3),
            "tok_s_chip_delta": round(
                a["tok_s_chip"] - s["tok_s_chip"], 1
            ),
            "outputs_identical": identical,
            "errors": len(a["errors"]) + len(s["errors"]),
            "init_s": round(init_s, 1),
            "warmup_s": round(warmup_s, 1),
            "chips": n_chips,
            "platform": platform,
            **eng.impl_info(),
            "paged_backend": eng.kernels.attn,
            "metrics": metrics_snapshot(),
            "attribution": attribution_snapshot(),
            "slo": slo_verdicts(),
        },
    })
    log_perf_table()
    exit_if_slo_breach(slo_verdicts())


def run_sessions_ffwd(eng, model, batch, steps, prompt_len, platform,
                      n_chips, quantize, init_s, warmup_s) -> None:
    """The grammar fast-forward A/B stage: the concurrent-sessions
    workload with EVERY completion constrained to the ToolPrompt JSON
    schema (the warmup-pre-specialized one, so both phases run
    compile-free), run TWICE against the same engine — fast-forward ON
    (forced-token runs splice into the paged KV as multi-token appends,
    no forward pass per forced token), then OFF (every token pays a
    dispatch). SAME prompt seeds both phases: byte-identical output text
    is the correctness half of the contract (a forced token is what the
    masked sampler would have picked anyway), and the OFF phase running
    second hands it the prefix-cache advantage — a handicap against the
    ON phase's tok/s. Decision numbers per phase: tok/s/chip, the
    forced-token fraction (what share of produced tokens needed no
    forward pass), and skipped dispatch counts."""
    from opsagent_tpu.serving.api import ServingStack
    from opsagent_tpu.serving.constrained import TOOLPROMPT_SCHEMA

    gen_tokens = max(16, steps // 8)
    rounds = 3
    rf = {"response_format": {"type": "json_schema", "json_schema": {
        "name": "toolprompt", "schema": TOOLPROMPT_SCHEMA,
    }}}
    phases: dict[str, dict] = {}
    for tag, on in (("on", True), ("off", False)):
        eng.cfg.grammar_ffwd = on
        get_perf_stats().reset()
        snap0 = metrics_snapshot()
        stack = ServingStack(eng)
        try:
            phases[tag] = _drive_sessions_streaming(
                stack, batch, rounds, gen_tokens, prompt_len, 6000,
                extra_body=rf,
            )
        finally:
            stack.close()
        r = phases[tag]
        r["p50_ttft_ms"] = (
            float(np.median(r["ttfts"]) * 1e3) if r["ttfts"] else 0.0
        )
        r["tok_s_chip"] = r["produced"] / max(1e-9, r["wall"]) / n_chips
        snap1 = metrics_snapshot()
        for short, metric in (
            ("ffwd_tokens", "opsagent_ffwd_tokens_total"),
            ("ffwd_runs", "opsagent_ffwd_runs_total"),
            ("skipped_dispatches",
             "opsagent_ffwd_skipped_dispatches_total"),
        ):
            r[short] = int(snap1.get(metric, 0) - snap0.get(metric, 0))
        r["forced_fraction"] = round(
            r["ffwd_tokens"] / max(1, r["produced"]), 3
        )
        log(f"bench[sessions-ffwd/{tag}]: {batch} sessions x {rounds} "
            f"rounds, {r['produced']} tokens in {r['wall']:.2f}s -> "
            f"{r['tok_s_chip']:.0f} tok/s/chip; forced fraction "
            f"{r['forced_fraction']:.1%} ({r['ffwd_tokens']} tokens in "
            f"{r['ffwd_runs']} runs, {r['skipped_dispatches']} dispatches "
            f"skipped); errors={len(r['errors'])}")
    a, b = phases["on"], phases["off"]
    identical = a["texts"] == b["texts"] and not a["errors"] and not b["errors"]
    qtag = f",{quantize}" if quantize else ""
    emit({
        "metric": f"sessions_ffwd[{model}{qtag},N={batch},{platform}]",
        "value": round(a["tok_s_chip"], 1),
        "unit": "tok/s/chip",
        "vs_baseline": vs_baseline(a["tok_s_chip"], model, platform),
        "extra": {
            "sessions": batch,
            "rounds": rounds,
            "p50_ttft_ms": round(a["p50_ttft_ms"], 1),
            "forced_fraction": a["forced_fraction"],
            "ffwd_tokens": a["ffwd_tokens"],
            "ffwd_runs": a["ffwd_runs"],
            "skipped_dispatches": a["skipped_dispatches"],
            "off_tok_s_chip": round(b["tok_s_chip"], 1),
            "off_p50_ttft_ms": round(b["p50_ttft_ms"], 1),
            "off_skipped_dispatches": b["skipped_dispatches"],
            "tok_s_chip_delta": round(
                a["tok_s_chip"] - b["tok_s_chip"], 1
            ),
            "outputs_identical": identical,
            "errors": len(a["errors"]) + len(b["errors"]),
            "init_s": round(init_s, 1),
            "warmup_s": round(warmup_s, 1),
            "chips": n_chips,
            "platform": platform,
            **eng.impl_info(),
            "paged_backend": eng.kernels.attn,
            "metrics": metrics_snapshot(),
            "attribution": attribution_snapshot(),
            "slo": slo_verdicts(),
        },
    })
    log_perf_table()
    exit_if_slo_breach(slo_verdicts())


def run_sessions_offload(eng, model, batch, steps, prompt_len, platform,
                         n_chips, quantize, init_s, warmup_s) -> None:
    """The hierarchical-KV A/B stage: the concurrent-sessions workload
    under HBM page pressure (num_pages was sized below the sessions'
    aggregate history) run TWICE against the same engine — offload tier
    OFF (evictions drop content, every comeback re-prefills), then ON
    (evictions spill to the host pool, sessions park between rounds like
    a tool-blocked agent turn, comebacks restore with a page copy). Both
    phases land in ONE JSON line: admission-wait p50 and
    re-prefill-avoided token counts are the decision numbers the offload
    tier exists for."""
    from opsagent_tpu.serving.api import ServingStack

    gen_tokens = max(16, steps // 8)
    rounds = 3
    mgr = eng.offload
    assert mgr is not None, "sessions-offload needs EngineConfig.offload"

    def _avoided() -> float:
        snap = metrics_snapshot()
        return float(
            snap.get("opsagent_offload_reprefill_avoided_tokens_total", 0.0)
        )

    phases: dict[str, dict] = {}
    # OFF first: the ON phase's host pool then holds only its own spills.
    for tag, flag, seed in (("off", False, 3000), ("on", True, 7000)):
        if flag:
            eng.offload = mgr
            eng.alloc.set_spill(eng._spill_page)
        else:
            eng.offload = None
            eng.alloc.set_spill(None)
        get_perf_stats().reset()
        avoided0 = _avoided()
        stack = ServingStack(eng)
        try:
            phases[tag] = _drive_sessions_streaming(
                stack, batch, rounds, gen_tokens, prompt_len, seed,
                park=flag,
            )
        finally:
            stack.close()
        r = phases[tag]
        r["p50_ttft_ms"] = (
            float(np.median(r["ttfts"]) * 1e3) if r["ttfts"] else 0.0
        )
        qw = get_perf_stats().get_stats().get("scheduler.queue_wait", {})
        r["admission_wait_p50_ms"] = float(qw.get("p50", 0.0))
        r["reprefill_avoided_tokens"] = int(_avoided() - avoided0)
        r["tok_s_chip"] = r["produced"] / max(1e-9, r["wall"]) / n_chips
        log(f"bench[sessions-offload/{tag}]: {batch} sessions x {rounds} "
            f"rounds, {r['produced']} tokens in {r['wall']:.2f}s -> "
            f"{r['tok_s_chip']:.0f} tok/s/chip; p50 TTFT "
            f"{r['p50_ttft_ms']:.0f} ms; admission-wait p50 "
            f"{r['admission_wait_p50_ms']:.1f} ms; re-prefill avoided "
            f"{r['reprefill_avoided_tokens']} tok; "
            f"errors={len(r['errors'])}")
    on, off = phases["on"], phases["off"]
    pool = mgr.stats()
    qtag = f",{quantize}" if quantize else ""
    emit({
        "metric": f"sessions_offload[{model}{qtag},N={batch},{platform}]",
        "value": round(on["tok_s_chip"], 1),
        "unit": "tok/s/chip",
        "vs_baseline": vs_baseline(on["tok_s_chip"], model, platform),
        "extra": {
            "sessions": batch,
            "rounds": rounds,
            "p50_ttft_ms": round(on["p50_ttft_ms"], 1),
            "admission_wait_p50_ms": round(on["admission_wait_p50_ms"], 2),
            "reprefill_avoided_tokens": on["reprefill_avoided_tokens"],
            "off_tok_s_chip": round(off["tok_s_chip"], 1),
            "off_p50_ttft_ms": round(off["p50_ttft_ms"], 1),
            "off_admission_wait_p50_ms": round(
                off["admission_wait_p50_ms"], 2
            ),
            "off_reprefill_avoided_tokens": off["reprefill_avoided_tokens"],
            "admission_wait_delta_ms": round(
                off["admission_wait_p50_ms"] - on["admission_wait_p50_ms"], 2
            ),
            "host_pool_pages": pool["pages"],
            "host_pool_bytes": pool["bytes"],
            "host_pool_drops": pool["drops"],
            "restored_tokens": pool["restored_tokens"],
            "errors": len(on["errors"]) + len(off["errors"]),
            "init_s": round(init_s, 1),
            "warmup_s": round(warmup_s, 1),
            "chips": n_chips,
            "platform": platform,
            **eng.impl_info(),
            "paged_backend": eng.kernels.attn,
            "metrics": metrics_snapshot(),
            "attribution": attribution_snapshot(),
            "slo": slo_verdicts(),
        },
    })
    log_perf_table()
    exit_if_slo_breach(slo_verdicts())


def run_fleet_affinity(eng, cfg, model, batch, steps, prompt_len, platform,
                       n_chips, quantize, init_s, warmup_s) -> None:
    """The fleet-affinity A/B stage (serving/fleet): N in-process engine
    replicas behind the FleetRouter, the concurrent-sessions workload
    with tool-window parking between rounds, run TWICE — prefix-affinity
    routing ON (sticky pinning + longest-cached-prefix placement: a
    session's comeback lands on the replica holding its KV and restores
    from the host pool), then OFF (stateless least-loaded placement: a
    comeback lands wherever occupancy is lowest and usually re-prefills
    its whole history). Decision numbers per phase: p50 client TTFT and
    re-prefill-avoided tokens summed over the fleet — what prefix-
    affinity routing is worth at fleet scale."""
    import threading
    from dataclasses import replace as dc_replace

    from opsagent_tpu.serving.api import ServingStack
    from opsagent_tpu.serving.engine import Engine
    from opsagent_tpu.serving.fleet.router import FleetRouter

    n_replicas = int(os.environ.get("OPSAGENT_BENCH_REPLICAS", "2"))
    gen_tokens = max(16, steps // 8)
    rounds = 3
    engines = [eng]
    for i in range(1, n_replicas):
        e = Engine(dc_replace(cfg, seed=cfg.seed))
        e.warmup("sessions")
        engines.append(e)
    stacks = [ServingStack(e) for e in engines]

    def drive(router, seed_base: int) -> dict:
        results: list[dict] = []
        errors: list[str] = []
        lock = threading.Lock()

        def session(sid: int) -> None:
            rng = np.random.default_rng(seed_base + sid)
            words = [
                f"w{rng.integers(0, 9999)}" for _ in range(prompt_len // 2)
            ]
            messages = [
                {"role": "system", "content": "fleet bench"},
                {"role": "user", "content": " ".join(words)},
            ]
            owner = None
            for r in range(rounds):
                if r and owner is not None:
                    # Tool window: the session's replica parks its KV to
                    # the host tier; the comeback restores ONLY if the
                    # router sends the turn back to that replica.
                    info = router.registry.get(owner)
                    if info is not None and info.handle is not None:
                        try:
                            info.handle.park_tokens(
                                info.handle.tokenize(
                                    {"messages": messages}
                                )
                            )
                        except Exception:  # noqa: BLE001
                            pass
                t0 = time.perf_counter()
                try:
                    gen = router.complete_stream({
                        "messages": messages,
                        "max_tokens": gen_tokens,
                        "temperature": 0.0,
                        "stream": True,
                    })
                    first = next(gen)
                    if "error" in first:
                        raise RuntimeError(first["error"]["message"])
                    ttft = time.perf_counter() - t0
                    owner = router.owner_of(first.get("id", "")) or owner
                    parts: list[str] = []
                    n_tok = 0
                    for ch in gen:
                        if "error" in ch:
                            raise RuntimeError(ch["error"]["message"])
                        delta = ch["choices"][0]["delta"]
                        if delta.get("content"):
                            parts.append(delta["content"])
                            n_tok += 1
                except Exception as e:  # noqa: BLE001
                    with lock:
                        errors.append(f"round {r + 1}: {e}")
                    return
                messages.append(
                    {"role": "assistant", "content": "".join(parts)}
                )
                messages.append(
                    {"role": "user", "content": f"continue {r}"}
                )
                with lock:
                    results.append({"ttft": ttft, "tokens": n_tok})

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=session, args=(i,))
            for i in range(batch)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return {
            "produced": sum(r["tokens"] for r in results),
            "wall": time.perf_counter() - t0,
            "ttfts": [r["ttft"] for r in results],
            "errors": errors,
        }

    def fleet_avoided() -> int:
        return sum(
            e.offload.restored_tokens for e in engines
            if e.offload is not None
        )

    phases: dict[str, dict] = {}
    for tag, flag, seed in (("affinity", True, 11000), ("off", False, 15000)):
        router = FleetRouter(
            affinity=flag, sticky=flag,
            placement="affinity" if flag else "round_robin",
        )
        for i, stack in enumerate(stacks):
            router.add_local(stack, f"bench-r{i}")
        avoided0 = fleet_avoided()
        phases[tag] = drive(router, seed)
        r = phases[tag]
        r["p50_ttft_ms"] = (
            float(np.median(r["ttfts"]) * 1e3) if r["ttfts"] else 0.0
        )
        r["reprefill_avoided_tokens"] = fleet_avoided() - avoided0
        r["tok_s_chip"] = r["produced"] / max(1e-9, r["wall"]) / n_chips
        log(f"bench[fleet-affinity/{tag}]: {batch} sessions x {rounds} "
            f"rounds over {n_replicas} replicas, {r['produced']} tokens "
            f"in {r['wall']:.2f}s -> {r['tok_s_chip']:.0f} tok/s/chip; "
            f"p50 TTFT {r['p50_ttft_ms']:.0f} ms; re-prefill avoided "
            f"{r['reprefill_avoided_tokens']} tok; "
            f"errors={len(r['errors'])}")
    on, off = phases["affinity"], phases["off"]
    snap = metrics_snapshot()
    qtag = f",{quantize}" if quantize else ""
    emit({
        "metric": (
            f"fleet_affinity[{model}{qtag},N={batch},R={n_replicas},"
            f"{platform}]"
        ),
        "value": round(on["tok_s_chip"], 1),
        "unit": "tok/s/chip",
        "vs_baseline": vs_baseline(on["tok_s_chip"], model, platform),
        "extra": {
            "replicas": n_replicas,
            "sessions": batch,
            "rounds": rounds,
            "p50_ttft_ms": round(on["p50_ttft_ms"], 1),
            "reprefill_avoided_tokens": on["reprefill_avoided_tokens"],
            "off_tok_s_chip": round(off["tok_s_chip"], 1),
            "off_p50_ttft_ms": round(off["p50_ttft_ms"], 1),
            "off_reprefill_avoided_tokens": off[
                "reprefill_avoided_tokens"
            ],
            "ttft_delta_ms": round(
                off["p50_ttft_ms"] - on["p50_ttft_ms"], 1
            ),
            "route_decisions": {
                k[len("opsagent_fleet_route_decisions_total"):] or "total": v
                for k, v in snap.items()
                if k.startswith("opsagent_fleet_route_decisions_total")
            },
            "kv_transfer_pages": snap.get(
                "opsagent_fleet_kv_transfer_pages_total", 0
            ),
            "errors": len(on["errors"]) + len(off["errors"]),
            "init_s": round(init_s, 1),
            "warmup_s": round(warmup_s, 1),
            "chips": n_chips,
            "platform": platform,
            **eng.impl_info(),
            "paged_backend": eng.kernels.attn,
            "metrics": snap,
            "attribution": attribution_snapshot(),
            "slo": slo_verdicts(),
        },
    })
    log_perf_table()
    for s in stacks:
        s.close()
    exit_if_slo_breach(slo_verdicts())


def run_fleet_global_kv(eng, cfg, model, batch, steps, prompt_len,
                        platform, n_chips, quantize, init_s,
                        warmup_s) -> None:
    """The fleet-global-KV A/B stage (serving/fleet/pagestore): the page
    directory + peer fault-in path ON vs OFF (legacy eager-push
    migration). Per session: turn 1 lands on replica A (the owner), the
    second turn is FORCED onto replica B with zero affinity — with the
    directory on, B faults the chain in peer-to-peer and restores over
    the wire; the same turn is then replayed on never-moved A and the
    greedy outputs must be byte-identical. The ON phase also promotes a
    standby replica mid-run and forces a third turn onto it (the
    scale-up story: a freshly promoted replica is instantly useful for
    EXISTING sessions). Decision numbers per phase: fleet-summed
    re-prefill-avoided tokens, pagestore remote-hit pages, p50 moved-
    turn latency, and the identical-output flags."""
    from dataclasses import replace as dc_replace

    from opsagent_tpu import obs as obs_mod
    from opsagent_tpu.serving.api import ServingStack
    from opsagent_tpu.serving.engine import Engine
    from opsagent_tpu.serving.fleet.router import FleetRouter

    n_replicas = int(os.environ.get("OPSAGENT_BENCH_REPLICAS", "2"))
    gen_tokens = max(16, steps // 8)
    engines = [eng]
    for _ in range(1, n_replicas + 1):   # +1: the standby replica
        e = Engine(dc_replace(cfg, seed=cfg.seed))
        e.warmup("sessions")
        engines.append(e)
    stacks = [ServingStack(e) for e in engines]

    def fleet_avoided() -> int:
        return sum(
            e.offload.restored_tokens for e in engines
            if e.offload is not None
        )

    def drive(router, seed_base: int, standby_id: str | None) -> dict:
        moved_ms: list[float] = []
        errors: list[str] = []
        identical = True
        standby_identical = True
        for sid in range(batch):
            rng = np.random.default_rng(seed_base + sid)
            words = [
                f"w{rng.integers(0, 9999)}" for _ in range(prompt_len // 2)
            ]
            messages = [
                {"role": "system", "content": "fleet global kv bench"},
                {"role": "user", "content": " ".join(words)},
            ]

            def turn(msgs, force):
                resp = router.complete(
                    {
                        "messages": msgs, "max_tokens": gen_tokens,
                        "temperature": 0.0,
                    },
                    force_replica=force,
                )
                return resp["choices"][0]["message"]["content"] or ""

            try:
                # Turn 1 establishes ownership on replica 0.
                t1 = turn(messages, "bench-r0")
                messages += [
                    {"role": "assistant", "content": t1},
                    {"role": "user", "content": f"continue {sid}"},
                ]
                # Turn 2 forced onto a NON-owner: the directory-on
                # phase faults the chain in; both phases must match the
                # never-moved replay on replica 0.
                t0 = time.perf_counter()
                moved = turn(messages, "bench-r1")
                moved_ms.append((time.perf_counter() - t0) * 1e3)
                stayed = turn(messages, "bench-r0")
                if moved != stayed:
                    identical = False
                if standby_id is not None:
                    # Turn 3 onto the freshly promoted standby.
                    messages += [
                        {"role": "assistant", "content": stayed},
                        {"role": "user", "content": "and then?"},
                    ]
                    t3_standby = turn(messages, standby_id)
                    t3_owner = turn(messages, "bench-r0")
                    if t3_standby != t3_owner:
                        standby_identical = False
            except Exception as e:  # noqa: BLE001
                errors.append(f"session {sid}: {e}")
        return {
            "moved_ms": moved_ms,
            "errors": errors,
            "identical": identical,
            "standby_identical": standby_identical,
        }

    def pagestore_counters() -> dict:
        snap = metrics_snapshot()
        return {
            "remote_hits": snap.get(
                "opsagent_pagestore_remote_hits_total", 0.0
            ),
            "fetch_bytes": snap.get(
                "opsagent_pagestore_fetch_bytes_total", 0.0
            ),
            "stale": snap.get(
                "opsagent_pagestore_stale_entries_total", 0.0
            ),
            "fallbacks": sum(
                v for k, v in snap.items()
                if k.startswith("opsagent_pagestore_fallbacks_total")
            ),
        }

    phases: dict[str, dict] = {}
    for tag, flag, seed in (("on", True, 21000), ("off", False, 25000)):
        router = FleetRouter(sticky=False, pagestore=flag)
        for i, stack in enumerate(stacks[: n_replicas]):
            router.add_local(stack, f"bench-r{i}")
        standby_id = None
        if flag:
            # The scale-up leg: register the spare as a standby, promote
            # it into the decode set mid-phase — its first-ever turns
            # must restore existing sessions' chains over the wire.
            standby_id = "bench-standby"
            router.add_local(stacks[n_replicas], standby_id,
                             role="standby")
            router.registry.set_role(standby_id, "decode")
        avoided0 = fleet_avoided()
        ps0 = pagestore_counters()
        compiles0 = obs_mod.POST_WARMUP_COMPILES.value()
        t0 = time.perf_counter()
        phases[tag] = drive(router, seed, standby_id)
        r = phases[tag]
        r["wall"] = time.perf_counter() - t0
        r["reprefill_avoided_tokens"] = fleet_avoided() - avoided0
        ps1 = pagestore_counters()
        r["pagestore"] = {
            k: ps1[k] - ps0[k] for k in ps1
        }
        r["post_compiles"] = (
            obs_mod.POST_WARMUP_COMPILES.value() - compiles0
        )
        r["directory"] = router.registry.directory.stats()
        r["p50_moved_ms"] = (
            float(np.median(r["moved_ms"])) if r["moved_ms"] else 0.0
        )
        log(f"bench[fleet-global-kv/{tag}]: {batch} sessions moved onto "
            f"non-owners; identical={r['identical']} "
            f"standby_identical={r['standby_identical']} "
            f"remote_hit_pages={r['pagestore']['remote_hits']:.0f} "
            f"re-prefill avoided {r['reprefill_avoided_tokens']} tok; "
            f"p50 moved-turn {r['p50_moved_ms']:.0f} ms; "
            f"post-warmup compiles {r['post_compiles']:.0f}; "
            f"errors={len(r['errors'])}")
    on, off = phases["on"], phases["off"]
    # Remote hits per phase: the ON phase restores over the wire
    # (directory + fault-in); the OFF phase may still avoid re-prefill
    # via the legacy eager push, but never through the page store.
    total_tokens = batch * gen_tokens * 4  # 2 turns + replay legs, approx
    tok_s_chip = total_tokens / max(1e-9, on["wall"]) / n_chips
    snap = metrics_snapshot()
    qtag = f",{quantize}" if quantize else ""
    emit({
        "metric": (
            f"fleet_global_kv[{model}{qtag},N={batch},R={n_replicas}+1,"
            f"{platform}]"
        ),
        "value": round(tok_s_chip, 1),
        "unit": "tok/s/chip",
        "extra": {
            "replicas": n_replicas,
            "standby": 1,
            "sessions": batch,
            "remote_hit_pages": on["pagestore"]["remote_hits"],
            "fetch_bytes": on["pagestore"]["fetch_bytes"],
            "stale_entries": on["pagestore"]["stale"],
            "fallbacks": on["pagestore"]["fallbacks"],
            "outputs_identical": on["identical"],
            "standby_identical": on["standby_identical"],
            "off_outputs_identical": off["identical"],
            "reprefill_avoided_tokens": on["reprefill_avoided_tokens"],
            "off_reprefill_avoided_tokens": off[
                "reprefill_avoided_tokens"
            ],
            "off_remote_hit_pages": off["pagestore"]["remote_hits"],
            "p50_moved_ms": round(on["p50_moved_ms"], 1),
            "off_p50_moved_ms": round(off["p50_moved_ms"], 1),
            "post_compiles": on["post_compiles"],
            "directory": on["directory"],
            "errors": len(on["errors"]) + len(off["errors"]),
            "error_detail": (on["errors"] + off["errors"])[:4],
            "init_s": round(init_s, 1),
            "warmup_s": round(warmup_s, 1),
            "chips": n_chips,
            "platform": platform,
            "metrics": snap,
            "attribution": attribution_snapshot(),
            "slo": slo_verdicts(),
        },
    })
    log_perf_table()
    for s in stacks:
        s.close()
    exit_if_slo_breach(slo_verdicts())


def run_audit_fanout(eng, cfg, model, batch, steps, prompt_len, platform,
                     n_chips, quantize, init_s, warmup_s) -> None:
    """The audit-fanout stage (agent/fanout): one cluster-scale audit as
    a fan-out/reduce workload over OPSAGENT_BENCH_REPLICAS (default 2)
    in-process replicas behind the fleet router. The seeded synthetic
    cluster gives ground truth, so the stage scores RECALL (must be 1.0)
    alongside the serving numbers: end-to-end audit latency (the
    headline, lower-better), per-fan-out shared-prefix hit rate
    (higher-better, its own result row), goodput (children/s), and the
    fraction of children whose prefill was served from the primed shared
    prefix. The audit runs TWICE — pass 1 warms the fan-out shape and
    pins the canonical report bytes, pass 2 is measured (post-warmup
    compiles over it must be zero) with a concurrent INTERACTIVE probe
    streaming against the same fleet: batch-class children must not
    starve interactive TTFT (reported as p50_ttft_ms so the perf gate
    ratchets it)."""
    import threading
    from dataclasses import replace as dc_replace

    from opsagent_tpu import obs as obs_mod
    from opsagent_tpu.agent.fanout import (
        FanoutConfig, SynthCluster, run_audit,
    )
    from opsagent_tpu.serving.api import ServingStack
    from opsagent_tpu.serving.engine import Engine
    from opsagent_tpu.serving.fleet.router import FleetRouter

    n_replicas = int(os.environ.get("OPSAGENT_BENCH_REPLICAS", "2"))
    resources = int(os.environ.get(
        "OPSAGENT_BENCH_FANOUT_RESOURCES", str(max(8, batch * 4))
    ))
    gen_tokens = max(8, steps // 8)
    engines = [eng]
    for _ in range(1, n_replicas):
        e = Engine(dc_replace(cfg, seed=cfg.seed))
        e.warmup("sessions")
        engines.append(e)
    stacks = [ServingStack(e) for e in engines]
    router = FleetRouter(sticky=False)
    for i, s in enumerate(stacks):
        router.add_local(s, f"bench-r{i}")
    cluster = SynthCluster(resources=resources, seed=0)
    fcfg = FanoutConfig(
        max_inflight=max(2, batch), max_tokens=gen_tokens,
    )

    rep1 = run_audit(router, cluster, fcfg)
    compiles0 = obs_mod.POST_WARMUP_COMPILES.value()
    ttft_ms: list[float] = []
    probe_errors: list[str] = []
    stop = threading.Event()

    def interactive_probe() -> None:
        n = 0
        while not stop.is_set():
            n += 1
            t0 = time.perf_counter()
            try:
                gen = router.complete_stream({
                    "messages": [
                        {"role": "user", "content": f"fleet status {n}"},
                    ],
                    "max_tokens": 4, "temperature": 0.0, "stream": True,
                    "slo_class": "interactive",
                })
                first = next(gen)
                if "error" in first:
                    raise RuntimeError(first["error"]["message"])
                ttft_ms.append((time.perf_counter() - t0) * 1e3)
                for ch in gen:
                    if "error" in ch:
                        raise RuntimeError(ch["error"]["message"])
            except Exception as e:  # noqa: BLE001 - probe outcome IS data
                probe_errors.append(f"{type(e).__name__}: {e}")
            stop.wait(0.05)

    probe = threading.Thread(target=interactive_probe, daemon=True)
    probe.start()
    rep2 = run_audit(router, cluster, fcfg)
    stop.set()
    probe.join(timeout=30.0)
    post_compiles = obs_mod.POST_WARMUP_COMPILES.value() - compiles0

    s1, s2 = rep1.stats, rep2.stats
    byte_identical = rep1.canonical == rep2.canonical
    recall = rep2.recall(cluster)
    audit_s = float(s2["audit_s"])
    goodput = resources / max(1e-9, audit_s)
    failed = resources - int(s2["outcomes"].get("ok", 0))
    p50_ttft = float(np.median(ttft_ms)) if ttft_ms else 0.0
    snap = metrics_snapshot()
    qtag = f",{quantize}" if quantize else ""
    tag = f"{model}{qtag},N={resources},R={n_replicas},{platform}"
    extra = {
        "replicas": n_replicas,
        "resources": resources,
        "children_ok": int(s2["outcomes"].get("ok", 0)),
        "failed_children": failed,
        "outcomes": s2["outcomes"],
        "recall": recall,
        "byte_identical": byte_identical,
        "goodput_children_s": round(goodput, 2),
        "prefix_hit_rate": s2["prefix_hit_rate"],
        "avoided_children": s2["avoided_children"],
        "shared_prefix_tokens": s2["shared_prefix_tokens"],
        "prefix_hit_tokens": s2["prefix_hit_tokens"],
        "scatter_s": round(float(s2["scatter_s"]), 3),
        "reduce_s": round(float(s2["reduce_s"]), 4),
        "warm_audit_ratio": round(
            audit_s / max(1e-9, float(s1["audit_s"])), 3
        ),
        "post_compiles": post_compiles,
        "p50_ttft_ms": round(p50_ttft, 1),
        "interactive_probes": len(ttft_ms),
        "probe_errors": len(probe_errors),
        "probe_error_detail": probe_errors[:4],
        "init_s": round(init_s, 1),
        "warmup_s": round(warmup_s, 1),
        "chips": n_chips,
        "platform": platform,
        "metrics": snap,
        "attribution": attribution_snapshot(),
        "slo": slo_verdicts(),
    }
    emit({
        "metric": f"audit_fanout[{tag}]",
        "value": round(audit_s, 3),
        "unit": "audit_latency_s",
        "extra": extra,
    })
    # The hit rate gets its own row so the perf gate ratchets BOTH
    # directions: latency cannot creep up, the shared-prefix path cannot
    # silently degrade into per-child re-prefill.
    emit({
        "metric": f"audit_fanout_prefix_hit[{tag}]",
        "value": round(float(s2["prefix_hit_rate"]), 4),
        "unit": "prefix_hit_rate",
        "extra": {"avoided_children": s2["avoided_children"],
                  "resources": resources},
    })
    log(f"bench[audit-fanout]: {resources} resources over {n_replicas} "
        f"replicas in {audit_s:.2f}s (goodput {goodput:.1f} children/s); "
        f"recall={recall:.2f} prefix_hit={s2['prefix_hit_rate']:.2f} "
        f"avoided={s2['avoided_children']}/{resources} "
        f"byte_identical={byte_identical} failed={failed} "
        f"post-warmup compiles {post_compiles:.0f}; interactive p50 TTFT "
        f"{p50_ttft:.0f} ms over {len(ttft_ms)} probes")
    log_perf_table()
    for s in stacks:
        s.close()
    exit_if_slo_breach(slo_verdicts())


def run_fleet_chaos(eng, cfg, model, batch, steps, prompt_len, platform,
                    n_chips, quantize, init_s, warmup_s) -> None:
    """The fleet-chaos A/B stage (serving/faults + router failover): two
    in-process engine replicas behind the FleetRouter, the concurrent-
    sessions streaming workload run TWICE — seeded faults OFF (reference
    run), then ON (mid-SSE disconnects + connect-phase failures from the
    deterministic injector). The failure-containment claim measured:
    the chaos phase finishes with ZERO failed requests (failovers resume
    every broken stream on the surviving replica, byte-identically under
    greedy decode); what containment costs is the p99 TTFT delta."""
    import threading
    from dataclasses import replace as dc_replace

    from opsagent_tpu.serving import faults
    from opsagent_tpu.serving.api import ServingStack
    from opsagent_tpu.serving.engine import Engine
    from opsagent_tpu.serving.fleet.router import FleetRouter

    n_replicas = int(os.environ.get("OPSAGENT_BENCH_REPLICAS", "2"))
    gen_tokens = max(16, steps // 8)
    rounds = 2
    engines = [eng]
    for _ in range(1, n_replicas):
        e = Engine(dc_replace(cfg, seed=cfg.seed))
        e.warmup("sessions")
        engines.append(e)
    stacks = [ServingStack(e) for e in engines]
    # Default spec: kill stream pulls and a connect at fixed hit counts —
    # same spec, same workload, same flight-event sequence every run.
    spec = os.environ.get(
        "OPSAGENT_BENCH_CHAOS_SPEC",
        "fleet.stream_disconnect@7;fleet.stream_disconnect@29;"
        "fleet.stream_disconnect@63",
    )

    def drive(router, seed_base: int) -> dict:
        texts: dict[int, list[str]] = {}
        ttfts: list[float] = []
        errors: list[str] = []
        lock = threading.Lock()

        def session(sid: int) -> None:
            rng = np.random.default_rng(seed_base + sid)
            words = [
                f"w{rng.integers(0, 9999)}" for _ in range(prompt_len // 2)
            ]
            messages = [
                {"role": "system", "content": "chaos bench"},
                {"role": "user", "content": " ".join(words)},
            ]
            for r in range(rounds):
                t0 = time.perf_counter()
                try:
                    gen = router.complete_stream({
                        "messages": messages,
                        "max_tokens": gen_tokens,
                        "temperature": 0.0,
                        "stream": True,
                    })
                    first = next(gen)
                    if "error" in first:
                        raise RuntimeError(first["error"]["message"])
                    ttft = time.perf_counter() - t0
                    parts: list[str] = []
                    for ch in gen:
                        if "error" in ch:
                            raise RuntimeError(ch["error"]["message"])
                        delta = ch["choices"][0]["delta"]
                        if delta.get("content"):
                            parts.append(delta["content"])
                except Exception as e:  # noqa: BLE001
                    with lock:
                        errors.append(f"session {sid} round {r + 1}: {e}")
                    return
                reply = "".join(parts)
                messages.append({"role": "assistant", "content": reply})
                messages.append({"role": "user", "content": f"go {r}"})
                with lock:
                    texts.setdefault(sid, []).append(reply)
                    ttfts.append(ttft)

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=session, args=(i,))
            for i in range(batch)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return {
            "texts": texts, "ttfts": ttfts, "errors": errors,
            "wall": time.perf_counter() - t0,
            "produced": sum(len(t) for ts in texts.values() for t in ts),
        }

    def counter(snap: dict, name: str) -> float:
        return sum(v for k, v in snap.items() if k.startswith(name))

    phases: dict[str, dict] = {}
    for tag, chaotic in (("off", False), ("chaos", True)):
        router = FleetRouter()
        for i, stack in enumerate(stacks):
            router.add_local(stack, f"chaos-r{i}")
        if chaotic:
            faults.configure(spec)
        else:
            faults.reset()
        before = metrics_snapshot()
        phases[tag] = drive(router, seed_base=21000)  # SAME seeds per phase
        faults.reset()
        after = metrics_snapshot()
        r = phases[tag]
        r["p99_ttft_ms"] = (
            float(np.percentile(r["ttfts"], 99) * 1e3) if r["ttfts"]
            else 0.0
        )
        for fam, key in (
            ("opsagent_fleet_failovers_total", "failovers"),
            ("opsagent_fleet_retries_total", "retries"),
            ("opsagent_fleet_shed_total", "shed"),
            ("opsagent_fault_injections_total", "injected"),
        ):
            r[key] = int(counter(after, fam) - counter(before, fam))
        log(f"bench[fleet-chaos/{tag}]: {batch} sessions x {rounds} "
            f"rounds, {r['produced']} replies in {r['wall']:.2f}s; "
            f"p99 TTFT {r['p99_ttft_ms']:.0f} ms; injected={r['injected']} "
            f"failovers={r['failovers']} retries={r['retries']} "
            f"shed={r['shed']} errors={len(r['errors'])}")
    off, chaos = phases["off"], phases["chaos"]
    identical = off["texts"] == chaos["texts"]
    snap = metrics_snapshot()
    qtag = f",{quantize}" if quantize else ""
    emit({
        "metric": (
            f"fleet_chaos[{model}{qtag},N={batch},R={n_replicas},"
            f"{platform}]"
        ),
        "value": len(chaos["errors"]),
        "unit": "failed_requests",
        "vs_baseline": None,
        "extra": {
            "replicas": n_replicas,
            "sessions": batch,
            "rounds": rounds,
            "spec": spec,
            "failed_requests": len(chaos["errors"]),
            "off_failed_requests": len(off["errors"]),
            "injected": chaos["injected"],
            "failovers": chaos["failovers"],
            "retries": chaos["retries"],
            "shed": chaos["shed"],
            "p99_ttft_ms": round(chaos["p99_ttft_ms"], 1),
            "off_p99_ttft_ms": round(off["p99_ttft_ms"], 1),
            "outputs_identical": identical,
            "init_s": round(init_s, 1),
            "warmup_s": round(warmup_s, 1),
            "chips": n_chips,
            "platform": platform,
            "metrics": snap,
            "attribution": attribution_snapshot(),
            "slo": slo_verdicts(),
        },
    })
    log_perf_table()
    for s in stacks:
        s.close()
    exit_if_slo_breach(slo_verdicts())


def run_fleet_journey(eng, cfg, model, batch, steps, prompt_len, platform,
                      n_chips, quantize, init_s, warmup_s) -> None:
    """The fleet-journey observability stage (ISSUE 16): two in-process
    replicas behind the FleetRouter. Two parts. (1) Obs-overhead A/B:
    the concurrent streamed sessions workload with journeys ON then OFF
    (no ID stamping, no participants map) — the reported delta is what
    cross-replica tracing costs on the request plane. (2) Stitched-
    timeline smoke: one request forced through a mid-SSE failover plus a
    pagestore peer fault-in must come back from the router as ONE
    stitched timeline with segment lanes from BOTH replicas, failover +
    fault_in windows, >= 95% coverage, and monotonic non-overlapping
    segments after skew correction — with byte-identical greedy text."""
    import threading
    from dataclasses import replace as dc_replace

    from opsagent_tpu.serving import faults
    from opsagent_tpu.serving.api import ServingStack
    from opsagent_tpu.serving.engine import Engine
    from opsagent_tpu.serving.fleet.router import FleetRouter

    gen_tokens = max(16, steps // 8)
    e2 = Engine(dc_replace(cfg, seed=cfg.seed))
    e2.warmup("sessions")
    stacks = [ServingStack(eng), ServingStack(e2)]

    def drive(router, seed_base: int) -> dict:
        chunks_total = [0]
        errors: list[str] = []
        lock = threading.Lock()

        def session(sid: int) -> None:
            rng = np.random.default_rng(seed_base + sid)
            words = [
                f"w{rng.integers(0, 9999)}" for _ in range(prompt_len // 2)
            ]
            n = 0
            try:
                for ch in router.complete_stream({
                    "messages": [
                        {"role": "system", "content": "journey bench"},
                        {"role": "user", "content": " ".join(words)},
                    ],
                    "max_tokens": gen_tokens, "temperature": 0.0,
                    "stream": True,
                }):
                    if "error" in ch:
                        raise RuntimeError(ch["error"]["message"])
                    if ch["choices"][0]["delta"].get("content"):
                        n += 1
            except Exception as e:  # noqa: BLE001
                with lock:
                    errors.append(f"session {sid}: {e}")
                return
            with lock:
                chunks_total[0] += n

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=session, args=(i,))
            for i in range(batch)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        return {
            "wall": wall, "errors": errors,
            "tok_s": chunks_total[0] / wall if wall > 0 else 0.0,
        }

    # (1) Obs-overhead A/B — distinct prompt seeds per phase so both
    # phases prefill cold (neither inherits the other's prefix cache).
    # A discarded warmup pass absorbs first-drive lazy-init costs
    # (thread spin-up, tokenizer caches) that would otherwise be billed
    # entirely to whichever phase runs first.
    warm_router = FleetRouter()
    for i, stack in enumerate(stacks):
        warm_router.add_local(stack, f"jr{i}")
    drive(warm_router, seed_base=30000)
    phases: dict[str, dict] = {}
    for tag, journeys, seed_base in (
        ("on", True, 31000), ("off", False, 32000),
    ):
        router = FleetRouter(journeys=journeys)
        for i, stack in enumerate(stacks):
            router.add_local(stack, f"jr{i}")
        phases[tag] = drive(router, seed_base=seed_base)
        r = phases[tag]
        log(f"bench[fleet-journey/{tag}]: {batch} streamed sessions in "
            f"{r['wall']:.2f}s ({r['tok_s']:.1f} chunk/s) "
            f"errors={len(r['errors'])}")
    on, off = phases["on"], phases["off"]
    overhead_pct = (
        (off["tok_s"] - on["tok_s"]) / off["tok_s"] * 100.0
        if off["tok_s"] > 0 else 0.0
    )

    # (2) Stitched-timeline smoke: failover + peer fault-in in ONE
    # journey, stitched from both replicas through the router.
    router = FleetRouter()   # journeys + pagestore directory on
    for i, stack in enumerate(stacks):
        router.add_local(stack, f"jr{i}")
    # Each turn must SEAL full KV pages (page_size is 64 at bench
    # geometry) or the directory has nothing for jr1 to fault in — size
    # both user turns at a few pages' worth of tokens, and generate
    # across multiple decode blocks so the injected disconnect lands
    # mid-flight. The failover push (migrate_chain) ships the chain
    # ahead of the resume; transfer.truncate@1 drops its first record
    # in transit, so the resuming replica's admission must repair the
    # hole through the page directory — a true peer fault-in on the
    # SAME journey as the failover.
    nfill = max(24, cfg.page_size // 2)
    filler = " ".join(f"ctx{i}" for i in range(nfill))
    filler2 = " ".join(f"doc{i}" for i in range(nfill))
    gen2 = max(32, cfg.decode_block * 2)
    messages = [
        {"role": "system", "content": "journey smoke"},
        {"role": "user", "content": f"first turn here {filler}"},
    ]
    r1 = router.complete(
        {"messages": messages, "max_tokens": 8, "temperature": 0},
        force_replica="jr0",
    )
    turn2 = list(messages) + [
        {"role": "assistant",
         "content": r1["choices"][0]["message"]["content"] or ""},
        {"role": "user", "content": f"second turn now {filler2}"},
    ]
    faults.configure("fleet.stream_disconnect@5;transfer.truncate@1")
    chunks = list(router.complete_stream({
        "messages": turn2, "max_tokens": gen2, "temperature": 0,
        "stream": True,
    }))
    faults.reset()
    text = "".join(
        c["choices"][0]["delta"].get("content") or "" for c in chunks
    )
    # Reference is a fault-free STREAM (forced jr0), computed AFTER the
    # faulted run so it cannot pre-park the turn-2 chain on jr0: the
    # seam comparison is stream-vs-stream — the non-stream body can
    # legitimately differ in how a trailing incomplete UTF-8 sequence
    # renders at EOS.
    want = "".join(
        c["choices"][0]["delta"].get("content") or ""
        for c in router.complete_stream(
            {"messages": turn2, "max_tokens": gen2, "temperature": 0,
             "stream": True},
            force_replica="jr0",
        )
    )
    jid = chunks[0].get("id", "")
    tl = router.timeline(jid) or {}
    seg_lanes = {s["replica"] for s in tl.get("segments", [])}
    win_kinds = {w["kind"] for w in tl.get("windows", [])}
    monotonic = all(
        cur["start_ms"] >= prev["end_ms"] - 1e-6
        for prev, cur in zip(tl.get("segments", []),
                             tl.get("segments", [])[1:])
    )
    smoke_ok = (
        text == want
        and tl.get("fleet") is True
        and len(seg_lanes) >= 2
        and "failover" in win_kinds
        and "fault_in" in win_kinds
        and tl.get("coverage", 0.0) >= 0.95
        and monotonic
    )
    log(f"bench[fleet-journey/smoke]: shape={tl.get('shape')} "
        f"lanes={sorted(seg_lanes)} windows={sorted(win_kinds)} "
        f"coverage={tl.get('coverage', 0.0):.3f} monotonic={monotonic} "
        f"identical={text == want} ok={smoke_ok}")
    if not smoke_ok:
        log(f"bench[fleet-journey/smoke]: FAILED timeline={tl}")

    snap = metrics_snapshot()
    qtag = f",{quantize}" if quantize else ""
    emit({
        "metric": f"fleet_journey[{model}{qtag},N={batch},{platform}]",
        "value": round(overhead_pct, 2),
        "unit": "overhead_pct",
        "vs_baseline": None,
        "extra": {
            "sessions": batch,
            "journeys_on_tok_s": round(on["tok_s"], 2),
            "journeys_off_tok_s": round(off["tok_s"], 2),
            "on_errors": len(on["errors"]),
            "off_errors": len(off["errors"]),
            "smoke_ok": smoke_ok,
            "smoke_shape": tl.get("shape"),
            "smoke_replica_lanes": sorted(seg_lanes),
            "smoke_windows": sorted(win_kinds),
            "smoke_coverage": tl.get("coverage", 0.0),
            "smoke_monotonic": monotonic,
            "smoke_identical": text == want,
            "init_s": round(init_s, 1),
            "warmup_s": round(warmup_s, 1),
            "chips": n_chips,
            "platform": platform,
            "metrics": snap,
            "attribution": attribution_snapshot(),
            "slo": slo_verdicts(),
        },
    })
    log_perf_table()
    for s in stacks:
        s.close()
    if not smoke_ok:
        raise SystemExit("bench: fleet-journey stitched-timeline smoke "
                         "failed (see log above)")
    exit_if_slo_breach(slo_verdicts())


def _verify_history_tiers() -> dict:
    """Walk a synthetic 90-minute clock through TelemetryHistory (no
    sleeping, no engine): prove the 1 s / 10 s / 60 s downsample tiers,
    exact counter-delta conservation across rollups (rates stay true at
    every tier), and — in a second tiny-budget pass — that the ring's
    byte bound actually evicts. Returns the verdict dict folded into the
    stage's extras; ``ok`` gates the stage exit code."""
    from opsagent_tpu.obs.history import TIER_SPECS, TelemetryHistory

    total = [0.0]
    gauge_val = [0.0]
    step_inc = 7.0
    n_sweeps = 90 * 60
    t0 = 1_700_000_000.0

    def walk(h) -> float:
        total[0] = 0.0
        for i in range(n_sweeps):
            total[0] += step_inc
            gauge_val[0] = float(i % 32)
            h.sample(now=t0 + i)
        return t0 + n_sweeps - 1

    # Pass 1: generous budget — no eviction, so conservation is exact.
    h = TelemetryHistory(max_bytes=8 * 1024 * 1024, interval_s=1.0)
    h.register("tokens", "counter", lambda: total[0])
    h.register("occupancy", "gauge", lambda: gauge_val[0])
    now = walk(h)
    st = h.stats()
    per_tier = st["points_per_tier"]
    # Tier shape: the fine tier only spans its horizon; the coarse tiers
    # hold the rest (2 series share each tier count).
    fine_ok = per_tier[0] <= 2 * (TIER_SPECS[0][1] + TIER_SPECS[1][0])
    spread_ok = per_tier[1] > 0 and per_tier[2] > 0
    q = h.query(series=["tokens"], since=n_sweeps + 60.0, now=now)
    pts = q["series"]["tokens"]["points"]
    # First sweep has no interval to delta over: n_sweeps - 1 deltas.
    want_total = step_inc * (n_sweeps - 1)
    conserved = abs(sum(p[1] for p in pts) - want_total) < 1e-6
    # Re-bucketed to 60 s, interior buckets must carry exactly 60 deltas.
    q60 = h.query(
        series=["tokens"], since=n_sweeps + 60.0, step=60.0, now=now
    )
    mid = q60["series"]["tokens"]["points"][2:-2]
    step60_ok = bool(mid) and all(
        abs(p[1] - 60 * step_inc) < 1e-6 for p in mid
    )
    rate = h.rate("tokens", window_s=3600.0, now=now)
    rate_ok = rate is not None and abs(rate - step_inc) < 0.05
    # Pass 2: a budget far below the walk's footprint must evict — and
    # the resident estimate must stay under it.
    h2 = TelemetryHistory(max_bytes=16 * 1024, interval_s=1.0)
    h2.register("tokens", "counter", lambda: total[0])
    h2.register("occupancy", "gauge", lambda: gauge_val[0])
    walk(h2)
    st2 = h2.stats()
    bound_ok = st2["evicted"] > 0 and st2["bytes"] <= st2["max_bytes"]
    return {
        "ok": all(
            (fine_ok, spread_ok, conserved, step60_ok, rate_ok, bound_ok)
        ),
        "fine_tier_bounded": fine_ok,
        "coarse_tiers_populated": spread_ok,
        "deltas_conserved": conserved,
        "step60_exact": step60_ok,
        "rate_1h": None if rate is None else round(rate, 4),
        "rate_ok": rate_ok,
        "byte_bound_ok": bound_ok,
        "bounded_bytes": st2["bytes"],
        "bounded_evicted": st2["evicted"],
        "points_per_tier": per_tier,
    }


def run_obs_history(eng, model, batch, steps, prompt_len, platform,
                    n_chips, quantize, init_s, warmup_s) -> None:
    """The telemetry-history overhead stage (ISSUE 18): the concurrent
    streamed sessions workload with the background history sampler ON
    (at 10x the production 1 Hz rate, so the bound is conservative) then
    OFF, same prompt seeds — byte-identical outputs are the correctness
    half, and a shared warmup drive pre-populates the prefix cache so
    neither phase rides a cache advantage. Overhead must be <= 2 % tok/s.
    The synthetic-clock tier walk (_verify_history_tiers) rides along as
    the downsampling/byte-bound proof."""
    from opsagent_tpu import obs
    from opsagent_tpu.serving.api import ServingStack

    tiers = _verify_history_tiers()
    log(f"bench[obs-history/tiers]: ok={tiers['ok']} "
        f"rate_1h={tiers['rate_1h']} "
        f"bounded_bytes={tiers['bounded_bytes']} "
        f"evicted={tiers['bounded_evicted']}")

    gen_tokens = max(16, steps // 8)
    rounds = 3
    seed = 41000
    h = obs.history.get_history()
    sampler_interval_s = 0.1
    stack = ServingStack(eng)
    phases: dict[str, dict] = {}
    try:
        # Discarded warmup drive, SAME seeds as the measured phases: it
        # absorbs lazy-init costs AND leaves the prefix cache warm for
        # both phases equally (temperature 0 makes the grown histories
        # identical), so the A/B delta isolates the sampler.
        _drive_sessions_streaming(
            stack, batch, rounds, gen_tokens, prompt_len, seed
        )
        for tag in ("on", "off"):
            if tag == "on":
                h.interval_s = sampler_interval_s
                h.start()
            get_perf_stats().reset()
            try:
                phases[tag] = _drive_sessions_streaming(
                    stack, batch, rounds, gen_tokens, prompt_len, seed
                )
            finally:
                if tag == "on":
                    h.stop()
                    h.interval_s = float(
                        os.environ.get("OPSAGENT_HISTORY_INTERVAL_S", "")
                        or 1.0
                    )
            r = phases[tag]
            r["tok_s_chip"] = (
                r["produced"] / max(1e-9, r["wall"]) / n_chips
            )
            log(f"bench[obs-history/{tag}]: {batch} sessions x {rounds} "
                f"rounds, {r['produced']} tokens in {r['wall']:.2f}s -> "
                f"{r['tok_s_chip']:.0f} tok/s/chip; "
                f"errors={len(r['errors'])}")
    finally:
        stack.close()
    hist_stats = h.stats()
    on, off = phases["on"], phases["off"]
    overhead_pct = (
        (off["tok_s_chip"] - on["tok_s_chip"]) / off["tok_s_chip"] * 100.0
        if off["tok_s_chip"] > 0 else 0.0
    )
    identical = (
        on["texts"] == off["texts"]
        and not on["errors"] and not off["errors"]
    )
    live_bound_ok = hist_stats["bytes"] <= hist_stats["max_bytes"]
    ok = (
        tiers["ok"] and identical and live_bound_ok
        and overhead_pct <= 2.0
    )
    qtag = f",{quantize}" if quantize else ""
    emit({
        "metric": f"obs_history[{model}{qtag},N={batch},{platform}]",
        "value": round(overhead_pct, 2),
        "unit": "overhead_pct",
        "vs_baseline": None,
        "extra": {
            "sessions": batch,
            "rounds": rounds,
            "sampler_on_tok_s_chip": round(on["tok_s_chip"], 1),
            "sampler_off_tok_s_chip": round(off["tok_s_chip"], 1),
            "sampler_interval_s": sampler_interval_s,
            "sampler_samples": hist_stats["samples"],
            "history_series": hist_stats["series"],
            "history_bytes": hist_stats["bytes"],
            "history_max_bytes": hist_stats["max_bytes"],
            "live_byte_bound_ok": live_bound_ok,
            "outputs_identical": identical,
            "tiers": tiers,
            "errors": len(on["errors"]) + len(off["errors"]),
            "init_s": round(init_s, 1),
            "warmup_s": round(warmup_s, 1),
            "chips": n_chips,
            "platform": platform,
            **eng.impl_info(),
            "paged_backend": eng.kernels.attn,
            "metrics": metrics_snapshot(),
            "attribution": attribution_snapshot(),
            "slo": slo_verdicts(),
        },
    })
    log_perf_table()
    if not ok:
        raise SystemExit(
            f"bench: obs-history smoke failed (tiers_ok={tiers['ok']} "
            f"identical={identical} live_bound={live_bound_ok} "
            f"overhead={overhead_pct:.2f}% > 2%)"
        )
    exit_if_slo_breach(slo_verdicts())


def run_agent_turns(eng, model, batch, prompt_len, platform, n_chips,
                    quantize, init_s, warmup_s, turns: int,
                    gen_tokens: int) -> None:
    """The literal north-star shape (BASELINE: "p50 TTFT per tool-call
    turn"): ``batch`` concurrent ReAct agent sessions, each running
    several tool-call turns in the reference's wire format — the
    assistant emits a Thought/Action, the tool observation comes back as
    a USER message (reference simple.go observation-as-user-message),
    and every turn re-sends the WHOLE grown history (the O(n^2) resend
    at reference pkg/assistants/simple.go:497-515). The prefix cache is
    the mechanism under test: turn N's prompt extends turn N-1's
    prompt+reply, so all but the newest messages of each re-prefill
    should be page-aligned trie hits. Reports client-observed streaming
    TTFT — p50 over tool-call turns (turn >= 2, the north-star number)
    with turn 1 (cold prefill) separate — plus the measured prefix-hit
    rate over the whole window."""
    import threading

    from opsagent_tpu.serving.api import ServingStack

    stack = ServingStack(eng)
    results: list[dict] = []   # one entry per completed turn
    errors: list[str] = []
    lock = threading.Lock()
    tok = eng.tokenizer
    # Snapshot through stack.engine (the scheduler's CURRENT engine), not
    # the local ``eng``: a mid-bench slice-restart rebuild swaps in a
    # fresh allocator, and diffing the dead engine's frozen counter would
    # silently zero the reported hit rate (ADVICE r05).
    hit0 = stack.engine.alloc.hit_tokens
    pre0 = get_perf_stats().get_stats().get("engine.prefill_tokens", {})
    prefill0 = pre0.get("count", 0) * pre0.get("avg", 0.0)

    def session(sid: int) -> None:
        # Distinct per-session prompts (own seed) so cross-session prefix
        # hits cannot inflate the hit rate; only a session's OWN history
        # should hit the trie.
        rng = np.random.default_rng(2000 + sid)

        def words(n: int) -> str:
            return " ".join(f"w{rng.integers(0, 9999)}" for _ in range(n))

        messages = [
            {"role": "system",
             "content": "You are a Kubernetes ops agent. " + words(16)},
            {"role": "user",
             "content": "diagnose pods: " + words(max(8, prompt_len // 4))},
        ]
        for turn in range(turns):
            body = {
                "messages": messages,
                "max_tokens": gen_tokens,
                "temperature": 0.0,
                "stream": True,
            }
            t0 = time.perf_counter()
            try:
                gen = stack.chat_completion_stream(body)
                # The first yielded chunk (role delta) is gated on the
                # engine's first real token, so time-to-first-yield IS the
                # client-observed TTFT — but ONLY for a successful turn: a
                # failed request also yields its error payload promptly,
                # and recording that as TTFT would count an errored turn
                # as a fast success (ADVICE r05).
                first = next(gen)
                if "error" in first:
                    raise RuntimeError(first["error"]["message"])
                ttft = time.perf_counter() - t0
                parts: list[str] = []
                for ch in gen:
                    if "error" in ch:
                        raise RuntimeError(ch["error"]["message"])
                    delta = ch["choices"][0]["delta"]
                    if delta.get("content"):
                        parts.append(delta["content"])
                wall = time.perf_counter() - t0
            except Exception as e:  # noqa: BLE001
                with lock:
                    errors.append(f"turn {turn + 1}: {e}")
                return
            text = "".join(parts)
            messages.append({"role": "assistant", "content": text})
            # Tool observation as a user message (the reference wire
            # format), distinct per session+turn like a real kubectl read.
            messages.append({
                "role": "user",
                "content": "Observation:\nNAME READY STATUS\n" + words(48),
            })
            with lock:
                results.append({
                    "turn": turn + 1,  # 1-based: turn 1 = cold prefill
                    "ttft": ttft,
                    "wall": wall,
                    "tokens": len(tok.encode(text)),
                })

    t0 = time.perf_counter()
    threads = [
        threading.Thread(target=session, args=(i,)) for i in range(batch)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0

    tool_turns = [r["ttft"] for r in results if r["turn"] >= 2]
    first_turns = [r["ttft"] for r in results if r["turn"] == 1]
    p50_tool_ms = float(np.median(tool_turns) * 1e3) if tool_turns else 0.0
    p99_tool_ms = (
        float(np.percentile(tool_turns, 99) * 1e3) if tool_turns else 0.0
    )
    p50_first_ms = float(np.median(first_turns) * 1e3) if first_turns else 0.0
    produced = sum(r["tokens"] for r in results)
    # Prefix-hit accounting over the timed window: the allocator counts
    # trie-borrowed tokens; engine.prefill_tokens counts what was actually
    # prefilled (the misses). hits / (hits + misses) = the hit rate the
    # agent loop achieved.
    hits = stack.engine.alloc.hit_tokens - hit0
    pre1 = get_perf_stats().get_stats().get("engine.prefill_tokens", {})
    prefilled = pre1.get("count", 0) * pre1.get("avg", 0.0) - prefill0
    hit_rate = hits / max(1.0, hits + prefilled)

    log(f"bench[agent]: {batch} sessions x {turns} turns, "
        f"{len(results)} turns done in {wall:.1f}s; "
        f"tool-call-turn p50 TTFT {p50_tool_ms:.0f} ms "
        f"(turn-1 {p50_first_ms:.0f} ms); prefix hit rate {hit_rate:.2f}; "
        f"errors={len(errors)}")
    qtag = f",{quantize}" if quantize else ""
    emit({
        "metric": f"agent_turn_ttft[{model}{qtag},N={batch},{platform}]",
        "value": round(p50_tool_ms, 1),
        "unit": "ms",
        "vs_baseline": None,
        "extra": {
            "sessions": batch,
            "turns": turns,
            "turns_completed": len(results),
            "turn1_p50_ttft_ms": round(p50_first_ms, 1),
            "p99_ttft_ms": round(p99_tool_ms, 1),
            "prefix_hit_rate": round(hit_rate, 3),
            "completion_tokens": produced,
            "agg_tok_s_chip": round(produced / wall / n_chips, 1),
            "errors": len(errors),
            "init_s": round(init_s, 1),
            "warmup_s": round(warmup_s, 1),
            "chips": n_chips,
            "platform": platform,
            **eng.impl_info(),
            "paged_backend": eng.kernels.attn,
            "metrics": metrics_snapshot(),
            "attribution": attribution_snapshot(),
            "slo": slo_verdicts(),
        },
    })
    if errors:
        log(f"bench[agent]: first error: {errors[0]}")
    log_perf_table()
    stack.close()
    exit_if_slo_breach(slo_verdicts())


def run_agent_conveyor(platform, n_chips) -> None:
    """The conveyor tool-overlap A/B stage: can the agent loop hide tool
    execution behind the decode of the constrained stream's tail?

    Random weights cannot drive this (an untrained model never closes
    the JSON fields the launch gate watches), so the stage first trains
    the tiny BPE agent IN-PROCESS to memorization on the
    count-namespaces episode (seconds on CPU: loss < 0.01 typically by
    step ~50), serves the checkpoint, and runs the scripted episode
    ``episodes`` times with conveyor launches ON then OFF against the
    same engine. The replayed kubectl is wrapped with a fixed artificial
    delay (identical in both phases) so the tool has a real execution
    window for the conveyor to overlap with the post-action decode
    (observation/final_answer fields). Decision numbers per phase: p50
    episode wall (one tool-call turn + one final-answer turn — the unit
    "ms/turn" is per scripted tool turn), overlap seconds banked, early
    launch count, byte-identical transcripts across phases (the launch
    is a prefix bet; correctness means it never changes WHAT the agent
    says), and zero post-warmup compiles in both phases."""
    import shutil
    import tempfile

    import jax.numpy as jnp

    scripts_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"
    )
    sys.path.insert(0, scripts_dir)
    try:
        from train_tiny_agent import (
            INSTRUCTION,
            SYS_PROMPT,
            train_checkpoint,
        )
    finally:
        sys.path.remove(scripts_dir)

    from opsagent_tpu import obs
    from opsagent_tpu import tools as tools_pkg
    from opsagent_tpu.agent.react import assistant_with_config
    from opsagent_tpu.serving import api as serving_api
    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.tools.replay import (
        NAMESPACES_SCRIPT,
        install_replay_kubectl,
    )

    episodes = int(os.environ.get("OPSAGENT_BENCH_AGENT_EPISODES", "6"))
    train_steps = int(os.environ.get("OPSAGENT_BENCH_TRAIN_STEPS", "600"))
    tool_delay_s = (
        float(os.environ.get("OPSAGENT_BENCH_TOOL_DELAY_MS", "150")) / 1e3
    )
    work = tempfile.mkdtemp(prefix="opsagent-bench-conveyor-")

    # -- train to memorization (the same recipe scripts/train_tiny_agent
    # uses; the BPE tokenizer keeps prompts compact and exercises the
    # HFTokenizer path real checkpoints use) ------------------------------
    ckpt, tok_path, cfg, loss, train_s = train_checkpoint(
        work, steps=train_steps
    )
    log(f"bench[agent-conveyor]: trained to loss {loss:.4f} "
        f"in {train_s:.1f}s")

    # -- serve the checkpoint; pace the replayed kubectl so the tool has
    # an execution window the conveyor can hide --------------------------
    install_replay_kubectl(NAMESPACES_SCRIPT, os.path.join(work, "bin"))
    real_kubectl = tools_pkg.get_tools()["kubectl"]

    def paced_kubectl(arg: str) -> str:
        time.sleep(tool_delay_s)
        return real_kubectl(arg)

    tools_pkg.copilot_tools["kubectl"] = paced_kubectl

    t0 = time.perf_counter()
    eng = Engine(
        EngineConfig(
            model="tiny-test",
            checkpoint=ckpt,
            tokenizer=tok_path,
            dtype=jnp.float32,
            num_pages=512,
            page_size=16,
            max_pages_per_seq=64,
            max_batch_size=2,
            prefill_buckets=(128, 512, 1024),
        ),
        model_cfg=cfg,
    )
    init_s = time.perf_counter() - t0
    # "sessions" warmup pre-specializes the ToolPrompt FSM tables and the
    # forced-token fast-forward program: both phases must decode
    # compile-free.
    warmup_s = eng.warmup("sessions")
    log(f"bench[agent-conveyor]: engine init {init_s:.1f}s "
        f"warmup {warmup_s:.1f}s")

    messages0 = [
        {"role": "system", "content": SYS_PROMPT},
        {"role": "user",
         "content": f"Here are the instructions: {INSTRUCTION}"},
    ]
    conveyor_prev = os.environ.get("OPSAGENT_CONVEYOR")
    phases: dict[str, dict] = {}
    try:
        for tag, on in (("on", True), ("off", False)):
            os.environ["OPSAGENT_CONVEYOR"] = "1" if on else "0"
            get_perf_stats().reset()
            overlap0 = obs.TOOL_OVERLAP_SECONDS.value()
            early0 = obs.TOOL_EARLY_LAUNCHES.value(tool="kubectl")
            compiles0 = obs.POST_WARMUP_COMPILES.value()
            stack = serving_api.ServingStack(eng)
            serving_api.install_stack("bench-conveyor", stack)
            walls: list[float] = []
            transcripts: list[str] = []
            errors: list[str] = []
            try:
                for _ in range(episodes):
                    te = time.perf_counter()
                    try:
                        _answer, history = assistant_with_config(
                            "tpu://bench-conveyor",
                            [dict(m) for m in messages0],
                            256, False, False, 4, "", "",
                        )
                    except Exception as e:  # noqa: BLE001
                        errors.append(str(e))
                        continue
                    walls.append(time.perf_counter() - te)
                    transcripts.append(json.dumps(
                        [(m["role"], m["content"]) for m in history]
                    ))
            finally:
                serving_api.uninstall_stack("bench-conveyor")
                stack.close()
            r = {
                "p50_ms": (
                    float(np.median(walls) * 1e3) if walls else 0.0
                ),
                "overlap_s": obs.TOOL_OVERLAP_SECONDS.value() - overlap0,
                "early_launches": int(
                    obs.TOOL_EARLY_LAUNCHES.value(tool="kubectl") - early0
                ),
                "post_warmup_compiles": int(
                    obs.POST_WARMUP_COMPILES.value() - compiles0
                ),
                "walls": walls,
                "transcripts": transcripts,
                "errors": errors,
            }
            phases[tag] = r
            log(f"bench[agent-conveyor/{tag}]: {len(walls)}/{episodes} "
                f"episodes, p50 {r['p50_ms']:.0f} ms/turn; "
                f"{r['early_launches']} early launches, "
                f"{r['overlap_s'] * 1e3:.0f} ms overlapped; "
                f"post-warmup compiles {r['post_warmup_compiles']}; "
                f"errors={len(errors)}")
    finally:
        if conveyor_prev is None:
            os.environ.pop("OPSAGENT_CONVEYOR", None)
        else:
            os.environ["OPSAGENT_CONVEYOR"] = conveyor_prev
        tools_pkg.copilot_tools["kubectl"] = real_kubectl

    a, b = phases["on"], phases["off"]
    identical = (
        a["transcripts"] == b["transcripts"]
        and not a["errors"] and not b["errors"]
    )
    emit({
        "metric": f"agent_conveyor[tiny-agent,{platform}]",
        "value": round(a["p50_ms"], 1),
        "unit": "ms/turn",
        "vs_baseline": None,
        "extra": {
            "episodes": episodes,
            "train_loss": round(loss, 4),
            "train_s": round(train_s, 1),
            "tool_delay_ms": round(tool_delay_s * 1e3, 1),
            "overlap_ms_per_turn": round(
                a["overlap_s"] / max(1, len(a["walls"])) * 1e3, 1
            ),
            "overlap_s_total": round(a["overlap_s"], 4),
            "early_launches": a["early_launches"],
            "off_p50_ms": round(b["p50_ms"], 1),
            "off_overlap_s_total": round(b["overlap_s"], 4),
            "off_early_launches": b["early_launches"],
            "p50_delta_ms": round(b["p50_ms"] - a["p50_ms"], 1),
            "outputs_identical": identical,
            "post_warmup_compiles_on": a["post_warmup_compiles"],
            "post_warmup_compiles_off": b["post_warmup_compiles"],
            "errors": len(a["errors"]) + len(b["errors"]),
            "init_s": round(init_s, 1),
            "warmup_s": round(warmup_s, 1),
            "chips": n_chips,
            "platform": platform,
            "metrics": metrics_snapshot(),
            "attribution": attribution_snapshot(),
            "slo": slo_verdicts(),
        },
    })
    if a["errors"] or b["errors"]:
        log(f"bench[agent-conveyor]: first error: "
            f"{(a['errors'] or b['errors'])[0]}")
    log_perf_table()
    shutil.rmtree(work, ignore_errors=True)
    exit_if_slo_breach(slo_verdicts())


if __name__ == "__main__":
    main()
