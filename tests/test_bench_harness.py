"""The bench harness's contract, tested on the CPU.

bench.py measures a chip. Its orchestrator must guarantee: (a) with no
TPU it exits non-zero and prints NO row — there is no CPU fallback; (b) a
wedged device stage is killed at its cap and the run fails the same way;
(c) every row names the device it ran on, and a row earned on the CPU
(a single NAMED configuration — the harness checks below) never carries
the per-chip rate unit.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# What bench.emit() writes in place of "tok/s/chip" off the chip.
CPU_RATE_UNIT = "tok/s (cpu)"


def _rows(stdout: str) -> list[dict]:
    rows = []
    for ln in stdout.splitlines():
        try:
            parsed = json.loads(ln)
        except json.JSONDecodeError:
            continue
        if isinstance(parsed, dict) and "metric" in parsed:
            rows.append(parsed)
    return rows


def _run_bench(env_extra: dict, timeout=420):
    env = dict(os.environ)
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "-u", os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


def test_single_mode_prints_parseable_json():
    out = _run_bench({
        "JAX_PLATFORMS": "cpu",
        "OPSAGENT_BENCH_MODEL": "tiny-test",
        "OPSAGENT_BENCH_BATCH": "2",
        "OPSAGENT_BENCH_STEPS": "8",
    })
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    parsed = json.loads(lines[-1])
    # A named configuration may run on the CPU as a harness check: the
    # row says so, and does not use the device unit.
    assert parsed["unit"] == CPU_RATE_UNIT
    assert "metric" in parsed and parsed["vs_baseline"] is None
    e = parsed["extra"]
    assert e["platform"] == "cpu" and e["device_kind"] == "cpu"
    assert e["device_count"] >= 1 and e["dtype"] == "float32"


def test_orchestrated_without_chip_exits_nonzero_and_prints_no_row():
    """On a cpu-only host `python bench.py` has nothing to measure: the
    device stage refuses to run off a TPU, the orchestrator exits
    non-zero, and nothing on stdout names a metric, a unit or a device
    number — no CPU child runs in its place."""
    out = _run_bench({
        "JAX_PLATFORMS": "cpu",
        "OPSAGENT_BENCH_BUDGET": "300",
    }, timeout=240)
    assert out.returncode != 0, out.stdout[-2000:]
    assert out.stdout.strip() == "", out.stdout[-2000:]
    assert "refusing to run" in out.stderr


def test_wedged_child_killed_and_run_fails(tmp_path):
    """A device stage that hangs forever at start-up must be killed at
    the stage cap, and the run must then FAIL with no row: a hung chip is
    not replaced by a CPU number."""
    # Wedge the device-stage child: a sitecustomize that sleeps forever
    # in a bench child with no explicit model (the orchestrator's env
    # markers are the only reliable discriminator; conftest pins
    # JAX_PLATFORMS=cpu for the whole process tree).
    site = tmp_path / "sitecustomize.py"
    site.write_text(
        "import os, time\n"
        "if (os.environ.get('_OPSAGENT_BENCH_CHILD')\n"
        "        and not os.environ.get('OPSAGENT_BENCH_MODEL')):\n"
        "    time.sleep(3600)\n"
    )
    out = _run_bench({
        "PYTHONPATH": f"{tmp_path}{os.pathsep}{REPO}",
        "OPSAGENT_BENCH_BUDGET": "300",
        "OPSAGENT_BENCH_STAGE1_CAP": "60",
    }, timeout=240)
    assert out.returncode != 0, (out.stdout + out.stderr)[-2000:]
    assert "TIMED OUT" in out.stderr
    assert _rows(out.stdout) == []


def test_tiny_budget_fails_without_a_row():
    """A budget too small for the device stage skips it — and with it
    the run: non-zero exit, no row, no CPU stand-in."""
    out = _run_bench({
        "JAX_PLATFORMS": "cpu",
        "OPSAGENT_BENCH_BUDGET": "50",
    }, timeout=120)
    assert out.returncode != 0, (out.stdout + out.stderr)[-1500:]
    assert "too little" in out.stderr
    assert _rows(out.stdout) == []


def test_vs_baseline_and_units_name_the_chip_only_on_the_chip(capsys):
    """The ratio against the north star is null unless the number is
    (a) measured on tpu AND (b) from a baseline-class (8B) model; and
    emit() stamps every row with the device and, off the chip, replaces
    the per-chip rate unit."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(REPO, "bench.py")
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    assert bench.vs_baseline(5858.9, "tiny-test", "cpu") is None
    assert bench.vs_baseline(187.6, "bench-1b", "tpu") is None  # not 8B-class
    assert bench.vs_baseline(2100.0, "bench-8b", "cpu") is None
    assert bench.vs_baseline(2100.0, "bench-8b", "tpu") == 1.05
    assert bench.vs_baseline(500.0, "llama-3-8b-instruct", "tpu") == 0.25
    # json.dumps renders the None as null, never a number.
    assert json.dumps({"vs_baseline": bench.vs_baseline(1.0, "x", "cpu")}) \
        == '{"vs_baseline": null}'

    bench.emit({"metric": "m", "value": 1.0, "unit": bench.CHIP_RATE_UNIT})
    bench.emit({"metric": "n", "value": 2.0, "unit": "ms"})
    first, second = _rows(capsys.readouterr().out)
    assert first["unit"] == CPU_RATE_UNIT == bench.CPU_RATE_UNIT
    assert second["unit"] == "ms"
    for row in (first, second):
        assert row["extra"]["platform"] == "cpu"
        assert row["extra"]["device_kind"] == "cpu"
        assert row["extra"]["device_count"] >= 1


def test_agent_mode_reports_per_turn_ttft_and_hit_rate():
    """OPSAGENT_BENCH_MODE=agent (the north-star shape: multi-turn ReAct
    sessions, full-history resend, prefix cache on) must complete every
    turn without OutOfPages — the page budget is sized from the final
    turn's history, not the linear-decode guard — and report per-turn
    TTFT plus a nonzero prefix-hit rate."""
    out = _run_bench({
        "JAX_PLATFORMS": "cpu",
        "OPSAGENT_BENCH_MODE": "agent",
        "OPSAGENT_BENCH_MODEL": "tiny-test",
        "OPSAGENT_BENCH_BATCH": "3",
        "OPSAGENT_BENCH_STEPS": "16",
        "OPSAGENT_BENCH_TURNS": "3",
    })
    assert out.returncode == 0, (out.stdout + out.stderr)[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    parsed = json.loads(lines[-1])
    assert parsed["metric"].startswith("agent_turn_ttft[")
    assert parsed["unit"] == "ms"
    assert parsed["vs_baseline"] is None
    e = parsed["extra"]
    assert e["errors"] == 0
    assert e["turns_completed"] == 3 * 3
    assert e["prefix_hit_rate"] > 0  # turn >= 2 prompts must hit the trie
    assert e["turn1_p50_ttft_ms"] > 0


def test_sessions_mixed_mode_reports_both_variants():
    """OPSAGENT_BENCH_MODE=sessions-mixed (the tier-1-safe fast-lane form
    of the on-chip N=32 stage: CPU, tiny model, small N) must run the
    sessions workload with mixed batching ON and OFF against one engine
    and emit BOTH variants in the JSON line, so the
    one-weight-stream-per-tick delta is a first-class artifact."""
    out = _run_bench({
        "JAX_PLATFORMS": "cpu",
        "OPSAGENT_BENCH_MODE": "sessions-mixed",
        "OPSAGENT_BENCH_MODEL": "tiny-test",
        "OPSAGENT_BENCH_BATCH": "3",
        "OPSAGENT_BENCH_STEPS": "16",
    })
    assert out.returncode == 0, (out.stdout + out.stderr)[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    parsed = json.loads(lines[-1])
    assert parsed["metric"].startswith("sessions_mixed[")
    assert parsed["unit"] == CPU_RATE_UNIT
    e = parsed["extra"]
    assert e["errors"] == 0
    # Both phases measured and distinguishable.
    assert e["p50_ttft_ms"] > 0 and e["split_p50_ttft_ms"] > 0
    assert "ttft_delta_ms" in e and "tok_s_chip_delta" in e
    # The mixed phase actually dispatched mixed programs.
    assert e["metrics"]['opsagent_decode_dispatches_total{kind="mixed"}'] > 0


def test_sessions_async_mode_reports_overlap_and_identical_text():
    """OPSAGENT_BENCH_MODE=sessions-async (the tier-1-safe fast-lane form
    of the async-tick A/B stage: CPU, tiny model, small N) must run the
    sessions workload with the one-step-lookahead pipeline (depth=2) and
    with synchronous ticks (depth=1) against one engine and emit BOTH
    phases in ONE JSON line. The on-phase must prove the overlap actually
    happened (overlapped commits > 0) and — same prompt seeds — the two
    phases' output text must be byte-identical: the lookahead changes
    WHEN host work runs, never WHAT gets generated."""
    out = _run_bench({
        "JAX_PLATFORMS": "cpu",
        "OPSAGENT_BENCH_MODE": "sessions-async",
        "OPSAGENT_BENCH_MODEL": "tiny-test",
        "OPSAGENT_BENCH_BATCH": "3",
        "OPSAGENT_BENCH_STEPS": "16",
    })
    assert out.returncode == 0, (out.stdout + out.stderr)[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    parsed = json.loads(lines[-1])
    assert parsed["metric"].startswith("sessions_async[")
    assert parsed["unit"] == CPU_RATE_UNIT
    e = parsed["extra"]
    assert e["errors"] == 0
    # Both phases measured and distinguishable.
    assert e["p50_ttft_ms"] > 0 and e["sync_p50_ttft_ms"] > 0
    # Tick phases (obs.phase): both phases did host work every tick and
    # spent part of the loop blocked on the device.
    assert e["host_work_ms"] > 0 and e["sync_host_work_ms"] > 0
    assert 0 < e["device_wait_share"] < 1
    assert 0 < e["sync_device_wait_share"] < 1
    # The on-phase actually overlapped host work with device compute...
    assert e["overlapped_commits"] > 0
    assert e["async_commits"] > 0
    # ...without changing a single output byte.
    assert e["outputs_identical"] is True


def test_sessions_offload_mode_reports_ab_decision_numbers():
    """OPSAGENT_BENCH_MODE=sessions-offload (the tier-1-safe fast-lane
    form of the hierarchical-KV A/B stage: CPU, tiny model, small N) must
    run the sessions workload with the offload tier OFF then ON against
    one engine and emit BOTH phases' admission-wait p50 and re-prefill-
    avoided token counts in ONE JSON line — the decision numbers the
    host-RAM tier exists for."""
    out = _run_bench({
        "JAX_PLATFORMS": "cpu",
        "OPSAGENT_BENCH_MODE": "sessions-offload",
        "OPSAGENT_BENCH_MODEL": "tiny-test",
        "OPSAGENT_BENCH_BATCH": "3",
        "OPSAGENT_BENCH_STEPS": "16",
    })
    assert out.returncode == 0, (out.stdout + out.stderr)[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    parsed = json.loads(lines[-1])
    assert parsed["metric"].startswith("sessions_offload[")
    assert parsed["unit"] == CPU_RATE_UNIT
    e = parsed["extra"]
    assert e["errors"] == 0
    # Both phases measured and distinguishable.
    assert e["p50_ttft_ms"] > 0 and e["off_p50_ttft_ms"] > 0
    assert "admission_wait_p50_ms" in e and "off_admission_wait_p50_ms" in e
    assert "admission_wait_delta_ms" in e
    # The ON phase actually restored instead of re-prefilling (inter-round
    # parking guarantees host-pool hits on every round >= 2 comeback); the
    # OFF phase, with the tier detached, cannot have.
    assert e["reprefill_avoided_tokens"] > 0
    assert e["off_reprefill_avoided_tokens"] == 0
    assert e["restored_tokens"] > 0


def test_sessions_ffwd_mode_reports_ab_numbers():
    """OPSAGENT_BENCH_MODE=sessions-ffwd (the tier-1-safe fast-lane form
    of the grammar fast-forward A/B stage: CPU, tiny model, small N) must
    run schema-constrained sessions with the forced-token fast-forward ON
    then OFF against one engine and emit BOTH phases in ONE JSON line.
    The on-phase must actually skip forward passes (skipped dispatches
    and forced fraction are exact counts, not chip-dependent) and — same
    greedy seeds — the two phases' output text must be byte-identical:
    the grammar changes WHEN tokens are computed, never WHICH tokens."""
    out = _run_bench({
        "JAX_PLATFORMS": "cpu",
        "OPSAGENT_BENCH_MODE": "sessions-ffwd",
        "OPSAGENT_BENCH_MODEL": "tiny-test",
        "OPSAGENT_BENCH_BATCH": "3",
        "OPSAGENT_BENCH_STEPS": "16",
    })
    assert out.returncode == 0, (out.stdout + out.stderr)[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    parsed = json.loads(lines[-1])
    assert parsed["metric"].startswith("sessions_ffwd[")
    assert parsed["unit"] == CPU_RATE_UNIT
    e = parsed["extra"]
    assert e["errors"] == 0
    # Both phases measured and distinguishable.
    assert e["p50_ttft_ms"] > 0 and e["off_p50_ttft_ms"] > 0
    assert "tok_s_chip_delta" in e
    # The on-phase actually fast-forwarded: whole singleton-mask runs
    # landed without a forward pass; the off-phase cannot have.
    assert e["skipped_dispatches"] > 0
    assert e["ffwd_tokens"] > 0 and e["ffwd_runs"] > 0
    assert 0 < e["forced_fraction"] <= 1
    assert e["off_skipped_dispatches"] == 0
    # ...without changing a single output byte.
    assert e["outputs_identical"] is True


def test_agent_conveyor_mode_reports_ab_numbers():
    """OPSAGENT_BENCH_MODE=agent-conveyor (the CPU-capable conveyor
    tool-overlap A/B stage) must train the tiny agent to memorization,
    run the scripted episode with conveyor launches ON then OFF against
    one warmed engine, and emit both phases in ONE JSON line. The
    on-phase must fire an early launch per tool turn and bank real
    overlap seconds; the off-phase must fire none; transcripts must be
    byte-identical across phases and neither may compile post-warmup."""
    out = _run_bench({
        "JAX_PLATFORMS": "cpu",
        "OPSAGENT_BENCH_MODE": "agent-conveyor",
        "OPSAGENT_BENCH_AGENT_EPISODES": "3",
    }, timeout=540)
    assert out.returncode == 0, (out.stdout + out.stderr)[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    parsed = json.loads(lines[-1])
    assert parsed["metric"].startswith("agent_conveyor[")
    assert parsed["unit"] == "ms/turn"
    assert parsed["value"] > 0
    e = parsed["extra"]
    assert e["errors"] == 0
    assert e["train_loss"] < 0.05
    # The on-phase launched the tool mid-decode on every scripted turn
    # and hid real tool time behind the stream's tail.
    assert e["early_launches"] >= 3
    assert e["overlap_s_total"] > 0
    assert e["overlap_ms_per_turn"] > 0
    # The off-phase is the classic blocking path.
    assert e["off_early_launches"] == 0
    assert e["off_overlap_s_total"] == 0
    assert e["off_p50_ms"] > 0
    # The launch is a prefix bet: it may move WHEN the tool runs, never
    # what the agent says.
    assert e["outputs_identical"] is True
    # Warmup covered both phases (FSM tables + ffwd programs).
    assert e["post_warmup_compiles_on"] == 0
    assert e["post_warmup_compiles_off"] == 0


def test_fleet_affinity_mode_reports_ab_numbers():
    """OPSAGENT_BENCH_MODE=fleet-affinity (the tier-1-safe fast-lane form
    of the fleet A/B stage: CPU, tiny model, 2 in-process replicas behind
    the FleetRouter) must run the sessions workload with prefix-affinity
    + sticky placement and with stateless round-robin placement, and emit
    BOTH phases' p50 TTFT and re-prefill-avoided token counts in ONE
    JSON line — the decision numbers prefix-affinity routing exists for.
    The affinity phase restores every parked comeback on its owning
    replica; the round-robin phase mis-routes some comebacks, so it can
    never avoid more re-prefill than affinity does."""
    out = _run_bench({
        "JAX_PLATFORMS": "cpu",
        "OPSAGENT_BENCH_MODE": "fleet-affinity",
        "OPSAGENT_BENCH_MODEL": "tiny-test",
        "OPSAGENT_BENCH_BATCH": "3",
        "OPSAGENT_BENCH_STEPS": "16",
    })
    assert out.returncode == 0, (out.stdout + out.stderr)[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    parsed = json.loads(lines[-1])
    assert parsed["metric"].startswith("fleet_affinity[")
    assert parsed["unit"] == CPU_RATE_UNIT
    e = parsed["extra"]
    assert e["errors"] == 0
    assert e["replicas"] == 2
    # Both phases measured and distinguishable.
    assert e["p50_ttft_ms"] > 0 and e["off_p50_ttft_ms"] > 0
    assert "ttft_delta_ms" in e
    # The affinity phase actually restored parked sessions on their
    # owning replicas; stateless placement cannot beat it.
    assert e["reprefill_avoided_tokens"] > 0
    assert e["off_reprefill_avoided_tokens"] <= \
        e["reprefill_avoided_tokens"]
    # The router's placement telemetry rode along.
    assert any("pinned" in k for k in e["route_decisions"])
    assert any("round_robin" in k for k in e["route_decisions"])


def test_fleet_global_kv_mode_reports_ab_numbers():
    """OPSAGENT_BENCH_MODE=fleet-global-kv (the tier-1-safe fast-lane
    form of the fleet-global KV A/B stage: CPU, tiny model, 2 replicas
    + 1 standby behind the FleetRouter). The ON phase forces second
    turns onto a NON-owning replica and third turns onto a freshly
    promoted standby: both must restore over the wire (remote_hit_pages
    > 0) with greedy output byte-identical to the never-moved replay.
    The OFF phase (directory disabled) proves the delta: zero remote
    hits, strictly less re-prefill avoided."""
    out = _run_bench({
        "JAX_PLATFORMS": "cpu",
        "OPSAGENT_BENCH_MODE": "fleet-global-kv",
        "OPSAGENT_BENCH_MODEL": "tiny-test",
        "OPSAGENT_BENCH_BATCH": "3",
        "OPSAGENT_BENCH_STEPS": "16",
    })
    assert out.returncode == 0, (out.stdout + out.stderr)[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    parsed = json.loads(lines[-1])
    assert parsed["metric"].startswith("fleet_global_kv[")
    assert parsed["unit"] == CPU_RATE_UNIT
    e = parsed["extra"]
    assert e["errors"] == 0
    assert e["replicas"] == 2 and e["standby"] == 1
    # The ON phase faulted pages in peer-to-peer; OFF could not.
    assert e["remote_hit_pages"] > 0
    assert e["off_remote_hit_pages"] == 0
    assert e["fetch_bytes"] > 0
    # Byte-identical on the non-owner AND on the promoted standby.
    assert e["outputs_identical"] is True
    assert e["standby_identical"] is True
    # The directory did the resolving.
    assert e["directory"]["hits"] > 0


def test_fleet_chaos_mode_zero_failed_requests_under_faults():
    """OPSAGENT_BENCH_MODE=fleet-chaos (the tier-1-safe fast-lane form of
    the chaos A/B stage: CPU, tiny model, 2 in-process replicas, seeded
    mid-SSE disconnects) must run the streaming workload fault-free and
    then under the injector, and emit BOTH phases in ONE JSON line. The
    containment claim: the chaos phase ends with ZERO failed requests
    and at least one recorded failover — every injected disconnect was
    absorbed by the router, and greedy outputs match the clean run
    byte-for-byte."""
    out = _run_bench({
        "JAX_PLATFORMS": "cpu",
        "OPSAGENT_BENCH_MODE": "fleet-chaos",
        "OPSAGENT_BENCH_MODEL": "tiny-test",
        "OPSAGENT_BENCH_BATCH": "3",
        "OPSAGENT_BENCH_STEPS": "16",
    })
    assert out.returncode == 0, (out.stdout + out.stderr)[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    parsed = json.loads(lines[-1])
    assert parsed["metric"].startswith("fleet_chaos[")
    assert parsed["unit"] == "failed_requests"
    assert parsed["value"] == 0
    e = parsed["extra"]
    assert e["replicas"] == 2
    # The injector actually fired, and every fault was contained.
    assert e["injected"] >= 1
    assert e["failovers"] >= 1
    assert e["failed_requests"] == 0
    assert e["off_failed_requests"] == 0
    assert e["outputs_identical"] is True
    # Both phases measured the containment cost.
    assert e["p99_ttft_ms"] > 0 and e["off_p99_ttft_ms"] > 0


def test_ragged_sweep_mode_emits_per_backend_identical_rows():
    """OPSAGENT_BENCH_MODE=ragged-sweep (the mixed-hot-path sweep) on CPU
    must run every KV dtype cell plus the weight-stream pair (the
    prefetch kernel through interpret-mode Pallas), emit one rate row per
    cell with the RESOLVED impls in extra, verify byte-identical greedy
    output against each group's xla weight-stream cell, and end with the
    best-cell summary line."""
    out = _run_bench({
        "JAX_PLATFORMS": "cpu",
        "OPSAGENT_BENCH_MODE": "ragged-sweep",
        "OPSAGENT_BENCH_MODEL": "tiny-test",
        "OPSAGENT_BENCH_BATCH": "2",
        "OPSAGENT_BENCH_STEPS": "8",
        "OPSAGENT_BENCH_PROMPT": "16",
    })
    assert out.returncode == 0, (out.stdout + out.stderr)[-2000:]
    rows = _rows(out.stdout)
    # 2 KV dtypes (weight quant stays off-chip) + the int8 weight-stream
    # pair (xla oracle + pallas-dma prefetch) + summary.
    assert len(rows) == 5, [r["metric"] for r in rows]
    cells = rows[:-1]
    for r in cells:
        assert r["unit"] == CPU_RATE_UNIT
        e = r["extra"]
        assert e["outputs_identical"] is True, r["metric"]
        assert e["post_warmup_compiles"] == 0, r["metric"]
        assert e["interpret"] is True
        # Self-describing: resolved impls + quant modes ride every row;
        # the attention reader is the engine's choice, the gather here.
        assert e["attn_impl"] == "xla" and ",xla," in r["metric"]
        assert "requested_backend" not in e
        assert e["weight_stream"] in ("xla", "pallas-dma")
        assert e["kv_quantize"] in ("none", "int8")
    assert [e["kv_quantize"] for e in (r["extra"] for r in cells)] == [
        "none", "int8", "none", "none",
    ]
    # The weight-stream cells: requesting pallas-dma with int8 weights
    # must RESOLVE to pallas-dma (quantized weights, tp=1 — no gate
    # trips) and still be byte-identical to its group's xla oracle.
    ws_rows = [
        r for r in cells
        if r["extra"]["requested_weight_stream"] == "pallas-dma"
    ]
    assert len(ws_rows) == 1, [r["metric"] for r in ws_rows]
    assert ws_rows[0]["extra"]["weight_stream"] == "pallas-dma"
    assert ws_rows[0]["extra"]["quantize"] == "int8"
    assert ",ws-pallas-dma," in ws_rows[0]["metric"]
    # Summary last: best cell's value with the per-cell map folded in.
    summary = rows[-1]
    assert summary["extra"]["cells"] == 4
    assert summary["value"] == max(r["value"] for r in cells)
    assert len(summary["extra"]["cell_tok_s_chip"]) == 4


def test_audit_fanout_mode_reports_numbers():
    """OPSAGENT_BENCH_MODE=audit-fanout must exit 0 and report the
    fan-out's decision numbers: recall 1.0 against the injected ground
    truth, a prefix-hit rate, and a byte-identical reduce across its two
    audit passes — plus the hit rate as its own higher-better row."""
    out = _run_bench({
        "JAX_PLATFORMS": "cpu",
        "OPSAGENT_BENCH_MODE": "audit-fanout",
        "OPSAGENT_BENCH_MODEL": "tiny-test",
        "OPSAGENT_BENCH_BATCH": "3",
        "OPSAGENT_BENCH_STEPS": "16",
    })
    assert out.returncode == 0, (out.stdout + out.stderr)[-2000:]
    rows = _rows(out.stdout)
    main = [r for r in rows if r["metric"].startswith("audit_fanout[")]
    hit = [
        r for r in rows
        if r["metric"].startswith("audit_fanout_prefix_hit[")
    ]
    assert len(main) == 1 and len(hit) == 1
    r = main[0]
    assert r["unit"] == "audit_latency_s" and r["value"] > 0
    e = r["extra"]
    assert e["recall"] == 1.0
    assert e["byte_identical"] is True
    assert e["failed_children"] == 0
    assert 0.0 <= e["prefix_hit_rate"] <= 1.0
    assert e["avoided_children"] >= 0.9 * e["resources"]
    assert e["interactive_probes"] >= 1 and e["probe_errors"] == 0
    assert hit[0]["unit"] == "prefix_hit_rate"
