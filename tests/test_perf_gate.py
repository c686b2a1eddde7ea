"""Perf-regression gate (fast lane): scripts/perf_gate.py /
``opsagent perf-check`` against fixture jsonl pairs — pass,
noise-tolerated wobble, and an injected 20 % regression -> exit 1 —
plus the bench orchestrator's --perf-gate plumbing."""

import json
import subprocess
import sys

import pytest

from opsagent_tpu.cli.perfcheck import (
    DEFAULT_TOLERANCE,
    compare,
    format_report,
    load_rows,
    run_perf_check,
)


def _row(metric, value, unit="tok/s/chip", ttft=None):
    d = {"metric": metric, "value": value, "unit": unit, "extra": {}}
    if ttft is not None:
        d["extra"]["p50_ttft_ms"] = ttft
    return d


BASELINE = [
    _row("paged_decode_throughput[bench-8b,int8,B=32,tpu]", 1899.0,
         ttft=95.3),
    _row("paged_decode_throughput[bench-1b,B=32,tpu]", 4775.2, ttft=117.4),
    # Duplicate metric with a deliberately-slow probe row: best-per-side
    # matching must pick 4775.2, not let 4308.5 mask a regression.
    _row("paged_decode_throughput[bench-1b,B=32,tpu]", 4308.5, ttft=103.4),
    _row("concurrent_sessions[bench-1b,N=32,tpu]", 210.1, ttft=7463.3),
    _row("agent_turn_ttft[bench-1b,tpu]", 180.0, unit="ms"),
]


def _jsonl(path, rows):
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    return str(path)


def test_identical_runs_pass(tmp_path):
    base = _jsonl(tmp_path / "base.jsonl", BASELINE)
    cur = _jsonl(tmp_path / "cur.jsonl", BASELINE)
    assert run_perf_check(cur, baseline=base) == 0


def test_noise_wobble_within_tolerance_passes(tmp_path):
    wobbled = [
        _row("paged_decode_throughput[bench-8b,int8,B=32,tpu]",
             1899.0 * 0.95, ttft=95.3 * 1.1),   # -5 % tok/s, +10 % ttft
        _row("paged_decode_throughput[bench-1b,B=32,tpu]", 4775.2 * 1.04,
             ttft=117.4),
        _row("concurrent_sessions[bench-1b,N=32,tpu]", 210.1 * 0.93,
             ttft=7463.3),
        _row("agent_turn_ttft[bench-1b,tpu]", 180.0 * 1.08, unit="ms"),
    ]
    base = _jsonl(tmp_path / "base.jsonl", BASELINE)
    cur = _jsonl(tmp_path / "cur.jsonl", wobbled)
    assert run_perf_check(cur, baseline=base) == 0


def test_injected_20pct_regression_fails(tmp_path, capsys):
    regressed = [
        _row("paged_decode_throughput[bench-8b,int8,B=32,tpu]",
             1899.0 * 0.80, ttft=95.3),          # the injected regression
        _row("paged_decode_throughput[bench-1b,B=32,tpu]", 4775.2,
             ttft=117.4),
    ]
    base = _jsonl(tmp_path / "base.jsonl", BASELINE)
    cur = _jsonl(tmp_path / "cur.jsonl", regressed)
    assert run_perf_check(cur, baseline=base) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out
    assert "bench-8b" in out


def test_lower_better_units_regress_upward(tmp_path):
    base = _jsonl(tmp_path / "base.jsonl", BASELINE)
    cur = _jsonl(tmp_path / "cur.jsonl", [
        _row("agent_turn_ttft[bench-1b,tpu]", 180.0 * 1.5, unit="ms"),
    ])
    assert run_perf_check(cur, baseline=base) == 1
    # ...and a big IMPROVEMENT (latency halved) passes.
    cur2 = _jsonl(tmp_path / "cur2.jsonl", [
        _row("agent_turn_ttft[bench-1b,tpu]", 90.0, unit="ms"),
    ])
    assert run_perf_check(cur2, baseline=base) == 0


def test_ttft_subseries_gates(tmp_path):
    """extra.p50_ttft_ms rides as its own lower-better comparison with
    the looser TTFT tolerance (25 %)."""
    base = _jsonl(tmp_path / "base.jsonl", BASELINE)
    cur = _jsonl(tmp_path / "cur.jsonl", [
        _row("concurrent_sessions[bench-1b,N=32,tpu]", 210.1,
             ttft=7463.3 * 1.5),  # TTFT +50 % at unchanged tok/s
    ])
    assert run_perf_check(cur, baseline=base) == 1


def test_disjoint_metrics_exit_2(tmp_path):
    base = _jsonl(tmp_path / "base.jsonl", BASELINE)
    cur = _jsonl(tmp_path / "cur.jsonl", [
        _row("paged_decode_throughput[tiny-test,B=4,cpu]", 33.0),
    ])
    assert run_perf_check(cur, baseline=base) == 2
    assert run_perf_check(str(tmp_path / "missing.jsonl"), baseline=base) == 2


def test_per_metric_tolerance_overrides(tmp_path):
    base = _jsonl(tmp_path / "base.jsonl", BASELINE)
    cur = _jsonl(tmp_path / "cur.jsonl", [
        _row("concurrent_sessions[bench-1b,N=32,tpu]", 210.1 * 0.7,
             ttft=7463.3),        # -30 %
    ])
    tol = tmp_path / "tol.json"
    tol.write_text(json.dumps({"concurrent_sessions": 0.4}))
    assert run_perf_check(cur, baseline=base,
                          tolerances_file=str(tol)) == 0
    assert run_perf_check(cur, baseline=base) == 1  # default 10 %: fails


def test_best_row_per_side_defeats_probe_masking():
    """The slow cold-restart probe row must not fake a regression for
    the 1B metric, and a current run whose best row regressed must fail
    even if it ALSO contains a slow extra row."""
    cur = [
        _row("paged_decode_throughput[bench-1b,B=32,tpu]", 4700.0),
        _row("paged_decode_throughput[bench-1b,B=32,tpu]", 1000.0),
    ]
    rep = compare(cur, BASELINE)
    v = next(
        x for x in rep["verdicts"]
        if x["metric"] == "paged_decode_throughput[bench-1b,B=32,tpu]"
    )
    assert v["status"] == "ok"
    assert v["baseline"] == 4775.2  # best, not the probe's 4308.5
    assert rep["pass"] is True


def test_compare_report_format():
    rep = compare(BASELINE, BASELINE)
    text = format_report(rep)
    assert "PASS" in text
    assert f"{DEFAULT_TOLERANCE:.0%}" in text


def test_scripts_perf_gate_shim(tmp_path):
    """The CI entrypoint: scripts/perf_gate.py runs jax-free and returns
    the gate's exit code."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base = _jsonl(tmp_path / "base.jsonl", BASELINE)
    cur = _jsonl(tmp_path / "cur.jsonl", [
        _row("paged_decode_throughput[bench-8b,int8,B=32,tpu]",
             1899.0 * 0.8),
    ])
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "perf_gate.py"),
         cur, "--baseline", base],
        capture_output=True, text=True, env=env, timeout=60,
    )
    assert r.returncode == 1, r.stdout + r.stderr
    assert "REGRESSION" in r.stdout


def test_committed_baseline_is_loadable():
    """The default baseline (newest BENCH_r*_local.jsonl) parses into
    comparable series — the gate's real-world input."""
    from opsagent_tpu.cli.perfcheck import default_baseline

    path = default_baseline()
    assert path is not None
    rows = load_rows(path)
    assert rows, "committed baseline has no result lines"
    rep = compare(rows, rows)
    assert rep["pass"] is True and rep["compared"] > 0


def test_bench_perf_gate_flag(monkeypatch):
    """bench.py --perf-gate mirrors --slo-strict: env/argv toggles, exit
    4 on a confirmed regression, no exit when nothing is comparable."""
    import bench

    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.delenv("OPSAGENT_BENCH_PERF_GATE", raising=False)
    assert not bench.perf_gate_enabled()
    monkeypatch.setenv("OPSAGENT_BENCH_PERF_GATE", "1")
    assert bench.perf_gate_enabled()
    monkeypatch.delenv("OPSAGENT_BENCH_PERF_GATE")
    monkeypatch.setattr(sys, "argv", ["bench.py", "--perf-gate"])
    assert bench.perf_gate_enabled()

    # Gate off: never exits, even on a catastrophic row.
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    bench.exit_if_perf_regression([
        _row("paged_decode_throughput[bench-1b,B=32,tpu]", 1.0)
    ])

    # Gate on + regression vs the committed baseline: exit 4.
    monkeypatch.setenv("OPSAGENT_BENCH_PERF_GATE", "1")
    with pytest.raises(SystemExit) as e:
        bench.exit_if_perf_regression([
            _row("paged_decode_throughput[bench-1b,B=32,tpu]", 1.0), None,
        ])
    assert e.value.code == 4

    # Gate on + disjoint metrics (cpu fallback run): passes with a note.
    bench.exit_if_perf_regression([
        _row("paged_decode_throughput[tiny-test,B=4,cpu]", 33.0)
    ])


def test_ragged_sweep_rows_gate_higher_better(tmp_path):
    """The ragged-sweep cells report tok/s/chip and must gate in the
    higher-is-better direction: a dropped kernel-cell value fails, a
    faster one passes, and a brand-new cell (no baseline twin) never
    gates."""
    from opsagent_tpu.cli.perfcheck import _higher_better

    assert _higher_better("tok/s/chip") is True
    cell = ("mixed_ragged_throughput[bench-8b,int8,kv-int8,xla,"
            "B=32,tpu]")
    base = _jsonl(tmp_path / "base.jsonl", BASELINE + [_row(cell, 2400.0)])
    slower = _jsonl(tmp_path / "cur.jsonl", [_row(cell, 2400.0 * 0.7)])
    assert run_perf_check(slower, baseline=base) == 1
    faster = _jsonl(tmp_path / "cur2.jsonl", [_row(cell, 2400.0 * 1.3)])
    assert run_perf_check(faster, baseline=base) == 0
    fresh = _jsonl(tmp_path / "cur3.jsonl", [
        _row("mixed_ragged_throughput[bench-8b,int4,kv-int8,xla,"
             "B=32,tpu]", 2800.0),
        _row("paged_decode_throughput[bench-8b,int8,B=32,tpu]", 1899.0),
    ])
    assert run_perf_check(fresh, baseline=base) == 0


def test_weight_stream_sweep_rows_gate_higher_better(tmp_path):
    """The weight-stream prefetch cells (`,ws-pallas-dma,` in the
    metric) are tok/s/chip rows like every other sweep cell: a prefetch
    kernel that loses its overlap must fail the gate, a faster one must
    pass, and the first run of a brand-new ws cell (no baseline twin)
    must not gate at all."""
    ws_cell = ("mixed_ragged_throughput[bench-8b,int8,kv-bf16,xla,"
               "ws-pallas-dma,B=32,tpu]")
    base = _jsonl(
        tmp_path / "base.jsonl", BASELINE + [_row(ws_cell, 3000.0)]
    )
    slower = _jsonl(tmp_path / "cur.jsonl", [_row(ws_cell, 3000.0 * 0.7)])
    assert run_perf_check(slower, baseline=base) == 1
    faster = _jsonl(tmp_path / "cur2.jsonl", [_row(ws_cell, 3000.0 * 1.2)])
    assert run_perf_check(faster, baseline=base) == 0
    # int4 ws cell has no baseline twin yet: reported, never gated.
    fresh = _jsonl(tmp_path / "cur3.jsonl", [
        _row("mixed_ragged_throughput[bench-8b,int4,kv-bf16,xla,"
             "ws-pallas-dma,B=32,tpu]", 3300.0),
        _row(ws_cell, 3000.0),
    ])
    assert run_perf_check(fresh, baseline=base) == 0


def test_audit_fanout_units_gate_in_the_right_direction(tmp_path):
    """audit_latency_s is lower-better (a slower audit regresses);
    prefix_hit_rate is higher-better (children re-prefilling the shared
    prefix regresses)."""
    base = _jsonl(tmp_path / "base.jsonl", [
        _row("audit_fanout[tiny,N=64,R=2,cpu]", 10.0,
             unit="audit_latency_s"),
        _row("audit_fanout_prefix_hit[tiny,N=64,R=2,cpu]", 1.0,
             unit="prefix_hit_rate"),
    ])
    # Latency up 50 % -> regression even though the value "went up".
    cur = _jsonl(tmp_path / "slow.jsonl", [
        _row("audit_fanout[tiny,N=64,R=2,cpu]", 15.0,
             unit="audit_latency_s"),
        _row("audit_fanout_prefix_hit[tiny,N=64,R=2,cpu]", 1.0,
             unit="prefix_hit_rate"),
    ])
    assert run_perf_check(cur, baseline=base) == 1
    # Hit rate collapsing -> regression even though latency held.
    cur2 = _jsonl(tmp_path / "cold.jsonl", [
        _row("audit_fanout[tiny,N=64,R=2,cpu]", 10.0,
             unit="audit_latency_s"),
        _row("audit_fanout_prefix_hit[tiny,N=64,R=2,cpu]", 0.4,
             unit="prefix_hit_rate"),
    ])
    assert run_perf_check(cur2, baseline=base) == 1
    # Both healthy (small wobble) -> pass.
    cur3 = _jsonl(tmp_path / "ok.jsonl", [
        _row("audit_fanout[tiny,N=64,R=2,cpu]", 9.5,
             unit="audit_latency_s"),
        _row("audit_fanout_prefix_hit[tiny,N=64,R=2,cpu]", 0.98,
             unit="prefix_hit_rate"),
    ])
    assert run_perf_check(cur3, baseline=base) == 0
