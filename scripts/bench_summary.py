#!/usr/bin/env python
"""Summarize bench JSONL results — terminal decision matrix AND the
generator for PERF.md's measurement table.

Usage:
    python scripts/bench_summary.py tpu_results_*/bench.jsonl
        # terminal summary (tok/s/chip + TTFT side by side, decision
        # answers: fastest 8B variant, kernel verdict, TTFT vs target)
    python scripts/bench_summary.py --perf-md [BENCH_r*_local.jsonl ...]
        # print the markdown measurement table generated from the
        # committed raw lines
    python scripts/bench_summary.py --update-perf [--check]
        # rewrite (or, with --check, verify) the generated block in
        # PERF.md between the BEGIN/END markers

PERF.md's "Measured so far" table is GENERATED from the committed
``BENCH_r*_local.jsonl`` raw lines — the same numbers, one source, so the
copies in PERF.md / BENCH artifacts / the jsonl cannot drift (VERDICT
weak #7: three hand-maintained copies of r04's numbers). A fast-lane test
runs ``--update-perf --check`` so CI catches a hand-edit or a stale
table.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GEN_BEGIN = "<!-- BEGIN bench_summary (generated; do not edit by hand) -->"
GEN_END = "<!-- END bench_summary -->"


def _round_of(path: str) -> str:
    m = re.search(r"BENCH_(r\d+)", os.path.basename(path))
    return m.group(1) if m else os.path.basename(path)


def load_rows(paths: list[str]) -> list[dict]:
    rows = []
    for path in paths:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    d = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if "metric" in d:
                    d["_round"] = _round_of(path)
                    rows.append(d)
    return rows


def _dedupe(rows: list[dict]) -> list[dict]:
    """The orchestrator's combined headline repeats a stage's metric/value
    with extra cross-stage keys folded in; keep ONE row per
    (round, metric, value) — the first, which is the stage's own line."""
    seen: set[tuple] = set()
    out = []
    for d in rows:
        key = (d["_round"], d["metric"], d.get("value"))
        if key in seen:
            continue
        seen.add(key)
        out.append(d)
    return out


def perf_md_table(paths: list[str]) -> str:
    rows = _dedupe(load_rows(paths))
    lines = [
        "| Round | Metric | Value | Unit | p50 TTFT (ms) | Backend "
        "| vs target |",
        "|---|---|---|---|---|---|---|",
    ]
    for d in rows:
        e = d.get("extra", {})
        vb = d.get("vs_baseline")
        p50 = e.get("p50_ttft_ms")
        lines.append(
            f"| {d['_round']} "
            f"| `{d['metric']}` "
            f"| {d['value']} "
            f"| {d.get('unit', '')} "
            f"| {p50 if p50 is not None else '—'} "
            f"| {e.get('paged_backend') or '—'} "
            f"| {f'{vb}×' if vb is not None else '—'} |"
        )
    return "\n".join(lines)


def update_perf_md(
    perf_path: str, paths: list[str], check: bool = False
) -> int:
    with open(perf_path) as f:
        text = f.read()
    if GEN_BEGIN not in text or GEN_END not in text:
        print(
            f"{perf_path} has no {GEN_BEGIN!r} / {GEN_END!r} markers",
            file=sys.stderr,
        )
        return 1
    head, rest = text.split(GEN_BEGIN, 1)
    _, tail = rest.split(GEN_END, 1)
    new = head + GEN_BEGIN + "\n" + perf_md_table(paths) + "\n" + GEN_END + tail
    if new == text:
        return 0
    if check:
        print(
            f"{perf_path} generated table is out of sync with the "
            f"BENCH_r*_local.jsonl raw lines; run "
            f"`python scripts/bench_summary.py --update-perf`",
            file=sys.stderr,
        )
        return 1
    with open(perf_path, "w") as f:
        f.write(new)
    print(f"updated {perf_path}")
    return 0


def terminal_summary(paths: list[str]) -> int:
    rows = load_rows(paths)
    if not rows:
        print("no result lines found", file=sys.stderr)
        return 1

    print(f"{'metric':58s} {'tok/s/chip':>10s} {'p50(ms)':>8s} "
          f"{'backend':>10s} {'vs_base':>8s}")
    for d in rows:
        e = d.get("extra", {})
        vb = d.get("vs_baseline")
        print(f"{d['metric'][:58]:58s} {d['value']:>10.1f} "
              f"{e.get('p50_ttft_ms', 0) or 0:>8.0f} "
              f"{e.get('paged_backend', '') or '-':>10s} "
              f"{vb if vb is not None else '-':>8}")

    # Decision answers (best-effort from metric names).
    tpu = [d for d in rows if ",tpu]" in d["metric"]]
    # tok/s rows only: agent_turn_ttft rows carry ms values that would
    # otherwise compete with throughputs in the max() below.
    eight_b = [d for d in tpu if "bench-8b" in d["metric"]
               and "concurrent" not in d["metric"]
               and d.get("unit") == "tok/s/chip"]
    if eight_b:
        best = max(eight_b, key=lambda d: d["value"])
        print(f"\nfastest 8B variant: {best['metric']} "
              f"at {best['value']:.0f} tok/s/chip "
              f"({'>=' if best['value'] >= 2000 else '<'} 2000 target)")
    # Ragged sweep (the MIXED hot path): best cell per RESOLVED attention
    # reader, with the byte-identical verdict.
    sweep = [d for d in rows
             if d["metric"].startswith("mixed_ragged_throughput")
             and "best_cell" not in d.get("extra", {})]
    if sweep:
        by_impl: dict[str, float] = {}
        for d in sweep:
            impl = d.get("extra", {}).get("attn_impl", "?")
            by_impl[impl] = max(by_impl.get(impl, 0.0), d["value"])
        ident = all(
            d.get("extra", {}).get("outputs_identical") for d in sweep
        )
        print("mixed-ragged sweep: "
              + "; ".join(f"{k} best {v:.0f}"
                          for k, v in sorted(by_impl.items()))
              + f" tok/s/chip over {len(sweep)} cells; outputs "
              f"identical: {ident}")
    sess = [d for d in tpu if "concurrent_sessions" in d["metric"]]
    if sess:
        # Best (lowest-TTFT) row, not positionally last: multiple files
        # may contribute sessions rows in arbitrary order.
        best_sess = min(
            sess, key=lambda d: d.get("extra", {}).get("p50_ttft_ms", 1e12)
        )
        p50 = best_sess.get("extra", {}).get("p50_ttft_ms", 0)
        print(f"sessions p50 TTFT (best of {len(sess)}): {p50:.0f} ms "
              f"({'<' if p50 < 500 else '>='} 500 ms target)")
    sasync = [d for d in tpu if d["metric"].startswith("sessions_async")]
    if sasync:
        d = sasync[-1]
        e = d.get("extra", {})
        print(
            f"async A/B: device wait share "
            f"{e.get('device_wait_share', 0)} (depth=2) vs "
            f"{e.get('sync_device_wait_share', 0)} (depth=1) at "
            f"{e.get('host_work_ms', 0)} ms host work a tick; "
            f"tok/s/chip {d['value']} vs "
            f"{e.get('sync_tok_s_chip', 0)}; outputs identical: "
            f"{e.get('outputs_identical')}"
        )
    sffwd = [d for d in tpu if d["metric"].startswith("sessions_ffwd")]
    if sffwd:
        d = sffwd[-1]
        e = d.get("extra", {})
        frac = e.get("forced_fraction", 0) or 0
        print(
            f"ffwd A/B: tok/s/chip {d['value']} (on) vs "
            f"{e.get('off_tok_s_chip', 0)} (off); forced fraction "
            f"{frac:.1%} ({e.get('skipped_dispatches', 0)} dispatches "
            f"skipped); outputs identical: {e.get('outputs_identical')}"
        )
    soff = [d for d in tpu if d["metric"].startswith("sessions_offload")]
    if soff:
        e = soff[-1].get("extra", {})
        print(
            f"offload A/B: admission-wait p50 "
            f"{e.get('admission_wait_p50_ms', 0)} ms (on) vs "
            f"{e.get('off_admission_wait_p50_ms', 0)} ms (off); "
            f"re-prefill avoided {e.get('reprefill_avoided_tokens', 0)} tok"
        )
    fleet = [d for d in tpu if d["metric"].startswith("fleet_affinity")]
    if fleet:
        e = fleet[-1].get("extra", {})
        print(
            f"fleet A/B ({e.get('replicas', '?')} replicas): p50 TTFT "
            f"{e.get('p50_ttft_ms', 0)} ms (affinity) vs "
            f"{e.get('off_p50_ttft_ms', 0)} ms (round-robin); "
            f"re-prefill avoided {e.get('reprefill_avoided_tokens', 0)} "
            f"vs {e.get('off_reprefill_avoided_tokens', 0)} tok"
        )
    fgkv = [d for d in tpu if d["metric"].startswith("fleet_global_kv")]
    if fgkv:
        e = fgkv[-1].get("extra", {})
        print(
            f"fleet-global-KV A/B ({e.get('replicas', '?')} replicas "
            f"+{e.get('standby', 0)} standby): "
            f"{e.get('remote_hit_pages', 0)} pages faulted in peer-to-peer "
            f"(vs {e.get('off_remote_hit_pages', 0)} off); re-prefill "
            f"avoided {e.get('reprefill_avoided_tokens', 0)} vs "
            f"{e.get('off_reprefill_avoided_tokens', 0)} tok; moved-turn "
            f"p50 {e.get('p50_moved_ms', 0)} ms (on) vs "
            f"{e.get('off_p50_moved_ms', 0)} ms (off); outputs identical: "
            f"{e.get('outputs_identical')}, standby: "
            f"{e.get('standby_identical')}"
        )
    chaos = [d for d in tpu if d["metric"].startswith("fleet_chaos")]
    if chaos:
        e = chaos[-1].get("extra", {})
        print(
            f"chaos A/B ({e.get('replicas', '?')} replicas, spec "
            f"{e.get('spec', '?')!r}): {e.get('failed_requests', '?')} "
            f"failed requests under {e.get('injected', 0)} injected "
            f"faults ({e.get('failovers', 0)} failovers, "
            f"{e.get('retries', 0)} retries, {e.get('shed', 0)} shed); "
            f"p99 TTFT {e.get('p99_ttft_ms', 0)} ms (chaos) vs "
            f"{e.get('off_p99_ttft_ms', 0)} ms (clean); outputs "
            f"identical: {e.get('outputs_identical')}"
        )
    coldst = [d for d in tpu
              if d["metric"].startswith("cold_start_request_ready")]
    if coldst:
        d = coldst[-1]
        e = d.get("extra", {})
        print(
            f"cold-start A/B: request-ready "
            f"{e.get('restore_request_ready_s', d['value'])} s (snapshot "
            f"restore) vs {e.get('fresh_request_ready_s', 0)} s (fresh "
            f"init) = {e.get('speedup_ratio', 0)}x; outputs identical: "
            f"{e.get('outputs_identical')}; post-warmup compiles on "
            f"restore: {e.get('post_warmup_compiles')}"
        )
    agent = [d for d in tpu if d["metric"].startswith("agent_turn_ttft")]
    if agent:
        best_a = min(agent, key=lambda d: d["value"])
        hr = best_a.get("extra", {}).get("prefix_hit_rate")
        print(f"agent tool-call-turn p50 TTFT (best of {len(agent)}): "
              f"{best_a['value']:.0f} ms "
              f"({'<' if best_a['value'] < 500 else '>='} 500 ms target); "
              f"prefix hit rate {hr}")
    # Conveyor A/B runs on CPU too — match across all rows, not just tpu.
    convey = [d for d in rows if d["metric"].startswith("agent_conveyor")]
    if convey:
        d = convey[-1]
        e = d.get("extra", {})
        print(
            f"conveyor A/B: agent turn p50 {d['value']:.0f} ms (on) vs "
            f"{e.get('off_p50_ms', 0):.0f} ms (off); "
            f"{e.get('overlap_ms_per_turn', 0)} ms/turn tool time hidden "
            f"behind decode ({e.get('early_launches', 0)} early "
            f"launches); transcripts identical: "
            f"{e.get('outputs_identical')}"
        )
    # SLO verdicts folded into the lines (bench.py extra.slo), newest last.
    slo_rows = [d for d in rows if d.get("extra", {}).get("slo")]
    if slo_rows:
        verdicts = slo_rows[-1]["extra"]["slo"].get("slos", [])
        breached = [v["name"] for v in verdicts if v.get("pass") is False]
        print(f"declared SLOs: {len(verdicts)} evaluated, "
              f"{'breached: ' + ', '.join(breached) if breached else 'all passing'}")
    return 0


def _default_local_jsonls() -> list[str]:
    return sorted(glob.glob(os.path.join(REPO, "BENCH_r*_local.jsonl")))


def main(argv: list[str]) -> int:
    check = "--check" in argv
    argv = [a for a in argv if a != "--check"]
    if argv and argv[0] == "--perf-md":
        paths = argv[1:] or _default_local_jsonls()
        print(perf_md_table(paths))
        return 0
    if argv and argv[0] == "--update-perf":
        paths = argv[1:] or _default_local_jsonls()
        return update_perf_md(
            os.path.join(REPO, "PERF.md"), paths, check=check
        )
    return terminal_summary(argv or ["tpu_results_r04/bench.jsonl"])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
