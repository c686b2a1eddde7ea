"""The opsagent CLI.

Capability parity with the reference's cmd/kube-copilot/: root command with
persistent flags --model/--max-tokens/--count-tokens/--verbose/
--max-iterations (main.go:28-32) and subcommands server (server.go), execute
(execute.go), analyze (analyze.go), audit (audit.go), diagnose (diagnose.go),
generate (generate.go), version (version.go). Unlike the reference fork —
which registers only ``server`` (main.go:34) and leaves the other commands as
dead code — every subcommand here is wired up. A new ``serve-engine``
subcommand starts the in-tree TPU serving engine.
"""

from __future__ import annotations

import argparse
import os
import sys

from .. import VERSION
from ..utils.config import load_config
from ..utils.globalstore import set_global
from ..utils.logger import get_logger, init_logger
from ..utils.perf import get_perf_stats


def _add_common(parser: argparse.ArgumentParser) -> None:
    parser.add_argument("--model", default="gpt-4", help="model name or tpu://<model>")
    parser.add_argument("--max-tokens", type=int, default=2048)
    parser.add_argument("--count-tokens", action="store_true", default=False)
    parser.add_argument("--verbose", action="store_true", default=False)
    parser.add_argument("--max-iterations", type=int, default=10)
    parser.add_argument("--api-key", default="", help="LLM API key (else env)")
    parser.add_argument("--base-url", default="", help="LLM base URL (else env)")
    parser.add_argument(
        "--metrics", action="store_true", default=False,
        help="print the Prometheus /metrics exposition to stderr after "
             "the run (same text a scrape of a server would return)",
    )


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="opsagent",
        description="Kubernetes AI agent with an in-tree TPU serving engine",
    )
    p.add_argument("--config", default="", help="path to config.yaml")
    sub = p.add_subparsers(dest="command")

    sp = sub.add_parser("server", help="run the REST API server")
    sp.add_argument("--port", type=int, default=None, help="default: config server.port")
    sp.add_argument("--host", default=None, help="default: config server.host")
    sp.add_argument("--jwt-key", default="")
    sp.add_argument("--show-thought", action="store_true", default=False)
    _add_common(sp)

    ex = sub.add_parser("execute", help="execute operations based on prompt instructions")
    ex.add_argument("instructions", nargs="+")
    _add_common(ex)

    an = sub.add_parser("analyze", help="analyze issues for a given resource")
    an.add_argument("--resource", default="pod")
    an.add_argument("--name", required=True)
    an.add_argument("--namespace", default="default")
    _add_common(an)

    au = sub.add_parser("audit", help="audit security issues for a pod")
    au.add_argument("--name", required=True)
    au.add_argument("--namespace", default="default")
    _add_common(au)

    af = sub.add_parser(
        "audit-fanout",
        help="fan one audit out over a synthetic cluster: N batch-class "
             "child sessions sharing one prefix chain through an "
             "in-process fleet, reduced to one deterministic report "
             "(exit 0 all children ok, 1 any finding_unavailable)",
    )
    af.add_argument("--model", default="tiny-test")
    af.add_argument(
        "--resources", type=int, default=64,
        help="synthetic cluster size (= fan-out children)",
    )
    af.add_argument("--seed", type=int, default=0)
    af.add_argument(
        "--issue-fraction", type=float, default=0.25,
        help="fraction of resources given an injected issue",
    )
    af.add_argument(
        "--replicas", type=int, default=2,
        help="in-process decode replicas behind the router",
    )
    af.add_argument(
        "--max-inflight", type=int, default=8,
        help="bounded scatter concurrency (the fan-out admission gate)",
    )
    af.add_argument("--max-tokens", type=int, default=16)
    af.add_argument(
        "--flight-sample", type=int, default=0,
        help=">1: sample admission/dispatch flight kinds 1-in-N during "
             "the wave (flood control)",
    )
    af.add_argument(
        "--json", action="store_true",
        help="print the canonical byte-stable report form",
    )
    af.add_argument(
        "--out", default="", help="also write the canonical report here",
    )

    di = sub.add_parser("diagnose", help="diagnose problems for a pod")
    di.add_argument("--name", required=True)
    di.add_argument("--namespace", default="default")
    _add_common(di)

    ge = sub.add_parser("generate", help="generate manifests and optionally apply")
    ge.add_argument("prompt", nargs="+")
    ge.add_argument("--yes", action="store_true", help="apply without confirmation")
    _add_common(ge)

    sub.add_parser("version", help="print version")

    sc = sub.add_parser(
        "slo-check",
        help="evaluate the declared serving SLOs (bench/CI gate: exit 0 "
             "pass, 1 breach, 2 no data)",
    )
    sc.add_argument(
        "--url", default="",
        help="base URL of a running server; fetches GET /api/slo",
    )
    sc.add_argument(
        "--bench", default="",
        help="BENCH json/jsonl file; reads the extra.slo verdicts "
             "bench.py folded in",
    )
    sc.add_argument(
        "--class", dest="slo_class", default="",
        choices=["", "interactive", "batch", "background"],
        help="gate one SLO class's attainment/burn (from the per-class "
             "report) instead of the global verdicts",
    )

    tp = sub.add_parser(
        "top",
        help="live fleet cockpit: replica table, per-class SLO rows "
             "with history sparklines, anomaly tail (ANSI, no curses)",
    )
    tp.add_argument(
        "--url", default="http://127.0.0.1:8090",
        help="base URL of a fleet router (or a single engine/agent "
             "server — the replica table degrades gracefully)",
    )
    tp.add_argument(
        "--interval", type=float, default=2.0,
        help="seconds between frames",
    )
    tp.add_argument(
        "--frames", type=int, default=0,
        help="render N frames then exit (0 = until interrupted)",
    )
    tp.add_argument(
        "--no-color", action="store_true", default=False,
        help="disable ANSI colors even on a TTY",
    )

    pc = sub.add_parser(
        "perf-check",
        help="compare a fresh bench jsonl against the committed "
             "BENCH_r*_local.jsonl baseline with noise tolerances "
             "(CI gate: exit 0 pass, 1 regression, 2 nothing comparable)",
    )
    pc.add_argument(
        "current",
        help="fresh bench jsonl (result lines), or a fleet router URL "
             "(http://...: live rows from GET /api/fleet/bench)",
    )
    pc.add_argument(
        "--baseline", default="",
        help="baseline jsonl (default: newest committed BENCH_r*_local.jsonl)",
    )
    pc.add_argument(
        "--tolerance", type=float, default=None,
        help="global relative tolerance (default 10%%, TTFT series 25%%)",
    )
    pc.add_argument(
        "--tolerances", default="",
        help="JSON file of {metric substring: tolerance} overrides",
    )

    tl = sub.add_parser(
        "timeline",
        help="render a request's lifecycle timeline as an ASCII Gantt "
             "(queue -> prefill -> decode -> tool-blocked, with the "
             "goodput split)",
    )
    tl.add_argument("request_id", help="request id (chatcmpl-... / req-...)")
    tl.add_argument(
        "--url", default="",
        help="base URL of a running server; fetches "
             "GET /api/timeline/{request_id}",
    )
    tl.add_argument(
        "--token", default="",
        help="bearer token for the agent server's JWT-guarded /api/ tree",
    )
    tl.add_argument(
        "--file", default="",
        help="read the timeline JSON from a file instead (e.g. the "
             "'timeline' line of a flight anomaly dump)",
    )
    tl.add_argument("--width", type=int, default=64, help="gantt bar width")
    tl.add_argument(
        "--json", action="store_true", default=False,
        help="print the raw timeline JSON instead of the gantt",
    )

    fkv = sub.add_parser(
        "fleet-kv",
        help="dump the fleet router's global KV page directory (which "
             "replica owns which prefix chains, tier footprints, "
             "advertisement staleness)",
    )
    fkv.add_argument(
        "--url", default="http://127.0.0.1:8090",
        help="fleet router base URL; fetches GET /api/fleet/directory",
    )
    fkv.add_argument(
        "--limit", type=int, default=256,
        help="max chain rows to fetch (the directory can hold thousands)",
    )
    fkv.add_argument(
        "--json", action="store_true", default=False,
        help="print the raw directory JSON instead of the table",
    )

    ffl = sub.add_parser(
        "fleet-flight",
        help="dump the fleet flight ledger: every replica's flight "
             "ring merged into one replica-tagged, skew-corrected, "
             "time-ordered event stream",
    )
    ffl.add_argument(
        "--url", default="http://127.0.0.1:8090",
        help="fleet router base URL; fetches GET /api/fleet/flight",
    )
    ffl.add_argument(
        "--n", type=int, default=64,
        help="merged event tail length (0 = everything in the rings)",
    )
    ffl.add_argument("--kind", default="", help="filter by event kind")
    ffl.add_argument(
        "--request-id", default="",
        help="filter to one journey's events (implies no tail cap)",
    )
    ffl.add_argument(
        "--json", action="store_true", default=False,
        help="print the raw ledger JSON instead of the table",
    )

    se = sub.add_parser("serve-engine", help="run the TPU serving engine (OpenAI-compatible)")
    se.add_argument("--port", type=int, default=8000)
    se.add_argument("--host", default="0.0.0.0")
    se.add_argument("--model-name", default="tiny-test",
                    help="model preset, or 'auto' to derive the "
                         "architecture from --checkpoint's config.json")
    se.add_argument("--checkpoint", default="", help="safetensors checkpoint dir")
    se.add_argument("--tokenizer", default="", help="HF tokenizer path (else byte tokenizer)")
    se.add_argument("--tp", type=int, default=0, help="tensor-parallel size (0 = all devices)")
    se.add_argument("--sp", type=int, default=1, help="sequence-parallel size for long-context prefill (ragged ring attention)")
    se.add_argument("--ep", type=int, default=1, help="expert-parallel size for MoE models (experts shard over ep)")
    se.add_argument("--max-batch-size", type=int, default=8)
    se.add_argument(
        "--quantize",
        default="",
        choices=("", "int8", "int4"),
        help="weight-only quantization: int8 halves weight HBM traffic "
             "and fits 8B-class models on one v5e chip; int4 (group-wise "
             "scales) halves it again for more decode throughput at some "
             "fidelity cost",
    )
    se.add_argument(
        "--kv-quantize",
        default="",
        choices=("", "int8"),
        help="KV-cache quantization: int8 pages + per-token scales halve "
             "decode-step KV reads (the dominant non-weight HBM term at "
             "serving shapes); not supported for MLA models",
    )
    se.add_argument(
        "--offload",
        action="store_true",
        default=False,
        help="hierarchical KV cache: spill evicted/parked KV pages to a "
             "bounded host-RAM pool (OPSAGENT_KV_HOST_POOL_BYTES, default "
             "1 GiB) and restore them on re-admission instead of "
             "re-prefilling — tool-blocked agent sessions stop pinning "
             "HBM between turns",
    )
    se.add_argument(
        "--async-depth",
        type=int,
        default=2,
        help="mixed-tick dispatch pipeline depth: 2 (default) enqueues "
             "tick t+1 before tick t's tokens are pulled to host "
             "(decode feedback stays device-resident), overlapping "
             "detokenize/stop-scan/streaming with device compute; "
             "1 = synchronous ticks",
    )
    se.add_argument(
        "--platform",
        default="",
        choices=("", "tpu", "cpu"),
        help="force the JAX platform (default: environment's choice)",
    )
    se.add_argument(
        "--profile-dir",
        default="",
        help="capture jax.profiler device traces into this directory",
    )
    se.add_argument(
        "--join-fleet", default="",
        help="fleet router base URL (opsagent serve-router): register "
             "this replica, heartbeat load + prefix digests, accept "
             "routed traffic and KV-page transfers",
    )
    se.add_argument(
        "--advertise", default="",
        help="URL the router should reach this replica at "
             "(default: http://<host>:<port>)",
    )
    se.add_argument(
        "--replica-id", default="",
        help="stable replica identity in the fleet (default: random)",
    )
    se.add_argument(
        "--replica-role", default="decode",
        choices=("decode", "prefill", "standby"),
        help="decode replicas serve sessions end-to-end; prefill "
             "replicas take the router's long cold admissions and hand "
             "their KV to a decode replica over the transfer path; "
             "standby replicas are registered but unroutable until the "
             "router's autoscaler promotes them to decode",
    )
    se.add_argument(
        "--restore-snapshot", default="",
        help="boot from an `opsagent snapshot create` directory instead "
             "of fresh init: weights mmap straight to device in recorded "
             "layout and warmup replays the packaged compile cache — "
             "model/engine flags are taken from the snapshot",
    )

    sr = sub.add_parser(
        "serve-router",
        help="run the fleet router: spreads sessions over N engine "
             "replicas with prefix-affinity + least-loaded placement, "
             "sticky pinning, KV-page session migration, and graceful "
             "drain (serving/fleet)",
    )
    sr.add_argument("--port", type=int, default=8090)
    sr.add_argument("--host", default="0.0.0.0")
    sr.add_argument(
        "--tokenizer", default="",
        help="HF tokenizer path for affinity scoring — MUST match the "
             "replicas' tokenizer (else scores silently zero and "
             "placement degrades to least-loaded); default: the "
             "hermetic byte tokenizer",
    )
    sr.add_argument(
        "--model-name", default="",
        help="model family for chat-template rendering in affinity "
             "scoring (matches the replicas' --model-name)",
    )
    sr.add_argument(
        "--no-affinity", action="store_true", default=False,
        help="disable prefix-affinity scoring (least-loaded only; the "
             "bench fleet-affinity stage's OFF phase)",
    )
    sr.add_argument(
        "--queue-spill", type=int, default=None,
        help="queue depth past which a pinned/affinity replica spills "
             "the route to the rest of the fleet (default: the "
             "replica's registered capacity)",
    )
    sr.add_argument(
        "--prefill-threshold", type=int, default=256,
        help="prompt tokens at which a cold admission goes to a "
             "role=prefill replica first (when one is registered)",
    )
    sr.add_argument(
        "--heartbeat-ttl", type=float, default=None,
        help="seconds without a heartbeat before a replica is reaped "
             "(default 10, or OPSAGENT_FLEET_HEARTBEAT_TTL_S)",
    )
    sr.add_argument(
        "--max-retries", type=int, default=2,
        help="connect-phase re-routes per request before the error "
             "surfaces to the client (failover rides the per-replica "
             "circuit breaker)",
    )
    sr.add_argument(
        "--hedge-queue-depth", type=int, default=None,
        help="TTFT hedging: race a duplicate of a queued cold "
             "non-streaming admission on a second replica once the "
             "chosen replica's queue is this deep (default: off)",
    )
    sr.add_argument(
        "--shed-queue-depth", type=int, default=None,
        help="overload shedding: 429 + Retry-After for new admissions "
             "once EVERY replica's queue is this deep (default: off)",
    )
    sr.add_argument(
        "--autoscale-snapshot", default="",
        help="elastic scale-out: launch standby replicas from this "
             "`opsagent snapshot create` directory when shed pressure "
             "appears, promote them once request-ready (default: off; "
             "pair with --shed-queue-depth, the scale-up signal)",
    )
    sr.add_argument(
        "--autoscale-max-replicas", type=int, default=4,
        help="upper bound on autoscaler-launched replicas",
    )
    sr.add_argument(
        "--autoscale-port-base", type=int, default=8400,
        help="first port for autoscaler-launched engine servers "
             "(sequential from here)",
    )
    sr.add_argument(
        "--autoscale-cooldown", type=float, default=30.0,
        help="seconds between autoscaler launches",
    )

    sn = sub.add_parser(
        "snapshot",
        help="engine snapshot lifecycle: `create` captures a fully-"
             "warmed engine (weights in device layout + compile cache + "
             "KV plan) as a restart artifact; `verify` checks one "
             "without importing jax (serving/snapshot)",
    )
    snsub = sn.add_subparsers(dest="snapshot_cmd", required=True)
    snc = snsub.add_parser(
        "create",
        help="build + warm an engine, then write its snapshot directory",
    )
    snc.add_argument("--out", required=True, help="snapshot directory")
    snc.add_argument("--model", default="tiny-test")
    snc.add_argument("--checkpoint", default="")
    snc.add_argument("--tokenizer", default="")
    snc.add_argument("--tp", type=int, default=0)
    snc.add_argument("--sp", type=int, default=1)
    snc.add_argument("--ep", type=int, default=1)
    snc.add_argument("--max-batch-size", type=int, default=8)
    snc.add_argument("--quantize", default="", choices=("", "int8"))
    snc.add_argument("--kv-quantize", default="", choices=("", "int8"))
    snc.add_argument("--offload", action="store_true", default=False)
    snc.add_argument("--async-depth", type=int, default=2)
    snc.add_argument(
        "--warmup-level", default="full",
        help="warmup sweep before capture (full/bench/sessions): whatever compiles here is what restore replays "
             "as cache hits",
    )
    snc.add_argument(
        "--platform", default="", choices=("", "tpu", "cpu"),
        help="force the JAX platform (default: environment's choice)",
    )
    snv = snsub.add_parser(
        "verify",
        help="check a snapshot's manifest, fingerprint, and weight-leaf "
             "digests (exit 0 ok / 1 failed / 2 unreadable)",
    )
    snv.add_argument("path", help="snapshot directory")
    snv.add_argument(
        "--quick", action="store_true", default=False,
        help="skip per-leaf content digests (existence + size only)",
    )

    return p


def _cfg_int(value, default: int) -> int:
    return default if value is None else int(value)


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    cfg = load_config(args.config or None)
    log_cfg = cfg.get("log", {})
    init_logger(
        level=log_cfg.get("level", "info"),
        fmt=log_cfg.get("format", "json"),
        output=log_cfg.get("output", "stdout"),
        file_path=log_cfg.get("file", "logs/opsagent.log"),
        # Null-in-YAML (a commented-out value) falls back to the default;
        # an explicit 0 is preserved (maxBytes=0 / backupCount=0 are the
        # stdlib's "disable" idioms).
        max_size_mb=_cfg_int(log_cfg.get("max_size_mb"), 10),
        max_backups=_cfg_int(log_cfg.get("max_backups"), 10),
        retention_days=_cfg_int(log_cfg.get("max_age_days"), 7),
        compress=bool(log_cfg.get("compress", True)),
    )
    log = get_logger("cli")

    if args.command is None:
        build_parser().print_help()
        return 1

    if args.command == "version":
        print(f"opsagent {VERSION}")
        return 0

    if args.command == "slo-check":
        from .slocheck import run_slo_check

        return run_slo_check(
            url=args.url, bench=args.bench, slo_class=args.slo_class
        )

    if args.command == "top":
        from .top import run_top

        return run_top(
            args.url,
            interval_s=args.interval,
            frames=args.frames,
            color=False if args.no_color else None,
        )

    if args.command == "perf-check":
        from .perfcheck import run_perf_check

        return run_perf_check(
            args.current, baseline=args.baseline,
            tolerance=args.tolerance, tolerances_file=args.tolerances,
        )

    if args.command == "timeline":
        import json as _json

        from ..obs import timeline as obs_timeline

        if args.file:
            with open(args.file) as f:
                data = _json.load(f)
            # Accept either a bare timeline dict or a flight-dump
            # "timeline" context line ({"kind": "timeline", ...}).
            tl_data = data.get("timeline", data) if isinstance(data, dict) \
                else data
        elif args.url:
            import urllib.request

            req = urllib.request.Request(
                args.url.rstrip("/") + f"/api/timeline/{args.request_id}"
            )
            if args.token:
                req.add_header("Authorization", f"Bearer {args.token}")
            try:
                with urllib.request.urlopen(req, timeout=10) as resp:
                    tl_data = _json.loads(resp.read().decode())
            except Exception as e:  # noqa: BLE001 - CLI surface
                print(f"timeline fetch failed: {e}", file=sys.stderr)
                return 1
        else:
            # Same-process assembly (useful right after an in-process
            # `opsagent execute --model tpu://...` run).
            tl_data = obs_timeline.assemble(args.request_id)
            if tl_data is None:
                print(
                    f"unknown request_id {args.request_id!r} in this "
                    "process; pass --url for a running server or --file "
                    "for a dump",
                    file=sys.stderr,
                )
                return 1
        if args.json:
            print(_json.dumps(tl_data, indent=2))
        elif isinstance(tl_data, dict) and tl_data.get("fleet"):
            # Fleet-scope stitched timeline (router): multi-lane gantt
            # with one row per replica plus the router-side windows.
            print(obs_timeline.render_fleet_gantt(
                tl_data, width=args.width
            ))
        else:
            print(obs_timeline.render_gantt(tl_data, width=args.width))
        return 0

    if args.command == "fleet-kv":
        import json as _json
        import urllib.request

        url = (
            args.url.rstrip("/")
            + f"/api/fleet/directory?limit={args.limit}"
        )
        try:
            with urllib.request.urlopen(  # noqa: S310 - operator URL
                url, timeout=10
            ) as resp:
                snap = _json.loads(resp.read().decode())
        except Exception as e:  # noqa: BLE001 - CLI surface
            print(f"directory fetch failed: {e}", file=sys.stderr)
            return 1
        if args.json:
            print(_json.dumps(snap, indent=2))
            return 0
        st = snap.get("stats", {})
        print(
            f"directory: {st.get('chains', 0)} chains over "
            f"{st.get('replicas', 0)} replicas | lookups "
            f"{st.get('lookups', 0)} (hits {st.get('hits', 0)}, misses "
            f"{st.get('misses', 0)}), stale evictions "
            f"{st.get('stale_evictions', 0)}"
        )
        replicas = snap.get("replicas", [])
        if replicas:
            print(f"\n{'replica':<16} {'role':<8} {'state':<9} "
                  f"{'digests':>8} {'pool pages':>11} {'hb age':>8}")
            for r in replicas:
                digests = str(r.get("digest_count", 0))
                if r.get("digest_truncated"):
                    digests += "+"
                print(
                    f"{r.get('id', '?'):<16} {r.get('role', '?'):<8} "
                    f"{r.get('state', '?'):<9} {digests:>8} "
                    f"{r.get('host_pool_pages', 0):>11} "
                    f"{r.get('heartbeat_age_s', 0):>7.1f}s"
                )
        rows = snap.get("rows", [])
        if rows:
            print(f"\n{'chain':<14} {'owners (freshest first)'}")
            for row in rows:
                owners = ", ".join(
                    f"{o.get('id', '?')} ({o.get('age_s', 0):.1f}s)"
                    for o in row.get("owners", [])
                )
                print(f"{row.get('chain', '?')[:12]:<14} {owners}")
            if snap.get("truncated"):
                print(f"... truncated at {len(rows)} rows "
                      f"(raise --limit for more)")
        return 0

    if args.command == "fleet-flight":
        import json as _json
        import urllib.request
        from urllib.parse import quote

        url = (
            args.url.rstrip("/")
            + f"/api/fleet/flight?n={args.n}"
            + (f"&kind={quote(args.kind)}" if args.kind else "")
            + (
                f"&request_id={quote(args.request_id)}"
                if args.request_id else ""
            )
        )
        try:
            with urllib.request.urlopen(  # noqa: S310 - operator URL
                url, timeout=15
            ) as resp:
                ledger = _json.loads(resp.read().decode())
        except Exception as e:  # noqa: BLE001 - CLI surface
            print(f"fleet flight fetch failed: {e}", file=sys.stderr)
            return 1
        if args.json:
            print(_json.dumps(ledger, indent=2))
            return 0
        offsets = ledger.get("clock_offset_s", {})
        if offsets:
            print("clock offsets: " + ", ".join(
                f"{r}={o * 1e3:+.1f}ms" for r, o in sorted(offsets.items())
            ))
        events = ledger.get("events", [])
        print(f"{len(events)} events from "
              f"{len(ledger.get('replicas', []))} replicas\n")
        for e in events:
            wall = e.get("wall_corrected", e.get("wall", 0.0))
            extras = " ".join(
                f"{k}={v}" for k, v in e.items()
                if k not in ("kind", "source", "replica", "wall",
                             "wall_corrected", "ts", "id")
            )
            print(f"{wall:>17.6f} {e.get('source', '?'):<10} "
                  f"{e.get('kind', '?'):<18} {extras}")
        return 0

    if args.command == "server":
        # Precedence: flag > env (how k8s Secrets are injected,
        # deploy/kubernetes/deployment-prod.yaml) > config file.
        jwt_key = (
            args.jwt_key
            or os.environ.get("OPSAGENT_JWT_KEY", "")
            or cfg.get("jwt", {}).get("key", "")
        )
        set_global("jwtKey", jwt_key)
        set_global("showThought", args.show_thought)
        from ..server.app import run_server

        srv_cfg = cfg.get("server", {})
        run_server(
            host=args.host or srv_cfg.get("host", "0.0.0.0"),
            port=args.port or srv_cfg.get("port", 8080),
        )
        return 0

    if args.command == "serve-engine":
        if args.profile_dir:
            # The trace destination (utils/profiling.py reads it).
            os.environ["OPSAGENT_PROFILE_DIR"] = args.profile_dir
        if args.platform:
            import jax

            jax.config.update("jax_platforms", args.platform)
        try:
            from ..serving.api import run_engine_server
        except ImportError as e:
            print(f"serving engine unavailable: {e}", file=sys.stderr)
            return 1

        run_engine_server(
            host=args.host,
            port=args.port,
            model_name=args.model_name,
            checkpoint=args.checkpoint,
            tokenizer=args.tokenizer,
            tp=args.tp,
            sp=args.sp,
            ep=args.ep,
            max_batch_size=args.max_batch_size,
            quantize=args.quantize,
            kv_quantize=args.kv_quantize,
            offload=args.offload,
            async_depth=args.async_depth,
            join_fleet=args.join_fleet,
            advertise=args.advertise,
            replica_id=args.replica_id,
            replica_role=args.replica_role,
            restore_snapshot=args.restore_snapshot,
        )
        return 0

    if args.command == "serve-router":
        # The router never builds an engine — only a tokenizer for
        # affinity scoring and the HTTP/registry plumbing.
        from ..serving.fleet.router import run_router_server

        run_router_server(
            host=args.host,
            port=args.port,
            tokenizer=args.tokenizer,
            model_name=args.model_name,
            affinity=not args.no_affinity,
            queue_spill=args.queue_spill,
            prefill_threshold=args.prefill_threshold,
            heartbeat_ttl_s=args.heartbeat_ttl,
            max_retries=args.max_retries,
            hedge_queue_depth=args.hedge_queue_depth,
            shed_queue_depth=args.shed_queue_depth,
            autoscale_snapshot=args.autoscale_snapshot,
            autoscale_max_replicas=args.autoscale_max_replicas,
            autoscale_port_base=args.autoscale_port_base,
            autoscale_cooldown_s=args.autoscale_cooldown,
        )
        return 0

    if args.command == "snapshot":
        import json as _json

        if args.snapshot_cmd == "verify":
            # jax-free on purpose: manifest.py only touches stdlib, so
            # this runs on any CI box that can read the artifact.
            from ..serving.snapshot.manifest import (
                SnapshotError,
                verify_snapshot,
            )

            try:
                report = verify_snapshot(args.path, quick=args.quick)
            except SnapshotError as e:
                print(f"snapshot unreadable: {e}", file=sys.stderr)
                return 2
            print(_json.dumps(report, indent=2))
            return 0 if report["ok"] else 1

        # snapshot create: build + warm a real engine, then capture it
        # together with the compile cache (engine.compile_cache_dir()).
        # Every compile must land in the persistent cache for the
        # snapshot to carry it, so drop the min-compile-time floor
        # before jax spins up.
        os.environ.setdefault("OPSAGENT_COMPILE_CACHE_MIN_S", "0")
        if args.platform:
            import jax

            jax.config.update("jax_platforms", args.platform)
        from ..models.config import resolve_model
        from ..serving.engine import Engine, EngineConfig

        model_name, model_cfg = resolve_model(args.model, args.checkpoint)
        eng_cfg = EngineConfig(
            model=model_name,
            checkpoint=args.checkpoint,
            tokenizer=args.tokenizer,
            tp=args.tp,
            sp=args.sp,
            ep=args.ep,
            max_batch_size=args.max_batch_size,
            quantize=args.quantize,
            kv_quantize=args.kv_quantize,
            offload=args.offload,
            async_depth=args.async_depth,
            warmup=False,
        )
        eng = Engine(eng_cfg, model_cfg=model_cfg)
        eng.warmup(args.warmup_level)
        man = eng.snapshot(args.out)
        print(_json.dumps({
            "path": os.path.abspath(args.out),
            "fingerprint": man["fingerprint"],
            "leaves": len(man["leaves"]),
            "compile_cache_entries": man["compile_cache"]["entries"],
        }, indent=2))
        return 0

    from ..utils.term import render_markdown

    if args.command == "execute":
        from ..agent.prompts import REACT_SYSTEM_PROMPT, REFORMAT_PROMPT
        from ..agent.react import assistant_with_config
        from ..workflows import assistant_flow

        from .. import obs

        instructions = " ".join(args.instructions)
        messages = [
            {"role": "system", "content": REACT_SYSTEM_PROMPT},
            {"role": "user", "content": f"Here are the instructions: {instructions}"},
        ]
        # Root the request trace here so verbose runs can print the span
        # summary afterwards (the ReAct loop would otherwise self-mint an
        # ID this layer never learns).
        with obs.trace_request(obs.new_request_id("cli")) as tr:
            response, _ = assistant_with_config(
                args.model, messages, args.max_tokens, args.count_tokens,
                args.verbose, args.max_iterations, args.api_key, args.base_url,
            )
        # Second LLM pass purely to reformat, as the reference does
        # (execute.go:280-281).
        try:
            from ..llm.client import ChatClient

            client = ChatClient(api_key=args.api_key, base_url=args.base_url)
            result = assistant_flow(args.model, REFORMAT_PROMPT + response, client=client)
        except Exception:  # noqa: BLE001 - reformat is best-effort
            result = response
        print(render_markdown(result))
        if args.verbose:
            print(get_perf_stats().format_table(), file=sys.stderr)
            print(obs.format_tree(tr.to_dict()), file=sys.stderr)
        if args.metrics:
            print(obs.metrics_text(), file=sys.stderr, end="")
        return 0

    if args.command == "analyze":
        from ..k8s import get_yaml
        from ..workflows import analysis_flow

        manifest = get_yaml(args.resource, args.name, args.namespace)
        result = analysis_flow(args.model, manifest)
        print(render_markdown(result))
        return 0

    if args.command == "audit":
        from ..workflows import audit_flow

        result = audit_flow(args.model, args.name, args.namespace)
        print(render_markdown(result))
        return 0

    if args.command == "audit-fanout":
        from .fanout import run_audit_fanout

        return run_audit_fanout(
            model=args.model,
            resources=args.resources,
            seed=args.seed,
            issue_fraction=args.issue_fraction,
            replicas=args.replicas,
            max_inflight=args.max_inflight,
            max_tokens=args.max_tokens,
            flight_sample=args.flight_sample,
            as_json=args.json,
            out=args.out,
        )

    if args.command == "diagnose":
        from ..agent.prompts import DIAGNOSE_SYSTEM_PROMPT
        from ..agent.react import assistant_with_config

        messages = [
            {"role": "system", "content": DIAGNOSE_SYSTEM_PROMPT},
            {
                "role": "user",
                "content": (
                    f"Diagnose the Pod '{args.name}' in namespace "
                    f"'{args.namespace}'."
                ),
            },
        ]
        from .. import obs

        with obs.trace_request(obs.new_request_id("cli")) as tr:
            response, _ = assistant_with_config(
                args.model, messages, args.max_tokens, args.count_tokens,
                args.verbose, args.max_iterations, args.api_key, args.base_url,
            )
        from ..utils.jsonrepair import extract_field

        final = extract_field(response, "final_answer") or response
        print(render_markdown(final))
        if args.verbose:
            print(obs.format_tree(tr.to_dict()), file=sys.stderr)
        if args.metrics:
            print(obs.metrics_text(), file=sys.stderr, end="")
        return 0

    if args.command == "generate":
        from ..utils.yamlutil import extract_yaml
        from ..workflows import generator_flow

        prompt = " ".join(args.prompt)
        result = generator_flow(args.model, prompt)
        manifests = extract_yaml(result)
        print(render_markdown(result))
        if not args.yes:
            try:
                answer = input("Apply these manifests to the cluster? (y/N) ")
            except EOFError:
                answer = "n"
            if answer.strip().lower() not in ("y", "yes"):
                log.info("apply skipped")
                return 0
        from ..k8s import apply_yaml

        applied = apply_yaml(manifests)
        for item in applied:
            print(f"applied: {item}")
        return 0

    return 1


if __name__ == "__main__":
    sys.exit(main())
