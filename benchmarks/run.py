#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX: it is the orchestrator and the load
generator, with its own clock and its own interpreter lock. It starts
``server.py`` (the one process that holds the chip), waits until the
program's HTTP app answers, runs the cell's traffic generator against it,
opens the window once the generator says its clients are warm, measures for
``--seconds``, and then has the server compare a sample of the finished
requests with the plain reference.

Everything that belongs to one cell is found by name from BENCHMARK.json:
the configuration file, ``traffic/<mix>.json``, ``generators/<kind>.py``
(named by the traffic file) and ``layer_metrics/<metric>.py``.

The last line of standard output is the result object. Without a TPU there
is no result line and the exit code is not 0. ``--rehearse`` (with
``JAX_PLATFORMS=cpu``) walks the same control flow at a tiny size, prints
counts only and always exits 3.
"""

from __future__ import annotations

T_START = __import__("time").perf_counter()

import argparse
import asyncio
import json
import math
import os
import random
import signal
import socket
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import check, client, tokens  # noqa: E402
from benchmarks.loading import load_data, load_module  # noqa: E402

STARTUP_TIMEOUT_S = 1100.0
TRACE_SECONDS = 3.0
# The client's count of tokens in the window against the program's own
# counter over the same scrapes: they are read some milliseconds apart and
# the program counts a token when it accepts it, one tick before it is
# streamed, so allow a step or two of every row and a small share.
TOKEN_COUNT_MARGIN = (0.03, 4)   # share of the count, tokens per client


class BenchFailure(Exception):
    """The run cannot give a result."""


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_cell(workload: str, rehearse: bool) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchFailure(
            f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_data(os.path.join(ROOT, entry["file"]), rehearse)
    traffic = load_data(
        os.path.join(HERE, "traffic", cell["traffic"] + ".json"), rehearse)

    def reported(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    # A per-layer metric is read in the cells that report the end-to-end
    # metric it moves (and, where it lists cells, only in those).
    end_to_end = [m for m in bench["end_to_end"] if reported(m)]
    moved = {m["name"] for m in end_to_end}
    return {
        "cell": cell, "config": config, "config_file": entry["file"],
        "traffic": traffic,
        "end_to_end": end_to_end,
        "per_layer": [m for m in bench["per_layer"]
                      if reported(m) and m["moves"] in moved],
    }


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def stop_child(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        os.killpg(proc.pid, signal.SIGTERM)
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait(timeout=20)


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def nearest_rank(values: list[float], q: float) -> float:
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 1))
    return ordered[int(rank) - 1]


class Window:
    """What the generator tells the harness: warm, and how late it ran."""

    def __init__(self) -> None:
        self.warm = asyncio.Event()
        self.lateness: list[float] = []

    def ready(self) -> None:
        self.warm.set()

    def late(self, seconds: float) -> None:
        self.lateness.append(seconds)


def end_to_end(records: list, t_open: float, t_close: float, chips: int,
               setup_s: float) -> tuple[dict, dict]:
    """The client-side numbers of the window and the counts behind them.

    All the window's work counts: a time to first token for every request
    whose first token arrived inside the window, a time per output token
    for every request that finished inside it, and every token that
    arrived inside it, whenever its request was sent. Requests still in
    flight at the close are neither attempted nor failed."""
    def inside(t: float) -> bool:
        return t_open <= t <= t_close

    done = [r for r in records if r.t_done and inside(r.t_done)]
    ok = [r for r in done if r.ok]
    ttft = [(r.chunks[0][0] - r.t_send) * 1e3 for r in records
            if r.chunks and inside(r.chunks[0][0])]
    tpot = [(r.chunks[-1][0] - r.chunks[0][0]) / (r.tokens - 1) * 1e3
            for r in ok if r.tokens > 1]
    window_tokens = sum(
        n for r in records for t, n in r.chunks if inside(t))
    seconds = t_close - t_open
    values = {"setup_s": setup_s}
    if ttft:
        values["ttft_p50_ms"] = nearest_rank(ttft, 0.50)
        values["ttft_p95_ms"] = nearest_rank(ttft, 0.95)
    if tpot:
        values["tpot_p50_ms"] = nearest_rank(tpot, 0.50)
    values["out_tokens_per_s"] = window_tokens / seconds / chips
    counts = {
        "attempted": len(done), "failed": len(done) - len(ok),
        "in_flight_at_close": sum(
            1 for r in records if r.t_send <= t_close
            and not (r.t_done and r.t_done <= t_close)),
        "ttft_samples": len(ttft), "tpot_samples": len(tpot),
        "window_tokens": window_tokens, "window_s": seconds,
        "errors": sorted({r.error for r in done if r.error})[:5],
    }
    say("first-token times, ms, in order: "
        + json.dumps([round(t, 1) for t in sorted(ttft)]))
    return values, counts


def resident_tokens(records: list, shared: int, times: tuple) -> float:
    """Mean over ``times`` of the distinct tokens whose keys and values the
    requests then in flight attend to: each request's own prompt and the
    tokens it had been served, and the shared system prompt once."""
    means = []
    for t in times:
        live = [r for r in records if r.t_send <= t and not 0 < r.t_done < t]
        own = sum(
            len(tokens.template_ids(r.messages)) - shared
            + sum(n for at, n in r.chunks if at <= t)
            for r in live
        )
        means.append(own + (shared if live else 0))
    return sum(means) / len(means)


def layer_metrics(cell: dict, ctx: dict) -> dict:
    out = {}
    for m in cell["per_layer"]:
        value = load_module("layer_metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


async def measure(cell: dict, args, base: str) -> dict:
    import aiohttp

    traffic, config = cell["traffic"], cell["config"]
    generator = load_module("generators", traffic["generator"])
    records: list = []
    model = config["preset"]
    timeout = aiohttp.ClientTimeout(total=None, sock_read=600)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as http:

        async def send(body: dict, meta: dict, on_first=None):
            rec = client.new_record(body, meta)
            records.append(rec)
            return await client.stream_chat(
                http, base, dict(body, model=model, temperature=0), rec,
                on_first)

        async def post(path: str, body: dict | None = None) -> dict:
            async with http.post(base + path, json=body or {}) as resp:
                if resp.status != 200:
                    raise BenchFailure(
                        f"POST {path} -> {resp.status}: "
                        f"{(await resp.text())[:2000]}")
                return await resp.json()

        async with http.get(base + "/bench/info") as resp:
            info = await resp.json()
        say(f"server set-up: {json.dumps(info['setup'])}")
        t0 = time.perf_counter()
        await generator.setup(traffic, args.seed, send)
        fill_s = time.perf_counter() - t0
        window = Window()
        t0 = time.perf_counter()
        task = asyncio.ensure_future(
            generator.run(traffic, args.seed, send, window))
        waiter = asyncio.ensure_future(window.warm.wait())
        await asyncio.wait({task, waiter}, return_when=asyncio.FIRST_COMPLETED)
        if task.done():
            waiter.cancel()
            task.result()
            raise BenchFailure("the generator ended before its clients were warm")
        before = await client.scrape(http, base)
        t_open = time.perf_counter()
        setup_s = t_open - T_START
        say(f"set-up split: server ready {info['setup']['server_ready_s']:.2f}s"
            f" (weights {info['setup']['weights_s']:.2f}s, engine "
            f"{info['setup']['engine_s']:.2f}s, warmup[{info['setup']['warmup_level']}] "
            f"{info['setup']['warmup_s']:.2f}s), cache fill {fill_s:.2f}s, "
            f"ramp {t_open - t0:.2f}s; window opens at {setup_s:.2f}s")
        trace = None
        if args.trace:
            lead = max(0.0, (args.seconds - TRACE_SECONDS) / 2)
            await asyncio.sleep(lead)
            say(f"tracing: {info['setup']['compile_cache_entries_at_start']} "
                "programs were in the compile cache at start, and the cache "
                "keys a program without its scope names: the trace shows "
                "the names of the build that compiled first, so a run that "
                "must show new names compiles fresh (an empty "
                f"{info['setup']['compile_cache_dir'] or 'cache directory'})")
            await post("/bench/trace/start")
            t_trace = time.perf_counter()
            await asyncio.sleep(min(TRACE_SECONDS, args.seconds / 2))
            capture_s = (await post("/bench/trace/stop"))["capture_s"]
        await asyncio.sleep(max(0.0, t_open + args.seconds - time.perf_counter()))
        after = await client.scrape(http, base)
        t_close = time.perf_counter()
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass
        chips = cell["cell"]["chips"]
        values, counts = end_to_end(records, t_open, t_close, chips, setup_s)
        counts["generator_late_max_ms"] = max(window.lateness, default=0.0) * 1e3
        counts["generator_late_mean_ms"] = (
            sum(window.lateness) / len(window.lateness) * 1e3
            if window.lateness else 0.0)
        say(f"window: {json.dumps(counts)}")
        say(f"client numbers: {json.dumps(values)}")

        compiles = client.total(after, "opsagent_post_warmup_compiles")
        if compiles > 0:
            async with http.get(base + "/api/debug/flight?kind=compile") as r:
                events = (await r.json()).get("events", [])
            raise BenchFailure(
                f"{compiles:.0f} program(s) compiled inside the window: a "
                f"fault of the cell's warm-up list: {json.dumps(events[-5:])}")
        served = client.delta(before, after, "opsagent_decode_tokens_total")
        share, per_client = TOKEN_COUNT_MARGIN
        margin = share * served + per_client * traffic["sessions"]
        say(f"token count: client {counts['window_tokens']} in the window, "
            f"program counter delta {served:.0f}, margin {margin:.0f}")
        if abs(counts["window_tokens"] - served) > margin:
            raise BenchFailure(
                "the client's token count and the program's counter disagree")

        finished = [
            {"prompt_ids": tokens.template_ids(r.messages),
             "reply_ids": tokens.encode(r.text),
             "constrained": r.constrained, "client": r.meta.get("slot")}
            for r in records
            if r.ok and t_open <= r.t_done <= t_close
        ]
        spec = config["check"]
        samples = check.select(
            finished, random.Random(args.seed), spec["sample_tokens"],
            spec["max_requests"], spec["limits"]["min_checked_tokens"])
        result = await post("/bench/finish", {
            "samples": samples, "control_bits": args.control_bits})
        numbers = result["check"]
        if args.trace:
            trace = dict(result["trace"], resident_tokens=resident_tokens(
                records, traffic["system_tokens"],
                (t_trace, t_trace + capture_s)))
            say(f"trace: {json.dumps(trace)}")
        wrong = check.precision_mismatches(
            config["precision"], info["setup"]["impl"])
        for line in wrong:
            say(f"precision not as stated: {line}")
        numbers["precision_mismatches"] = len(wrong)
        say(f"reference check: {json.dumps(numbers)}")
        correct, lines = check.verdict(numbers, spec["limits"])
        for line in lines:
            say(line)
        compared = {
            name: {"value": value if math.isfinite(value) else None,
                   "limit": f"{op} {limit!r}"}
            for name, value, op, limit, _met in check.comparisons(
                numbers, spec["limits"])
        }
        ctx = {"before": before, "after": after, "trace": trace,
               "config": config, "traffic": traffic, "counts": counts,
               "client": values,
               "device": result["device"]}
        return {"values": values, "counts": counts, "correct": correct,
                "compared": compared, "verdict_lines": lines,
                "device": result["device"], "trace": trace, "ctx": ctx}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on any backend; counts only; exits 3")
    ap.add_argument("--control-bits", type=int, default=0,
                    help="also run the lower-precision control (4)")
    ap.add_argument("--engine", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="a control, never a cell: one engine setting of "
                         "the configuration file replaced, as in "
                         "kv_quantize=int8 (the program's own int8 pages)")
    ap.add_argument("--break-every", type=int, default=0,
                    help="rehearsal only: alter every n-th served token")
    args = ap.parse_args()
    try:
        cell = load_cell(args.workload, args.rehearse)
    except (BenchFailure, OSError, KeyError, ValueError) as e:
        say(f"FAILED: {e}")
        return 2
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if (not args.rehearse and platforms
            and "tpu" not in platforms.split(",")):
        say(f"no TPU: JAX_PLATFORMS={platforms!r}; nothing runs in its place")
        return 2

    out_dir = os.path.join(ROOT, ".bench_out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    log_path = os.path.join(out_dir, "server.log")
    port = free_port()
    cmd = [
        sys.executable, os.path.join(HERE, "server.py"),
        "--config", os.path.join(ROOT, cell["config_file"]),
        "--seed", str(args.seed), "--port", str(port),
        "--chips", str(cell["cell"]["chips"]), "--out", out_dir,
    ]
    if args.rehearse:
        cmd.append("--rehearse")
    if args.break_every:
        cmd += ["--break-every", str(args.break_every)]
    for setting in args.engine:
        cmd += ["--engine", setting]
        say(f"CONTROL RUN, not the cell as committed: engine {setting}")
    env = dict(os.environ)
    # Every program into the persistent cache, however fast it compiled:
    # the second run of a cell compiles nothing.
    env["OPSAGENT_COMPILE_CACHE_MIN_S"] = "0"
    env.pop("OPSAGENT_PROFILE_DIR", None)
    env["OPSAGENT_FLIGHT_DIR"] = os.path.join(out_dir, "flight")
    say(f"cell {args.workload}: seed {args.seed}, {args.seconds}s, "
        f"trace {args.trace}; server log {log_path}")
    with open(log_path, "w") as log_file:
        proc = subprocess.Popen(
            cmd, cwd=ROOT, env=env, stdout=log_file,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
    base = f"http://127.0.0.1:{port}"
    try:
        import urllib.error
        import urllib.request

        while True:
            if proc.poll() is not None:
                raise BenchFailure(
                    f"server exited with {proc.returncode} before it "
                    f"answered; its last lines:\n{tail(log_path)}")
            if time.perf_counter() - T_START > STARTUP_TIMEOUT_S:
                raise BenchFailure(
                    f"server not up after {STARTUP_TIMEOUT_S:.0f}s:\n"
                    f"{tail(log_path)}")
            try:
                with urllib.request.urlopen(base + "/healthz", timeout=5):
                    break
            except (urllib.error.URLError, OSError):
                time.sleep(0.25)
        got = asyncio.run(measure(cell, args, base))
    except BenchFailure as e:
        say(f"FAILED: {e}")
        say(f"server log tail:\n{tail(log_path, 15)}")
        return 1
    finally:
        stop_child(proc)

    device = got["device"]
    unit = {m["name"]: m["unit"] for m in cell["end_to_end"]}
    if args.trace:
        trace = got["trace"]
        metrics = layer_metrics(cell, got["ctx"])
        device = dict(device, busy_s=trace["busy_s"],
                      window_s=trace["window_s"])
    else:
        metrics = {
            name: {"value": got["values"][name], "unit": unit[name]}
            for name in unit if name in got["values"]
        }
    result = {
        "correct": got["correct"], "attempted": got["counts"]["attempted"],
        "failed": got["counts"]["failed"], "metrics": metrics,
        "device": device,
    }
    if args.trace:
        result["breakdown"] = {
            "device_ops": got["trace"]["device_ops"],
            "idle_gaps": got["trace"]["idle_gaps"],
        }
    if args.rehearse:
        # Counts only: no number of a CPU run under a device metric's name.
        result["metrics"] = {}
        result.pop("breakdown", None)
        result["device"] = {k: device[k] for k in ("platform", "kind", "count")}
        result["rehearsal"] = {
            "end_to_end_seen": sorted(n for n in got["values"] if n in unit),
            "per_layer_seen": sorted(metrics) if args.trace else [],
        }
        say("rehearsal complete: control flow only, which proves nothing "
            "about the chip")
        print_result(result, got)
        return 3
    missing = [n for n in unit if n not in metrics] if not args.trace else []
    if missing:
        say(f"FAILED: the window gave no sample for {missing}")
        return 1
    print_result(result, got)
    return 0


def print_result(result: dict, got: dict) -> None:
    """The result's line, last on standard output, with each number
    compared beside its limit as its last key; the same, as lines, last on
    standard error."""
    result["compared"] = got["compared"]
    for line in got["verdict_lines"]:
        print(f"[bench] {line}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.exit(main())
