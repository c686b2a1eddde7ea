#!/usr/bin/env python3
"""Prove that the serving engine starts and answers on the chip.

    python chip_smoke.py             one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4   the tensor-parallel path on four chips
    python chip_smoke.py --rehearse  control flow only: tiny-test on whatever
                                     backend is there; ALWAYS exits non-zero

One chip: starts the `tpu://` provider's server through its normal entry
point — `python -m opsagent_tpu.cli.main serve-engine --model-name
qwen2.5-7b-instruct --quantize int8`, all 28 layers at the published
widths, seeded random int8 weights built on the device, byte tokenizer —
waits for `/healthz`, and sends `/v1/chat/completions` a plain greedy
request, the same request again (prefix-trie hit: identical text), a
streaming one, a logprobs one (finite values), one constrained to the
agent's ToolPrompt JSON schema (must parse and conform) and three in
flight together (a mixed prefill+decode tick). Afterwards
`opsagent_post_warmup_compiles` must be 0 and decode tokens > 0.

The chip belongs to one process at a time. Here that process is the
server child: this parent never imports JAX and learns the device from
the child's `/healthz`. With `--chips 4` nothing is spawned and this
process drives four chips itself: the same engine at tp=4 against tp=1 on
one device with the same seed (first-step logits, greedy tokens, shards on
four devices, per-device peak memory about a quarter), and no other phase.

The LAST line of stdout is the result, and is printed only on success:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
Any failure, and any platform other than tpu, exits non-zero without it.
Everything else worth knowing is printed on earlier lines.
"""

from __future__ import annotations

import argparse
import http.client
import json
import math
import os
import signal
import socket
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out")
MODEL = "qwen2.5-7b-instruct"
REHEARSAL_MODEL = "tiny-test"
STARTUP_TIMEOUT_S = 1000.0  # weights + full warmup, cold compile cache
REQUEST_TIMEOUT_S = 300.0


class SmokeFailure(Exception):
    """A phase of the smoke did not hold."""


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    say(f"ok: {what}")


# -- one chip: the server as a child ----------------------------------------
def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(port: int, method: str, path: str, body: dict | None = None,
              timeout: float = REQUEST_TIMEOUT_S):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        data = None if body is None else json.dumps(body)
        conn.request(method, path, body=data,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read().decode("utf-8")
        if resp.status != 200:
            raise SmokeFailure(f"{method} {path} -> {resp.status}: {raw[:500]}")
        ctype = resp.getheader("Content-Type", "")
        return json.loads(raw) if "json" in ctype else raw
    finally:
        conn.close()


def chat(port: int, body: dict,
         timeout: float = REQUEST_TIMEOUT_S) -> tuple[dict, float]:
    t0 = time.perf_counter()
    out = http_json(port, "POST", "/v1/chat/completions", body, timeout)
    return out, time.perf_counter() - t0


def chat_stream(port: int, body: dict) -> tuple[str, int, float, float]:
    """(text, content chunks, seconds to first content chunk, total s)."""
    conn = http.client.HTTPConnection(
        "127.0.0.1", port, timeout=REQUEST_TIMEOUT_S
    )
    t0 = time.perf_counter()
    first = None
    text, chunks, done = "", 0, False
    try:
        conn.request("POST", "/v1/chat/completions",
                     body=json.dumps(dict(body, stream=True)),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise SmokeFailure(f"stream -> {resp.status}: {resp.read()[:500]}")
        for raw in resp:
            line = raw.decode("utf-8").strip()
            if not line.startswith("data:"):
                continue
            payload = line[5:].strip()
            if payload == "[DONE]":
                done = True
                break
            delta = json.loads(payload)["choices"][0].get("delta", {})
            if delta.get("content"):
                if first is None:
                    first = time.perf_counter() - t0
                text += delta["content"]
                chunks += 1
    finally:
        conn.close()
    if not done:
        raise SmokeFailure("stream ended without [DONE]")
    return text, chunks, first or 0.0, time.perf_counter() - t0


def metric(text: str, name: str, **labels: str) -> float:
    """Sum of the samples of one family in a Prometheus exposition."""
    total, seen = 0.0, False
    for line in text.splitlines():
        if line.startswith("#") or not line.startswith(name):
            continue
        head, _, value = line.rpartition(" ")
        if head != name and not head.startswith(name + "{"):
            continue
        if all(f'{k}="{v}"' in head for k, v in labels.items()):
            total += float(value)
            seen = True
    if not seen:
        raise SmokeFailure(f"/metrics has no sample of {name}{labels or ''}")
    return total


def conforms(text: str, schema: dict) -> bool:
    """The reply, byte by byte, through the repo's own compiler of the
    schema (serving/constrained.py: JSON schema -> regex -> byte DFA) —
    the automaton the constrained decode was masked by, applied here to
    the text that came back over the wire."""
    from opsagent_tpu.serving.constrained import (
        compile_regex, schema_to_regex,
    )

    dfa = compile_regex(schema_to_regex(schema, 4))
    state = dfa.run(dfa.start, text.encode("utf-8"))
    return state >= 0 and bool(dfa.accept[state])


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


def run_one_chip(rehearse: bool) -> dict:
    model = REHEARSAL_MODEL if rehearse else MODEL
    port = free_port()
    os.makedirs(OUT_DIR, exist_ok=True)
    log_path = os.path.join(OUT_DIR, "chip_smoke_server.log")
    cmd = [
        sys.executable, "-m", "opsagent_tpu.cli.main", "serve-engine",
        "--model-name", model, "--host", "127.0.0.1", "--port", str(port),
    ]
    if not rehearse:
        # --platform tpu: without a TPU the child fails at backend
        # start-up; it can never come up on another backend instead.
        cmd += ["--quantize", "int8", "--platform", "tpu"]
    say("starting: " + " ".join(cmd[1:]))
    t_start = time.perf_counter()
    with open(log_path, "w") as log_file:
        proc = subprocess.Popen(
            cmd, cwd=HERE, stdout=log_file, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
    try:
        health = None
        while time.perf_counter() - t_start < STARTUP_TIMEOUT_S:
            if proc.poll() is not None:
                raise SmokeFailure(
                    f"server exited with {proc.returncode} before it was "
                    f"healthy; its last lines:\n{tail(log_path)}"
                )
            try:
                health = http_json(port, "GET", "/healthz", timeout=5)
                break
            except (OSError, http.client.HTTPException):
                time.sleep(2)
        if health is None:
            raise SmokeFailure(
                f"server not healthy after {STARTUP_TIMEOUT_S:.0f}s; its "
                f"last lines:\n{tail(log_path)}"
            )
        ready_s = time.perf_counter() - t_start
        return drive_server(port, health, ready_s, rehearse)
    finally:
        stop_child(proc)
        say(f"server stopped (log: {log_path})")


def drive_server(port: int, health: dict, ready_s: float,
                 rehearse: bool) -> dict:
    assert "jax" not in sys.modules, "the parent must stay off JAX"
    from opsagent_tpu.serving.constrained import TOOLPROMPT_SCHEMA

    impl, init = health["impl"], health["init"]
    device = {"platform": impl["platform"], "kind": impl["device_kind"],
              "count": impl["device_count"]}
    say(f"healthy after {ready_s:.1f}s: weights {init['weights_load_s']}s, "
        f"warmup {init['warmup_s']}s")
    say(f"device (from the server): {json.dumps(device)}")
    say(f"impl_info: {json.dumps(impl)}")
    cache_dir = init["compile_cache_dir"]
    say(f"compile cache: {cache_dir} — {init['compile_cache_entries_at_start']}"
        f" entries before start, "
        f"{len(os.listdir(cache_dir)) if cache_dir else 0} after warmup")
    if not rehearse:
        check(device["platform"] == "tpu", "the server runs on a tpu")
        check(impl["quantize"] == "int8" and impl["dtype"] == "bfloat16",
              "int8 weights, bf16 compute")
    check(health["status"] == "ok" and health["model"] in (MODEL,
          REHEARSAL_MODEL), "healthz reports ok")

    ask = [{"role": "system", "content": "You are a Kubernetes ops agent."},
           {"role": "user", "content": "How many pods are running in the "
                                       "default namespace?"}]
    plain = {"model": health["model"], "messages": ask, "max_tokens": 32,
             "temperature": 0}

    # 1 + 2: plain greedy, then the same again through the prefix trie.
    first, s1 = chat(port, plain)
    hits0 = http_json(port, "GET", "/healthz")["prefix_hit_tokens"]
    again, s2 = chat(port, plain)
    hits1 = http_json(port, "GET", "/healthz")["prefix_hit_tokens"]
    text1 = first["choices"][0]["message"]["content"]
    text2 = again["choices"][0]["message"]["content"]
    say(f"plain: {first['usage']} in {s1:.2f}s; repeat in {s2:.2f}s; "
        f"text {text1!r}")
    check(first["usage"]["completion_tokens"] > 0, "plain greedy completion")
    check(text1 == text2 and first["usage"] == again["usage"],
          "the repeated request gives the identical text")
    check(hits1 > hits0, f"the repeat hit the prefix trie "
                         f"({hits1 - hits0} prompt tokens reused)")

    # 3: streaming (SSE), same greedy request -> same text.
    stext, chunks, ttfc, s3 = chat_stream(port, plain)
    say(f"stream: {chunks} content chunks, first after {ttfc:.2f}s, "
        f"{s3:.2f}s in all")
    # A random model emits stray bytes of multi-byte characters; the
    # incremental detokenizer withholds an incomplete tail that the
    # one-shot decode renders as U+FFFD, so compare without those.
    def solid(t: str) -> str:
        return t.replace("\ufffd", "")

    check(solid(stext) == solid(text1),
          f"the streamed text equals the plain one ({stext!r})")

    # 4: logprobs — the values behind the tokens are finite.
    lp, s4 = chat(port, dict(plain, max_tokens=8, logprobs=True,
                             top_logprobs=5))
    content = lp["choices"][0]["logprobs"]["content"]
    vals = [c["logprob"] for c in content] + [
        t["logprob"] for c in content for t in c["top_logprobs"]
    ]
    say(f"logprobs: {len(content)} tokens in {s4:.2f}s, chosen "
        f"{[round(c['logprob'], 3) for c in content]}")
    check(len(content) > 0 and all(
        math.isfinite(v) and v <= 1e-3 for v in vals
    ), "token logprobs are finite and <= 0")
    check(all(
        abs(c["logprob"] - c["top_logprobs"][0]["logprob"]) < 1e-3
        for c in content
    ), "each greedy token is the top-1 alternative")

    # 5: the agent's constrained-decode path. Sampled, because a random
    # model under greedy never leaves a string body (no closing quote).
    schema_req = {
        "model": health["model"], "messages": ask, "max_tokens": 4096,
        "temperature": 1.0,
        "response_format": {"type": "json_schema", "json_schema": {
            "name": "ToolPrompt", "schema": TOOLPROMPT_SCHEMA}},
    }
    # Six unbounded strings, each closed when the model samples a quote
    # (about one token in two hundred): give it the server's own deadline.
    con, s5 = chat(port, schema_req, timeout=650)
    ctext = con["choices"][0]["message"]["content"] or ""
    say(f"constrained: {con['usage']} in {s5:.2f}s, finish "
        f"{con['choices'][0]['finish_reason']!r}, FSM tables: "
        f"{impl['fsm_impl']}")
    check(con["choices"][0]["finish_reason"] == "stop",
          "the constrained reply ran to its end")
    parsed = json.loads(ctext)
    check(list(parsed) == list(TOOLPROMPT_SCHEMA["properties"])
          and conforms(ctext, TOOLPROMPT_SCHEMA),
          "the constrained reply parses and conforms to the ToolPrompt "
          "schema")

    # 6: three requests in flight together, one with a long prompt, so
    # that prefill chunks ride ticks in which other rows decode.
    bodies = [
        dict(plain, max_tokens=48, messages=[{
            "role": "user", "content": f"Session {i}: " + note}])
        for i, note in enumerate((
            "describe the failing deployment.",
            "list every namespace and its pod count. " * 40,
            "why is the node NotReady?",
        ))
    ]
    results: list = [None] * len(bodies)

    def worker(i: int) -> None:
        try:
            results[i] = chat(port, bodies[i])
        except Exception as e:  # noqa: BLE001 - reported by the check below
            results[i] = e

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(bodies))]
    for t in threads:
        t.start()
        time.sleep(0.15)
    for t in threads:
        t.join(REQUEST_TIMEOUT_S)
    bad = [r for r in results if not isinstance(r, tuple)]
    check(not bad, f"three concurrent requests answered ({bad or 'all'})")
    for i, (r, s) in enumerate(results):
        say(f"concurrent[{i}]: {r['usage']} in {s:.2f}s")
    events = http_json(
        port, "GET", "/api/debug/flight?kind=dispatch"
    )["events"]
    mixed = [e for e in events if e.get("op") == "mixed"
             and e.get("decode_seq_ids") and e.get("prefill_seq_ids")]
    check(bool(mixed), f"{len(mixed)} mixed ticks carried decode rows AND "
                       f"prefill chunks together")

    # After the requests: nothing compiled, tokens were decoded.
    metrics = http_json(port, "GET", "/metrics")
    post = metric(metrics, "opsagent_post_warmup_compiles")
    decoded = metric(metrics, "opsagent_decode_tokens_total")
    say(f"post-warmup compiles {post:.0f}, decode tokens {decoded:.0f}")
    check(post == 0, "zero post-warmup compiles")
    check(decoded > 0, "decode tokens > 0")
    end = http_json(port, "GET", "/healthz")
    for m in end["device_memory"]:
        say(f"device {m['device']}: peak HBM "
            f"{m['peak_bytes_in_use'] / 2**30:.2f} GiB of "
            f"{m['bytes_limit'] / 2**30:.2f} GiB")
    say(f"compile cache entries at exit: "
        f"{len(os.listdir(cache_dir)) if cache_dir else 0}")
    return device


# -- four chips: tp=4 against tp=1, in this process --------------------------
def run_four_chips(rehearse: bool) -> dict:
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"device: {json.dumps(device)}")
    if not rehearse:
        check(device["platform"] == "tpu" and device["count"] == 4,
              "four tpu chips")
    check(len(devs) >= 4, "at least four devices")

    from opsagent_tpu.models.config import get_config_preset

    kw = dict(model=MODEL, quantize="int8", max_batch_size=4)
    # bf16 tolerance on unit-variance logits: the two row-parallel
    # matmuls of each layer round their partial sums in another order
    # under tp, one bf16 ulp (2^-8) each, accumulating as a random walk
    # over 2L roundings; the largest of 152k errors sits near 5 sigma.
    tol = 8 * 2.0**-8 * math.sqrt(2 * get_config_preset(MODEL).num_layers)
    if rehearse:
        # tiny-test has two kv heads: the widest mesh it shards over is 2.
        kw = dict(model=REHEARSAL_MODEL, quantize="int8", max_batch_size=4,
                  num_pages=256, max_pages_per_seq=16,
                  prefill_buckets=(64,), dtype=jnp.float32)
        tol = 1e-3
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 250, size=n).tolist() for n in (40, 23, 57)]
    greedy = SamplingParams(temperature=0.0, max_tokens=16)
    greedy_lp = SamplingParams(temperature=0.0, max_tokens=16,
                               logprobs=True, top_logprobs=2)

    def phase(tp: int) -> dict:
        t0 = time.perf_counter()
        eng = Engine(EngineConfig(tp=tp, **kw))
        say(f"tp={tp}: engine up in {time.perf_counter() - t0:.1f}s, "
            f"impl_info {json.dumps(eng.impl_info())}")
        leaves = jax.tree.leaves((eng.params, eng.cache))
        spread = {len({s.device for s in x.addressable_shards})
                  for x in leaves}
        # First-step logits, full vocabulary, from the engine's own
        # prefill program (all-dropped page table: no cache write).
        bucket = eng.cfg.prefill_buckets[0]
        drop = jnp.full((1, eng.cfg.max_pages_per_seq), -1, jnp.int32)
        logits = []
        for p in prompts:
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :len(p)] = p
            with eng.mesh_ctx():
                lg, eng.cache = eng._prefill_jit(
                    eng.params, jnp.asarray(toks),
                    jnp.asarray([len(p)], jnp.int32), eng.cache, drop,
                )
            logits.append(np.asarray(lg, np.float32)[0])
        plain = eng.generate(prompts, greedy)
        with_lp = []
        for p in prompts:
            sid = eng.add_request(p, greedy_lp)
            while not eng.sequences[sid].done:
                eng.step_block([sid])
            data = list(eng.sequences[sid].logprob_data)
            with_lp.append((eng.finish(sid), data))
        out = {"logits": logits, "plain": plain, "with_lp": with_lp,
               "spread": spread, "memory": eng.device_memory(),
               "n_leaves": len(leaves)}
        del eng, leaves
        gc.collect()
        left = devs[0].memory_stats()
        if left:
            say(f"tp={tp}: engine dropped, device 0 holds "
                f"{left['bytes_in_use'] / 2**30:.2f} GiB")
        return out

    wide = 2 if rehearse else 4
    sharded, single = phase(wide), phase(1)

    check(sharded["spread"] == {wide},
          f"every one of the {sharded['n_leaves']} parameter and page "
          f"leaves has shards on {wide} devices")
    check(single["spread"] == {1}, "the tp=1 engine sits on one device")
    errs = [np.abs(a - b) for a, b in
            zip(sharded["logits"], single["logits"])]
    for i, (e, b) in enumerate(zip(errs, single["logits"])):
        say(f"prompt {i}: first-step logits |tp{wide} - tp1| max "
            f"{float(e.max()):.4f} mean {float(e.mean()):.4f} (logits "
            f"{float(b.min()):.2f}..{float(b.max()):.2f}, std "
            f"{float(b.std()):.2f})")
    for i, e in enumerate(errs):
        check(bool(np.isfinite(e).all()) and float(e.max()) <= tol,
              f"prompt {i}: first-step logits agree within {tol:.3f}")
    for i, ((ta, da), (tb, db)) in enumerate(
        zip(sharded["with_lp"], single["with_lp"])
    ):
        agreed = 0
        for t, (x, y) in enumerate(zip(ta, tb)):
            if x != y:
                margin = db[t]["top"][0][1] - db[t]["top"][1][1]
                check(margin <= tol,
                      f"prompt {i}: tokens part at step {t} only inside "
                      f"the tolerance (top-two margin {margin:.4f})")
                break
            agreed += 1
        say(f"prompt {i}: greedy tokens equal for {agreed} of "
            f"{min(len(ta), len(tb))} steps; block path equal: "
            f"{sharded['plain'][i] == single['plain'][i]}")
    if sharded["memory"] and single["memory"]:
        one = single["memory"][0]["peak_bytes_in_use"]
        for m in sharded["memory"]:
            share = m["peak_bytes_in_use"] / one
            say(f"device {m['device']}: peak "
                f"{m['peak_bytes_in_use'] / 2**30:.2f} GiB at tp={wide} = "
                f"{share:.2f} of the one-chip {one / 2**30:.2f} GiB")
            check(0.2 <= share <= 0.4,
                  f"device {m['device']} holds about a quarter")
    else:
        check(rehearse, "the backend reports device memory")
    return device


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="control flow only, tiny-test on any backend; exits non-zero",
    )
    args = ap.parse_args()
    sys.path.insert(0, HERE)

    # The platform first. This process may not touch JAX while a server
    # child needs the chip, so it reads what JAX will read.
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if (not args.rehearse and platforms
            and "tpu" not in platforms.split(",")):
        say(f"no TPU: JAX_PLATFORMS={platforms!r}. This script proves the "
            "chip path and runs nothing else in its place.")
        return 2
    say(f"python {sys.version.split()[0]}; " + "; ".join(
        f"{m} {_version(m)}" for m in ("jax", "jaxlib", "libtpu")))
    try:
        device = (run_four_chips if args.chips == 4 else run_one_chip)(
            args.rehearse
        )
    except SmokeFailure as e:
        say(f"FAILED: {e}")
        return 1
    if args.rehearse:
        say("rehearsal complete: every phase held on "
            f"{json.dumps(device)} — which proves nothing about the chip")
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def _version(module: str) -> str:
    """Installed version, from package metadata (no import: this process
    stays off JAX)."""
    from importlib import metadata

    try:
        return metadata.version(module)
    except metadata.PackageNotFoundError:
        return "not installed"


if __name__ == "__main__":
    sys.exit(main())
