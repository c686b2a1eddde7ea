"""The one process that imports JAX and holds the chip.

Started by ``run.py`` as a child. It builds the cell's weights on the device
from the seed, hands them to the program's ``Engine`` with the engine
settings of the cell's configuration file, warms up the programs the cell's
traffic reaches, and serves the program's own HTTP app
(``build_engine_app``) on localhost: the normal served path.

Beside the program's routes it mounts four of the benchmark's own:
``GET /bench/info`` (device, set-up split), ``POST /bench/trace/start`` and
``/bench/trace/stop`` (a ``jax.profiler`` capture, reduced here by
``trace_reduce``), and ``POST /bench/finish``, which stops the scheduler,
reads the device's peak memory, frees the engine and only then runs the
plain reference over the sample of finished requests it is given.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import glob
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


from benchmarks.loading import load_data, load_family  # noqa: E402


def say(msg: str) -> None:
    print(f"[bench.server] {msg}", flush=True)


def model_config(config: dict):
    """The program's ModelConfig from the file's published keys, as the
    configuration's family builds it."""
    return load_family(config).model_config(config)


def _flat(fields: dict, prefix: str = "") -> dict:
    """A nested dict with dotted keys: ``{"moe": {"num_experts": 8}}`` ->
    ``{"moe.num_experts": 8}``."""
    out = {}
    for key, value in fields.items():
        if isinstance(value, dict):
            out.update(_flat(value, f"{prefix}{key}."))
        else:
            out[prefix + key] = value
    return out


def check_against_preset(config: dict, mc) -> None:
    """Every field of the file's model equals the in-tree preset it names;
    only the fields that the family's ``REDUCED`` map gives for the keys
    under ``reduced`` may differ."""
    import dataclasses

    from opsagent_tpu.models.config import get_config_preset

    preset = get_config_preset(config["preset"])
    ours = _flat(dataclasses.asdict(mc))
    theirs = _flat(dataclasses.asdict(preset))
    allowed = load_family(config).REDUCED
    unknown = [k for k in config.get("reduced", []) if k not in allowed]
    if unknown:
        raise SystemExit(
            f"reduced keys the family cannot cut: {unknown}; it allows "
            f"{sorted(allowed)}")
    skip = {allowed[k] for k in config.get("reduced", [])}
    diff = {k: (ours.get(k), theirs.get(k)) for k in sorted({*ours, *theirs})
            if k not in skip and ours.get(k) != theirs.get(k)}
    if diff:
        raise SystemExit(f"configuration differs from preset: {diff}")


def tree_builder(config: dict):
    """``root key -> the seeded weights in the layout the program serves``,
    to be jitted: each of the family's stacks filled layer by layer under
    ``lax.map``, an int8 pair held as a ``QuantizedLinear`` (its scale
    broadcast over the contraction axis), every other leaf as it is."""
    import jax
    import jax.numpy as jnp

    from benchmarks import weights as W
    from opsagent_tpu.models.quant import QuantizedLinear

    family = load_family(config)
    sz, no = family.sizes(config), family.LEAF_NO

    def served(leaf):
        if isinstance(leaf, tuple):
            q, scale = leaf
            return QuantizedLinear(q, scale[..., None, :])
        return leaf

    stacks = [(key, kind, jnp.arange(first, first + count, dtype=jnp.int32))
              for key, kind, first, count in family.stacks(sz)]

    def build(root):
        tree = {}
        for key, kind, layers in stacks:
            stacked = jax.lax.map(
                lambda layer: family.layer_leaves(root, kind, layer, sz),
                layers)
            tree[key] = {name: served(leaf) for name, leaf in stacked.items()}
        q, scale = W.matrix(root, no["lm_head"], 0, sz["d"], sz["v"])
        tree.update({
            "embed": W.embedding(root, no["embed"], sz["v"], sz["d"]),
            "final_norm": W.norm(root, no["final_norm"], 0, sz["d"]),
            "lm_head": QuantizedLinear(q, scale[None, :]),
        })
        return tree

    return build


def program_tree(config: dict, seed: int):
    """The cell's weights, made on the device in one jitted call."""
    import jax

    from benchmarks import weights as W

    return jax.jit(tree_builder(config))(W.root_key(seed))


def bench_tokenizer(vocab_size: int):
    """The program's byte tokenizer with the benchmark's text mapping
    (``tokens.py``): same ids, markers and byte values, every id visible."""
    from benchmarks import tokens
    from opsagent_tpu.serving.tokenizer import ByteTokenizer

    class BenchTokenizer(ByteTokenizer):
        def encode(self, text: str) -> list[int]:
            return tokens.encode(text)

        def decode(self, ids: list[int]) -> str:
            return tokens.decode(ids)

        def token_str(self, token_id: int) -> str:
            return tokens.char_of(token_id)

    return BenchTokenizer(vocab_size=vocab_size)


def break_tokens(engine, every: int) -> None:
    """Rehearsal only: alter every ``every``-th token where the engine
    accepts it, so that the test of ``correct`` can see it come out false."""
    accept = engine._accept_token
    count = [0]

    def broken(seq, token):
        count[0] += 1
        if count[0] % every == 0:
            token = 0x20 + (token + 1) % 0x5F
        return accept(seq, token)

    engine._accept_token = broken


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--break-every", type=int, default=0)
    ap.add_argument("--engine", action="append", default=[])
    args = ap.parse_args()

    config = load_data(args.config, args.rehearse)
    engine_cfg = dict(config["engine"])
    for setting in args.engine:     # a control run: see run.py --engine
        key, _, value = setting.partition("=")
        try:
            engine_cfg[key] = json.loads(value)
        except ValueError:
            engine_cfg[key] = value

    import jax
    import jax.numpy as jnp

    devs = jax.devices()
    say(f"devices: {len(devs)} x {devs[0].device_kind} ({devs[0].platform})")
    if not args.rehearse and (
        devs[0].platform != "tpu" or len(devs) < args.chips
    ):
        say(f"needs {args.chips} tpu chip(s): refusing to run")
        return 2
    if args.break_every and not args.rehearse:
        say("--break-every is a rehearsal flag")
        return 2

    from aiohttp import web

    from benchmarks import check, trace_reduce
    from opsagent_tpu.serving.api import ServingStack, build_engine_app
    from opsagent_tpu.serving.engine import Engine, EngineConfig

    mc = model_config(config)
    if not args.rehearse:
        check_against_preset(config, mc)

    t0 = time.perf_counter()
    params = program_tree(config, args.seed)
    jax.block_until_ready(params)
    weights_s = time.perf_counter() - t0

    level = engine_cfg.pop("warmup_level")
    dtype = jnp.dtype(engine_cfg.pop("dtype"))
    for key in ("prefill_buckets", "mixed_buckets"):
        engine_cfg[key] = tuple(engine_cfg[key])
    cfg = EngineConfig(
        model=config["preset"], dtype=dtype, warmup=False,
        seed=args.seed % (2**31 - 2), **engine_cfg,
    )
    t0 = time.perf_counter()
    engine = Engine(
        cfg, model_cfg=mc, params=params, params_quantized=True,
        tokenizer=bench_tokenizer(mc.vocab_size),
    )
    del params
    engine_s = time.perf_counter() - t0
    warmup_s = engine.warmup(level)
    if args.break_every:
        break_tokens(engine, args.break_every)
    cache_dir = engine.compile_cache_dir or ""
    setup = {
        "weights_s": weights_s, "engine_s": engine_s, "warmup_s": warmup_s,
        "warmup_level": level,
        "compile_cache_dir": cache_dir,
        "compile_cache_entries_at_start":
            engine.init_stats["compile_cache_entries_at_start"],
        "compile_cache_entries_after_warmup":
            len(os.listdir(cache_dir)) if cache_dir else 0,
        "impl": engine.impl_info(),
        "server_ready_s": time.perf_counter() - T_START,
    }
    say(f"set-up: {json.dumps(setup)}")

    stack = ServingStack(engine, restart_tolerant=False)
    app = build_engine_app(stack)
    trace_dir = os.path.join(args.out, "trace")
    state = {"t_trace": 0.0}

    async def info(request):
        return web.json_response(
            {"device": device_info(args.chips), "setup": setup}
        )

    async def trace_start(request):
        for old in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True):
            os.remove(old)
        # The trap of scope names: JAX's persistent compile cache keys a
        # program without its metadata, so an executable that another build
        # compiled first serves this one too, and the trace then shows THAT
        # build's ``jax.named_scope`` names. A traced run that must show
        # names this build (or a family's program) added compiles fresh;
        # ``run.py`` prints as much beside the cache's count at start.
        # The program's annotations and the device's operations; not every
        # Python call of the host, which would slow the host it measures.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        state["t_trace"] = time.perf_counter()
        return web.json_response({"ok": True})

    async def trace_stop(request):
        capture_s = time.perf_counter() - state["t_trace"]
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, jax.profiler.stop_trace)
        state["traced"] = True
        return web.json_response({"capture_s": capture_s})

    def reduce_trace() -> dict:
        """After the window: reading a profile is seconds of Python, which
        would hold the interpreter the scheduler needs."""
        found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise RuntimeError(f"no .xplane.pb under {trace_dir}")
        return trace_reduce.reduce_file(
            max(found, key=os.path.getmtime), chips=args.chips)

    async def finish(request):
        body = await request.json()

        def work():
            nonlocal engine
            stack.scheduler.stop()
            device = device_info(args.chips)
            say(f"window closed: {json.dumps(device)}")
            # Free the engine before the reference takes the device: the
            # peak above stays the program's.
            for leaf in jax.tree.leaves((engine.params, engine.cache)):
                leaf.delete()
            stack.scheduler.engine = None
            engine = None
            gc.collect()
            trace = reduce_trace() if state.get("traced") else None
            stats = jax.devices()[0].memory_stats() or {}
            say(f"engine freed: {stats.get('bytes_in_use', 0)} bytes in use "
                "before the reference runs")
            numbers = check.run_check(
                config, args.seed, body["samples"],
                control_bits=int(body.get("control_bits", 0)),
            )
            return {"device": device, "check": numbers, "trace": trace}

        loop = asyncio.get_running_loop()
        return web.json_response(await loop.run_in_executor(None, work))

    app.router.add_get("/bench/info", info)
    app.router.add_post("/bench/trace/start", trace_start)
    app.router.add_post("/bench/trace/stop", trace_stop)
    app.router.add_post("/bench/finish", finish)
    web.run_app(app, host="127.0.0.1", port=args.port, print=None,
                handle_signals=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
