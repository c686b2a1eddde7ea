"""Time an expert share's layer under the XLA loop and under the grouped
kernel on the chip.

``chiprun -- python scripts/moe_microbench.py [--quick]`` (PERF.md section 6,
PR 44; ``--quick``: the kernel as the code chooses it, no other tile).
One process, one chip. Each candidate runs ``llama._moe_share`` itself (the
router, the sort, the blocks, the gather back) over the same seeded tokens
at an expert cell's widths, through ``LAYERS`` layers of a whole int8
stack under one ``jit``, each layer's output the next one's input; the
time is the host clock around ``block_until_ready`` over ``REPS`` calls
after one that compiles, per layer, beside the blocks in use a layer (the
device's own plan, recomputed on the host from the router's choice), so
that a difference divides into microseconds a block. The kernel's output
is compared with the loop's at the FIRST layer (the same tokens to the same
experts; further down a near-tied expert flips under bfloat16 and one
token's row differs by its whole size). Lines go to stdout and to
``chiprun_out/moe_microbench.jsonl``. On the CPU it refuses to run: a time
from there would mean nothing (``--rehearse`` walks the same control flow
there at the tiny presets with the kernel interpreted, prints no time and
exits 3).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from opsagent_tpu.models import llama  # noqa: E402
from opsagent_tpu.models.config import get_config_preset  # noqa: E402
from opsagent_tpu.ops import moe_experts_pallas as grouped  # noqa: E402

LAYERS, REPS = 4, 20
# columns of the intermediate width a grid step, tried beside the kernel's own
TILES = {"cell5": (512, 768, 1536), "cell3": (256, 640, 1280)}


def cell_config(name: str):
    """(config cut to LAYERS expert layers, token counts to time)."""
    if name == "cell5":         # glm47-flash-l12: 64 of 64, top-4
        cfg = get_config_preset("glm-4.7-flash")
        return dataclasses.replace(
            cfg, num_layers=cfg.moe_layer_start + LAYERS), (256, 64)
    if name == "cell3":         # solar-open2-ep8-l8: 40 of 320, top-8
        full = get_config_preset("solar-open2-250b")
        return dataclasses.replace(
            full, num_layers=LAYERS, vocab_size=24576,
            moe=dataclasses.replace(full.moe, num_experts=40)), (256, 32)
    return get_config_preset(name), (37,)


def expert_layers(params):
    """Every expert layer of a tree as (stack, index in its leading axes)."""
    out = []

    def walk(tree):
        if "eg" in tree:
            lead = tree["eg"].q.shape[:-3]
            out.extend((tree, idx) for idx in np.ndindex(*lead))
            return
        for sub in tree.values():
            if isinstance(sub, dict):
                walk(sub)

    walk(params["moe_layers"])
    return out[:LAYERS]


def blocks_a_layer(cfg, layers, h, bm: int) -> float:
    """Blocks in use a layer at ``bm`` rows, from the router's own choice
    over the first layer's input (every layer sees other tokens' values,
    the same count of them)."""
    m = cfg.moe
    stack, idx = layers[0]
    _, chosen, _ = llama._route(h, llama._LayerView(stack, idx, True), cfg)
    local = np.asarray(chosen).reshape(-1) - m.first_expert
    sizes = np.bincount(
        local[(local >= 0) & (local < m.num_experts)], minlength=m.num_experts)
    return float(np.sum(-(-sizes // bm)))


def run(impl: str, cfg, layers, h):
    # the stacks go in as arguments: closed over, every program would hold
    # gigabytes of them as constants
    stacks = list({id(stack): stack for stack, _ in layers}.values())
    where = [(next(i for i, s in enumerate(stacks) if s is stack), idx)
             for stack, idx in layers]

    def step(h, stacks):
        first = None
        for i, idx in where:
            y, _ = llama._moe_share(
                h, llama._LayerView(stacks[i], idx, True), cfg, None, impl)
            first = y if first is None else first
            h = h + y
        return first, h

    fn = jax.jit(step)
    first, _ = jax.block_until_ready(fn(h, stacks))
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(h, stacks))
        times.append(time.perf_counter() - t0)
    return first, float(np.median(times)) / len(layers)


def main() -> int:
    rehearse = "--rehearse" in sys.argv
    quick = rehearse or "--quick" in sys.argv   # the kernel as chosen, alone
    platform = jax.devices()[0].platform
    if platform != "tpu" and not rehearse:
        print(f"moe_microbench: {platform} is not the chip; --rehearse "
              "walks the control flow without a time", file=sys.stderr)
        return 1
    if rehearse:
        os.environ["OPSAGENT_PALLAS_INTERPRET"] = "1"
    cells = ("tiny-glm-flash", "tiny-hybrid") if rehearse else (
        "cell5", "cell3")
    chosen = grouped.f_tile
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_microbench.jsonl", "a") as log:
        for cell in cells:
            cfg, token_counts = cell_config(cell)
            layers = expert_layers(llama.init_params_random_quantized(cfg, 0))
            for tokens in token_counts:
                h = jax.random.normal(
                    jax.random.PRNGKey(tokens), (1, tokens, cfg.hidden_size)
                ).astype(jnp.float32 if rehearse else jnp.bfloat16)
                want, loop_s = run("xla", cfg, layers, h)
                d, f = cfg.hidden_size, cfg.moe.expert_intermediate_size
                least = grouped.MIN_BLOCK_ROWS
                variants = [(tf, least) for tf in sorted(
                    {chosen(d, f), *TILES.get(cell, ())})]
                if quick:
                    variants = [(chosen(d, f), least)]
                elif not rehearse:  # blocks twice as tall at the chosen tile
                    variants.append((chosen(d, f), 2 * least))
                for tf, rows in variants:
                    grouped.f_tile = lambda d, f, tf=tf: tf
                    grouped.MIN_BLOCK_ROWS = rows
                    try:
                        got, kernel_s = run(grouped.IMPL, cfg, layers, h)
                    except Exception as e:  # noqa: BLE001 - the compiler's no
                        print(json.dumps(dict(
                            cell=cell, tokens=tokens, tf=tf,
                            refused=str(e)[-400:])), flush=True)
                        continue
                    gap = float(jnp.max(jnp.abs(
                        got.astype(jnp.float32) - want.astype(jnp.float32))))
                    line = dict(
                        cell=cell, tokens=tokens, layers=len(layers),
                        platform=platform, tf=tf, least_rows=rows,
                        chosen=(tf, rows) == (chosen(d, f), least),
                        blocks_loop=blocks_a_layer(
                            cfg, layers, h,
                            llama._share_buffer(cfg.moe, tokens)[0]),
                        blocks_kernel=blocks_a_layer(
                            cfg, layers, h,
                            llama._share_buffer(cfg.moe, tokens, rows)[0]),
                        gap_max=gap,
                        scale=float(jnp.max(jnp.abs(want.astype(jnp.float32)))),
                    )
                    if not rehearse:
                        line.update(
                            loop_us_a_layer=loop_s * 1e6,
                            kernel_us_a_layer=kernel_s * 1e6)
                    print(json.dumps(line), flush=True)
                    log.write(json.dumps(line) + "\n")
                grouped.f_tile, grouped.MIN_BLOCK_ROWS = chosen, least
    return 3 if rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
