"""The chip's compiler, asked without the chip: what the files
``tests/test_tpu_compile_*.py`` share (the set-up, the described device, the
cells' shapes, and the compiles of whole step programs).

Interpret mode runs a Pallas kernel's body on the CPU and knows nothing
of Mosaic: block shapes the TPU lowering refuses, ops it cannot legalize,
slices off the memory tiling and blocks that overrun VMEM all pass there.
The TPU compiler is installed beside JAX and compiles for a device that
is DESCRIBED (``v5e:2x2``), not attached, so every Pallas entry point is
compiled here at the widths of the model the chip smoke serves
(``qwen2.5-7b-instruct``: 28/4 heads of 128, hidden 3584, FFN 18944, vocab
152064; 2048 pages of 16 tokens, 320 per sequence) and at the benchmark
cells' shapes — kernels only, a second or two each; and so is the gather
where it is the only reader (int8 pages, MLA). A combination the choice
function sends to the gather (``ops.kernels.pallas_refusal``) is pinned
from both sides: the kernel's or the compiler's refusal, and the choice.

A compile that passes is not a chip run, and nothing here is a time.
"""

import dataclasses
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp
# Describing a topology loads libtpu, which by default is one process's at a
# time (a lock file); nothing here touches a device, so test workers and a
# builder's scratch compile may share it.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from opsagent_tpu.models import llama  # noqa: E402
from opsagent_tpu.models.config import get_config_preset  # noqa: E402
from opsagent_tpu.ops.kernels import Kernels  # noqa: E402

CFG = get_config_preset("qwen2.5-7b-instruct")
H, K, D, L = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim_, CFG.num_layers
N, PAGE, MAXP, B = 2048, 16, 320, 8  # EngineConfig / serve-engine defaults


@pytest.fixture(scope="module")
def v5e():
    """The four described devices of a v5e 2x2 host. The persistent
    compile cache is off around these compiles: an executable built for
    a described device is written to it but cannot be read back without
    a chip (a warning per compile, and an entry nothing can use)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no libtpu, or it is locked
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _one_chip(devices):
    one = SingleDeviceSharding(devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one
    )


# -- the streaming kernel at the benchmark cells' own engine shapes -----------
# (benchmarks/configs/*.json: rows, mixed and prefill buckets, heads, pages a
# sequence, pages; layers as many as the cell's cache stacks.)
STREAM_CELLS = {
    "qwen25-7b.agent-turns": dict(
        b=32, h=28, k=4, maxp=384, n=2560, layers=28, s=(1, 16, 32, 256)),
    "qwen25-72b-l8.long-generate": dict(
        b=16, h=64, k=8, maxp=104, n=2048, layers=8, s=(1, 16, 32, 64, 256)),
    "solar-open2-ep8-l8.doc-turns": dict(
        b=32, h=64, k=8, maxp=512, n=12288, layers=2, s=(1, 16, 256)),
    "olmo-hybrid-7b.log-turns": dict(
        b=16, h=30, k=30, maxp=336, n=2048, layers=8, s=(1, 16, 256)),
}
# The cells whose models keep a recurrent state: (linear heads, key dim,
# value dim, a decay a channel, linear layers, state slots a layer: a live
# one a row and the snapshots, conv tail width).
STATE_CELLS = {
    "solar-open2-ep8-l8.doc-turns": (64, 128, 128, True, 6, 32 + 96, 73728),
    "olmo-hybrid-7b.log-turns": (30, 96, 192, False, 24, 16 + 32, 34560),
}


def _latent_reader(cfg, **other):
    """What an engine of an MLA model that holds the latent tells the
    choice (``Engine.__init__``): the shapes its READER is handed."""
    return dict(dict(
        head_dim=cfg.mla.page_dim, kv_heads_per_shard=1, page_itemsize=2,
        mla=True, shared_kv=True), **other)


# -- the KV pages' held form: no step re-tiles the whole cache ---------------
# The cells' cache geometry (benchmarks/configs): the 7B holds 2560 pages of
# 16 tokens, 384 a sequence; the 72B widths 2048 pages, 104 a sequence.
# Two layers and eight rows keep a gathered block (rows x pages a sequence)
# smaller than one layer-stacked K array, so size alone tells them apart.
STEP_ROWS, STEP_TOKENS, STEP_LAYERS = 8, 32, 2
GEOMETRY = {
    "qwen2.5-7b-instruct": (2560, 384),
    "qwen2.5-72b-instruct": (2048, 104),
}


def _copies_of(hlo: str, elements: int, axes=None) -> list[str]:
    """Names of the ``copy`` instructions of an optimized HLO module whose
    result has at least ``elements`` elements (and, given ``axes``, those
    axes in any order), wherever they sit: in the layer loop's body or at
    the program's entry or exit."""
    out = []
    for name, dims in re.findall(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]+)\]\S* copy\(", hlo, re.M
    ):
        sizes = [int(d) for d in dims.split(",")]
        if np.prod(sizes) >= elements and (
            axes is None or sorted(sizes) == sorted(axes)
        ):
            out.append(f"{name}[{dims}]")
    return out


def _step_shapes(sds, preset: str, kv: str, impl: str,
                 layers: int = STEP_LAYERS, int8: bool = False):
    """A preset's widths cut to ``layers`` layers, with parameters (bf16,
    or the int8 leaves the cells serve), the cache ``llama.make_cache``
    gives it for the attention backend ``impl``, a key, and makers of
    row-shaped arguments, all as shapes on the chip."""
    cfg = dataclasses.replace(get_config_preset(preset), num_layers=layers)
    n, _ = GEOMETRY[preset]
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: sds(x.shape, x.dtype), tree
    )
    params = on_chip(jax.eval_shape(
        (lambda: llama.init_params_random_quantized(cfg, 0)) if int8 else
        (lambda: llama.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    ))
    cache = on_chip(jax.eval_shape(
        lambda: llama.make_cache(
            cfg, n, PAGE, jnp.bfloat16, kv_quantize=kv,
            form=llama.cache_form(cfg, 1, impl),
        )
    ))
    key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    return cfg, params, cache, key


def _whole_cache_copies(compiled, cfg, preset: str, impl: str, axes=None):
    whole = (
        cfg.num_layers * GEOMETRY[preset][0] * PAGE
        * cfg.num_kv_heads * cfg.head_dim_
    )
    hlo = compiled.as_text()
    assert ("tpu_custom_call" in hlo) == (impl != "xla")
    return _copies_of(hlo, whole, axes)


def _mixed_step(sds, preset: str, kv: str, impl: str = "xla", *,
                rows: int = STEP_ROWS, tokens: int = STEP_TOKENS,
                step_tokens: int = 0, layers: int = STEP_LAYERS,
                int8: bool = False, experts: str = "xla"):
    """Compile the engine's ``_mixed_carry`` program (decode_loop.
    mixed_step_carry, the cache donated) at a preset's widths cut to
    ``layers`` layers, with the cache ``llama.make_cache`` gives it for
    the attention backend ``impl`` (``experts``: who runs an expert share's
    blocks beside it); ``rows`` x ``tokens`` slots, packed to
    ``step_tokens`` where half of that is fewer."""
    from opsagent_tpu.serving import decode_loop

    cfg, params, cache, key = _step_shapes(sds, preset, kv, impl, layers, int8)
    maxp = GEOMETRY[preset][1]
    b = rows
    i32 = lambda *s: sds(s, jnp.int32)       # noqa: E731
    f32 = lambda *s: sds(s, jnp.float32)     # noqa: E731
    flag = lambda *s: sds(s, jnp.bool_)      # noqa: E731

    def step(params, tokens, use_carry, carry, starts, qlens, emits, cache,
             table, key, temps, top_k, top_p):
        return decode_loop.mixed_step_carry(
            params, cfg, tokens, use_carry, carry, starts, qlens, emits,
            cache, table, key, temps, top_k, top_p,
            kernels=Kernels(attn=impl, experts=experts),
            step_tokens=step_tokens,
        )

    compiled = jax.jit(step, donate_argnames=("cache",)).lower(
        params, i32(b, tokens), flag(b), i32(b), i32(b), i32(b),
        flag(b), cache, i32(b, maxp), key, f32(b), i32(b), f32(b),
    ).compile()
    return cfg, cache, _whole_cache_copies(compiled, cfg, preset, impl), compiled


def _fused_block(params, cfg, tokens, write_at, active, budgets, cache, table,
                 key, temps, top_k, top_p, eos, pad, **how):
    """``decode_loop.decode_block_carry`` with every lane seated anew."""
    from opsagent_tpu.serving import decode_loop

    return decode_loop.decode_block_carry(
        params, cfg, tokens, write_at, jnp.zeros_like(active), key,
        jnp.ones_like(active), tokens, write_at, active, budgets, cache,
        table, temps, top_k, top_p, eos, pad, **how)


def _decode_block_compiled(sds, preset: str, impl: str, steps: int = 8, *,
                           rows: int = STEP_ROWS, layers: int = STEP_LAYERS,
                           int8: bool = False, experts: str = "xla"):
    """Compile the fused decode block (decode_loop.decode_block_carry:
    ``steps`` greedy passes under one scan, the cache its carry and
    donated), what a cell runs between admissions: (config, cache shapes,
    executable)."""
    cfg, params, cache, key = _step_shapes(sds, preset, "", impl, layers, int8)
    maxp = GEOMETRY[preset][1]
    b = rows
    i32 = lambda *s: sds(s, jnp.int32)       # noqa: E731
    f32 = lambda *s: sds(s, jnp.float32)     # noqa: E731

    def block(params, tokens, write_at, active, budgets, cache, table, key,
              temps, top_k, top_p, eos, pad):
        return _fused_block(
            params, cfg, tokens, write_at, active, budgets, cache, table,
            key, temps, top_k, top_p, eos, pad, n_steps=steps, greedy=True,
            kernels=Kernels(attn=impl, experts=experts),
        )

    compiled = jax.jit(block, donate_argnames=("cache",)).lower(
        params, i32(b), i32(b), sds((b,), jnp.bool_), i32(b), cache,
        i32(b, maxp), key, f32(b), i32(b), f32(b), i32(), i32(),
    ).compile()
    return cfg, cache, compiled


def _results_outside_fusions(hlo: str):
    """(computation, name, element type, dims, operation) of every
    instruction that is not inside a fused computation: what an optimized
    module writes to memory, as far as its text says."""
    for comp in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", hlo):
        head, _, body = comp.partition("\n")
        name = head.removeprefix("ENTRY ").split(" ")[0]
        if "fused_computation" in name:
            continue
        for m in re.finditer(
                r"^\s*(?:ROOT )?(%[\w.\-]+) = (\w+)\[([\d,]+)\]\S* ([\w\-]+)\(",
                body, re.M):
            dims = tuple(int(x) for x in m.group(3).split(","))
            yield name, m.group(1), m.group(2), dims, m.group(4)


def _state_cell_mixed_step(sds, cell: str, state_impl: str, layers: int = 4,
                           experts: str = "xla"):
    """A state cell's mixed program (all rows of its one bucket of 16,
    packed to 256 tokens, int8 leaves, the streaming kernel) at one period
    of its layers, the slots held for and updated by ``state_impl``."""
    from opsagent_tpu.serving import decode_loop

    if cell.startswith("solar"):        # as benchmarks/configs cuts it
        full = get_config_preset("solar-open2-250b")
        cfg = dataclasses.replace(
            full, num_layers=layers, vocab_size=24576,
            moe=dataclasses.replace(full.moe, num_experts=40))
    else:
        cfg = dataclasses.replace(
            get_config_preset("olmo-hybrid-7b"), num_layers=layers)
    c = STREAM_CELLS[cell]
    b, s = c["b"], 16
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: sds(x.shape, x.dtype), tree)
    params = on_chip(jax.eval_shape(
        lambda: llama.init_params_random_quantized(cfg, 0)))
    cache = on_chip(jax.eval_shape(lambda: llama.make_cache(
        cfg, c["n"], PAGE, jnp.bfloat16, state_slots=STATE_CELLS[cell][5],
        form=llama.cache_form(cfg, 1, "pallas-stream"),
        state_impl=state_impl)))
    key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    i32 = lambda *d: sds(d, jnp.int32)       # noqa: E731
    f32 = lambda *d: sds(d, jnp.float32)     # noqa: E731
    flag = lambda *d: sds(d, jnp.bool_)      # noqa: E731

    def step(params, tokens, use_carry, carry, starts, qlens, emits, cache,
             table, key, temps, top_k, top_p):
        return decode_loop.mixed_step_carry(
            params, cfg, tokens, use_carry, carry, starts, qlens, emits,
            cache, table, key, temps, top_k, top_p,
            kernels=Kernels(
                attn="pallas-stream", state=state_impl, experts=experts),
            step_tokens=256)

    compiled = jax.jit(step, donate_argnames=("cache",)).lower(
        params, i32(b, s), flag(b), i32(b), i32(b), i32(b), flag(b), cache,
        i32(b, c["maxp"] + llama.STATE_COLUMNS), key, f32(b), i32(b), f32(b),
    ).compile()
    return cache, compiled


# -- GLM-4.7-Flash at the new cell's shapes (glm47-flash-l12.longdoc-turns) ----
# benchmarks/configs/glm47-flash-l12-int8.json: 16 rows, 16,384 pages of 16,
# 1,216 a sequence, one mixed bucket of 16 packed to the step's 256 tokens,
# fused decode blocks of 8, int8 weights, 12 layers (one dense, 11 of 64
# experts), latent pages [12, 16384, 16, 640] (the 576-wide latent on whole lanes).
GEOMETRY["glm-4.7-flash"] = (16384, 1216)
CHIP_HBM_BYTES = 15.75 * 2**30      # what a v5e chip's runtime reports
