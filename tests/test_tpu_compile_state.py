"""The chip's compiler, asked without the chip (tests/tpu_compile_common.py has
the how and why): the recurrent state. The state kernel and the scan kernel
at the cells' shapes, and the step programs of the cells whose models keep a
state (Solar-Open2's and Olmo-Hybrid's linear layers, Jamba's Mamba layers):
no program copies every slot, the state moves only inside the kernel, and
Jamba's whole 28 layers fit the chip.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opsagent_tpu.models import llama
from opsagent_tpu.models.config import get_config_preset
from opsagent_tpu.ops import kernels
from opsagent_tpu.ops import linear_state_pallas as lsp
from opsagent_tpu.ops.kernels import Kernels
from tpu_compile_common import (  # noqa: F401 (fixtures)
    CHIP_HBM_BYTES,
    PAGE,
    STATE_CELLS,
    STREAM_CELLS,
    _copies_of,
    _fused_block,
    _one_chip,
    _results_outside_fusions,
    _state_cell_mixed_step,
    v5e,
)


def test_a_linear_layer_reads_its_rows_state_without_copying_every_slot(v5e):
    """Olmo-Hybrid-7B's widths at one period (three linear layers and one
    attention layer), the cell's 16 rows x 32 slots packed to 256 tokens,
    2048 pages and 48 state slots: the mixed step holds no operation as
    large as the whole state. Read as ONE gather of 2.2 MB rows the chip's
    compiler first slices all of ``[layers x slots, ...]`` into pieces a
    row of which is under a megabyte (``mini-gather-slice``), in every
    layer: 2.4 GB of temporaries at the model's 24 linear layers, which did
    not fit the chip (compile, PR 33). ``llama._state_read`` takes a row at
    a time, and the program's scratch HBM is under 256 MB."""
    from opsagent_tpu.models import llama
    from opsagent_tpu.serving import decode_loop

    sds = _one_chip(v5e)
    cfg = dataclasses.replace(get_config_preset("olmo-hybrid-7b"), num_layers=4)
    b, s, n, maxp, slots = 16, 32, 2048, 336, 48
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: sds(x.shape, x.dtype), tree)
    params = on_chip(jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)))
    cache = on_chip(jax.eval_shape(lambda: llama.make_cache(
        cfg, n, PAGE, jnp.bfloat16, state_slots=slots,
        form=llama.cache_form(cfg, 1, "pallas-stream"))))
    assert cache["state"].shape == (3, slots, 4320, 128), "nothing padded"
    key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    i32 = lambda *d: sds(d, jnp.int32)       # noqa: E731
    f32 = lambda *d: sds(d, jnp.float32)     # noqa: E731
    flag = lambda *d: sds(d, jnp.bool_)      # noqa: E731

    def step(params, tokens, use_carry, carry, starts, qlens, emits, cache,
             table, key, temps, top_k, top_p):
        return decode_loop.mixed_step_carry(
            params, cfg, tokens, use_carry, carry, starts, qlens, emits,
            cache, table, key, temps, top_k, top_p,
            kernels=Kernels(attn="pallas-stream"), step_tokens=256)

    compiled = jax.jit(step, donate_argnames=("cache",)).lower(
        params, i32(b, s), flag(b), i32(b), i32(b), i32(b), flag(b), cache,
        i32(b, maxp + llama.STATE_COLUMNS), key, f32(b), i32(b), f32(b),
    ).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "mini-gather" not in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


# -- the state kernel at the state cells' own shapes ---------------------------
def _state_kernel(sds, cell: str, b: int, s: int):
    """Compile ``delta_rule_slots`` over one cell's whole state and conv
    arrays as ``llama.make_state`` holds them for the kernel, both donated."""
    h, dk, dv, by_channel, layers, slots, width = STATE_CELLS[cell]
    p = lsp.heads_packed(dv)
    f32 = lambda *d: sds(d, jnp.float32)     # noqa: E731
    i32 = lambda *d: sds(d, jnp.int32)       # noqa: E731
    n = layers * slots
    return jax.jit(lsp.delta_rule_slots, donate_argnums=(5, 6)).lower(
        f32(b, s, h, dk), f32(b, s, h, dk), f32(b, s, h, dv),
        f32(b, s, h, dk) if by_channel else f32(b, s, h), f32(b, s, h),
        f32(n, h // p, dk, p * dv),
        sds((n, *lsp.conv_slot_shape(width)), jnp.bfloat16),
        sds((b, width), jnp.bfloat16), i32(b), i32(b), sds((b,), jnp.bool_),
        i32(b),
    ).compile()


@pytest.mark.parametrize(
    "cell,s", [(cell, s) for cell in STATE_CELLS for s in (1, 16, 256)])
def test_state_kernel_compiles_at_the_cells_shapes(v5e, cell, s):
    """The fused block's ``[B, 1]``, the mixed bucket ``[B, 16]`` and the
    prefill bucket (``EngineConfig.prefill_batch`` rows of 256) of both
    state cells: a decay a channel at 128 x 128 and a decay a head at 96 x
    192 with two heads side by side. The state and the conv tails go
    through the call in place: no operation but the call gives an array of
    their shapes, and the program's scratch HBM is the re-layout of q, k,
    v and the decay."""
    h, dk, dv, _, layers, slots, width = STATE_CELLS[cell]
    b = STREAM_CELLS[cell]["b"] if s <= 16 else 4
    compiled = _state_kernel(_one_chip(v5e), cell, b, s)
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    whole = {layers * slots * h * dk * dv,
             layers * slots * int(np.prod(lsp.conv_slot_shape(width)))}
    made = [
        f"{name} {kind}{list(dims)} {op}"
        for _, name, kind, dims, op in _results_outside_fusions(hlo)
        if int(np.prod(dims)) in whole
        and op not in ("parameter", "get-tuple-element", "bitcast")]
    assert made == []
    assert compiled.memory_analysis().temp_size_in_bytes < 192 << 20


def _state_sized(hlo: str, cache, rows: int):
    """What an optimized module says of state-sized arrays: the operations
    outside fusions whose result has the shape of the whole ``state`` or
    ``conv`` array (stacked by layer, or flat over layers x slots), and
    whether any array anywhere, a fusion's inside included, has the shape
    of every row's state at once."""
    whole = set()
    for leaf in (cache["state"], cache["conv"]):
        whole |= {leaf.shape, (leaf.shape[0] * leaf.shape[1], *leaf.shape[2:])}
    passes = sorted({
        op for _, _, _, dims, op in _results_outside_fusions(hlo)
        if dims in whole} - {"parameter", "get-tuple-element", "bitcast"})
    per_row = ",".join(str(d) for d in (rows, *cache["state"].shape[2:]))
    return passes, f"f32[{per_row}]" in hlo


def test_cell_3s_mixed_step_moves_state_only_inside_the_kernel(v5e):
    """Cell 3's mixed program with its slots held for the state kernel:
    two custom calls a period body (attention, state), no array shaped like
    all 32 rows' state (``f32[32,64,128,128]``: under XLA the gathered S0,
    the chunk form's products and S1, four to five passes a layer) and no
    operation shaped like the whole ``state`` or ``conv`` array: the call
    updates both in place (``input_output_aliases``), so neither the state's
    two scatters nor the tail's two whole-array ``dynamic-update-slice``
    passes are left. Held for XLA, the same program shows all of them: the
    test cannot pass for want of something to find."""
    sds = _one_chip(v5e)
    cell = "solar-open2-ep8-l8.doc-turns"
    cache, compiled = _state_cell_mixed_step(sds, cell, "pallas-state")
    assert cache["state"].shape == (3, 128, 64, 128, 128)
    assert cache["conv"].shape == (3, 128, 576, 128)
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 2
    passes, per_row = _state_sized(hlo, cache, 32)
    assert passes == [] and not per_row
    # the parent's program, the slots held for XLA
    cache, compiled = _state_cell_mixed_step(sds, cell, "xla")
    assert cache["conv"].shape == (3, 128, 73728)
    passes, per_row = _state_sized(compiled.as_text(), cache, 32)
    assert per_row and "dynamic-update-slice" in passes, passes


def test_state_kernel_is_exported_once_a_shape(v5e, tmp_path, monkeypatch):
    """As the streaming kernel: a second program holding the state kernel
    at the same shape inlines the exported bytes, and a new process reads
    them back from beside the compile cache."""
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    traced = []
    kernel = lsp._kernel
    monkeypatch.setattr(
        lsp, "_kernel", lambda *a, **kw: traced.append(1) or kernel(*a, **kw))
    cell = "olmo-hybrid-7b.log-turns"

    def new_process():
        lsp._kernel_call.cache_clear()
        jax.clear_caches()

    def compiled():
        return _state_kernel(_one_chip(v5e), cell, 16, 1).as_text()

    try:
        new_process()
        assert "tpu_custom_call" in compiled() and len(traced) == 1
        files = [f for f in os.listdir(tmp_path) if f.endswith(".export")]
        assert len(files) == 1 and files[0].startswith("linear_state-")
        jax.clear_caches()              # another program, the same shape
        compiled()
        assert len(traced) == 1
        new_process()
        assert "tpu_custom_call" in compiled() and len(traced) == 1
        os.remove(tmp_path / files[0])
        new_process()
        compiled()
        assert len(traced) == 2
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        new_process()


# -- AI21-Jamba2-3B: Mamba layers over the state slots (PR 42) ------------------
def _jamba_cell():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmarks", "configs", "jamba2-3b-int8.json")) as f:
        return json.load(f)


def _jamba_shapes(sds, state_impl: str = "pallas-ssm"):
    """The cell's model whole (28 layers, int8 leaves, its own head), its
    pages for the streaming kernel and its state slots (held for the scan
    kernel, as the engine holds them on a TPU), as shapes on the chip, with
    the cell's engine settings."""
    engine = _jamba_cell()["engine"]
    cfg = get_config_preset("jamba2-3b-untied")
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: sds(x.shape, x.dtype), tree)
    params = on_chip(jax.eval_shape(
        lambda: llama.init_params_random_quantized(cfg, 0)))
    cache = on_chip(jax.eval_shape(lambda: llama.make_cache(
        cfg, engine["num_pages"], PAGE, jnp.bfloat16,
        state_slots=engine["max_batch_size"] + engine["state_snapshots"],
        form=llama.cache_form(cfg, 1, "pallas-stream"),
        state_impl=state_impl)))
    key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    return engine, cfg, params, cache, key


def _held(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def test_jambas_state_slots_are_held_with_nothing_padded(v5e):
    """A slot of the 3B: 26 layers of ``[16, 5120]`` float32 (the channels
    on the lanes, the 16 state indices on the sublanes) and a flat conv
    tail of 3 x 5120 bfloat16: 9,318,400 B, what ISSUE 42 reckoned. Held
    row-major, the published ``[5120, 16]`` would pad its minor 16 to 128
    lanes (or be held in a layout of the compiler's choosing, copied at
    every program's entry: ``MLAConfig.page_dim``). Pinned on the chip's own
    layouts: the restore program (``copy_state_slots``, the cache donated)
    holds the arrays' bytes, pages at one kv head included, and not a tile
    more."""
    sds = _one_chip(v5e)

    def per_slot(cache, slots):
        return sum(
            int(np.prod(cache[p].shape)) * cache[p].dtype.itemsize // slots
            for p in ("state", "conv"))

    engine, cfg, _, cache, _ = _jamba_shapes(sds, "xla")
    slots = engine["max_batch_size"] + engine["state_snapshots"]
    assert cache["state"].shape == (26, slots, 16, 5120)
    assert cache["state"].dtype == jnp.float32
    assert cache["conv"].shape == (26, slots, 15360)
    assert per_slot(cache, slots) == 9_318_400 == 26 * (
        16 * 5120 * 4 + 3 * 5120 * 2)
    # as the engine holds them on the chip: the same state, and a tail as
    # whole tiles of rows of 128 (120 rows of it used), 0.6% more a slot
    assert kernels.ssm_state_backend(
        platform="tpu", state_dtype="float32", d_state=16, d_inner=5120
    ) == "pallas-ssm"
    engine, cfg, _, cache, _ = _jamba_shapes(sds)
    assert cache["state"].shape == (26, slots, 16, 5120)
    assert cache["conv"].shape == (26, slots, 128, 128)
    assert per_slot(cache, slots) == 9_371_648 < 1.006 * 9_318_400
    assert jax.tree.leaves(cache["k"])[0].shape == (2, 16384, PAGE, 1, 128)
    # 2 attention layers x (k, v) x one kv head of 128 bfloat16
    assert 2 * 2 * 128 * 2 == 1024
    i32 = lambda *d: sds(d, jnp.int32)       # noqa: E731
    compiled = jax.jit(
        llama.copy_state_slots, donate_argnames=("cache",)
    ).lower(cache, i32(8), i32(8)).compile()
    m = compiled.memory_analysis()
    arrays = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                 for a in jax.tree.leaves(cache))
    assert arrays <= m.argument_size_in_bytes < 1.001 * arrays + 4096


def test_jambas_mixed_step_whole_fits_the_chip(v5e):
    """The cell's one mixed program WHOLE (28 layers, int8 weights, the
    full vocabulary, 64 rows x 16 slots packed to 256 tokens, 16,384 pages,
    256 state slots): the streaming kernel reads the two attention layers'
    pages at one kv head, no operation copies the whole state array, and
    arguments, results and scratch together fit the chip's memory."""
    from opsagent_tpu.serving import decode_loop

    sds = _one_chip(v5e)
    engine, cfg, params, cache, key = _jamba_shapes(sds)
    b, s = engine["max_batch_size"], engine["mixed_buckets"][-1]
    assert (b, engine["mixed_buckets"], engine["max_step_tokens"]) == (
        64, [s], 256)
    assert llama.pack_widths(b * s, 256) == (256, 128)
    i32 = lambda *d: sds(d, jnp.int32)       # noqa: E731
    f32 = lambda *d: sds(d, jnp.float32)     # noqa: E731
    flag = lambda *d: sds(d, jnp.bool_)      # noqa: E731

    def step(params, tokens, use_carry, carry, starts, qlens, emits, cache,
             table, key, temps, top_k, top_p):
        return decode_loop.mixed_step_carry(
            params, cfg, tokens, use_carry, carry, starts, qlens, emits,
            cache, table, key, temps, top_k, top_p,
            kernels=Kernels(attn="pallas-stream", state="pallas-ssm"),
            step_tokens=256)

    compiled = jax.jit(step, donate_argnames=("cache",)).lower(
        params, i32(b, s), flag(b), i32(b), i32(b), i32(b), flag(b), cache,
        i32(b, engine["max_pages_per_seq"] + llama.STATE_COLUMNS), key,
        f32(b), i32(b), f32(b),
    ).compile()
    hlo = compiled.as_text()
    # the streaming attention kernel and the scan kernel, once a run's body
    assert hlo.count("tpu_custom_call") >= 3 and "mini-gather" not in hlo
    assert _copies_of(hlo, int(np.prod(cache["state"].shape))) == []
    # no operation shaped like the whole ``state`` or ``conv`` array: the
    # kernel takes a row's slot in and out itself, both arrays in place
    # (all 64 rows' state at once is the shape of this bucket's x and dt,
    # 64 x 16 x 5120, so that is asked of the decode block)
    assert _state_sized(hlo, cache, b)[0] == []
    m = compiled.memory_analysis()
    held = _held(compiled)
    assert held < CHIP_HBM_BYTES, f"{held / 2**30:.2f} GiB"
    print(f"jamba mixed step: arguments {m.argument_size_in_bytes / 2**30:.2f}"
          f" GiB, scratch {m.temp_size_in_bytes / 2**30:.2f} GiB, held "
          f"{held / 2**30:.2f} GiB")


def test_jambas_decode_block_whole_fits_the_chip(v5e):
    """Eight greedy passes of all 64 rows under one scan, pages and slots
    its carry and donated: no whole-state copy, and it fits."""
    from opsagent_tpu.serving import decode_loop

    sds = _one_chip(v5e)
    engine, cfg, params, cache, key = _jamba_shapes(sds)
    b = engine["max_batch_size"]
    i32 = lambda *d: sds(d, jnp.int32)       # noqa: E731
    f32 = lambda *d: sds(d, jnp.float32)     # noqa: E731

    def block(params, tokens, write_at, active, budgets, cache, table, key,
              temps, top_k, top_p, eos, pad):
        return _fused_block(
            params, cfg, tokens, write_at, active, budgets, cache, table,
            key, temps, top_k, top_p, eos, pad,
            n_steps=engine["decode_block"], greedy=True,
            kernels=Kernels(attn="pallas-stream", state="pallas-ssm"))

    compiled = jax.jit(block, donate_argnames=("cache",)).lower(
        params, i32(b), i32(b), sds((b,), jnp.bool_), i32(b), cache,
        i32(b, engine["max_pages_per_seq"] + llama.STATE_COLUMNS), key,
        f32(b), i32(b), f32(b), i32(), i32(),
    ).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 3
    assert _copies_of(hlo, int(np.prod(cache["state"].shape))) == []
    passes, per_row = _state_sized(hlo, cache, b)
    assert passes == [] and not per_row
    held = _held(compiled)
    assert held < CHIP_HBM_BYTES, f"{held / 2**30:.2f} GiB"
    print(f"jamba decode block: scratch "
          f"{compiled.memory_analysis().temp_size_in_bytes / 2**30:.2f} GiB, "
          f"held {held / 2**30:.2f} GiB")


@pytest.mark.parametrize("b,s", [(64, 16), (64, 1), (1, 256), (8, 256)],
                         ids=["mixed", "decode", "prefill-1", "prefill-8"])
def test_scan_kernel_compiles_at_the_cells_shapes(v5e, b, s):
    """The scan kernel alone at the 3B's ``[16, 5120]`` state over the
    cell's 26 x 256 slots: the mixed bucket, a decode pass, and the prefill
    bucket of 256 (a row's channels in four blocks)."""
    from opsagent_tpu.ops import selective_scan_pallas as ssp

    sds = _one_chip(v5e)
    c, n, slots, w = 5120, 16, 26 * 256, 15360
    f32 = lambda *d: sds(d, jnp.float32)     # noqa: E731
    i32 = lambda *d: sds(d, jnp.int32)       # noqa: E731
    compiled = jax.jit(ssp.selective_scan_slots, donate_argnums=(5, 6)).lower(
        f32(b, s, c), f32(b, s, c), f32(n, c), f32(b, s, n), f32(b, s, n),
        f32(slots, n, c), sds((slots, *lsp.conv_slot_shape(w)), jnp.bfloat16),
        sds((b, w), jnp.bfloat16), i32(b), i32(b), sds((b,), jnp.bool_),
        i32(b)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
