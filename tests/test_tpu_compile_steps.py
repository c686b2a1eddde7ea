"""The chip's compiler, asked without the chip (tests/tpu_compile_common.py has
the how and why): the dense cells' WHOLE step programs, the mixed step and
the fused decode block as the engine jits them. The KV pages' held form: no
step re-tiles or copies a whole K or V array; a packed mixed step holds both
widths in one program and writes its keys and values by token.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opsagent_tpu.models import llama
from tpu_compile_common import (  # noqa: F401 (fixtures)
    D,
    GEOMETRY,
    H,
    K,
    PAGE,
    STEP_LAYERS,
    STEP_ROWS,
    STEP_TOKENS,
    _copies_of,
    _decode_block_compiled,
    _mixed_step,
    _one_chip,
    _results_outside_fusions,
    _whole_cache_copies,
    v5e,
)


def _decode_block(sds, preset: str, impl: str, steps: int = 8):
    """The whole-K-array copies of ``_decode_block_compiled``'s program."""
    cfg, cache, compiled = _decode_block_compiled(sds, preset, impl, steps)
    # By shape, not by size alone: at the 72B's widths one bf16 projection
    # stack [2, 8192, 8192] is larger than a K array, and this program
    # copies one at its entry (the cells' weights are int8: not compiled
    # here, ROADMAP S2).
    return _whole_cache_copies(compiled, cfg, preset, impl, cache["k"].shape)


@pytest.mark.parametrize("preset,kv,impl,form", [
    ("qwen2.5-7b-instruct", "", "xla", "merged"),    # cell 1: 4 kv heads
    ("qwen2.5-7b-instruct", "int8", "xla", "merged"),
    ("qwen2.5-72b-instruct", "", "xla", "split"),    # cell 2's widths: 8
    ("qwen2.5-72b-instruct", "int8", "xla", "split"),
    # What the chip runs since PR 29: the kernel reads merged pages at any
    # head count, and the page write's scatter runs in the same tiling.
    ("qwen2.5-7b-instruct", "", "pallas-stream", "merged"),
    ("qwen2.5-72b-instruct", "", "pallas-stream", "merged"),
])
def test_no_step_copies_a_whole_k_or_v_array(v5e, preset, kv, impl, form):
    """The mixed step holds no copy as large as one layer-stacked K array,
    in the layer loop or outside it: the page write's scatter and the page
    reader run in the tiling the pages are held in. Under the gather that
    is the merged form at 4 kv heads and the split one at 8 (where merged
    would add a copy of each gathered block); the streaming kernel gathers
    nothing and holds merged pages at both."""
    from opsagent_tpu.models import llama

    cfg, cache, copies, _ = _mixed_step(_one_chip(v5e), preset, kv, impl)
    assert llama.cache_form(cfg, 1, impl) == form
    n = GEOMETRY[preset][0]
    k, d = cfg.num_kv_heads, cfg.head_dim_
    row = (k * d,) if form == "merged" else (k, d)
    assert cache["k"].shape == (STEP_LAYERS, n, PAGE) + row
    assert copies == []


def test_cell_1s_mixed_step_runs_its_matmuls_over_the_steps_tokens(v5e):
    """Cell 1's widest mixed program, 32 rows of the 32-slot bucket under
    the kernel, packed to the step's 256 tokens (``Engine.step_tokens``):
    the FFN's matmuls take ``[256, 3584]`` and give ``[256, 18944]``, no
    array of 32 x 32 x 18944 elements is left anywhere, attention still
    sees q un-packed to its 32 x 32 rows, no dequantized weight or whole
    K array is written out, and the program's scratch HBM stays a few MB
    (the compiler's ``temp_size_in_bytes``: 1.53 MB over rows, 2.16 MB
    packed, compile, PR 32: in both the activations live in on-chip
    memory and the cache is updated in place, so the FFN's
    ``[32, 32, 18944]`` arrays, 38.8 MB each, never were HBM temporaries;
    at the 72B's widths with int8 weights it reads 62.2 MB over
    ``[16, 64]`` rows and 2.35 MB packed; since PR 34 the results of the
    three conditionals a layer are HBM buffers, 9.1 MB here)."""
    cfg, _, copies, compiled = _mixed_step(
        _one_chip(v5e), "qwen2.5-7b-instruct", "", "pallas-stream",
        rows=32, tokens=32, step_tokens=256)
    hlo = compiled.as_text()
    d, f = cfg.hidden_size, cfg.intermediate_size
    assert re.search(rf"bf16\[256,{f}\]\S* convolution\(", hlo)
    assert re.search(rf"bf16\[256,{d}\]\S* convolution\(", hlo)
    assert not re.search(rf"\[32,32,{f}\]", hlo)
    assert re.search(rf"bf16\[1024,{d}\]\S* gather\(", hlo)   # q, un-packed
    # k and v stay packed: the page write scatters the step's 256 tokens
    scatters, _ = _scatters_and_gathers(hlo)
    kv = cfg.num_kv_heads * cfg.head_dim_
    assert [u for _, u in scatters] == [(256, kv)] * 2
    assert copies == []
    assert not re.search(rf"bf16\[1,{d},{d}\]\S* fusion\(", hlo)  # a weight whole
    scratch = compiled.memory_analysis().temp_size_in_bytes
    assert scratch < 10 << 20, (
        f"{scratch / 1e6:.1f} MB of scratch: 1.9 MB with one width, 9.1 MB "
        "with Pack.dense's three conditionals a layer, whose results are "
        "HBM buffers; more than that is an array per row slot, or a weight")


@pytest.mark.parametrize("preset,rows,tokens,layers", [
    ("qwen2.5-7b-instruct", 32, 32, 28),     # cell 1's widest mixed program
    ("qwen2.5-72b-instruct", 16, 64, 8),     # cell 2's
], ids=["cell_1", "cell_2"])
def test_a_packed_mixed_step_holds_both_widths_in_one_program(
        v5e, preset, rows, tokens, layers):
    """The cells' widest mixed programs with the int8 leaves they serve,
    at the cells' depth (a stack of two layers is small enough for the
    compiler to prefetch whole, which reads as a copy): three conditionals
    a layer (q/k/v; the output projection and its residual; norm, MLP and
    residual), each with a 128-row and a 256-row branch
    (``llama.Pack.dense``), in the ONE program of the bucket. What the
    conditionals must not cost (compile, PR 34): no whole weight is an
    operation's result outside a fusion, neither dequantized (both
    branches dequantize the same leaf, which invites hoisting the convert
    above the conditional: ROADMAP S2 (i) again) nor as an int8 slice of
    its stack (a leaf sliced BEFORE the conditional is an operand of its
    own, 68 MB written a matrix a layer at the 7B: ``llama._LayerView``
    slices inside the branch) nor as a re-laid-out stack (with q split
    into heads inside the branch, the 128-row branch wanted ``wq``
    transposed: ``s8[28,3584,3584]`` copied at the entry and back in the
    256-row branch, every layer, 830 MB of scratch: ``llama._heads`` runs
    after the conditional); no K or V array is copied; the scratch HBM
    stays in megabytes (8.3 and 15.9 MB here; 1.9 and 2.4 before)."""
    cfg, _, copies, compiled = _mixed_step(
        _one_chip(v5e), preset, "", "pallas-stream", rows=rows,
        tokens=tokens, step_tokens=256, layers=layers, int8=True)
    hlo = compiled.as_text()
    d, f = cfg.hidden_size, cfg.intermediate_size
    kv = cfg.num_kv_heads * cfg.head_dim_
    for width in (128, 256):
        assert re.search(rf"bf16\[{width},{f}\]\S* convolution\(", hlo)
    assert len(re.findall(r" conditional\(", hlo)) == 3
    weights = {(d, f), (f, d), (d, d), (d, kv)}
    written = [
        f"{comp}: {name} {kind}{list(dims)} {op}"
        for comp, name, kind, dims, op in _results_outside_fusions(hlo)
        if kind in ("bf16", "s8") and dims[-2:] in weights
        and op not in ("parameter", "get-tuple-element", "bitcast")]
    assert written == []
    assert copies == []
    assert compiled.memory_analysis().temp_size_in_bytes < 32 << 20


def _scatters_and_gathers(hlo: str):
    """([(result dims, updates dims)] of every scatter, [result dims] of
    every gather) of an optimized module, the updates' dims read where the
    scatter's third operand is defined."""
    dims_of = {
        name: tuple(int(x) for x in dims.split(","))
        for name, dims in re.findall(
            r"^\s*(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]+)\]", hlo, re.M)}
    found = re.findall(
        r"^\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]\S* (scatter|gather)"
        r"\(([^)]*)\)", hlo, re.M)
    scatters, gathers = [], []
    for dims, op, operands in found:
        dims = tuple(int(x) for x in dims.split(","))
        if op == "gather":
            gathers.append(dims)
        else:
            updates = operands.split(",")[2].split()[-1]
            scatters.append((dims, dims_of[updates]))
    return scatters, gathers


@pytest.mark.parametrize("preset,rows,tokens,layers", [
    ("qwen2.5-7b-instruct", 32, 32, 28),     # cell 1's widest mixed program
    ("qwen2.5-72b-instruct", 16, 64, 8),     # cell 2's
], ids=["cell_1", "cell_2"])
def test_a_packed_mixed_step_writes_its_keys_and_values_by_token(
        v5e, preset, rows, tokens, layers):
    """The cells' widest mixed programs (1024 slots, packed to 256 tokens)
    hand the page write the tick's tokens: the two scatters into the K and
    the V array take ``[256, K*D]`` updates and none takes the rows'
    ``[1024, K*D]`` (a scatter on the chip walks the rows it is handed,
    written or dropped: 96 ns a row of 1 KB, my chip run, PR 39); the one
    array of 1024 rows a layer still gathers is q, un-packed for the
    attention kernel; k and v reach the scatter without one. The cache is
    no operand of a conditional (three a layer, as before), no K or V
    array is copied and the scratch HBM stays where it was (8.4 and 16.3
    MB; 8.3 and 15.9 with the write by rows; compile, PR 39)."""
    cfg, cache, copies, compiled = _mixed_step(
        _one_chip(v5e), preset, "", "pallas-stream", rows=rows,
        tokens=tokens, step_tokens=256, layers=layers, int8=True)
    hlo = compiled.as_text()
    kv = cfg.num_kv_heads * cfg.head_dim_
    slots = int(np.prod(cache["k"].shape[:3]))
    scatters, gathers = _scatters_and_gathers(hlo)
    assert sorted(scatters) == [((slots, kv), (256, kv))] * 2
    wide = [g for g in gathers if g[0] == rows * tokens]
    assert wide == [(rows * tokens, cfg.num_heads * cfg.head_dim_)]
    assert len(re.findall(r" conditional\(", hlo)) == 3
    assert copies == []
    assert compiled.memory_analysis().temp_size_in_bytes < 32 << 20


@pytest.mark.parametrize("preset,impl", [
    ("qwen2.5-7b-instruct", "pallas-stream"),
    ("qwen2.5-72b-instruct", "pallas-stream"),    # cell 2's widths
    ("qwen2.5-72b-instruct", "xla"),
])
def test_no_decode_block_copies_a_whole_k_or_v_array(v5e, preset, impl):
    """The fused decode block carries the cache through a scan over its
    steps as well as over the layers; neither loop, nor the program's
    entry or exit, holds a copy as large as one layer-stacked K array."""
    assert _decode_block(_one_chip(v5e), preset, impl) == []


def test_split_pages_at_four_kv_heads_are_copied_whole_in_every_layer(v5e):
    """What the merged form removes, kept here as it was (the flat-slot
    scatter and the paged gather over ``[L, N, P, 4, 128]`` pages), so the
    test above cannot pass for want of a copy to find: the compiler
    re-tiles all of K and all of V between the write and the gather, in
    the loop's body."""
    sds = _one_chip(v5e)
    n, maxp = GEOMETRY["qwen2.5-7b-instruct"]
    b, s, layers = STEP_ROWS, STEP_TOKENS, STEP_LAYERS

    def write(pages, new, flat):
        pf = pages.reshape(layers * n * PAGE, K, D)
        return pf.at[flat].set(new.reshape(b * s, K, D), mode="drop").reshape(
            pages.shape
        )

    def gather(pages, table, layer):
        paged = pages.reshape(layers * n, PAGE, K, D)
        return paged[table + layer * n].reshape(b, maxp * PAGE, K, D)

    def step(kc, vc, q, k_new, v_new, table, flat):
        def body(carry, _):
            kc, vc, layer, acc = carry
            at = flat + layer * n * PAGE
            kc, vc = write(kc, k_new, at), write(vc, v_new, at)
            scores = jnp.einsum(
                "bskgd,btkd->bkgst", (q + acc).reshape(b, s, K, H // K, D),
                gather(kc, table, layer),
            )
            out = jnp.einsum(
                "bkgst,btkd->bskgd", jax.nn.softmax(scores, -1),
                gather(vc, table, layer),
            )
            return (kc, vc, layer + 1, out.reshape(q.shape)), None

        init = (kc, vc, jnp.int32(0), jnp.zeros_like(q))
        (kc, vc, _, acc), _ = jax.lax.scan(body, init, None, length=layers)
        return kc, vc, acc

    pages = sds((layers, n, PAGE, K, D), jnp.bfloat16)
    new = sds((b, s, K, D), jnp.bfloat16)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        pages, pages, sds((b, s, H, D), jnp.bfloat16), new, new,
        sds((b, maxp), jnp.int32), sds((b * s,), jnp.int32),
    ).compile()
    hlo = compiled.as_text()
    copies = _copies_of(hlo, layers * n * PAGE * K * D)
    assert len(copies) == 2, copies     # all of K, and all of V
    body = hlo[: hlo.index("\nENTRY ")]
    assert all(c.split("[")[0] + " = " in body for c in copies)
