"""The chip's compiler, asked without the chip (tests/tpu_compile_common.py has
the how and why): the grouped expert kernel at both expert cells' shapes
(GLM-4.7-Flash's and Solar-Open2's) and inside their mixed programs: one
kernel call a layer and no loop, exported once a shape.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from opsagent_tpu.models import llama
from opsagent_tpu.models.config import MoEConfig, get_config_preset
from opsagent_tpu.models.quant import QuantizedLinear
from opsagent_tpu.ops import kernels
from opsagent_tpu.ops import moe_experts_pallas as grouped
from tpu_compile_common import (  # noqa: F401 (fixtures)
    _compile,
    _copies_of,
    _mixed_step,
    _one_chip,
    _state_cell_mixed_step,
    v5e,
)


# -- the grouped expert kernel at both expert cells' shapes (PR 44) -------------
# cell: experts held, top-k, router width, d, f, the stack's leading axes as
# the cell's program holds it, and the token counts of its programs (GLM: the
# mixed step's 256 and the 64-token prefill; Solar: the mixed step's 256 and
# its decode block's 32 rows).
EXPERT_CELLS = {
    "glm47-flash-l12.longdoc-turns": (64, 4, 64, 2048, 1536, (11,), (256, 64)),
    "solar-open2-ep8-l8.doc-turns": (40, 8, 320, 4096, 1280, (2, 1), (256, 32)),
}


def _expert_kernel(sds, cell: str, tokens: int):
    """Compile ``moe_expert_blocks`` over a cell's whole int8 stacks at the
    buffer ``_moe_share`` makes of ``tokens`` tokens."""
    e, k, width, d, f, lead, _ = EXPERT_CELLS[cell]
    bm, rows = llama._share_buffer(
        MoEConfig(num_experts=e, num_experts_per_token=k, router_experts=width),
        tokens, grouped.MIN_BLOCK_ROWS)
    leaf = lambda a, b: QuantizedLinear(            # noqa: E731
        sds((*lead, e, a, b), jnp.int8), sds((*lead, e, 1, b), jnp.float32))
    return bm, rows, _compile(
        lambda xs, expert, used, stacks, idx: grouped.moe_expert_blocks(
            xs, expert, used, stacks, idx, bm=bm),
        sds((rows, d), jnp.bfloat16), sds((rows // bm,), jnp.int32),
        sds((), jnp.int32), (leaf(d, f), leaf(d, f), leaf(f, d)),
        tuple(sds((), jnp.int32) for _ in lead))


@pytest.mark.parametrize(
    "cell,tokens",
    [(cell, t) for cell, c in EXPERT_CELLS.items() for t in c[-1]])
def test_expert_kernel_compiles_at_the_cells_shapes(v5e, cell, tokens):
    """Blocks of 16 rows at every token count of both cells (a bfloat16
    tile; the loop's 8 at Solar's counts and at GLM's prefill), the whole
    stack an operand as it lies: no copy of it, no scratch in HBM."""
    e, _, _, d, f, lead, _ = EXPERT_CELLS[cell]
    bm, rows, compiled = _expert_kernel(_one_chip(v5e), cell, tokens)
    assert bm == 16 and rows % bm == 0
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert _copies_of(hlo, e * d * f) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert f % grouped.f_tile(d, f) == 0 and grouped.f_tile(d, f) % 128 == 0


@pytest.mark.parametrize("cell", list(EXPERT_CELLS))
def test_the_expert_blocks_are_one_kernel_call_and_no_loop(v5e, cell):
    """The mixed program of each cell with an expert share (GLM's at three
    layers, two of them with experts; Solar's at one period) as a TPU's
    engine traces it (``moe_experts_backend`` answers the kernel for both):
    the blocks are one custom call under the scope ``moe_experts`` (the
    name ``benchmarks/scope_reduce.py`` reads the kernel's time by), no
    ``while`` lies under that scope (the loop of two or three fusion calls
    a block that the parent's program held there, and whose own time read
    unscoped), and no expert stack is copied or sliced out to feed it. The
    loop's program, traced without the choice, still shows its ``while``:
    the test cannot pass for want of something to find."""
    sds = _one_chip(v5e)
    e, _, _, d, f, _, _ = EXPERT_CELLS[cell]
    cfg = get_config_preset(
        "glm-4.7-flash" if cell.startswith("glm") else "solar-open2-250b")
    assert kernels.moe_experts_backend(
        platform="tpu", quantize="int8", hidden_size=cfg.hidden_size,
        expert_width=cfg.moe.expert_intermediate_size, tp=1) == grouped.IMPL
    assert (cfg.hidden_size, cfg.moe.expert_intermediate_size) == (d, f)

    def program(impl: str) -> str:
        if cell.startswith("glm"):
            *_, compiled = _mixed_step(
                sds, "glm-4.7-flash", "", "pallas-stream", rows=16,
                tokens=16, step_tokens=256, layers=3, int8=True,
                experts=impl)
        else:
            _, compiled = _state_cell_mixed_step(
                sds, cell, "pallas-state", experts=impl)
        return compiled.as_text()

    def under_scope(hlo: str, what: str) -> list[str]:
        return [line for line in hlo.splitlines() if re.search(
            rf'\b{what}\(.*op_name="[^"]*moe_experts', line)]

    hlo = program(grouped.IMPL)
    calls = [line for line in under_scope(hlo, "custom-call")
             if "tpu_custom_call" in line]
    assert len(calls) >= 1 and all(
        f"bf16[{2048 if cell.startswith('glm') else 2688},{d}]" in line
        for line in calls), calls
    assert under_scope(hlo, "while") == []
    assert "moe_experts/while" not in hlo
    assert _copies_of(hlo, e * d * f) == []
    assert not re.search(
        rf"(bf16|s8)\[{e},{d},{f}\]\S* (fusion|copy|dynamic-slice)\(", hlo)
    assert under_scope(program("xla"), "while") != []


def test_expert_kernel_is_exported_once_a_shape(v5e, tmp_path, monkeypatch):
    """As the streaming kernel: a second program holding the expert kernel
    at the same shape inlines the exported bytes, and a new process reads
    them back from beside the compile cache."""
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    traced = []
    kernel = grouped._kernel
    monkeypatch.setattr(
        grouped, "_kernel",
        lambda *a, **kw: traced.append(1) or kernel(*a, **kw))
    cell = "solar-open2-ep8-l8.doc-turns"

    def new_process():
        grouped._kernel_call.cache_clear()
        jax.clear_caches()

    def compiled():
        return _expert_kernel(_one_chip(v5e), cell, 32)[2].as_text()

    try:
        new_process()
        assert "tpu_custom_call" in compiled() and len(traced) == 1
        files = [f for f in os.listdir(tmp_path) if f.endswith(".export")]
        assert len(files) == 1 and files[0].startswith("moe_experts-")
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
