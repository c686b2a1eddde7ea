"""The chip's compiler, asked without the chip (tests/tpu_compile_common.py has
the how and why): GLM-4.7-Flash's WHOLE 12-layer mixed step and fused decode
block over latent (MLA) pages, int8 weights and every expert, under the
streaming kernel, under the gather and with the harness's int8 pages: no copy
as large as the latent cache, no expert stack written out, inside the chip's
memory.
"""

import json
import os
import re

import jax
import numpy as np
import pytest

from opsagent_tpu.models.config import get_config_preset
from opsagent_tpu.ops import kernels
from opsagent_tpu.ops import moe_experts_pallas as grouped
from tpu_compile_common import (  # noqa: F401 (fixtures)
    CHIP_HBM_BYTES,
    GEOMETRY,
    _copies_of,
    _decode_block_compiled,
    _latent_reader,
    _mixed_step,
    _one_chip,
    v5e,
)


def _experts_beside(attn_impl: str) -> str:
    """Who runs the expert blocks in the program beside this attention
    reader: the cell as a TPU's engine traces it (the streaming kernel)
    runs the grouped expert kernel too (PR 44); the gather's programs, the
    oracle's record and the harness's control, keep the loop."""
    return grouped.IMPL if attn_impl == "pallas-stream" else "xla"


def _glm_cell():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmarks", "configs", "glm47-flash-l12-int8.json")) as f:
        return json.load(f)


def test_glm_flash_geometry_is_the_cells():
    engine = _glm_cell()["engine"]
    assert GEOMETRY["glm-4.7-flash"] == (
        engine["num_pages"], engine["max_pages_per_seq"])
    assert (engine["max_batch_size"], engine["mixed_buckets"],
            engine["max_step_tokens"], engine["decode_block"]) == (
        16, [16], 256, 8)
    assert kernels.paged_attention_backend(
        platform="tpu", **_latent_reader(get_config_preset("glm-4.7-flash"))
    ) == "pallas-stream"


@pytest.mark.parametrize("kv,impl", [
    ("", "pallas-stream"),      # the cell (PR 41)
    ("", "xla"),                # the oracle's record (PR 40's program)
    ("int8", "xla"),            # the harness's control
], ids=["bf16", "bf16-gather", "int8-pages"])
def test_glm_flash_mixed_step_copies_no_latent_cache_and_fits_the_chip(
    v5e, kv, impl
):
    """The cell's one mixed program WHOLE (12 layers, int8 weights, every
    expert, the full vocabulary, 16,384 latent pages), under the streaming
    kernel as the cell runs it, under the gather, and with the int8 pages
    of the harness's control (the gather's): the kernel is in the program
    where it is the reader; no copy as large as the latent cache,
    at the program's entry, its exit or in its layer loops (held with a
    unit axis the cache was copied twice, 3.6 GB each at this size: it
    would not have fitted), no layer's expert stack written out (an expert
    share reads one expert at a time out of the whole stack), and
    arguments, results and scratch together inside the chip's memory.
    Under the kernel the gathered rows and the f32 scores are gone from
    the scratch."""
    cfg, cache, _, compiled = _mixed_step(
        _one_chip(v5e), "glm-4.7-flash", kv, impl, rows=16, tokens=16,
        step_tokens=256, layers=12, int8=True, experts=_experts_beside(impl))
    assert cfg.moe_layer_start == 1 and cfg.moe.router_experts == 64
    latent = jax.tree.leaves(cache["k"])[0]
    assert latent.shape == (12, 16384, 16, 640)
    assert "stats" in cache
    hlo = compiled.as_text()
    assert ("tpu_custom_call" in hlo) == (impl == "pallas-stream")
    assert _copies_of(hlo, int(np.prod(latent.shape))) == []
    experts = 64 * cfg.hidden_size * cfg.moe.expert_intermediate_size
    assert _copies_of(hlo, experts) == []
    assert not re.search(
        rf"(bf16|s8)\[64,{cfg.hidden_size},1536\]\S* (fusion|copy|dynamic-slice)\(",
        hlo), "a layer's 64 experts taken out of the stack"
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert held < CHIP_HBM_BYTES, f"{held / 2**30:.2f} GiB"
    if impl == "pallas-stream":
        # the gather's rows [16, 19456, 640] bf16 alone are 0.37 GiB
        assert m.temp_size_in_bytes < 0.3 * 2**30
    print(f"glm mixed step [{kv or 'bf16'} pages, {impl}]: arguments "
          f"{m.argument_size_in_bytes / 2**30:.2f} GiB, scratch "
          f"{m.temp_size_in_bytes / 2**30:.2f} GiB, held {held / 2**30:.2f} GiB")


@pytest.mark.parametrize("impl", ["pallas-stream", "xla"])
def test_glm_flash_decode_block_copies_no_latent_cache(v5e, impl):
    """The fused decode block at the cell's rows (8 passes under one scan,
    the latent cache its carry), under the kernel's decode form as the
    cell runs it and under the gather: no copy as large as the cache."""
    _, cache, compiled = _decode_block_compiled(
        _one_chip(v5e), "glm-4.7-flash", impl, rows=16, layers=12,
        int8=True, experts=_experts_beside(impl))
    hlo = compiled.as_text()
    assert ("tpu_custom_call" in hlo) == (impl == "pallas-stream")
    assert _copies_of(hlo, int(np.prod(cache["k"].shape))) == []
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert held < CHIP_HBM_BYTES, f"{held / 2**30:.2f} GiB"
    print(f"glm decode block [{impl}]: scratch "
          f"{m.temp_size_in_bytes / 2**30:.2f} GiB, "
          f"held {held / 2**30:.2f} GiB")
