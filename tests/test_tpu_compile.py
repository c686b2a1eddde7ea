"""The chip's compiler, asked without the chip.

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
function sends to the gather (``ops.attention.pallas_refusal``) is pinned
from both sides: the kernel's or the compiler's refusal, and the choice.

A compile that passes is not a chip run, and nothing here is a time.
"""

import dataclasses
import json
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp
# Describing a topology loads libtpu, which by default is one process's at a
# time (a lock file); nothing here touches a device, so test workers and a
# builder's scratch compile may share it.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from opsagent_tpu.models import llama
from opsagent_tpu.models.config import MoEConfig, get_config_preset
from opsagent_tpu.models.quant import QuantizedLinear, QuantizedLinear4
from opsagent_tpu.ops import attention
from opsagent_tpu.ops import linear_state_pallas as lsp
from opsagent_tpu.ops import moe_experts_pallas as grouped
from opsagent_tpu.ops import quant_matmul_pallas as qmp
from opsagent_tpu.ops.attention import QuantizedPages, pallas_refusal

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


def _stream(sds, *, b, s, h, k, maxp, n, layers, d=D):
    """Compile ``paged_ragged_attention_auto`` under "pallas-stream" over a
    layer-stacked cache in the form ``page_form`` holds for it."""
    merged = attention.page_form(k, "pallas-stream") == "merged"
    pages = sds(
        (layers, n, PAGE, k * d) if merged else (layers, n, PAGE, k, d),
        jnp.bfloat16,
    )
    return _compile(
        lambda q, k_, v_, t, st, ql, ly: attention.paged_ragged_attention_auto(
            q, k_, v_, t, st, ql, impl="pallas-stream", layer=ly),
        sds((b, s, h, d), jnp.bfloat16), pages, pages,
        sds((b, maxp), jnp.int32), sds((b,), jnp.int32),
        sds((b,), jnp.int32), sds((), jnp.int32),
    )


@pytest.mark.parametrize(
    "cell,s",
    [(cell, s) for cell, shape in STREAM_CELLS.items() for s in shape["s"]],
)
def test_stream_kernel_compiles_at_the_cells_shapes(v5e, cell, s):
    """Decode rows (the fused blocks' S = 1), every mixed bucket and the
    prefill bucket of each cell, at 4 kv heads (merged pages) and at 8."""
    shape = {k: v for k, v in STREAM_CELLS[cell].items() if k != "s"}
    compiled = _stream(_one_chip(v5e), s=s, **shape)
    assert "tpu_custom_call" in compiled.as_text()


def test_stream_kernel_compiles_at_one_kv_head(v5e):
    """One kv head unsharded (split pages with a unit axis, which the
    wrapper drops): the kernel compiles, and dropping the axis copies no
    cache."""
    compiled = _stream(
        _one_chip(v5e), b=8, s=16, h=7, k=1, maxp=MAXP, n=N, layers=L)
    assert _copies_of(compiled.as_text(), N * PAGE * D) == []


@pytest.mark.parametrize("s", [0, 1, 16, 128])
def test_stream_kernel_compiles_at_serve_engines_default_shapes(v5e, s):
    """What ``serve-engine --model-name qwen2.5-7b-instruct`` runs with no
    option set (this file's N, PAGE, MAXP, B), which no cell has: the
    decode form (s == 0) and ragged at decode rows and at the smallest and
    the largest default mixed bucket."""
    sds = _one_chip(v5e)
    if s:
        compiled = _stream(sds, b=B, s=s, h=H, k=K, maxp=MAXP, n=N, layers=L)
    else:
        pages = sds((L, N, PAGE, K * D), jnp.bfloat16)
        compiled = _compile(
            lambda q, k_, v_, t, ln, ly: attention.paged_decode_attention_auto(
                q, k_, v_, t, ln, impl="pallas-stream", layer=ly),
            sds((B, H, D), jnp.bfloat16), pages, pages,
            sds((B, MAXP), jnp.int32), sds((B,), jnp.int32),
            sds((), jnp.int32),
        )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("s", [4, 64])
def test_stream_kernel_compiles_at_pages_of_64_slots(v5e, s):
    """What ``bench.py``'s stages run on a chip now that nothing pins them
    to the gather: ``bench-8b`` (32/8 heads of 128) at its own page size
    of 64 slots, 8 pages a sequence, 32 rows, at the ragged sweep's two
    mixed buckets. Every cell holds pages of 16."""
    sds = _one_chip(v5e)
    cfg = get_config_preset("bench-8b")
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_) == (32, 8, 128)
    pages = sds((cfg.num_layers, 256, 64, 8 * 128), jnp.bfloat16)
    compiled = _compile(
        lambda q, k_, v_, t, st, ql, ly: attention.paged_ragged_attention_auto(
            q, k_, v_, t, st, ql, impl="pallas-stream", layer=ly),
        sds((32, s, 32, 128), jnp.bfloat16), pages, pages,
        sds((32, 8), jnp.int32), sds((32,), jnp.int32),
        sds((32,), jnp.int32), sds((), jnp.int32),
    )
    assert "tpu_custom_call" in compiled.as_text()


# -- the gather where it is the only reader (int8 pages), and over the latent --
def _gather(sds, pages, *, b, s, h, d, maxp):
    """Compile the xla reader over a layer-stacked cache; s == 0 means the
    decode form (what a fused decode block runs)."""
    table, rows = sds((b, maxp), jnp.int32), sds((b,), jnp.int32)
    if s == 0:
        return _compile(
            lambda q, k_, v_, t, ln, ly: attention.paged_decode_attention_auto(
                q, k_, v_, t, ln, impl="xla", layer=ly),
            sds((b, h, d), jnp.bfloat16), pages, pages, table, rows,
            sds((), jnp.int32),
        )
    return _compile(
        lambda q, k_, v_, t, st, ql, ly: attention.paged_ragged_attention_auto(
            q, k_, v_, t, st, ql, impl="xla", layer=ly),
        sds((b, s, h, d), jnp.bfloat16), pages, pages, table, rows, rows,
        sds((), jnp.int32),
    )


# The decode form (0), the smallest mixed bucket and the cell's largest
# one; cell 3 has the one mixed bucket, so its prefill bucket, where the
# gather walks the score matrix in blocks.
INT8_ROWS = {
    "qwen25-7b.agent-turns": (0, 16, 32),
    "qwen25-72b-l8.long-generate": (0, 16, 64),
    "solar-open2-ep8-l8.doc-turns": (0, 16, 256),
}


@pytest.mark.parametrize(
    "cell,s", [(cell, s) for cell, rows in INT8_ROWS.items() for s in rows]
)
def test_int8_pages_gather_compiles_at_the_cells_shapes(v5e, cell, s):
    """``kv_quantize="int8"`` on the chip: the choice sends it to the
    gather (the kernel has no int8 reader), over ``QuantizedPages`` in the
    form ``page_form`` gives the gather at the cell's kv heads (merged at
    4, split at 8) with the scale planes beside them. The compiler takes
    it and the program fits the chip."""
    sds = _one_chip(v5e)
    c = STREAM_CELLS[cell]
    k, n, layers = c["k"], c["n"], c["layers"]
    merged = attention.page_form(k, "xla") == "merged"
    assert merged == (k == 4)
    pages = QuantizedPages(
        sds((layers, n, PAGE, k * D) if merged else (layers, n, PAGE, k, D),
            jnp.int8),
        sds((layers, n, PAGE, k), jnp.float32),
    )
    compiled = _gather(
        sds, pages, b=c["b"], s=s, h=c["h"], d=D, maxp=c["maxp"])
    assert "tpu_custom_call" not in compiled.as_text()


def _latent_reader(cfg, **other):
    """What an engine of an MLA model that holds the latent tells the
    choice (``Engine.__init__``): the shapes its READER is handed."""
    return dict(dict(
        head_dim=cfg.mla.page_dim, kv_heads_per_shard=1, page_itemsize=2,
        mla=True, shared_kv=True), **other)


@pytest.mark.parametrize("b,s", [(16, 16), (16, 1), (1, 64)],
                         ids=["mixed-bucket", "decode-form", "prefill-bucket"])
def test_stream_kernel_compiles_over_the_latent_at_the_cells_shapes(v5e, b, s):
    """``glm47-flash-l12.longdoc-turns``: the absorbed queries ``[16, 16,
    20, 640]`` of the cell's one mixed bucket, the decode form ``[16, 1,
    20, 640]`` of its fused blocks and a prefill bucket's 64 slots of one
    row, against latent pages ``[12, 16384, 16, 640]`` handed ONCE as keys
    and values (one kv head of 640 = 5 x 128 lanes, a group of 20): Mosaic
    takes the kernel, and no copy of the cache stands beside it."""
    sds = _one_chip(v5e)
    layers, (n, maxp) = 12, (16384, 1216)
    pages = sds((layers, n, PAGE, 640), jnp.bfloat16)
    compiled = _compile(
        lambda q, kc, t, st, ql, ly: attention.paged_ragged_attention_auto(
            q, kc, kc, t, st, ql, impl="pallas-stream", layer=ly),
        sds((b, s, 20, 640), jnp.bfloat16), pages,
        sds((b, maxp), jnp.int32), sds((b,), jnp.int32),
        sds((b,), jnp.int32), sds((), jnp.int32),
    )
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert _copies_of(hlo, layers * n * PAGE * 640) == []


def test_mla_latent_gather_compiles_and_copies_no_latent_cache(v5e):
    """The gather over the latent on the chip: the oracle's record, and
    the reader of the harness's int8-latent control (the cell itself runs
    the streaming kernel since PR 41, below). GLM-4.7-Flash's
    absorbed queries (20 heads against the 576-wide latent: 512 + 64 rope)
    over its one-head latent pages, which keys and values share, at the
    geometry of ``glm47-flash-l12.longdoc-turns`` (too large for the
    compiler to stage the array in fast memory, which reads as a copy too).
    The latent is held merged and padded to whole lane tiles, ``[L, N, P,
    640]`` (``MLAConfig.page_dim``), and the compiler takes the gather over
    it with no copy of the cache. Held 576 wide it is copied WHOLE, once
    for the keys' read and once for the values': the TPU holds an array
    whose minor axis is off the 128 lanes pages-innermost (``{1,3,2,0}``),
    with or without a unit kv-head axis (PR 30 pinned the copies and blamed
    the unit axis), and the gather wants it row-major. Pinned from both
    sides."""
    cfg = get_config_preset("glm-4.7-flash")
    m = cfg.mla
    assert m.latent_cache and (m.latent_dim, m.page_dim) == (576, 640)
    assert llama.cache_form(cfg) == "merged"
    sds = _one_chip(v5e)
    layers, n, maxp = 12, 16384, 1216
    assert attention.paged_attention_backend(
        platform="tpu", **_latent_reader(cfg)) == "pallas-stream"
    assert attention.paged_attention_backend(
        platform="tpu", **_latent_reader(cfg, page_itemsize=1)) == "xla"
    for row, copied in (((640,), 0), ((576,), 2), ((1, 576), 2)):
        pages = sds((layers, n, PAGE, *row), jnp.bfloat16)
        compiled = _gather(
            sds, pages, b=16, s=16, h=cfg.num_heads, d=row[-1], maxp=maxp)
        hlo = compiled.as_text()
        assert "tpu_custom_call" not in hlo
        whole = layers * n * PAGE * row[-1]
        assert len(_copies_of(hlo, whole)) == copied, row


@pytest.mark.parametrize("cell", list(STREAM_CELLS))
def test_the_choice_for_each_cells_configuration(cell):
    """``paged_attention_backend`` for the configuration each cell serves,
    read from its file under benchmarks/configs: the streaming kernel on a
    TPU, the gather on the CPU; a pure function of what it is given."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = {w["name"]: w["config"] for w in bench["workloads"]}[cell]
    path = {c["name"]: c["file"] for c in bench["configs"]}[name]
    with open(os.path.join(root, path)) as f:
        config = json.load(f)
    engine = config["engine"]
    assert engine["dtype"] == "bfloat16" and "kv_quantize" not in engine
    shapes = dict(
        head_dim=config.get(
            "head_dim",
            config["hidden_size"] // config["num_attention_heads"]),
        kv_heads_per_shard=config["num_key_value_heads"] // engine["tp"],
        page_itemsize=2,
    )
    c = STREAM_CELLS[cell]
    assert (c["k"], c["h"]) == (
        config["num_key_value_heads"], config["num_attention_heads"])
    assert (c["b"], c["maxp"], c["n"]) == (
        engine["max_batch_size"], engine["max_pages_per_seq"],
        engine["num_pages"])
    assert attention.paged_attention_backend(
        platform="tpu", **shapes) == "pallas-stream"
    assert attention.paged_attention_backend(platform="cpu", **shapes) == "xla"
    # who updates the recurrent state, where the cell's model has one
    if cell in STATE_CELLS:
        la = get_config_preset(config["preset"]).linear_attn
        state = dict(
            state_dtype=jnp.dtype(llama.STATE_DTYPE).name,
            key_dim=la.key_head_dim, value_dim=la.value_head_dim,
            heads=la.num_heads)
        assert (la.num_heads, la.key_head_dim, la.value_head_dim,
                la.decay == "channel") == STATE_CELLS[cell][:4]
        assert attention.linear_state_backend(
            platform="tpu", **state) == "pallas-state"
        assert attention.linear_state_backend(platform="cpu", **state) == "xla"


# -- quantized matmul: weight dtype x projection x rows ----------------------
SHAPES = {
    "qkv": (CFG.hidden_size, (H + 2 * K) * D),
    "o": (H * D, CFG.hidden_size),
    "gate_up": (CFG.hidden_size, CFG.intermediate_size),
    "down": (CFG.intermediate_size, CFG.hidden_size),
    "lm_head": (CFG.hidden_size, CFG.vocab_size),
}


def _weight(sds, mode: str, n_in: int, n_out: int):
    if mode == "int4":
        return QuantizedLinear4(
            sds((n_in // 2, n_out), jnp.int8),
            sds((n_in // 128, 1, n_out), jnp.float32),
        )
    return QuantizedLinear(
        sds((n_in, n_out), jnp.int8), sds((1, n_out), jnp.float32)
    )


@pytest.mark.parametrize("t", [32, 256])
@pytest.mark.parametrize("name", list(SHAPES))
@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_quant_matmul_compiles(v5e, mode, name, t):
    """The weight-stream kernel at every projection of the model. The
    int4 form was refused here ("failed to legalize operation
    'arith.shli'" on int8 vectors) until its nibble unpack moved to
    int32 lanes."""
    sds = _one_chip(v5e)
    n_in, n_out = SHAPES[name]
    assert _compile(
        qmp.quant_matmul_pallas,
        sds((t, n_in), jnp.bfloat16), _weight(sds, mode, n_in, n_out),
    ) is not None


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_quant_matmul_compiles_at_the_largest_mixed_bucket(v5e, mode):
    """32 rows x the 128-token mixed bucket = 4096 activation rows: as
    one resident (T, In) block this overran scoped VMEM ("Ran out of
    memory in memory space vmem"); row tiles of T_TILE keep it inside."""
    sds = _one_chip(v5e)
    n_in, n_out = SHAPES["down"]  # the widest contraction
    assert 4096 > qmp.T_TILE
    assert _compile(
        qmp.quant_matmul_pallas,
        sds((4096, n_in), jnp.bfloat16), _weight(sds, mode, n_in, n_out),
    ) is not None


# -- tensor parallelism: the shard_map wrapper on the four devices -----------
@pytest.mark.parametrize("tp", [2, 4])
def test_stream_kernel_compiles_under_tp(v5e, tp):
    """The streaming kernel through the tp shard_map wrapper at the 7B's
    heads, by the engine's own dispatch (the form check counts the kv
    heads a SHARD holds): two shards of two kv heads each read merged
    pages (a shard's heads are contiguous lanes), four shards of ONE hold
    split pages with a unit axis; no shard copies its cache."""
    mesh = Mesh(np.array(v5e[:tp]).reshape(tp), ("tp",))

    def sds(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec)
        )

    merged = attention.page_form(K // tp, "pallas-stream") == "merged"
    pages = (
        sds((L, N, PAGE, K * D), jnp.bfloat16, P(None, None, None, "tp"))
        if merged else
        sds((L, N, PAGE, K, D), jnp.bfloat16, P(None, None, None, "tp", None))
    )
    table, rows = sds((B, MAXP), jnp.int32), sds((B,), jnp.int32)
    compiled = _compile(
        lambda q, k_, v_, t, st, ql, ly: (
            attention.paged_ragged_attention_auto(
                q, k_, v_, t, st, ql, impl="pallas-stream", layer=ly,
                mesh=mesh,
            )
        ),
        sds((B, 32, H, D), jnp.bfloat16, P(None, None, "tp", None)),
        pages, pages, table, rows, rows, sds((), jnp.int32),
    )
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert _copies_of(hlo, L * N * PAGE * K * D // tp) == []


# -- what the kernel cannot read goes to the gather -------------------------
STREAM_REFUSALS = [
    # (id, model, kv_quantize, kernel shapes (k, d, kv), the words)
    ("head-dim-64", "bench-1b", "", (8, 64, "bf16"), "128-lane tiling"),
    ("int8-pages", "qwen2.5-7b-instruct", "int8", (4, 128, "int8"),
     "int8 pages"),
]


@pytest.mark.parametrize(
    "model,kvq,shapes,words",
    [r[1:] for r in STREAM_REFUSALS], ids=[r[0] for r in STREAM_REFUSALS],
)
def test_stream_refusals_pinned_from_both_sides(
    v5e, monkeypatch, model, kvq, shapes, words
):
    """The two shape rules ``pallas_refusal`` has: a head dim off the 128
    lanes (a kv head is a lane slice of the merged row) and int8 pages (no
    reader). The dispatcher refuses each with the words of the rule, the
    choice sends such an engine to the gather on a TPU, and an engine
    whose choice is made to answer the kernel all the same refuses at
    init with the rule's reason; the aligned bf16 neighbours are the
    compiling cases above."""
    from opsagent_tpu.serving.engine import (
        BackendRefused, Engine, EngineConfig,
    )

    k, d, kv = shapes
    sds = _one_chip(v5e)
    pages = sds((L, N, PAGE, k * d), jnp.int8 if kv == "int8" else jnp.bfloat16)
    if kv == "int8":
        pages = QuantizedPages(pages, sds((L, N, PAGE, k), jnp.float32))
    table, rows = sds((B, MAXP), jnp.int32), sds((B,), jnp.int32)
    with pytest.raises(ValueError, match=words):
        _compile(
            lambda q, k_, v_, t, st, ql: attention.paged_ragged_attention_auto(
                q, k_, v_, t, st, ql, impl="pallas-stream"),
            sds((B, 16, k * 7, d), jnp.bfloat16), pages, pages, table, rows,
            rows,
        )
    cfg = get_config_preset(model)
    assert (cfg.num_kv_heads, cfg.head_dim_) == (k, d)
    rule = dict(
        head_dim=d, kv_heads_per_shard=k, page_itemsize=1 if kvq else 2
    )
    why = pallas_refusal("pallas-stream", **rule)
    assert why is not None and words in why
    assert attention.paged_attention_backend(platform="tpu", **rule) == "xla"
    monkeypatch.setattr(
        attention, "paged_attention_backend", lambda **_: "pallas-stream"
    )
    monkeypatch.delenv("OPSAGENT_PALLAS_INTERPRET", raising=False)
    with pytest.raises(BackendRefused) as refused:
        Engine(EngineConfig(model=model, kv_quantize=kvq, quantize="int8"))
    assert str(refused.value) == why
    assert pallas_refusal(
        "pallas-stream", head_dim=128, kv_heads_per_shard=k, page_itemsize=2
    ) is None


def test_mla_refusals_that_remain_and_the_latents_acceptance(monkeypatch):
    """MLA with materialised heads (no latent cache) and the latent under
    tp > 1 have no reader in the kernel: the choice sends them to the
    gather, and an engine whose choice is made to answer the kernel all
    the same refuses at init with the rule's words. The latent on the
    lanes at tp=1 is accepted (its compiles are above)."""
    from opsagent_tpu.serving.engine import (
        BackendRefused, Engine, EngineConfig,
    )

    glm = get_config_preset("glm-4.7-flash")
    heads = dict(
        head_dim=glm.head_dim_, kv_heads_per_shard=glm.num_kv_heads,
        page_itemsize=2, mla=True)
    assert glm.head_dim_ % 128 == 0     # so it is the MLA rule that speaks
    for shapes, words in (
        (heads, "materialised heads"),
        (_latent_reader(glm, tp=4), "tp=4"),
        (_latent_reader(glm, page_itemsize=1), "int8 pages"),
        (_latent_reader(glm, head_dim=glm.mla.latent_dim),
         "128-lane tiling"),
    ):
        assert words in pallas_refusal("pallas-stream", **shapes)
        assert attention.paged_attention_backend(
            platform="tpu", **shapes) == "xla"
    assert pallas_refusal("pallas-stream", **_latent_reader(glm)) is None
    assert attention.paged_attention_backend(
        platform="tpu", **_latent_reader(glm)) == "pallas-stream"
    monkeypatch.setattr(
        attention, "paged_attention_backend", lambda **_: "pallas-stream"
    )
    monkeypatch.delenv("OPSAGENT_PALLAS_INTERPRET", raising=False)
    with pytest.raises(BackendRefused, match="materialised heads"):
        Engine(EngineConfig(model="tiny-mla"))


def test_interpret_mode_is_an_error_on_the_chip(monkeypatch):
    """OPSAGENT_PALLAS_INTERPRET is the CPU tests' switch; on the tpu
    backend it would turn a kernel into a slow success that never ran
    Mosaic, so there it raises."""
    monkeypatch.setenv("OPSAGENT_PALLAS_INTERPRET", "1")
    assert attention.pallas_interpret() is True  # the CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="interpret mode is for CPU"):
        attention.pallas_interpret()


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
    from opsagent_tpu.models import llama

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
                int8: bool = False):
    """Compile the engine's ``_mixed_carry`` program (decode_loop.
    mixed_step_carry, the cache donated) at a preset's widths cut to
    ``layers`` layers, with the cache ``llama.make_cache`` gives it for
    the attention backend ``impl``; ``rows`` x ``tokens`` slots, packed
    to ``step_tokens`` where half of that is fewer."""
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
            cache, table, key, temps, top_k, top_p, attn_impl=impl,
            step_tokens=step_tokens,
        )

    compiled = jax.jit(step, donate_argnames=("cache",)).lower(
        params, i32(b, tokens), flag(b), i32(b), i32(b), i32(b),
        flag(b), cache, i32(b, maxp), key, f32(b), i32(b), f32(b),
    ).compile()
    return cfg, cache, _whole_cache_copies(compiled, cfg, preset, impl), compiled


def _decode_block_compiled(sds, preset: str, impl: str, steps: int = 8, *,
                           rows: int = STEP_ROWS, layers: int = STEP_LAYERS,
                           int8: bool = False):
    """Compile the fused decode block (decode_loop.decode_block: ``steps``
    greedy passes under one scan, the cache its carry and donated), what
    a cell runs between admissions: (config, cache shapes, executable)."""
    from opsagent_tpu.serving import decode_loop

    cfg, params, cache, key = _step_shapes(sds, preset, "", impl, layers, int8)
    maxp = GEOMETRY[preset][1]
    b = rows
    i32 = lambda *s: sds(s, jnp.int32)       # noqa: E731
    f32 = lambda *s: sds(s, jnp.float32)     # noqa: E731

    def block(params, tokens, write_at, active, budgets, cache, table, key,
              temps, top_k, top_p, eos, pad):
        return decode_loop.decode_block(
            params, cfg, tokens, write_at, active, budgets, cache, table,
            key, temps, top_k, top_p, eos, pad, n_steps=steps, greedy=True,
            attn_impl=impl,
        )

    compiled = jax.jit(block, donate_argnames=("cache",)).lower(
        params, i32(b), i32(b), sds((b,), jnp.bool_), i32(b), cache,
        i32(b, maxp), key, f32(b), i32(b), f32(b), i32(), i32(),
    ).compile()
    return cfg, cache, compiled


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
            attn_impl="pallas-stream", step_tokens=256)

    compiled = jax.jit(step, donate_argnames=("cache",)).lower(
        params, i32(b, s), flag(b), i32(b), i32(b), i32(b), flag(b), cache,
        i32(b, maxp + llama.STATE_COLUMNS), key, f32(b), i32(b), f32(b),
    ).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "mini-gather" not in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


def test_stream_kernel_is_exported_once_a_shape(v5e, tmp_path, monkeypatch):
    """A second program holding the kernel at the same shape inlines the
    exported bytes (no second trace of the kernel's body); the bytes lie
    beside JAX's compile cache, and a new process (here: every in-process
    cache dropped) reads them back instead of tracing, for as long as the
    file is there."""
    from opsagent_tpu.ops import paged_attention_stream as stream

    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    traced = []
    kernel = stream._kernel
    monkeypatch.setattr(
        stream, "_kernel", lambda *a, **kw: traced.append(1) or kernel(*a, **kw)
    )

    def new_process():
        stream._kernel_call.cache_clear()
        stream._stream.clear_cache()

    def compiled():
        return _stream(
            _one_chip(v5e), b=4, s=16, h=14, k=2, maxp=MAXP, n=N, layers=L
        ).as_text()

    try:
        new_process()
        assert "tpu_custom_call" in compiled() and len(traced) == 1
        files = [f for f in os.listdir(tmp_path) if f.endswith(".export")]
        assert len(files) == 1
        compiled()                      # another program, the same shape
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


def _state_cell_mixed_step(sds, cell: str, state_impl: str, layers: int = 4):
    """A state cell's mixed program (all rows of its one bucket of 16,
    packed to 256 tokens, int8 leaves, the streaming kernel) at one period
    of its layers, the slots held for ``state_impl``."""
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
            attn_impl="pallas-stream", step_tokens=256)

    compiled = jax.jit(step, donate_argnames=("cache",)).lower(
        params, i32(b, s), flag(b), i32(b), i32(b), i32(b), flag(b), cache,
        i32(b, c["maxp"] + llama.STATE_COLUMNS), key, f32(b), i32(b), f32(b),
    ).compile()
    return cache, compiled


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



# -- GLM-4.7-Flash at the new cell's shapes (glm47-flash-l12.longdoc-turns) ----
# benchmarks/configs/glm47-flash-l12-int8.json: 16 rows, 16,384 pages of 16,
# 1,216 a sequence, one mixed bucket of 16 packed to the step's 256 tokens,
# fused decode blocks of 8, int8 weights, 12 layers (one dense, 11 of 64
# experts), latent pages [12, 16384, 16, 640] (the 576-wide latent on whole lanes).
GEOMETRY["glm-4.7-flash"] = (16384, 1216)
CHIP_HBM_BYTES = 15.75 * 2**30      # what a v5e chip's runtime reports


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
    assert attention.paged_attention_backend(
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
    with attention.moe_experts_scope(_experts_beside(impl)):
        cfg, cache, _, compiled = _mixed_step(
            _one_chip(v5e), "glm-4.7-flash", kv, impl, rows=16, tokens=16,
            step_tokens=256, layers=12, int8=True)
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
    with attention.moe_experts_scope(_experts_beside(impl)):
        _, cache, compiled = _decode_block_compiled(
            _one_chip(v5e), "glm-4.7-flash", impl, rows=16, layers=12,
            int8=True)
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
    assert attention.moe_experts_backend(
        platform="tpu", quantize="int8", hidden_size=cfg.hidden_size,
        expert_width=cfg.moe.expert_intermediate_size, tp=1) == grouped.IMPL
    assert (cfg.hidden_size, cfg.moe.expert_intermediate_size) == (d, f)

    def program(impl: str) -> str:
        with attention.moe_experts_scope(impl):
            if cell.startswith("glm"):
                *_, compiled = _mixed_step(
                    sds, "glm-4.7-flash", "", "pallas-stream", rows=16,
                    tokens=16, step_tokens=256, layers=3, int8=True)
            else:
                _, compiled = _state_cell_mixed_step(sds, cell, "pallas-state")
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
    assert attention.ssm_state_backend(
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
            attn_impl="pallas-stream", step_tokens=256)

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
        return decode_loop.decode_block(
            params, cfg, tokens, write_at, active, budgets, cache, table,
            key, temps, top_k, top_p, eos, pad,
            n_steps=engine["decode_block"], greedy=True,
            attn_impl="pallas-stream")

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
