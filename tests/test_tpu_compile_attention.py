"""The chip's compiler, asked without the chip (tests/tpu_compile_common.py has
the how and why): the Pallas kernels alone at the cells' shapes (the
streaming attention kernel, over latent pages too, and the gather where it is
the only reader; the quantized matmul), and each combination the choice sends
to the gather pinned from both sides: the kernel's or the compiler's refusal,
and the choice (``ops.kernels.pallas_refusal``).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from opsagent_tpu.models import llama
from opsagent_tpu.models.config import get_config_preset
from opsagent_tpu.models.quant import QuantizedLinear, QuantizedLinear4
from opsagent_tpu.ops import attention, kernels
from opsagent_tpu.ops import quant_matmul_pallas as qmp
from opsagent_tpu.ops.attention import QuantizedPages
from opsagent_tpu.ops.kernels import pallas_refusal
from tpu_compile_common import (  # noqa: F401 (fixtures)
    B,
    CFG,
    D,
    H,
    K,
    L,
    MAXP,
    N,
    PAGE,
    STATE_CELLS,
    STREAM_CELLS,
    _compile,
    _copies_of,
    _latent_reader,
    _one_chip,
    v5e,
)


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
    assert kernels.paged_attention_backend(
        platform="tpu", **_latent_reader(cfg)) == "pallas-stream"
    assert kernels.paged_attention_backend(
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
    assert kernels.paged_attention_backend(
        platform="tpu", **shapes) == "pallas-stream"
    assert kernels.paged_attention_backend(platform="cpu", **shapes) == "xla"
    # who updates the recurrent state, where the cell's model has one
    if cell in STATE_CELLS:
        la = get_config_preset(config["preset"]).linear_attn
        state = dict(
            state_dtype=jnp.dtype(llama.STATE_DTYPE).name,
            key_dim=la.key_head_dim, value_dim=la.value_head_dim,
            heads=la.num_heads)
        assert (la.num_heads, la.key_head_dim, la.value_head_dim,
                la.decay == "channel") == STATE_CELLS[cell][:4]
        assert kernels.linear_state_backend(
            platform="tpu", **state) == "pallas-state"
        assert kernels.linear_state_backend(platform="cpu", **state) == "xla"


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
    assert kernels.paged_attention_backend(platform="tpu", **rule) == "xla"
    monkeypatch.setattr(
        kernels, "paged_attention_backend", lambda **_: "pallas-stream"
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
        assert kernels.paged_attention_backend(
            platform="tpu", **shapes) == "xla"
    assert pallas_refusal("pallas-stream", **_latent_reader(glm)) is None
    assert kernels.paged_attention_backend(
        platform="tpu", **_latent_reader(glm)) == "pallas-stream"
    monkeypatch.setattr(
        kernels, "paged_attention_backend", lambda **_: "pallas-stream"
    )
    monkeypatch.delenv("OPSAGENT_PALLAS_INTERPRET", raising=False)
    with pytest.raises(BackendRefused, match="materialised heads"):
        Engine(EngineConfig(model="tiny-mla"))


def test_interpret_mode_is_an_error_on_the_chip(monkeypatch):
    """OPSAGENT_PALLAS_INTERPRET is the CPU tests' switch; on the tpu
    backend it would turn a kernel into a slow success that never ran
    Mosaic, so there it raises."""
    monkeypatch.setenv("OPSAGENT_PALLAS_INTERPRET", "1")
    assert kernels.pallas_interpret() is True  # the CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="interpret mode is for CPU"):
        kernels.pallas_interpret()


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
