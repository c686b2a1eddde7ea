"""Double-buffered Pallas quantized matmul vs the XLA dequant oracle.

Interpret mode on CPU (the TPU-lowered path shares the trace), mirroring
tests/test_pallas_paged.py: kernel-level parity for int8 and packed-int4
weights — including the ragged last contraction tile and the
contraction-smaller-than-group edge — the column-parallel shard_map form,
and the engine-level acceptance gates: ``weight_stream="pallas-dma"``
must produce BYTE-IDENTICAL greedy output to the xla weight stream
through the mixed hot path with zero post-warmup compiles, and must be
refused at init whenever its gates (quantized weights, tp == 1) trip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opsagent_tpu.models.quant import (
    QuantizedLinear,
    QuantizedLinear4,
    quantize_weight,
    quantize_weight4,
)
from opsagent_tpu.ops.quant_matmul_pallas import (
    quant_matmul_pallas,
    quant_matmul_pallas_tp,
    supports,
)

# Count real XLA compiles process-wide (same listener discipline as
# tests/test_mixed_batching.py): fires once per backend compile, never
# on jit-cache hits; tests diff around the window they care about.
_COMPILES: list[str] = []


def _on_event(name: str, *a, **kw) -> None:
    if name == "/jax/core/compile/backend_compile_duration":
        _COMPILES.append(name)


jax.monitoring.register_event_duration_secs_listener(_on_event)


def _oracle(x, w):
    """The XLA path's elementwise math (llama._mm): dequantize, cast to
    the activation dtype, one long contraction."""
    return x @ w.dequantize().astype(x.dtype)


def _assert_matches(got, ref, exact):
    """Single-tile contractions share the oracle's reduction order ->
    exact equality; multi-tile streams sum f32 partials per tile, the
    same fidelity class as the paged Pallas kernels vs the XLA gather."""
    if exact:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    else:
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=1e-3, atol=1e-3
        )


# -- int8 kernel --------------------------------------------------------------
@pytest.mark.parametrize(
    "T,In,Out,exact",
    [
        (8, 256, 384, True),     # exactly one contraction tile
        (16, 300, 256, False),   # ragged last tile (clamp + re-read zero)
        (4, 64, 128, True),      # contraction smaller than IN_TILE
        (32, 512, 512, False),   # multi-tile contraction
        (1, 256, 128, True),     # single decode row
    ],
)
def test_int8_matches_oracle(T, In, Out, exact):
    """Tile-by-tile dequant mirrors the oracle's elementwise math:
    single-tile shapes are bit-exact, multi-tile shapes differ only by
    f32 reduction order."""
    rng = np.random.default_rng(0)
    w = quantize_weight(
        jnp.asarray(rng.standard_normal((In, Out)), jnp.float32)
    )
    x = jnp.asarray(rng.standard_normal((T, In)), jnp.float32)
    got = quant_matmul_pallas(x, w, interpret=True)
    _assert_matches(got, _oracle(x, w), exact)


def test_int8_bf16_activations():
    """bf16 activations keep the oracle's cast discipline (dequantized
    tile cast to bf16 BEFORE the dot) — still elementwise identical."""
    rng = np.random.default_rng(1)
    w = quantize_weight(
        jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    )
    x = jnp.asarray(rng.standard_normal((8, 256)), jnp.bfloat16)
    got = quant_matmul_pallas(x, w, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(_oracle(x, w), np.float32)
    )


# -- packed int4 kernel -------------------------------------------------------
@pytest.mark.parametrize(
    "T,In,Out,group,exact",
    [
        (8, 256, 384, 128, False),   # two scale groups (two DMA steps)
        (16, 256, 256, 256, True),   # single group = whole contraction
        (4, 64, 128, 128, True),     # contraction < requested group
        (32, 512, 512, 128, False),  # many groups, many out tiles
    ],
)
def test_int4_matches_oracle(T, In, Out, group, exact):
    rng = np.random.default_rng(2)
    w = quantize_weight4(
        jnp.asarray(rng.standard_normal((In, Out)), jnp.float32),
        group=group,
    )
    x = jnp.asarray(rng.standard_normal((T, In)), jnp.float32)
    got = quant_matmul_pallas(x, w, interpret=True)
    _assert_matches(got, _oracle(x, w), exact)


def test_int4_nibble_order_against_manual_unpack():
    """The kernel's in-register unpack must reproduce quantize_weight4's
    packing exactly: low nibble = even contraction row, high = odd,
    arithmetic shifts sign-extending negatives."""
    rng = np.random.default_rng(3)
    In, Out = 32, 128
    w = quantize_weight4(
        jnp.asarray(rng.standard_normal((In, Out)), jnp.float32), group=In
    )
    # One-hot activations read out single dequantized rows.
    x = jnp.eye(In, dtype=jnp.float32)
    got = quant_matmul_pallas(x, w, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(w.dequantize(), np.float32)
    )


# -- supports() / error surface ----------------------------------------------
def test_supports_gates():
    w8 = quantize_weight(jnp.ones((64, 128), jnp.float32))
    assert supports(w8)
    # Stacked/MoE 3D leaves stay on the XLA path.
    stacked = QuantizedLinear(
        jnp.zeros((2, 64, 128), jnp.int8), jnp.ones((2, 1, 128))
    )
    assert not supports(stacked)
    w4 = quantize_weight4(jnp.ones((64, 128), jnp.float32))
    assert supports(w4)
    # Odd scale group would split packed bytes across groups.
    odd = QuantizedLinear4(
        jnp.zeros((48, 64), jnp.int8), jnp.ones((32, 1, 64), jnp.float32)
    )
    assert not supports(odd)
    assert not supports(jnp.ones((64, 128)))


def test_rejects_bad_shapes():
    w = quantize_weight(jnp.ones((64, 128), jnp.float32))
    with pytest.raises(ValueError, match="In"):
        quant_matmul_pallas(jnp.ones((4, 32)), w, interpret=True)
    with pytest.raises(ValueError, match=r"\[T, In\]"):
        quant_matmul_pallas(jnp.ones((2, 4, 64)), w, interpret=True)
    stacked = QuantizedLinear(
        jnp.zeros((2, 64, 128), jnp.int8), jnp.ones((2, 1, 128))
    )
    with pytest.raises(ValueError, match="2D"):
        quant_matmul_pallas(jnp.ones((4, 64)), stacked, interpret=True)


# -- TP shard_map form --------------------------------------------------------
@pytest.mark.parametrize("quant", ["int8", "int4"])
def test_tp_column_parallel_matches_oracle(quant):
    """tp=2 mesh, weight sharded on the OUTPUT axis, x replicated: each
    shard streams only its own columns; concatenated output must equal
    the unsharded oracle exactly."""
    from opsagent_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    mesh = make_mesh(tp=2, dp=1, sp=1, devices=jax.devices()[:2])
    rng = np.random.default_rng(4)
    dense = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    w = (
        quantize_weight(dense) if quant == "int8"
        else quantize_weight4(dense, group=128)
    )
    x = jnp.asarray(rng.standard_normal((8, 256)), jnp.float32)
    got = quant_matmul_pallas_tp(x, w, mesh, interpret=True)
    # int8 shards see one contraction tile (exact); int4 has two scale
    # groups per shard, so only reduction order differs.
    _assert_matches(got, _oracle(x, w), exact=(quant == "int8"))


# -- engine acceptance gates --------------------------------------------------
ENGINE_BASE = dict(
    model="tiny-test", dtype=jnp.float32, tp=1, page_size=4,
    num_pages=128, max_pages_per_seq=24, max_batch_size=3,
    prefill_buckets=(8, 16), decode_block=4,
    mixed_batching=True, mixed_buckets=(4, 8, 16), max_step_tokens=32,
    async_depth=1, warmup=False,
)

PROMPTS = [
    [257] + list(range(1, 12)),
    [257] + [5, 9, 2, 8, 1, 7, 3, 3, 4, 6, 2, 9, 8, 1, 5, 5, 2],
    [257, 4, 4, 2],
]


def _run_mixed(eng, level):
    """Chunked mixed admission + interleaved decode to completion, with
    the zero-post-warmup-compile assertion around the serving window."""
    from opsagent_tpu.serving.sampler import SamplingParams

    eng.warmup(level)
    sampling = SamplingParams(temperature=0.0, max_tokens=8)
    n0 = len(_COMPILES)
    sids: list[int] = []
    for prompt in PROMPTS:
        b = eng.begin_request(prompt, sampling)
        while b in eng._prefilling:
            done, total = eng.prefill_progress(b)
            lanes = [s for s in sids if not eng.sequences[s].done][:2]
            eng.step_mixed(lanes, {b: min(total - done, 16)})
        sids.append(b)
    live = [s for s in sids if not eng.sequences[s].done]
    while live:
        eng.step_mixed(live, {})
        live = [s for s in live if not eng.sequences[s].done]
    outs = [eng.finish(s) for s in sids]
    assert len(_COMPILES) == n0, (
        f"{len(_COMPILES) - n0} post-warmup compiles with "
        f"weight_stream={eng.kernels.weights}"
    )
    return outs


@pytest.mark.parametrize(
    "quant,level",
    [
        ("int8", "sessions"),     # ffwd + full mixed family warmed
        ("int4", "bench-mixed"),  # the sweep's minimal mixed-only level
    ],
)
def test_engine_weight_streams_byte_identical(monkeypatch, quant, level):
    """The tentpole acceptance gate: pallas-dma weight streaming through
    the REAL mixed hot path (chunked admission + interleaved decode, the
    exact step_mixed composition serving runs) produces byte-identical
    greedy output to the xla weight stream, with zero post-warmup
    compiles on both engines."""
    from opsagent_tpu.serving.engine import Engine, EngineConfig

    monkeypatch.setenv("OPSAGENT_PALLAS_INTERPRET", "1")
    outs = {}
    for ws in ("xla", "pallas-dma"):
        eng = Engine(EngineConfig(
            quantize=quant, weight_stream=ws, **ENGINE_BASE
        ))
        assert eng.kernels.weights == ws
        assert eng.impl_info()["weight_stream"] == ws
        outs[ws] = _run_mixed(eng, level)
    assert outs["xla"] == outs["pallas-dma"], outs


def test_engine_weight_stream_env_knob(monkeypatch):
    """OPSAGENT_WEIGHT_STREAM is the deploy-side spelling of the config
    field; the config field wins when both are set."""
    from opsagent_tpu.serving.engine import Engine, EngineConfig

    monkeypatch.setenv("OPSAGENT_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("OPSAGENT_WEIGHT_STREAM", "pallas-dma")
    eng = Engine(EngineConfig(quantize="int8", **ENGINE_BASE))
    assert eng.kernels.weights == "pallas-dma"


def test_engine_refuses_weight_stream_without_quantized_weights():
    """pallas-dma weight streaming needs narrow storage to stream: an
    explicit request on full-precision weights is refused at init with
    the reason — it never runs as xla under the kernel's name."""
    from opsagent_tpu.serving.engine import (
        BackendRefused, Engine, EngineConfig,
    )

    with pytest.raises(BackendRefused, match="needs quantize=int8"):
        Engine(EngineConfig(weight_stream="pallas-dma", **ENGINE_BASE))


def test_engine_refuses_weight_stream_on_tp():
    """The prefetch kernel is single-shard until the row-parallel psum
    epilogue is wired: a sharded engine asking for it is refused."""
    from opsagent_tpu.serving.engine import (
        BackendRefused, Engine, EngineConfig,
    )

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    cfg = dict(ENGINE_BASE, tp=2)
    with pytest.raises(BackendRefused, match="single-shard only"):
        Engine(EngineConfig(
            quantize="int8", weight_stream="pallas-dma", **cfg
        ))


def test_engine_rejects_unknown_weight_stream():
    from opsagent_tpu.serving.engine import Engine, EngineConfig

    with pytest.raises(ValueError, match="weight_stream"):
        Engine(EngineConfig(weight_stream="dma2", **ENGINE_BASE))


def test_attribution_reroutes_weight_bytes_under_prefetch():
    """weight_stream=pallas-dma moves the weight bytes to the
    weights_prefetch kind and prices the step at the OVERLAPPED roofline
    max(bytes/bw, flops/peak); the serial model is unchanged."""
    from opsagent_tpu.obs.attribution import Attribution

    kw = dict(
        num_params=1_000_000, num_layers=4, num_heads=8, num_kv_heads=4,
        head_dim=64, vocab_size=1000, quantize="int8",
        device_kind="TPU v5 lite",
    )
    serial = Attribution(**kw)
    overlap = Attribution(weight_stream="pallas-dma", **kw)
    cs = serial.cost(q_tokens=4, kv_read_tokens=100, kv_write_tokens=4)
    co = overlap.cost(q_tokens=4, kv_read_tokens=100, kv_write_tokens=4)
    assert cs["weights"] > 0 and cs["weights_prefetch"] == 0
    assert co["weights"] == 0 and co["weights_prefetch"] == cs["weights"]
    assert co["total"] == cs["total"]
    # Bytes-bound composition: overlapped floor equals the bytes floor.
    assert co["modeled_s"] == cs["modeled_s"]
    # Compute-bound composition: the FLOP term takes over.
    big = overlap.cost(q_tokens=100_000, attn_q_ctx=10_000_000)
    assert big["modeled_s"] > big["total"] / overlap.hbm_bytes_s
    assert big["modeled_s"] == pytest.approx(
        big["flops"] / overlap.peak_flops_s
    )
