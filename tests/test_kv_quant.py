"""int8 KV-cache quantization (ops.attention.QuantizedPages).

Decode-step KV reads are the dominant non-weight HBM term at serving
shapes (PERF.md roofline); int8 pages + per-token-per-head scales halve
them. These tests pin the write/read roundtrip against the bf16 page
path and the engine-level wiring (config validation, backend forcing,
end-to-end generation).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from opsagent_tpu.ops.attention import (
    QuantizedPages,
    paged_decode_attention,
    paged_prefix_attention,
    quantize_kv_rows,
    write_kv_pages,
)


def _rand_case(rng, B=2, S=12, K=2, D=16, P=4, MaxP=6, num_pages=16):
    q = jnp.asarray(rng.standard_normal((B, S, K * 2, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, K, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, K, D)), jnp.float32)
    table = np.full((B, MaxP), -1, np.int32)
    used = 0
    for b in range(B):
        for p in range((S + P - 1) // P):
            table[b, p] = used
            used += 1
    return q, k, v, jnp.asarray(table)


def _pages(num_pages, P, K, D, quant):
    if quant:
        return QuantizedPages(
            jnp.zeros((num_pages, P, K, D), jnp.int8),
            jnp.ones((num_pages, P, K), jnp.float32),
        )
    return jnp.zeros((num_pages, P, K, D), jnp.float32)


def test_quantize_kv_rows_roundtrip_error():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 5, 3, 16)), jnp.float32)
    qv, sc = quantize_kv_rows(x)
    assert qv.dtype == jnp.int8 and sc.shape == (2, 5, 3)
    err = np.abs(np.asarray(qv, np.float32) * np.asarray(sc)[..., None] - np.asarray(x))
    # Symmetric absmax int8: error bounded by half a step per row.
    assert (err <= np.asarray(sc)[..., None] / 2 + 1e-6).all()


@pytest.mark.parametrize("reader", ["decode", "prefix"])
def test_quantized_pages_attention_matches_fp(reader):
    """write -> gather-attend through QuantizedPages must match the bf16
    page path to int8-rounding tolerance."""
    rng = np.random.default_rng(1)
    B, S, K, D, P, MaxP, N = 2, 12, 2, 16, 4, 6, 16
    q, k, v, table = _rand_case(rng, B, S, K, D, P, MaxP, N)
    start = jnp.zeros((B,), jnp.int32)
    lens = jnp.full((B,), S, jnp.int32)

    kf, vf = write_kv_pages(
        _pages(N, P, K, D, False), _pages(N, P, K, D, False),
        k, v, table, start, valid_len=lens,
    )
    kq, vq = write_kv_pages(
        _pages(N, P, K, D, True), _pages(N, P, K, D, True),
        k, v, table, start, valid_len=lens,
    )
    assert isinstance(kq, QuantizedPages)
    if reader == "decode":
        q1 = q[:, -1]
        ref = paged_decode_attention(q1, kf, vf, table, lens)
        got = paged_decode_attention(q1, kq, vq, table, lens)
    else:
        ref = paged_prefix_attention(q, kf, vf, table, start, lens)
        got = paged_prefix_attention(q, kq, vq, table, start, lens)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=5e-2, atol=5e-2
    )


def test_quantized_pages_layer_form_and_chunked_writes():
    """The [L, N, P, K(, D)] layer form: chunked writes at an offset land
    in the right layer's region and read back through the decode path."""
    rng = np.random.default_rng(2)
    B, S, K, D, P, MaxP, N, L = 1, 8, 2, 8, 4, 4, 8, 2
    q, k, v, table = _rand_case(rng, B, S, K, D, P, MaxP, N)
    lens = jnp.full((B,), S, jnp.int32)

    def layered(quant):
        if quant:
            return QuantizedPages(
                jnp.zeros((L, N, P, K, D), jnp.int8),
                jnp.ones((L, N, P, K), jnp.float32),
            )
        return jnp.zeros((L, N, P, K, D), jnp.float32)

    for li in range(L):
        kf, vf = layered(False), layered(False)
        kq, vq = layered(True), layered(True)
        # Two chunked writes: [0, S/2) then [S/2, S).
        h = S // 2
        for lo, hi in ((0, h), (h, S)):
            seg_k, seg_v = k[:, lo:hi], v[:, lo:hi]
            st = jnp.full((B,), lo, jnp.int32)
            vl = jnp.full((B,), hi - lo, jnp.int32)
            kf, vf = write_kv_pages(
                kf, vf, seg_k, seg_v, table, st,
                valid_len=vl, layer=jnp.int32(li),
            )
            kq, vq = write_kv_pages(
                kq, vq, seg_k, seg_v, table, st,
                valid_len=vl, layer=jnp.int32(li),
            )
        ref = paged_decode_attention(
            q[:, -1], kf, vf, table, lens, layer=jnp.int32(li)
        )
        got = paged_decode_attention(
            q[:, -1], kq, vq, table, lens, layer=jnp.int32(li)
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=5e-2, atol=5e-2
        )


# -- pallas-dma quantized kernel ---------------------------------------------

def test_pallas_dma_quantized_matches_xla_reader():
    """The manual-DMA kernel fed QuantizedPages (interpret mode) must
    match the XLA gather reader on the same quantized cache — same
    dequantize math, different data path."""
    from opsagent_tpu.ops.paged_attention_pallas import (
        paged_decode_attention_pallas_dma,
    )

    rng = np.random.default_rng(5)
    B, S, K, D, P, MaxP, N = 2, 20, 2, 32, 4, 8, 16
    q, k, v, table = _rand_case(rng, B, S, K, D, P, MaxP, N)
    start = jnp.zeros((B,), jnp.int32)
    lens = jnp.full((B,), S, jnp.int32)
    kq, vq = write_kv_pages(
        _pages(N, P, K, D, True), _pages(N, P, K, D, True),
        k, v, table, start, valid_len=lens,
    )
    q1 = q[:, -1]
    ref = paged_decode_attention(q1, kq, vq, table, lens)
    got = paged_decode_attention_pallas_dma(
        q1, kq, vq, table, lens, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_pallas_dma_quantized_layer_form():
    """Whole-cache [L, N, ...] QuantizedPages with a layer offset through
    the dma kernel (interpret) vs the XLA reader."""
    from opsagent_tpu.ops.paged_attention_pallas import (
        paged_decode_attention_pallas_dma,
    )
    from opsagent_tpu.ops.attention import QuantizedPages

    rng = np.random.default_rng(6)
    B, S, K, D, P, MaxP, N, L = 1, 10, 2, 16, 4, 4, 8, 3
    q, k, v, table = _rand_case(rng, B, S, K, D, P, MaxP, N)
    start = jnp.zeros((B,), jnp.int32)
    lens = jnp.full((B,), S, jnp.int32)
    pages = QuantizedPages(
        jnp.zeros((L, N, P, K, D), jnp.int8),
        jnp.ones((L, N, P, K), jnp.float32),
    )
    kq = write_kv_pages(
        pages, pages, k, v, table, start,
        valid_len=lens, layer=jnp.int32(2),
    )[0]
    q1 = q[:, -1]
    ref = paged_decode_attention(q1, kq, kq, table, lens, layer=jnp.int32(2))
    got = paged_decode_attention_pallas_dma(
        q1, kq, kq, table, lens, interpret=True, layer=jnp.int32(2)
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.slow
def test_pallas_dma_quantized_at_bench_8b_decode_shape():
    """Interpret parity at the EXACT pallas-dma-kv bench stage shape
    (B=32, K=8, D=128, P=64, MaxP=12, int8 pages, ragged + one full row)
    — validated before the stage burns chip time, like the bf16 twin in
    test_pallas_paged."""
    from opsagent_tpu.ops.attention import QuantizedPages
    from opsagent_tpu.ops.paged_attention_pallas import (
        paged_decode_attention_pallas_dma,
    )

    rng = np.random.default_rng(43)
    B, K, D, P, MaxP, N = 32, 8, 128, 64, 12, 32 * 12 + 2
    H = 32
    lengths = np.asarray(
        [MaxP * P] + [int(rng.integers(1, MaxP * P + 1)) for _ in range(B - 1)],
        np.int32,
    )
    table = np.full((B, MaxP), -1, np.int32)
    free = list(range(N))
    for b in range(B):
        for i in range(-(-int(lengths[b]) // P)):
            table[b, i] = free.pop()
    # f32 queries: both paths then compute in f32 and must agree tightly
    # (the kernel applies scales in score space, the reader dequantizes —
    # algebraically identical). bf16 rounding-order differences between
    # the two paths are covered by the bf16 twin in test_pallas_paged;
    # THIS test de-risks grid/scratch/indexing at the exact stage shape.
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    kq = QuantizedPages(
        jnp.asarray(rng.integers(-127, 128, size=(N, P, K, D)), jnp.int8),
        jnp.asarray(rng.uniform(0.01, 0.2, size=(N, P, K)), jnp.float32),
    )
    vq = QuantizedPages(
        jnp.asarray(rng.integers(-127, 128, size=(N, P, K, D)), jnp.int8),
        jnp.asarray(rng.uniform(0.01, 0.2, size=(N, P, K)), jnp.float32),
    )
    tbl = jnp.asarray(table)
    lens = jnp.asarray(lengths)
    ref = paged_decode_attention(q, kq, vq, tbl, lens)
    got = paged_decode_attention_pallas_dma(
        q, kq, vq, tbl, lens, interpret=True
    )
    # atol 1e-3: f32 blockwise online softmax vs the reference's full
    # softmax reorder accumulation over up to 768 tokens; observed worst
    # deviation ~3e-4 on near-zero outputs.
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=1e-3, atol=1e-3,
    )


def test_pallas_dma_quantized_under_tp_matches_oracle():
    """QuantizedPages through the tp shard_map wrapper: the scale-plane
    PartitionSpec pytree must mirror the leaf structure and put tp on the
    kv-head axis (one fewer trailing dim than the values)."""
    import jax

    from opsagent_tpu.ops.attention import paged_decode_attention_pallas_tp
    from opsagent_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 2:
        import pytest

        pytest.skip("needs >= 2 devices")
    mesh = make_mesh(tp=2, dp=1, sp=1, devices=jax.devices()[:2])
    rng = np.random.default_rng(7)
    B, S, K, D, P, MaxP, N = 2, 17, 2, 32, 8, 4, 10
    q, k, v, table = _rand_case(rng, B, S, K, D, P, MaxP, N)
    start = jnp.zeros((B,), jnp.int32)
    lens = jnp.full((B,), S, jnp.int32)
    kq, vq = write_kv_pages(
        _pages(N, P, K, D, True), _pages(N, P, K, D, True),
        k, v, table, start, valid_len=lens,
    )
    q1 = q[:, -1]
    ref = paged_decode_attention(q1, kq, vq, table, lens)
    got = paged_decode_attention_pallas_tp(
        q1, kq, vq, table, lens, mesh, interpret=True, impl="pallas-dma",
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


# -- engine wiring -----------------------------------------------------------

def _engine_kwargs():
    return dict(
        model="tiny-test", max_batch_size=2, num_pages=32, page_size=8,
        max_pages_per_seq=8, prefill_buckets=(16,), decode_block=4,
    )


def test_engine_kv_quantize_generates():
    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams

    eng = Engine(EngineConfig(kv_quantize="int8", **_engine_kwargs()))
    assert eng.attn_impl == "xla"
    sid = eng.begin_request(
        [5, 6, 7, 8], SamplingParams(max_tokens=6, temperature=0.0)
    )
    while not eng.sequences[sid].done:
        eng.step_block([sid])
    toks = eng.finish(sid)
    assert len(toks) == 6 and all(0 <= t < 512 for t in toks)


def test_engine_kv_quantize_close_to_fp_cache_on_pinned_context():
    """tiny-test at f32: int8 KV rounding must stay near-lossless. The old
    form compared raw greedy tokens — weight-dependent near-ties at the
    argmax flip under rounding, so the expectation was data, not
    correctness. Pinned-logit harness instead: a +100 logit_bias forces
    BOTH engines through the identical token context (so the caches hold
    the same history), and the per-step top-logprob distributions over
    that shared context must agree within a small tolerance — the actual
    near-lossless claim, deterministic on CPU."""
    import numpy as np

    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams

    prompt = [11, 12, 13, 14, 15]
    pin = 42  # forced continuation token: identical context in both runs
    runs = []
    for kvq in ("", "int8"):
        eng = Engine(EngineConfig(kv_quantize=kvq, **_engine_kwargs()))
        sid = eng.begin_request(
            prompt,
            SamplingParams(
                max_tokens=6, temperature=0.0,
                logit_bias=((pin, 100.0),),
                logprobs=True, top_logprobs=20,
            ),
        )
        while not eng.sequences[sid].done:
            eng.step_block([sid])
        seq = eng.sequences[sid]
        runs.append((eng.finish(sid), list(seq.logprob_data)))
    (toks_fp, lp_fp), (toks_q, lp_q) = runs
    assert toks_fp == [pin] * 6 == toks_q  # bias pinned both contexts
    assert len(lp_fp) == len(lp_q) == 6
    # Steps >= 1 read the quantized pages the pinned context wrote (step 0
    # reads only prefill-written pages — also quantized). Compare the fp
    # run's strongest alternatives against the quantized run's top-20 by
    # token id: every high-mass token must be present with a close
    # logprob. 0.25 nats is far below any argmax-relevant margin while
    # leaving room for int8 rounding at this tiny head dim.
    for step_fp, step_q in zip(lp_fp, lp_q):
        q_by_id = dict(step_q["top"])
        for tid, lp in step_fp["top"][:5]:
            assert tid in q_by_id, f"fp top-5 token {tid} left int8 top-20"
            assert abs(lp - q_by_id[tid]) < 0.25, (
                f"token {tid}: fp {lp} vs int8 {q_by_id[tid]}"
            )


def test_engine_keeps_pallas_dma_with_kv_quantize_at_aligned_shapes(
    monkeypatch,
):
    """kv_quantize does not force xla when the manual-DMA kernel (which
    has a quantized path) is selected AND the shapes satisfy Mosaic's
    alignment rules: head_dim a multiple of 128, and the kv heads of one
    shard a multiple of the page dtype's sublane packing (4 for int8).
    Short of either, the engine refuses with the compiler's reason."""
    from dataclasses import replace

    from opsagent_tpu.models.config import get_config_preset
    from opsagent_tpu.serving.engine import (
        BackendRefused, Engine, EngineConfig,
    )

    monkeypatch.setenv("OPSAGENT_PAGED_BACKEND", "pallas-dma")
    cfg128 = replace(
        get_config_preset("tiny-test"), head_dim=128, num_kv_heads=4
    )
    kw = dict(kv_quantize="int8", warmup=False, **_engine_kwargs())
    eng = Engine(EngineConfig(tp=1, **kw), model_cfg=cfg128)
    assert eng.attn_impl == "pallas-dma"
    with pytest.raises(BackendRefused, match=r"2 kv head\(s\) per shard"):
        Engine(EngineConfig(tp=2, **kw), model_cfg=cfg128)


def test_engine_rejects_bad_kv_quantize_and_mla_combo():
    from opsagent_tpu.serving.engine import Engine, EngineConfig

    with pytest.raises(ValueError, match="kv_quantize"):
        Engine(EngineConfig(kv_quantize="int4", **_engine_kwargs()))
    kwargs = dict(_engine_kwargs(), model="tiny-mla")
    with pytest.raises(ValueError, match="MLA"):
        Engine(EngineConfig(kv_quantize="int8", **kwargs))


def test_engine_kv_quantize_speculative_matches_plain():
    """Speculative decoding over the quantized cache (verify_step writes
    and reads QuantizedPages) must emit exactly the plain quantized
    engine's greedy tokens — speculation is exact for greedy regardless
    of the cache's storage format."""
    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams

    prompt = [7, 8, 9, 7, 8, 9, 7, 8]  # repetitive: lets drafts engage
    outs = []
    for k in (0, 3):
        eng = Engine(EngineConfig(
            kv_quantize="int8", speculative_k=k, **_engine_kwargs()
        ))
        sid = eng.begin_request(
            prompt, SamplingParams(max_tokens=10, temperature=0.0)
        )
        while not eng.sequences[sid].done:
            eng.step_block([sid])
        outs.append(eng.finish(sid))
    assert outs[0] == outs[1]


def test_engine_kv_quantize_under_tp_mesh():
    """Quantized pages (values AND scales) must shard over tp and execute."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams

    eng = Engine(EngineConfig(
        tp=2, kv_quantize="int8", **_engine_kwargs()
    ))
    sid = eng.begin_request(
        [3, 4, 5], SamplingParams(max_tokens=4, temperature=0.0)
    )
    while not eng.sequences[sid].done:
        eng.step_block([sid])
    assert len(eng.finish(sid)) == 4
