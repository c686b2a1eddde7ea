"""Pallas paged-decode-attention kernel vs the XLA gather reference.

Runs the kernel in interpreter mode on CPU (the TPU-lowered path shares the
same trace), asserting numerical equivalence with
``ops.attention.paged_decode_attention`` across ragged lengths, GQA group
sizes, multi-page sequences, and inactive (length-0) batch slots.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from opsagent_tpu.ops.attention import paged_decode_attention
from opsagent_tpu.ops.paged_attention_pallas import (
    paged_decode_attention_pallas,
    paged_decode_attention_pallas_dma,
)

KERNELS = [paged_decode_attention_pallas, paged_decode_attention_pallas_dma]


def _make_case(
    rng, B, H, K, D, P, MaxP, num_pages, lengths,
):
    """Random paged KV state with each sequence owning disjoint pages."""
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k_pages = jnp.asarray(rng.standard_normal((num_pages, P, K, D)), jnp.float32)
    v_pages = jnp.asarray(rng.standard_normal((num_pages, P, K, D)), jnp.float32)
    table = np.full((B, MaxP), -1, np.int32)
    free = list(range(num_pages))
    rng.shuffle(free)
    for b, n in enumerate(lengths):
        need = -(-n // P)
        for i in range(need):
            table[b, i] = free.pop()
    return q, k_pages, v_pages, jnp.asarray(table), jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize(
    "B,H,K,D,P,MaxP,lengths",
    [
        (2, 4, 2, 64, 8, 4, [5, 17]),          # GQA, ragged, multi-page
        (1, 2, 2, 32, 4, 6, [24]),             # MHA (G=1), exactly full pages
        (3, 8, 2, 16, 8, 3, [1, 8, 20]),       # boundary lengths
        (2, 4, 4, 32, 8, 4, [9, 0]),           # inactive slot (length 0)
    ],
)
def test_pallas_matches_xla_reference(B, H, K, D, P, MaxP, lengths, kernel):
    rng = np.random.default_rng(0)
    q, k_pages, v_pages, table, lens = _make_case(
        rng, B, H, K, D, P, MaxP, num_pages=B * MaxP + 2, lengths=lengths
    )
    ref = paged_decode_attention(q, k_pages, v_pages, table, lens)
    got = kernel(
        q, k_pages, v_pages, table, lens, interpret=True
    )
    # Inactive slots: the kernel defines them as zeros; the reference
    # produces attention over a masked-everything row (softmax of -inf) —
    # compare only active rows, then check the kernel's zeros.
    active = np.asarray(lens) > 0
    np.testing.assert_allclose(
        np.asarray(got)[active], np.asarray(ref)[active], rtol=2e-5, atol=2e-5
    )
    assert not np.isnan(np.asarray(got)).any()
    if (~active).any():
        np.testing.assert_array_equal(np.asarray(got)[~active], 0.0)


@pytest.mark.parametrize("kernel", KERNELS)
def test_pallas_bf16_tolerance(kernel):
    rng = np.random.default_rng(1)
    q, k_pages, v_pages, table, lens = _make_case(
        rng, B=2, H=4, K=2, D=64, P=8, MaxP=4, num_pages=12, lengths=[13, 29]
    )
    q, k_pages, v_pages = (
        x.astype(jnp.bfloat16) for x in (q, k_pages, v_pages)
    )
    ref = paged_decode_attention(q, k_pages, v_pages, table, lens)
    got = kernel(
        q, k_pages, v_pages, table, lens, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_decode_step_with_pallas_impl_matches_xla():
    """End-to-end: llama.decode_step with attn_impl="pallas" (interpret via
    env is not available, so call through the model with monkeypatched
    dispatcher interpret flag) equals the xla impl."""
    from opsagent_tpu.models import llama
    from opsagent_tpu.models.config import get_config_preset
    from opsagent_tpu.ops import paged_attention_pallas as pp

    cfg = get_config_preset("tiny-test")
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    P, NP, MaxP, B = 8, 16, 4, 2
    # The Pallas kernels take split pages (2 kv heads would be merged).
    cache = llama.make_cache(
        cfg, NP, P, dtype=jnp.float32,
        form=llama.cache_form(cfg, attn_impl="pallas"),
    )

    # Prefill two sequences to populate pages.
    lens = [5, 9]
    table = np.full((B, MaxP), -1, np.int32)
    table[0, :2] = [0, 1]
    table[1, :2] = [2, 3]
    S = 16
    tokens = np.zeros((B, S), np.int32)
    rng = np.random.default_rng(2)
    for b, n in enumerate(lens):
        tokens[b, :n] = rng.integers(1, cfg.vocab_size, n)
    logits, cache = llama.prefill(
        params, cfg, jnp.asarray(tokens), jnp.asarray(lens, jnp.int32),
        cache, jnp.asarray(table), dtype=jnp.float32,
    )

    step_args = (
        jnp.asarray([7, 8], jnp.int32),
        jnp.asarray(lens, jnp.int32),
    )
    out_xla, _ = llama.decode_step(
        params, cfg, step_args[0], step_args[1], cache,
        jnp.asarray(table), jnp.asarray([True, True]),
        dtype=jnp.float32, attn_impl="xla",
    )

    # Force interpret mode inside the pallas path for the CPU test.
    orig = pp.paged_decode_attention_pallas

    def interp(q, k, v, t, ln, interpret=False, layer=None):
        return orig(q, k, v, t, ln, interpret=True, layer=layer)

    pp.paged_decode_attention_pallas = interp
    try:
        out_pl, _ = llama.decode_step(
            params, cfg, step_args[0], step_args[1], cache,
            jnp.asarray(table), jnp.asarray([True, True]),
            dtype=jnp.float32, attn_impl="pallas",
        )
    finally:
        pp.paged_decode_attention_pallas = orig
    np.testing.assert_allclose(
        np.asarray(out_xla), np.asarray(out_pl), rtol=1e-4, atol=1e-4
    )


def test_pallas_under_tp_matches_oracle():
    """VERDICT item: the kernel must run under tensor parallelism. shard_map
    over a tp=2 mesh (q heads + kv heads both tp-sharded) must reproduce the
    unsharded XLA oracle — per-shard GQA needs no collective."""
    from opsagent_tpu.ops.attention import paged_decode_attention_pallas_tp
    from opsagent_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(tp=2, dp=1, sp=1, devices=jax.devices()[:2])
    rng = np.random.default_rng(3)
    # K=2 kv heads (1 per shard), H=4 query heads (2 per shard), G=2.
    q, k_pages, v_pages, table, lens = _make_case(
        rng, B=2, H=4, K=2, D=64, P=8, MaxP=4, num_pages=10,
        lengths=[5, 17],
    )
    ref = paged_decode_attention(q, k_pages, v_pages, table, lens)
    got = paged_decode_attention_pallas_tp(
        q, k_pages, v_pages, table, lens, mesh, interpret=True
    )
    active = np.asarray(lens) > 0
    np.testing.assert_allclose(
        np.asarray(got)[active], np.asarray(ref)[active], rtol=2e-5, atol=2e-5
    )


def test_pallas_under_tp_layer_form():
    """The tp wrapper with the whole-cache [L, N, P, K, D] form + layer
    offset must select the right layer's pages per shard."""
    from opsagent_tpu.ops.attention import paged_decode_attention_pallas_tp
    from opsagent_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(tp=2, dp=1, sp=1, devices=jax.devices()[:2])
    rng = np.random.default_rng(4)
    q, k_pages, v_pages, table, lens = _make_case(
        rng, B=2, H=4, K=2, D=32, P=8, MaxP=3, num_pages=8,
        lengths=[9, 20],
    )
    L = 3
    k_l = jnp.stack([
        jnp.asarray(rng.standard_normal(k_pages.shape), jnp.float32)
        for _ in range(L)
    ])
    v_l = jnp.stack([
        jnp.asarray(rng.standard_normal(v_pages.shape), jnp.float32)
        for _ in range(L)
    ])
    for layer in (0, 2):
        ref = paged_decode_attention(
            q, k_l[layer], v_l[layer], table, lens
        )
        got = paged_decode_attention_pallas_tp(
            q, k_l, v_l, table, lens, mesh,
            layer=jnp.int32(layer), interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


def test_pallas_dma_under_tp_matches_oracle():
    """The manual-DMA kernel under tensor parallelism (impl dispatch)."""
    from opsagent_tpu.ops.attention import paged_decode_attention_pallas_tp
    from opsagent_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(tp=2, dp=1, sp=1, devices=jax.devices()[:2])
    rng = np.random.default_rng(5)
    q, k_pages, v_pages, table, lens = _make_case(
        rng, B=2, H=4, K=2, D=64, P=8, MaxP=4, num_pages=10,
        lengths=[5, 17],
    )
    ref = paged_decode_attention(q, k_pages, v_pages, table, lens)
    got = paged_decode_attention_pallas_tp(
        q, k_pages, v_pages, table, lens, mesh, interpret=True,
        impl="pallas-dma",
    )
    active = np.asarray(lens) > 0
    np.testing.assert_allclose(
        np.asarray(got)[active], np.asarray(ref)[active], rtol=2e-5, atol=2e-5
    )


def test_pallas_dma_layer_form():
    """Whole-cache [L, N, P, K, D] + layer offset on the DMA kernel."""
    rng = np.random.default_rng(6)
    q, k_pages, v_pages, table, lens = _make_case(
        rng, B=2, H=4, K=2, D=32, P=8, MaxP=3, num_pages=8,
        lengths=[9, 20],
    )
    L = 3
    k_l = jnp.stack([
        jnp.asarray(rng.standard_normal(k_pages.shape), jnp.float32)
        for _ in range(L)
    ])
    v_l = jnp.stack([
        jnp.asarray(rng.standard_normal(v_pages.shape), jnp.float32)
        for _ in range(L)
    ])
    for layer in (0, 2):
        ref = paged_decode_attention(q, k_l[layer], v_l[layer], table, lens)
        got = paged_decode_attention_pallas_dma(
            q, k_l, v_l, table, lens, interpret=True, layer=jnp.int32(layer)
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


@pytest.mark.slow
def test_pallas_dma_at_bench_8b_decode_shape():
    """Interpret-mode parity at the EXACT bench-8b decode shape (B=32,
    K=8, D=128, P=64, MaxP=12, bf16 pages, ragged lengths): the shape the
    on-chip kernel sweep runs, validated before burning chip time on it.
    Reduced batch rows would hide grid/scratch sizing mistakes that only
    appear at the serving shape."""
    rng = np.random.default_rng(42)
    B, H, K, D, P, MaxP = 32, 32, 8, 128, 64, 12
    lengths = [int(rng.integers(1, MaxP * P + 1)) for _ in range(B)]
    lengths[0] = MaxP * P  # pin the exactly-full boundary the bench reaches
    q, k_pages, v_pages, table, lens = _make_case(
        rng, B, H, K, D, P, MaxP, num_pages=B * MaxP + 2, lengths=lengths
    )
    q = q.astype(jnp.bfloat16)
    k_pages = k_pages.astype(jnp.bfloat16)
    v_pages = v_pages.astype(jnp.bfloat16)
    ref = paged_decode_attention(q, k_pages, v_pages, table, lens)
    got = paged_decode_attention_pallas_dma(
        q, k_pages, v_pages, table, lens, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_pallas_dma_rejects_unaligned_head_dim():
    """Compiled mode refuses head_dim % 128 != 0 up front (Mosaic's
    manual-DMA slices must be 128-aligned on the minormost dim; r04
    on-chip failure) instead of a deep Mosaic error."""
    rng = np.random.default_rng(11)
    q, k_pages, v_pages, table, lens = _make_case(
        rng, B=1, H=4, K=2, D=64, P=8, MaxP=2, num_pages=4, lengths=[8]
    )
    with pytest.raises(ValueError, match="head_dim"):
        paged_decode_attention_pallas_dma(
            q, k_pages, v_pages, table, lens, interpret=False
        )


def test_engine_refuses_pallas_dma_on_small_head_dim(monkeypatch):
    """tiny-test (head_dim 16) + OPSAGENT_PAGED_BACKEND=pallas-dma is
    refused at engine init with Mosaic's reason — neither a death at
    first prefill nor an xla run under the kernel's name."""
    monkeypatch.setenv("OPSAGENT_PAGED_BACKEND", "pallas-dma")
    from opsagent_tpu.serving.engine import (
        BackendRefused, Engine, EngineConfig,
    )

    with pytest.raises(BackendRefused, match="head_dim 16.*tiling"):
        Engine(EngineConfig(
            model="tiny-test", max_batch_size=2, num_pages=16, page_size=8,
            max_pages_per_seq=4, prefill_buckets=(16,), decode_block=4,
        ))


def test_pallas_dma_length_beyond_table_clamps():
    """lengths > MaxP*P (tolerated by the grid kernel via clamping) must
    not read the page table out of bounds or leak a prefetch DMA."""
    rng = np.random.default_rng(7)
    q, k_pages, v_pages, table, lens = _make_case(
        rng, B=2, H=4, K=2, D=32, P=8, MaxP=3, num_pages=8,
        lengths=[24, 24],  # exactly fills all 3 pages
    )
    over = jnp.asarray([24, 40], jnp.int32)  # row 1 claims 5 pages of 3
    ref = paged_decode_attention(q, k_pages, v_pages, table, jnp.asarray([24, 24], jnp.int32))
    got = paged_decode_attention_pallas_dma(
        q, k_pages, v_pages, table, over, interpret=True
    )
    # Row 0 is unaffected; row 1 attends over its 3 real pages only (the
    # reference clamps identically), and nothing NaNs.
    np.testing.assert_allclose(
        np.asarray(got)[0], np.asarray(ref)[0], rtol=2e-5, atol=2e-5
    )
    assert not np.isnan(np.asarray(got)).any()


# -- ragged-query kernel (mixed prefill+decode step) -------------------------
def _make_ragged_case(rng, B, S, H, K, D, P, MaxP, num_pages, start, q_lens):
    """Random paged KV state for the ragged kernel: each row owns enough
    pages for start + q_len tokens (the chunk's KV is treated as already
    written, like the engine after write_kv_pages)."""
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k_pages = jnp.asarray(
        rng.standard_normal((num_pages, P, K, D)), jnp.float32
    )
    v_pages = jnp.asarray(
        rng.standard_normal((num_pages, P, K, D)), jnp.float32
    )
    table = np.full((B, MaxP), -1, np.int32)
    free = list(range(num_pages))
    rng.shuffle(free)
    for b in range(B):
        need = -(-(start[b] + q_lens[b]) // P)
        for i in range(need):
            table[b, i] = free.pop()
    return (
        q, k_pages, v_pages, jnp.asarray(table),
        jnp.asarray(start, jnp.int32), jnp.asarray(q_lens, jnp.int32),
    )


@pytest.mark.parametrize(
    "B,S,H,K,D,P,MaxP,start,q_lens",
    [
        # decode row (q_len=1) + prefill chunk + inactive row in one batch
        (3, 8, 4, 2, 32, 4, 8, [9, 4, 0], [1, 6, 0]),
        # fresh prompt chunk from position 0, full S
        (2, 8, 4, 4, 16, 8, 4, [0, 0], [8, 3]),
        # chunk crossing page boundaries with a long cached prefix
        (2, 4, 8, 2, 32, 4, 10, [13, 30], [4, 2]),
    ],
)
def test_ragged_pallas_matches_xla_reference(
    B, S, H, K, D, P, MaxP, start, q_lens
):
    from opsagent_tpu.ops.attention import paged_ragged_attention
    from opsagent_tpu.ops.paged_attention_pallas import (
        paged_ragged_attention_pallas,
    )

    rng = np.random.default_rng(11)
    q, k_pages, v_pages, table, st, ql = _make_ragged_case(
        rng, B, S, H, K, D, P, MaxP, num_pages=B * MaxP + 2,
        start=start, q_lens=q_lens,
    )
    ref = paged_ragged_attention(q, k_pages, v_pages, table, st, ql)
    got = paged_ragged_attention_pallas(
        q, k_pages, v_pages, table, st, ql, interpret=True
    )
    # Compare only valid query rows; padded rows (s >= q_len) are garbage
    # in both but must stay finite.
    for b in range(B):
        n = q_lens[b]
        if n:
            np.testing.assert_allclose(
                np.asarray(got)[b, :n], np.asarray(ref)[b, :n],
                rtol=2e-5, atol=2e-5,
            )
    assert np.isfinite(np.asarray(got)).all()


def test_ragged_decode_row_matches_decode_kernel_semantics():
    """A q_len=1 ragged row must equal single-token decode attention over
    the same cache state (the mixed step's decode-lane guarantee)."""
    from opsagent_tpu.ops.attention import (
        paged_decode_attention, paged_ragged_attention,
    )

    rng = np.random.default_rng(12)
    B, S, H, K, D, P, MaxP = 2, 4, 4, 2, 32, 4, 6
    start = [7, 14]
    q, k_pages, v_pages, table, st, ql = _make_ragged_case(
        rng, B, S, H, K, D, P, MaxP, num_pages=B * MaxP + 2,
        start=start, q_lens=[1, 1],
    )
    ragged = paged_ragged_attention(q, k_pages, v_pages, table, st, ql)
    dec = paged_decode_attention(
        q[:, 0], k_pages, v_pages, table, st + 1
    )
    np.testing.assert_allclose(
        np.asarray(ragged)[:, 0], np.asarray(dec), rtol=2e-5, atol=2e-5
    )


# -- ragged manual-DMA kernel (the mixed hot path's bytes-diet form) ---------
@pytest.mark.parametrize(
    "B,S,H,K,D,P,MaxP,start,q_lens",
    [
        # decode row (q_len=1) + prefill chunk + inactive row in one batch
        (3, 8, 4, 2, 32, 4, 8, [9, 4, 0], [1, 6, 0]),
        # fresh prompt chunk from position 0, full S
        (2, 8, 4, 4, 16, 8, 4, [0, 0], [8, 3]),
        # chunk crossing page boundaries with a long cached prefix
        (2, 4, 8, 2, 32, 4, 10, [13, 30], [4, 2]),
        # all-decode tick (the steady-state mixed shape) + inactive rows
        (4, 4, 4, 2, 16, 4, 6, [7, 3, 0, 15], [1, 1, 0, 1]),
    ],
)
def test_ragged_dma_matches_xla_reference(
    B, S, H, K, D, P, MaxP, start, q_lens
):
    from opsagent_tpu.ops.attention import paged_ragged_attention
    from opsagent_tpu.ops.paged_attention_pallas import (
        paged_ragged_attention_pallas_dma,
    )

    rng = np.random.default_rng(21)
    q, k_pages, v_pages, table, st, ql = _make_ragged_case(
        rng, B, S, H, K, D, P, MaxP, num_pages=B * MaxP + 2,
        start=start, q_lens=q_lens,
    )
    ref = paged_ragged_attention(q, k_pages, v_pages, table, st, ql)
    got = paged_ragged_attention_pallas_dma(
        q, k_pages, v_pages, table, st, ql, interpret=True
    )
    for b in range(B):
        n = q_lens[b]
        if n:
            np.testing.assert_allclose(
                np.asarray(got)[b, :n], np.asarray(ref)[b, :n],
                rtol=2e-5, atol=2e-5,
            )
        else:
            # q_len=0 rows stream ZERO pages (n=0 warmup skip) and must
            # come out exactly zero, not garbage.
            assert (np.asarray(got)[b] == 0).all()
    assert np.isfinite(np.asarray(got)).all()


def test_ragged_dma_bf16_tolerance():
    from opsagent_tpu.ops.attention import paged_ragged_attention
    from opsagent_tpu.ops.paged_attention_pallas import (
        paged_ragged_attention_pallas_dma,
    )

    rng = np.random.default_rng(22)
    q, k_pages, v_pages, table, st, ql = _make_ragged_case(
        rng, B=2, S=8, H=4, K=2, D=32, P=4, MaxP=8,
        num_pages=18, start=[9, 0], q_lens=[1, 8],
    )
    q = q.astype(jnp.bfloat16)
    k_pages = k_pages.astype(jnp.bfloat16)
    v_pages = v_pages.astype(jnp.bfloat16)
    ref = paged_ragged_attention(q, k_pages, v_pages, table, st, ql)
    got = paged_ragged_attention_pallas_dma(
        q, k_pages, v_pages, table, st, ql, interpret=True
    )
    for b, n in enumerate([1, 8]):
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[b, :n],
            np.asarray(ref, np.float32)[b, :n],
            rtol=3e-2, atol=3e-2,
        )


def test_ragged_dma_quantized_matches_xla_reader():
    """int8 QuantizedPages through the ragged DMA kernel (interpret) must
    match the XLA ragged gather on the SAME quantized cache — identical
    dequantize math, pages never materialized full-dtype."""
    from opsagent_tpu.ops.attention import (
        QuantizedPages, paged_ragged_attention, write_kv_pages,
    )
    from opsagent_tpu.ops.paged_attention_pallas import (
        paged_ragged_attention_pallas_dma,
    )

    rng = np.random.default_rng(23)
    B, S, H, K, D, P, MaxP, N = 3, 8, 4, 2, 32, 4, 8, 26
    q, k_pages, v_pages, table, st, ql = _make_ragged_case(
        rng, B, S, H, K, D, P, MaxP, num_pages=N,
        start=[9, 0, 4], q_lens=[1, 8, 0],
    )
    kq = QuantizedPages(
        jnp.zeros((N, P, K, D), jnp.int8), jnp.ones((N, P, K), jnp.float32)
    )
    vq = QuantizedPages(
        jnp.zeros((N, P, K, D), jnp.int8), jnp.ones((N, P, K), jnp.float32)
    )
    # Fill each row's resident KV (cached prefix + chunk) through the
    # real write path so scales are per-token absmax, like the engine.
    total = int(max(s + l for s, l in zip([9, 0, 4], [1, 8, 0])))
    kw = jnp.asarray(rng.standard_normal((B, total, K, D)), jnp.float32)
    vw = jnp.asarray(rng.standard_normal((B, total, K, D)), jnp.float32)
    kq, vq = write_kv_pages(
        kq, vq, kw, vw, table, jnp.zeros((B,), jnp.int32),
        valid_len=st + ql,
    )
    ref = paged_ragged_attention(q, kq, vq, table, st, ql)
    got = paged_ragged_attention_pallas_dma(
        q, kq, vq, table, st, ql, interpret=True
    )
    for b, n in enumerate([1, 8, 0]):
        if n:
            np.testing.assert_allclose(
                np.asarray(got)[b, :n], np.asarray(ref)[b, :n],
                rtol=2e-5, atol=2e-5,
            )


def test_ragged_dma_layer_form():
    """Whole-cache [L, N, P, K, D] + layer offset on the ragged DMA
    kernel selects the right layer's pages."""
    from opsagent_tpu.ops.attention import paged_ragged_attention
    from opsagent_tpu.ops.paged_attention_pallas import (
        paged_ragged_attention_pallas_dma,
    )

    rng = np.random.default_rng(24)
    q, k_pages, v_pages, table, st, ql = _make_ragged_case(
        rng, B=2, S=4, H=4, K=2, D=32, P=4, MaxP=6,
        num_pages=14, start=[9, 0], q_lens=[1, 4],
    )
    L = 3
    k_l = jnp.stack([
        jnp.asarray(rng.standard_normal(k_pages.shape), jnp.float32)
        for _ in range(L)
    ])
    v_l = jnp.stack([
        jnp.asarray(rng.standard_normal(v_pages.shape), jnp.float32)
        for _ in range(L)
    ])
    for layer in (0, 2):
        ref = paged_ragged_attention(
            q, k_l[layer], v_l[layer], table, st, ql
        )
        got = paged_ragged_attention_pallas_dma(
            q, k_l, v_l, table, st, ql,
            interpret=True, layer=jnp.int32(layer),
        )
        for b, n in enumerate([1, 4]):
            np.testing.assert_allclose(
                np.asarray(got)[b, :n], np.asarray(ref)[b, :n],
                rtol=2e-5, atol=2e-5,
            )


def test_ragged_dma_under_tp_matches_oracle():
    """The ragged DMA kernel under tensor parallelism (impl dispatch in
    the shared TP wrapper): tp=2 mesh, q + kv heads sharded, no
    collective — must reproduce the unsharded XLA ragged oracle."""
    from opsagent_tpu.ops.attention import (
        paged_ragged_attention, paged_ragged_attention_pallas_tp,
    )
    from opsagent_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(tp=2, dp=1, sp=1, devices=jax.devices()[:2])
    rng = np.random.default_rng(25)
    q, k_pages, v_pages, table, st, ql = _make_ragged_case(
        rng, B=2, S=8, H=4, K=2, D=32, P=4, MaxP=8,
        num_pages=18, start=[9, 0], q_lens=[1, 8],
    )
    ref = paged_ragged_attention(q, k_pages, v_pages, table, st, ql)
    got = paged_ragged_attention_pallas_tp(
        q, k_pages, v_pages, table, st, ql, mesh,
        interpret=True, impl="pallas-dma",
    )
    for b, n in enumerate([1, 8]):
        np.testing.assert_allclose(
            np.asarray(got)[b, :n], np.asarray(ref)[b, :n],
            rtol=2e-5, atol=2e-5,
        )


def test_ragged_dma_rejects_unaligned_head_dim():
    """Compiled mode refuses head_dim % 128 != 0 up front (the same
    Mosaic manual-DMA alignment rule as the decode kernel)."""
    from opsagent_tpu.ops.paged_attention_pallas import (
        paged_ragged_attention_pallas_dma,
    )

    rng = np.random.default_rng(26)
    q, k_pages, v_pages, table, st, ql = _make_ragged_case(
        rng, B=1, S=4, H=4, K=2, D=64, P=4, MaxP=2,
        num_pages=4, start=[0], q_lens=[4],
    )
    with pytest.raises(ValueError, match="head_dim"):
        paged_ragged_attention_pallas_dma(
            q, k_pages, v_pages, table, st, ql, interpret=False
        )


def test_ragged_dma_length_beyond_table_clamps():
    """start + q_len claiming more pages than the table holds must clamp
    to resident pages (like the decode kernel) — no OOB table read, no
    leaked prefetch DMA, no NaN."""
    from opsagent_tpu.ops.attention import paged_ragged_attention
    from opsagent_tpu.ops.paged_attention_pallas import (
        paged_ragged_attention_pallas_dma,
    )

    rng = np.random.default_rng(27)
    q, k_pages, v_pages, table, st, ql = _make_ragged_case(
        rng, B=2, S=4, H=4, K=2, D=32, P=4, MaxP=3,
        num_pages=8, start=[11, 11], q_lens=[1, 1],
    )
    over = jnp.asarray([11, 27], jnp.int32)  # row 1 claims 7 pages of 3
    ref = paged_ragged_attention(q, k_pages, v_pages, table, st, ql)
    got = paged_ragged_attention_pallas_dma(
        q, k_pages, v_pages, table, over, ql, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got)[0, :1], np.asarray(ref)[0, :1], rtol=2e-5, atol=2e-5
    )
    assert not np.isnan(np.asarray(got)).any()


def _quantized_case(rng, B, S, H, K, D, P, MaxP, N, start, q_lens):
    """int8 QuantizedPages filled through the real write path (per-token
    absmax scales, like the engine), for the grid-kernel scale tests."""
    from opsagent_tpu.ops.attention import QuantizedPages, write_kv_pages

    q, _, _, table, st, ql = _make_ragged_case(
        rng, B, S, H, K, D, P, MaxP, num_pages=N, start=start, q_lens=q_lens,
    )
    kq = QuantizedPages(
        jnp.zeros((N, P, K, D), jnp.int8), jnp.ones((N, P, K), jnp.float32)
    )
    vq = QuantizedPages(
        jnp.zeros((N, P, K, D), jnp.int8), jnp.ones((N, P, K), jnp.float32)
    )
    total = int(max(s + l for s, l in zip(start, q_lens)))
    kw = jnp.asarray(rng.standard_normal((B, total, K, D)), jnp.float32)
    vw = jnp.asarray(rng.standard_normal((B, total, K, D)), jnp.float32)
    kq, vq = write_kv_pages(
        kq, vq, kw, vw, table, jnp.zeros((B,), jnp.int32), valid_len=st + ql,
    )
    return q, kq, vq, table, st, ql


@pytest.mark.parametrize(
    "start,q_lens",
    [
        ([9, 0, 4], [1, 8, 0]),   # decode row + chunk + inactive row
        ([13, 30, 0], [4, 2, 8]), # page-crossing chunks, fresh prompt
    ],
)
def test_ragged_grid_quantized_matches_xla_reader(start, q_lens):
    """int8 QuantizedPages through the plain-pallas RAGGED GRID kernel
    (interpret): the score-space scale path (k scales multiply scores,
    v scales multiply probabilities) must match the XLA ragged gather on
    the SAME quantized cache — this is the cell the sweep previously
    silently resolved to xla."""
    from opsagent_tpu.ops.attention import paged_ragged_attention
    from opsagent_tpu.ops.paged_attention_pallas import (
        paged_ragged_attention_pallas,
    )

    rng = np.random.default_rng(31)
    q, kq, vq, table, st, ql = _quantized_case(
        rng, B=3, S=8, H=4, K=2, D=32, P=4, MaxP=10, N=32,
        start=start, q_lens=q_lens,
    )
    ref = paged_ragged_attention(q, kq, vq, table, st, ql)
    got = paged_ragged_attention_pallas(q, kq, vq, table, st, ql,
                                        interpret=True)
    for b, n in enumerate(q_lens):
        if n:
            np.testing.assert_allclose(
                np.asarray(got)[b, :n], np.asarray(ref)[b, :n],
                rtol=2e-5, atol=2e-5,
            )
    assert np.isfinite(np.asarray(got)).all()


def test_decode_grid_quantized_matches_xla_reader():
    """int8 QuantizedPages through the plain-pallas DECODE grid kernel
    (interpret) vs the XLA gather on the same quantized cache."""
    from opsagent_tpu.ops.attention import paged_decode_attention
    from opsagent_tpu.ops.paged_attention_pallas import (
        paged_decode_attention_pallas,
    )

    rng = np.random.default_rng(32)
    lengths = [5, 17, 1]
    q, kq, vq, table, st, ql = _quantized_case(
        rng, B=3, S=1, H=4, K=2, D=32, P=4, MaxP=8, N=26,
        start=[n - 1 for n in lengths], q_lens=[1, 1, 1],
    )
    lens = jnp.asarray(lengths, jnp.int32)
    ref = paged_decode_attention(q[:, 0], kq, vq, table, lens)
    got = paged_decode_attention_pallas(
        q[:, 0], kq, vq, table, lens, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_auto_dispatch_keeps_pallas_backend_for_quantized_pages(monkeypatch):
    """The auto dispatchers no longer demote QuantizedPages to xla: with
    OPSAGENT_PAGED_BACKEND=pallas the grid kernel runs (and matches the
    gather), for both the decode and ragged entry points."""
    from opsagent_tpu.ops.attention import (
        paged_decode_attention, paged_decode_attention_auto,
        paged_ragged_attention, paged_ragged_attention_auto,
    )

    monkeypatch.setenv("OPSAGENT_PAGED_BACKEND", "pallas")
    monkeypatch.setenv("OPSAGENT_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(33)
    q, kq, vq, table, st, ql = _quantized_case(
        rng, B=2, S=8, H=4, K=2, D=32, P=4, MaxP=8, N=18,
        start=[9, 0], q_lens=[1, 8],
    )
    ref = paged_ragged_attention(q, kq, vq, table, st, ql)
    got = paged_ragged_attention_auto(q, kq, vq, table, st, ql)
    for b, n in enumerate([1, 8]):
        np.testing.assert_allclose(
            np.asarray(got)[b, :n], np.asarray(ref)[b, :n],
            rtol=2e-5, atol=2e-5,
        )
    lens = st + ql
    ref_d = paged_decode_attention(q[:, 0], kq, vq, table, lens)
    got_d = paged_decode_attention_auto(q[:, 0], kq, vq, table, lens)
    np.testing.assert_allclose(
        np.asarray(got_d), np.asarray(ref_d), rtol=2e-5, atol=2e-5
    )


@pytest.mark.slow
def test_ragged_dma_at_bench_8b_mixed_shape():
    """Interpret parity at the EXACT bench-8b mixed decode-tick shape
    (B=32, S=4 bucket, H=32, K=8, D=128, P=64, bf16): all-decode rows at
    ragged positions plus one admitting chunk row — the sweep stage's
    steady-state dispatch, validated before burning chip time."""
    from opsagent_tpu.ops.attention import paged_ragged_attention
    from opsagent_tpu.ops.paged_attention_pallas import (
        paged_ragged_attention_pallas_dma,
    )

    rng = np.random.default_rng(28)
    B, S, H, K, D, P, MaxP = 32, 4, 32, 8, 128, 64, 12
    start = [int(rng.integers(0, MaxP * P - S)) for _ in range(B)]
    q_lens = [1] * B
    q_lens[-1] = S  # one admitting chunk row rides along
    q_lens[5] = 0   # and one inactive slot
    q, k_pages, v_pages, table, st, ql = _make_ragged_case(
        rng, B, S, H, K, D, P, MaxP, num_pages=B * MaxP + 2,
        start=start, q_lens=q_lens,
    )
    q = q.astype(jnp.bfloat16)
    k_pages = k_pages.astype(jnp.bfloat16)
    v_pages = v_pages.astype(jnp.bfloat16)
    ref = paged_ragged_attention(q, k_pages, v_pages, table, st, ql)
    got = paged_ragged_attention_pallas_dma(
        q, k_pages, v_pages, table, st, ql, interpret=True
    )
    for b in range(B):
        n = q_lens[b]
        if n:
            np.testing.assert_allclose(
                np.asarray(got, np.float32)[b, :n],
                np.asarray(ref, np.float32)[b, :n],
                rtol=3e-2, atol=3e-2,
            )


# -- the streaming kernel ("pallas-stream", ops/paged_attention_stream.py) ---
# Interpreted on the CPU against the gather, at the page size and head dim
# the chip runs (16 slots, 128 lanes) and key blocks of 2-4 pages, so that
# a row crosses several blocks and the unmasked and the masked loops run.
SP, SD, SMAXP = 16, 128, 12


def _stream_case(rng, S, K, G, start, q_lens, dtype=jnp.float32, layers=0):
    """Pages in the form ``page_form(K, "pallas-stream")`` holds them in:
    merged ``[N, P, K*D]`` above one kv head, ``[N, P, 1, D]`` at one."""
    B = len(start)
    n = B * SMAXP + 3
    q = jnp.asarray(rng.standard_normal((B, S, K * G, SD)), dtype)
    shape = (n, SP, K * SD) if K > 1 else (n, SP, 1, SD)
    if layers:
        shape = (layers, *shape)
    k_pages = jnp.asarray(rng.standard_normal(shape), dtype)
    v_pages = jnp.asarray(rng.standard_normal(shape), dtype)
    table = np.full((B, SMAXP), -1, np.int32)
    free = list(range(n))
    rng.shuffle(free)
    for b in range(B):
        for i in range(-(-(start[b] + q_lens[b]) // SP)):
            table[b, i] = free.pop()
    return (
        q, k_pages, v_pages, jnp.asarray(table),
        jnp.asarray(start, jnp.int32), jnp.asarray(q_lens, jnp.int32),
    )


def _assert_live_rows_match(got, ref, q_lens, tol=2e-5):
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    assert not np.isnan(got).any()
    for b, n in enumerate(q_lens):
        np.testing.assert_allclose(got[b, :n], ref[b, :n], rtol=tol, atol=tol)
        # Query slots past a row's q_len in a block the kernel skipped or
        # cut short come back as zeros, never as what VMEM held.
        assert np.isfinite(got[b, n:]).all()


@pytest.mark.parametrize("S", [1, 16, 32, 64])
@pytest.mark.parametrize("K,G", [(1, 7), (4, 7), (8, 8)])
def test_stream_matches_oracle_with_rows_of_every_kind(K, G, S):
    """One batch holds an inactive row, a decode row, a whole chunk and a
    part chunk, at 1, 4 (merged, the 7B's) and 8 kv heads."""
    from opsagent_tpu.ops.attention import paged_ragged_attention
    from opsagent_tpu.ops.paged_attention_stream import (
        paged_ragged_attention_stream,
    )

    rng = np.random.default_rng(100 + S + K)
    part = max(1, S // 2 - 1)
    start, q_lens = [40, 37, 64, 5], [0, 1, S, part]
    args = _stream_case(rng, S, K, G, start, q_lens)
    ref = paged_ragged_attention(*args)
    got = paged_ragged_attention_stream(*args, interpret=True, block_pages=2)
    _assert_live_rows_match(got, ref, q_lens)
    np.testing.assert_array_equal(np.asarray(got)[0], 0.0)


@pytest.mark.parametrize(
    "total", [15, 16, 17, 63, 64, 65, 127, 128, SMAXP * SP],
    ids=lambda t: f"len{t}",
)
def test_stream_lengths_on_and_off_page_and_key_block_boundaries(total):
    """A decode row and a chunk row whose last key sits just below, on and
    just above a page (16) and a key-block (64) boundary, and one that
    fills the page table to its last slot."""
    from opsagent_tpu.ops.attention import paged_ragged_attention
    from opsagent_tpu.ops.paged_attention_stream import (
        paged_ragged_attention_stream,
    )

    rng = np.random.default_rng(total)
    S = 8
    chunk = min(S, total)
    start, q_lens = [total - 1, total - chunk], [1, chunk]
    args = _stream_case(rng, S, 4, 2, start, q_lens)
    ref = paged_ragged_attention(*args)
    got = paged_ragged_attention_stream(*args, interpret=True, block_pages=4)
    _assert_live_rows_match(got, ref, q_lens)


@pytest.mark.parametrize("S", [128, 160])
def test_stream_walks_query_blocks_of_an_admission_chunk(S):
    """``paged_prefix_attention`` at a prefill bucket: more query slots
    than a block holds (160: and not a whole number of blocks), so the
    grid walks query blocks, each streaming the keys below its own last
    query."""
    from opsagent_tpu.ops.attention import paged_ragged_attention
    from opsagent_tpu.ops.paged_attention_stream import (
        QUERY_BLOCK_TOKENS, paged_ragged_attention_stream,
    )

    assert S > QUERY_BLOCK_TOKENS
    rng = np.random.default_rng(S)
    room = SMAXP * SP - S
    start, q_lens = [room, 0, 3], [S, S - 70, 1]
    args = _stream_case(rng, S, 4, 2, start, q_lens)
    ref = paged_ragged_attention(*args)
    got = paged_ragged_attention_stream(*args, interpret=True)
    _assert_live_rows_match(got, ref, q_lens)


def test_stream_layer_axis_form():
    """The layer-stacked cache the engine threads through its scan: the
    scalar-prefetched base offsets every page lookup into the layer."""
    from opsagent_tpu.ops.attention import paged_ragged_attention
    from opsagent_tpu.ops.paged_attention_stream import (
        paged_ragged_attention_stream,
    )

    rng = np.random.default_rng(7)
    start, q_lens = [33, 0], [1, 16]
    args = _stream_case(rng, 16, 4, 2, start, q_lens, layers=3)
    for layer in (0, 2):
        ref = paged_ragged_attention(*args, layer=jnp.int32(layer))
        got = paged_ragged_attention_stream(
            *args, layer=jnp.int32(layer), interpret=True, block_pages=2
        )
        _assert_live_rows_match(got, ref, q_lens)


def test_stream_bf16_is_the_oracles_arithmetic():
    """bf16 operands with f32 accumulation and an f32 softmax, the
    probabilities cast to bf16 before the second dot: what
    ``_ragged_attention_block`` does, so the two agree to bf16's last
    place over a few hundred keys, not to a looser f32-dot tolerance."""
    from opsagent_tpu.ops.attention import paged_ragged_attention
    from opsagent_tpu.ops.paged_attention_stream import (
        paged_ragged_attention_stream,
    )

    rng = np.random.default_rng(11)
    start, q_lens = [150, 100], [1, 32]
    args = _stream_case(rng, 32, 4, 7, start, q_lens, dtype=jnp.bfloat16)
    ref = paged_ragged_attention(*args)
    got = paged_ragged_attention_stream(*args, interpret=True, block_pages=4)
    assert got.dtype == jnp.bfloat16
    _assert_live_rows_match(got, ref, q_lens, tol=1.6e-2)


def test_stream_decode_form_matches_the_decode_oracle():
    from opsagent_tpu.ops.paged_attention_stream import (
        paged_decode_attention_stream,
    )

    rng = np.random.default_rng(13)
    lengths = [1, 16, 77, 0, SMAXP * SP]
    q, kp, vp, table, _, _ = _stream_case(
        rng, 1, 8, 8, [n - 1 if n else 0 for n in lengths],
        [1 if n else 0 for n in lengths],
    )
    lens = jnp.asarray(lengths, jnp.int32)
    ref = paged_decode_attention(q[:, 0], kp, vp, table, lens)
    got = paged_decode_attention_stream(
        q[:, 0], kp, vp, table, lens, interpret=True
    )
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(ref)[live], rtol=2e-5, atol=2e-5
    )
    np.testing.assert_array_equal(np.asarray(got)[~live], 0.0)


def test_stream_refuses_int8_pages_by_name():
    """No cell holds int8 pages; the kernel has no reader for them (a
    16-token page is half an int8 tile) and says so, and the choice
    function sends such an engine to the gather."""
    from opsagent_tpu.ops.attention import (
        QuantizedPages, paged_attention_backend, paged_ragged_attention_auto,
        pallas_refusal,
    )

    q = jnp.zeros((1, 1, 8, SD), jnp.float32)
    pages = QuantizedPages(
        jnp.zeros((4, SP, 4 * SD), jnp.int8),
        jnp.ones((4, SP, 4), jnp.float32),
    )
    table = jnp.zeros((1, 2), jnp.int32)
    one = jnp.ones((1,), jnp.int32)
    with pytest.raises(ValueError, match="int8 pages"):
        paged_ragged_attention_auto(
            q, pages, pages, table, one, one, impl="pallas-stream"
        )
    shapes = dict(head_dim=128, kv_heads_per_shard=4)
    assert "int8 pages" in pallas_refusal(
        "pallas-stream", page_itemsize=1, **shapes
    )
    assert pallas_refusal("pallas-stream", page_itemsize=2, **shapes) is None
    assert paged_attention_backend(
        platform="tpu", page_itemsize=1, **shapes
    ) == "xla"


def test_stream_refuses_split_pages():
    from opsagent_tpu.ops.attention import paged_ragged_attention_auto

    q = jnp.zeros((1, 1, 8, SD), jnp.float32)
    pages = jnp.zeros((4, SP, 4, SD), jnp.float32)
    table = jnp.zeros((1, 2), jnp.int32)
    one = jnp.ones((1,), jnp.int32)
    with pytest.raises(ValueError, match="split pages"):
        paged_ragged_attention_auto(
            q, pages, pages, table, one, one, impl="pallas-stream"
        )


@pytest.mark.parametrize("tp,K", [(2, 4), (4, 4)])
def test_stream_under_tp_matches_oracle(tp, K):
    """Through the shard_map wrapper: a shard of merged pages is whole
    heads (a contiguous run of lanes), and at one head a shard the held
    form is split with a unit axis, which the kernel reads as it is."""
    from opsagent_tpu.ops.attention import (
        page_form, paged_ragged_attention, paged_ragged_attention_pallas_tp,
    )
    from opsagent_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(tp=tp, dp=1, sp=1, devices=jax.devices()[:tp])
    rng = np.random.default_rng(50 + tp)
    start, q_lens = [70, 0], [1, 16]
    q, kp, vp, table, st, ql = _stream_case(rng, 16, K, 2, start, q_lens)
    if page_form(K // tp, "pallas-stream") == "split":
        kp = kp.reshape(*kp.shape[:-1], K, SD)
        vp = vp.reshape(*vp.shape[:-1], K, SD)
    ref = paged_ragged_attention(q, kp, vp, table, st, ql)
    got = paged_ragged_attention_pallas_tp(
        q, kp, vp, table, st, ql, mesh, interpret=True, impl="pallas-stream",
    )
    _assert_live_rows_match(got, ref, q_lens)
