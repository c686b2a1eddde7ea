"""The streaming paged-attention kernel vs the XLA gather reference.

Runs the kernel in interpreter mode on CPU (the TPU-lowered path shares the
same trace), asserting numerical equivalence with
``ops.attention.paged_ragged_attention`` / ``paged_decode_attention`` across
ragged lengths, GQA group sizes, multi-page sequences, and inactive
(length-0) batch slots.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from opsagent_tpu.ops.attention import (
    paged_decode_attention,
    paged_ragged_attention,
)
from opsagent_tpu.ops.paged_attention_stream import (
    paged_decode_attention_stream,
    paged_ragged_attention_stream,
)


# -- small, odd shapes -------------------------------------------------------
# Two kv heads, pages of 4 and 8 slots, head dims of 16-64 and batches of
# one to four rows: far from the chip's (16 slots, 128 lanes) that the
# cases further down hold to, and what interpret mode alone can run.
def _merged(pages):
    """Split ``[.., P, K, D]`` pages re-held as the kernel reads them
    (``page_form(K, "pallas-stream")``): the same bytes, ``[.., P, K*D]``."""
    return pages.reshape(*pages.shape[:-2], -1)


def _make_ragged_case(rng, B, S, H, K, D, P, MaxP, num_pages, start, q_lens):
    """Random paged KV state, split pages: each row owns enough pages for
    start + q_len tokens (the chunk's KV is treated as already written,
    like the engine after write_kv_pages)."""
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


def _make_case(rng, B, H, K, D, P, MaxP, num_pages, lengths):
    """The decode form of ``_make_ragged_case``: one query a row, sitting
    at ``lengths - 1``."""
    q, kp, vp, table, _, _ = _make_ragged_case(
        rng, B, 1, H, K, D, P, MaxP, num_pages,
        start=[max(n - 1, 0) for n in lengths],
        q_lens=[min(n, 1) for n in lengths],
    )
    return q[:, 0], kp, vp, table, jnp.asarray(lengths, jnp.int32)


def _assert_live_rows_match(got, ref, q_lens, tol=2e-5):
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    assert not np.isnan(got).any()
    for b, n in enumerate(q_lens):
        np.testing.assert_allclose(got[b, :n], ref[b, :n], rtol=tol, atol=tol)
        # Query slots past a row's q_len in a block the kernel skipped or
        # cut short come back as zeros, never as what VMEM held.
        assert np.isfinite(got[b, n:]).all()


RAGGED_SHAPES = {
    # decode row (q_len=1) + prefill chunk + inactive row in one batch
    "decode+chunk+inactive": (3, 8, 4, 2, 32, 4, 8, [9, 4, 0], [1, 6, 0]),
    # fresh prompt chunk from position 0, full S
    "fresh-chunk": (2, 8, 4, 4, 16, 8, 4, [0, 0], [8, 3]),
    # chunk crossing page boundaries with a long cached prefix
    "long-prefix": (2, 4, 8, 2, 32, 4, 10, [13, 30], [4, 2]),
    # all-decode tick (the steady-state mixed shape) + inactive rows
    "all-decode": (4, 4, 4, 2, 16, 4, 6, [7, 3, 0, 15], [1, 1, 0, 1]),
}


@pytest.mark.parametrize(
    "shape,block_pages",
    # The table in one key block (what the kernel picks at these sizes),
    # and a page a block where a row holds several: the open (unmasked)
    # and the masked loop both run, at pages of 4 slots.
    [(name, None) for name in RAGGED_SHAPES]
    + [(name, 1) for name in RAGGED_SHAPES if name != "fresh-chunk"],
)
def test_stream_matches_gather_at_small_odd_shapes(shape, block_pages):
    B, S, H, K, D, P, MaxP, start, q_lens = RAGGED_SHAPES[shape]
    rng = np.random.default_rng(11)
    q, k_pages, v_pages, table, st, ql = _make_ragged_case(
        rng, B, S, H, K, D, P, MaxP, num_pages=B * MaxP + 2,
        start=start, q_lens=q_lens,
    )
    ref = paged_ragged_attention(q, k_pages, v_pages, table, st, ql)
    got = paged_ragged_attention_stream(
        q, _merged(k_pages), _merged(v_pages), table, st, ql,
        interpret=True, block_pages=block_pages,
    )
    _assert_live_rows_match(got, ref, q_lens)
    for b, n in enumerate(q_lens):
        if not n:   # q_len == 0 rows stream nothing and come out zero
            np.testing.assert_array_equal(np.asarray(got)[b], 0.0)


@pytest.mark.parametrize(
    "B,H,K,D,P,MaxP,lengths",
    [
        (2, 4, 2, 64, 8, 4, [5, 17]),          # GQA, ragged, multi-page
        (1, 2, 2, 32, 4, 6, [24]),             # MHA (G=1), exactly full pages
        (3, 8, 2, 16, 8, 3, [1, 8, 20]),       # boundary lengths
        (2, 4, 4, 32, 8, 4, [9, 0]),           # inactive slot (length 0)
    ],
)
def test_stream_decode_form_matches_gather_at_small_odd_shapes(
    B, H, K, D, P, MaxP, lengths
):
    rng = np.random.default_rng(0)
    q, k_pages, v_pages, table, lens = _make_case(
        rng, B, H, K, D, P, MaxP, num_pages=B * MaxP + 2, lengths=lengths
    )
    ref = paged_decode_attention(q, k_pages, v_pages, table, lens)
    got = paged_decode_attention_stream(
        q, _merged(k_pages), _merged(v_pages), table, lens, interpret=True
    )
    # Inactive slots: the kernel defines them as zeros; the reference
    # produces attention over a masked-everything row (softmax of -inf) —
    # compare only active rows, then check the kernel's zeros.
    active = np.asarray(lens) > 0
    np.testing.assert_allclose(
        np.asarray(got)[active], np.asarray(ref)[active], rtol=2e-5, atol=2e-5
    )
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_array_equal(np.asarray(got)[~active], 0.0)


def test_ragged_decode_row_matches_decode_kernel_semantics():
    """A q_len=1 ragged row must equal single-token decode attention over
    the same cache state (the mixed step's decode-lane guarantee): of the
    gather's two forms, which are the oracles of everything here."""
    rng = np.random.default_rng(12)
    B, S, H, K, D, P, MaxP = 2, 4, 4, 2, 32, 4, 6
    q, k_pages, v_pages, table, st, ql = _make_ragged_case(
        rng, B, S, H, K, D, P, MaxP, num_pages=B * MaxP + 2,
        start=[7, 14], q_lens=[1, 1],
    )
    ragged = paged_ragged_attention(q, k_pages, v_pages, table, st, ql)
    dec = paged_decode_attention(
        q[:, 0], k_pages, v_pages, table, st + 1
    )
    np.testing.assert_allclose(
        np.asarray(ragged)[:, 0], np.asarray(dec), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("form", ["decode", "ragged"])
def test_stream_length_beyond_table_clamps(form):
    """A row claiming more pages than the table holds (5 or 7 of 3) must
    clamp to resident pages, as the gather does: no page-table read out of
    bounds, no page copy left in flight, no NaN; its neighbour is
    unaffected."""
    rng = np.random.default_rng(7)
    if form == "decode":
        q, k_pages, v_pages, table, lens = _make_case(
            rng, B=2, H=4, K=2, D=32, P=8, MaxP=3, num_pages=8,
            lengths=[24, 24],  # exactly fills all 3 pages
        )
        ref = paged_decode_attention(q, k_pages, v_pages, table, lens)
        got = paged_decode_attention_stream(
            q, _merged(k_pages), _merged(v_pages), table,
            jnp.asarray([24, 40], jnp.int32), interpret=True,
        )
    else:
        q, k_pages, v_pages, table, st, ql = _make_ragged_case(
            rng, B=2, S=4, H=4, K=2, D=32, P=4, MaxP=3,
            num_pages=8, start=[11, 11], q_lens=[1, 1],
        )
        ref = paged_ragged_attention(q, k_pages, v_pages, table, st, ql)[:, 0]
        got = paged_ragged_attention_stream(
            q, _merged(k_pages), _merged(v_pages), table,
            jnp.asarray([11, 27], jnp.int32), ql, interpret=True,
        )[:, 0]
    np.testing.assert_allclose(
        np.asarray(got)[0], np.asarray(ref)[0], rtol=2e-5, atol=2e-5
    )
    assert not np.isnan(np.asarray(got)).any()


@pytest.mark.parametrize("form", ["decode", "ragged"])
def test_stream_bf16_tolerance_at_small_odd_shapes(form):
    """bf16 pages of 64- and 32-wide heads (half and a quarter of the
    lanes) against the gather at bf16's tolerance."""
    if form == "decode":
        q, k_pages, v_pages, table, lens = _make_case(
            np.random.default_rng(1), B=2, H=4, K=2, D=64, P=8, MaxP=4,
            num_pages=12, lengths=[13, 29],
        )
        q, k_pages, v_pages = (
            x.astype(jnp.bfloat16) for x in (q, k_pages, v_pages)
        )
        ref = paged_decode_attention(q, k_pages, v_pages, table, lens)
        got = paged_decode_attention_stream(
            q, _merged(k_pages), _merged(v_pages), table, lens,
            interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            rtol=2e-2, atol=2e-2,
        )
        return
    q, k_pages, v_pages, table, st, ql = _make_ragged_case(
        np.random.default_rng(22), B=2, S=8, H=4, K=2, D=32, P=4, MaxP=8,
        num_pages=18, start=[9, 0], q_lens=[1, 8],
    )
    q, k_pages, v_pages = (
        x.astype(jnp.bfloat16) for x in (q, k_pages, v_pages)
    )
    ref = paged_ragged_attention(q, k_pages, v_pages, table, st, ql)
    got = paged_ragged_attention_stream(
        q, _merged(k_pages), _merged(v_pages), table, st, ql, interpret=True
    )
    _assert_live_rows_match(got, ref, [1, 8], tol=3e-2)


@pytest.mark.parametrize("form", ["decode", "ragged", "layer-axis"])
def test_stream_under_tp2_at_small_odd_shapes(form):
    """The kernel must run under tensor parallelism: shard_map over a tp=2
    mesh (q heads + kv heads both tp-sharded, ONE kv head a shard, so the
    held form is split with a unit axis a shard) must reproduce the
    unsharded gather — per-shard GQA needs no collective. The layer-axis
    form must select the right layer's pages on every shard."""
    from opsagent_tpu.ops.attention import (
        paged_decode_attention_pallas_tp, paged_ragged_attention_pallas_tp,
    )
    from opsagent_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(tp=2, dp=1, sp=1, devices=jax.devices()[:2])
    if form == "ragged":
        q, k_pages, v_pages, table, st, ql = _make_ragged_case(
            np.random.default_rng(25), B=2, S=8, H=4, K=2, D=32, P=4,
            MaxP=8, num_pages=18, start=[9, 0], q_lens=[1, 8],
        )
        ref = paged_ragged_attention(q, k_pages, v_pages, table, st, ql)
        got = paged_ragged_attention_pallas_tp(
            q, k_pages, v_pages, table, st, ql, mesh, interpret=True
        )
        _assert_live_rows_match(got, ref, [1, 8])
        return
    if form == "decode":
        q, k_pages, v_pages, table, lens = _make_case(
            np.random.default_rng(3), B=2, H=4, K=2, D=64, P=8, MaxP=4,
            num_pages=10, lengths=[5, 17],
        )
        ref = paged_decode_attention(q, k_pages, v_pages, table, lens)
        got = paged_decode_attention_pallas_tp(
            q, k_pages, v_pages, table, lens, mesh, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
        )
        return
    rng = np.random.default_rng(4)
    q, k_pages, v_pages, table, lens = _make_case(
        rng, B=2, H=4, K=2, D=32, P=8, MaxP=3, num_pages=8, lengths=[9, 20]
    )
    k_l, v_l = (
        jnp.asarray(rng.standard_normal((3, *k_pages.shape)), jnp.float32)
        for _ in range(2)
    )
    for layer in (0, 2):
        ref = paged_decode_attention(q, k_l[layer], v_l[layer], table, lens)
        got = paged_decode_attention_pallas_tp(
            q, k_l, v_l, table, lens, mesh,
            layer=jnp.int32(layer), interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


def test_stream_inert_rows_with_unassigned_pages_stay_finite(monkeypatch):
    """What the engine's padded rows look like, through the engine's own
    door (``paged_ragged_attention_auto``): ``q_len == 0`` beside live
    rows, the inert rows' page-table rows all -1 and one of them with a
    stale ``start``. They stream nothing (no page -1 is read) and give
    finite output the caller may discard; the live rows are the gather's."""
    from opsagent_tpu.ops.attention import paged_ragged_attention_auto

    monkeypatch.setenv("OPSAGENT_PALLAS_INTERPRET", "1")
    q, k_pages, v_pages, table, st, ql = _make_ragged_case(
        np.random.default_rng(29), B=4, S=4, H=4, K=2, D=32, P=4, MaxP=6,
        num_pages=26, start=[0, 7, 0, 2], q_lens=[0, 1, 0, 4],
    )
    st = st.at[2].set(19)
    assert (np.asarray(table)[[0, 2]] == -1).all()
    ref = paged_ragged_attention(q, k_pages, v_pages, table, st, ql)
    got = paged_ragged_attention_auto(
        q, _merged(k_pages), _merged(v_pages), table, st, ql,
        impl="pallas-stream",
    )
    assert np.isfinite(np.asarray(got)).all()
    _assert_live_rows_match(got, ref, [0, 1, 0, 4])


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


@pytest.mark.parametrize("S", [1, 16, 32, 64])
@pytest.mark.parametrize("K,G", [(1, 7), (4, 7), (8, 8), (1, 20)])
def test_stream_matches_oracle_with_rows_of_every_kind(K, G, S):
    """One batch holds an inactive row, a decode row, a whole chunk and a
    part chunk, at 1, 4 (merged, the 7B's) and 8 kv heads; and at a group
    of 20 (MLA's absorbed heads over the one latent head), where a decode
    row's heads fill a decode branch of 32 rows, not 16."""
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
    """The tail prefill's call at a prefill bucket: more query slots
    than a block holds (160: and not a whole number of blocks), so the
    grid walks query blocks, each streaming the keys below its own last
    query."""
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


def _latent_case(rng, S, G, D, start, q_lens, dtype=jnp.float32, layers=0):
    """MLA's latent as the kernel is handed it: ONE merged head, ``[(L,)
    N, P, D]`` with no unit axis (``llama.make_cache``), and the absorbed
    queries of ``G`` heads."""
    q, pages, _, table, st, ql = _stream_case(
        rng, S, 1, G, start, q_lens, dtype=dtype, layers=layers)
    q = jnp.asarray(rng.standard_normal((*q.shape[:-1], D)), dtype)
    pages = jnp.asarray(
        rng.standard_normal((*pages.shape[:-2], D)), dtype)
    return q, pages, table, st, ql


@pytest.mark.parametrize("D", [640, 24], ids=["on-the-lanes", "off-tile"])
@pytest.mark.parametrize("S", [1, 16])
def test_stream_reads_pages_that_are_keys_and_values_alike(S, D):
    """Handed one array twice (``v_pages is k_pages``: the latent), the
    kernel holds one page buffer and fetches a page once; the answer is
    the gather's over the same array, and BITWISE what the kernel gives
    for a second array of the same bytes, fetched twice."""
    rng = np.random.default_rng(640 + S + D)
    start, q_lens = [40, 37, 64, 5], [0, 1, S, max(1, S // 2 - 1)]
    q, pages, table, st, ql = _latent_case(rng, S, 20, D, start, q_lens)
    # without a layer axis the gather tells one head by its unit axis
    split = pages[:, :, None, :]
    ref = paged_ragged_attention(q, split, split, table, st, ql)
    got = paged_ragged_attention_stream(
        q, pages, pages, table, st, ql, interpret=True, block_pages=2)
    _assert_live_rows_match(got, ref, q_lens)
    np.testing.assert_array_equal(np.asarray(got)[0], 0.0)
    twice = paged_ragged_attention_stream(
        q, pages, pages + 0, table, st, ql, interpret=True, block_pages=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(twice))


def test_stream_fetches_a_shared_page_once():
    """The kernel of a shared call takes no value pages and holds one
    page buffer and one semaphore a slot; handed two arrays it holds two
    of each."""
    from opsagent_tpu.ops import paged_attention_stream as pas

    shape = dict(B=2, nQ=1, K=1, TM=32, D=640, TS=1, G=20, MaxP=4, bp=2,
                 P=16, pages=8, page_dtype="bfloat16", q_dtype="bfloat16",
                 interpret=True)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)      # noqa: E731
    bf16 = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)  # noqa: E731
    rows = (i32(2, 4), i32(2), i32(2), i32(1), bf16(2, 1, 32, 640),
            i32(32, 1))
    for shared, sides in ((True, 1), (False, 2)):
        call = pas._pallas_call(**shape, shared=shared)
        jaxpr = jax.make_jaxpr(call)(*rows, *[bf16(8, 16, 640)] * sides)
        eqn, = (e for e in jaxpr.eqns if e.primitive.name == "pallas_call")
        scratch = [
            v.aval for v in eqn.params["jaxpr"].invars
        ][-(4 + sides):]
        bufs = [a for a in scratch if a.shape == (2, 2, 16, 640)]
        assert len(bufs) == sides
        sem, = (a for a in scratch if a.shape in ((1, 2), (2, 2)))
        assert sem.shape == (sides, 2)
        assert len(eqn.invars) == len(rows) + sides


def test_stream_layer_axis_form_over_the_latent(monkeypatch):
    """The latent cache as the engine threads it through its scan, ``[L,
    N, P, 640]``: four axes that are layers and ONE merged head, not a
    unit kv-head axis; the base offsets every page lookup into the layer,
    through the engine's own door."""
    from opsagent_tpu.ops.attention import paged_ragged_attention_auto

    monkeypatch.setenv("OPSAGENT_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(41)
    start, q_lens = [33, 0], [1, 16]
    q, pages, table, st, ql = _latent_case(
        rng, 16, 20, 640, start, q_lens, layers=3)
    assert pages.shape == (3, 2 * SMAXP + 3, SP, 640)
    for layer in (0, 2):
        ref = paged_ragged_attention(
            q, pages, pages, table, st, ql, layer=jnp.int32(layer))
        got = paged_ragged_attention_auto(
            q, pages, pages, table, st, ql, impl="pallas-stream",
            layer=jnp.int32(layer))
        _assert_live_rows_match(got, ref, q_lens)


def test_stream_bf16_over_the_latent_is_the_oracles_arithmetic():
    """bf16 latent pages and absorbed queries at the cell's widths: the
    kernel and the gather agree to bf16's last place."""
    rng = np.random.default_rng(43)
    start, q_lens = [150, 100], [1, 16]
    q, pages, table, st, ql = _latent_case(
        rng, 16, 20, 640, start, q_lens, dtype=jnp.bfloat16)
    split = pages[:, :, None, :]
    ref = paged_ragged_attention(q, split, split, table, st, ql)
    got = paged_ragged_attention_stream(
        q, pages, pages, table, st, ql, interpret=True, block_pages=4)
    assert got.dtype == jnp.bfloat16
    _assert_live_rows_match(got, ref, q_lens, tol=1.6e-2)


def test_stream_layer_axis_form():
    """The layer-stacked cache the engine threads through its scan: the
    scalar-prefetched base offsets every page lookup into the layer."""
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
    rng = np.random.default_rng(11)
    start, q_lens = [150, 100], [1, 32]
    args = _stream_case(rng, 32, 4, 7, start, q_lens, dtype=jnp.bfloat16)
    ref = paged_ragged_attention(*args)
    got = paged_ragged_attention_stream(*args, interpret=True, block_pages=4)
    assert got.dtype == jnp.bfloat16
    _assert_live_rows_match(got, ref, q_lens, tol=1.6e-2)


def test_stream_decode_form_matches_the_decode_oracle():
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
    16-token page is half an int8 tile) and says so (the choice function
    sends such an engine to the gather: tests/test_kernels.py)."""
    from opsagent_tpu.ops.attention import (
        QuantizedPages, paged_ragged_attention_auto,
    )
    from opsagent_tpu.ops.kernels import pallas_refusal

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


def test_a_name_that_is_no_readers_is_an_error_not_a_quiet_gather():
    """The dispatch runs the gather for "xla" alone: a deleted kernel's
    name, or a typo, must not run it under that name."""
    from opsagent_tpu.ops.attention import (
        paged_decode_attention_auto, paged_ragged_attention_auto,
    )

    q = jnp.zeros((1, 1, 8, SD), jnp.float32)
    pages = jnp.zeros((4, SP, 4 * SD), jnp.float32)
    table = jnp.zeros((1, 2), jnp.int32)
    one = jnp.ones((1,), jnp.int32)
    for name in ("pallas-dma", "pallas", "auto"):
        with pytest.raises(ValueError, match="expected one of"):
            paged_ragged_attention_auto(
                q, pages, pages, table, one, one, impl=name
            )
        with pytest.raises(ValueError, match="expected one of"):
            paged_decode_attention_auto(
                q[:, 0], pages, pages, table, one, impl=name
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
        q, kp, vp, table, st, ql, mesh, interpret=True,
    )
    _assert_live_rows_match(got, ref, q_lens)
