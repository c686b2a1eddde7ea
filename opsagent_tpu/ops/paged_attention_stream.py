"""Streaming ragged paged attention, Pallas TPU ("pallas-stream").

The reader of paged keys and values that the code chooses on a TPU
(``ops.kernels.paged_attention_backend``). The XLA reader gathers every
row's ``MaxP`` pages into a ``[B, MaxP*P, K, D]`` block whatever the row's
length, scores all ``S`` query slots against it in f32 and writes the
``[B, K, G, S, MaxP*P]`` scores to HBM (PERF.md PR 29: 251 ms of a 339 ms
step at the 7B cell). This kernel does the same arithmetic and moves only
what the arithmetic needs:

- **Only live pages.** Grid ``(B, S / TS)``; a query block of row ``b``
  streams the pages below its last live query (``start + q_len``, scalar-
  prefetched with the page table). A row with ``q_len == 0`` streams
  nothing.
- **Scores never leave VMEM.** ``kb`` key positions a compute step
  (several pages, one DMA a page into a double-buffered block), with the
  running max, sum and accumulator of the flash-style softmax in f32
  scratch.
- **The configuration's precision.** Operands enter the MXU in the pages'
  dtype (bf16) with f32 accumulation; the softmax is f32; probabilities
  are cast to the values' dtype before the second dot: the arithmetic of
  ``ops.attention._ragged_attention_block``, which stays the oracle.
- **One dot a kv head over its own group of query heads**: queries are
  laid ``[B, K, S*G, D]`` (row ``s*G + g``), so head ``k`` multiplies
  ``[rows, D] x [D, kb]`` against its own 128-lane slice of the page row.
- **Pages read as they are held**: the merged form ``[.., P, K*D]`` of
  ``ops.attention.page_form``, whose 16 page slots fill the tile's rows
  at any head count; the cache is neither re-tiled nor padded. One kv
  head a shard is the same bytes with a unit axis.
- **Query slots past ``q_len`` cost little.** Live rows are a prefix of
  the ``s``-major query rows; a block whose live rows fit the decode
  branch (a decode row inside a ``[32, 32]`` mixed step) runs the same
  loop over those rows alone. The branch holds one query slot's heads:
  ``ROWS_SMALL`` rows wherever the group fits them, the group rounded up
  to whole tiles above that (32 rows for MLA's 20 absorbed heads). Key
  blocks wholly below the first query need no mask and get none.
- **A page that is keys and values alike is fetched once.** MLA's latent
  cache hands the kernel ONE array twice (``v_pages is k_pages``): one kv
  head of ``MLAConfig.page_dim`` lanes under the absorbed queries, a
  group of every query head. The kernel then holds one page buffer, one
  DMA and one wait a page, and the values are the keys' block in VMEM.
  Nothing else here knows of MLA: it is ``K`` = 1 at a head dim of 640.
- **Traced and lowered once a shape.** The kernel's body is Python that
  every step program holding it would trace and lower again; each shape
  is exported once (``_kernel_call``) and the programs inline its bytes,
  which outlive the process beside JAX's compile cache.

Correctness oracle: ``ops.attention.paged_ragged_attention`` (interpret
mode on the CPU, tests/test_pallas_paged.py); the chip's compiler is asked
at the benchmark cells' shapes, the latent's among them, in
tests/test_tpu_compile_attention.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_export import exported_call

NEG_INF = -1e30

QUERY_BLOCK_TOKENS = 64   # query slots a grid step (x G rows a kv head)
ROWS_SMALL = 16           # the decode branch: bf16 tiles of 16 query rows
PAGES_UNROLL = 8          # page copies written out a turn of the fetch loop
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def key_block_pages(
    page_size: int, row_bytes: int, max_pages: int, rows: int
) -> int:
    """Pages a compute step: 512 key positions where a double-buffered K
    and V block of them stays within 4 MB and the f32 scores of the
    query rows within 640 KB, else 256; never more than the table holds.
    (640 KB is 320 rows, a mixed bucket of 16 under MLA's 20 absorbed
    heads: there 512 positions read 18 % faster than 256, most rows of
    such a step being decode rows that pay a block's fixed costs; 448
    rows and more, the GQA cells' widest blocks, keep 256. PERF.md
    section 6, PRs 29 and 41.)"""
    tokens = 512
    if 4 * tokens * row_bytes > 4 * 1024 * 1024 or rows * tokens * 4 > 640 * 1024:
        tokens = 256
    return max(1, min(tokens // page_size, max_pages))


def _kernel(
    # scalar prefetch
    table_ref,     # [B, MaxP] int32 page indices (-1 = unassigned)
    start_ref,     # [B] int32 tokens already in cache (queries begin here)
    qlens_ref,     # [B] int32 valid query slots (0 = inactive row)
    base_ref,      # [1] int32 flat-page offset (layer * N; 0 without layers)
    # blocks
    q_ref,         # [1, K, TM, D] the block's queries, row s*G + g
    slot_ref,      # [TM, 1] int32 query slot of each row inside the block
    k_hbm,         # [pages, P, K*D] in HBM
    v_hbm,         # like k_hbm; None: the keys' pages are the values too
    o_ref,         # [1, K, TM, D]
    # scratch
    k_buf,         # [2, bp, P, K*D]
    v_buf,         # [2, bp, P, K*D]; None with ``v_hbm``
    sem,           # DMA [2 (k, v; 1 with no ``v_hbm``), 2 slots]
    m_ref,         # [K, TM, 1] f32 running max
    l_ref,         # [K, TM, 1] f32 running sum
    acc_ref,       # [K, TM, D] f32
    *,
    block_tokens: int,   # TS: query slots a block
    group: int,          # G: query heads a kv head
    max_pages: int,
):
    shared = v_hbm is None      # one fetch a page, keys and values alike
    b = pl.program_id(0)
    qi = pl.program_id(1)
    _, K, TM, D = q_ref.shape
    _, bp, P, _ = k_buf.shape
    kb = bp * P
    scale = D ** -0.5

    start = start_ref[b]
    live = jnp.clip(qlens_ref[b] - qi * block_tokens, 0, block_tokens)
    q0 = start + qi * block_tokens      # position of the block's first query
    kv_end = q0 + live                  # keys any live query of the block sees
    n_pages = jnp.minimum(pl.cdiv(kv_end, P), max_pages)
    n_blocks = pl.cdiv(n_pages, bp)
    # Key blocks wholly at or below the first query: every row sees every
    # key of them, so they run without the mask.
    n_open = jnp.minimum((q0 + 1) // kb, n_blocks)

    def pages_loop(one):
        """``one(j)`` for every page slot of a block, ``PAGES_UNROLL`` to a
        turn of a rolled loop: a rolled loop a page cost a seventh of the
        kernel's time in scalar work. The copies of a turn are an
        unrolled ``fori_loop``, traced once and written out when the
        kernel is lowered: written out in Python, every page copy of
        every fetch site was traced anew in every step program, 1.7 s a
        program on the chip's host (PERF.md section 6, PR 29)."""
        group_pages = _largest_divisor(bp, PAGES_UNROLL)

        def some(g, _):
            jax.lax.fori_loop(
                0, group_pages,
                lambda j, _: one(g * group_pages + j), None, unroll=True,
            )

        if group_pages == bp:
            some(0, None)
        else:
            jax.lax.fori_loop(0, bp // group_pages, some, None)

    def fetch(i, slot):
        # One DMA a page, K and V (one in all where the page is both):
        # pages of a row are anywhere in the pool.
        def one(j):
            at = jnp.minimum(i * bp + j, n_pages - 1)
            page = jnp.maximum(table_ref[b, at], 0) + base_ref[0]
            pltpu.make_async_copy(
                k_hbm.at[page], k_buf.at[slot, j], sem.at[0, slot]
            ).start()
            if not shared:
                pltpu.make_async_copy(
                    v_hbm.at[page], v_buf.at[slot, j], sem.at[1, slot]
                ).start()

        pages_loop(one)

    def arrive(slot):
        # One wait a page: a DMA semaphore counts what its copy moved.
        def one(j):
            pltpu.make_async_copy(
                k_hbm.at[0], k_buf.at[slot, j], sem.at[0, slot]
            ).wait()
            if not shared:
                pltpu.make_async_copy(
                    v_hbm.at[0], v_buf.at[slot, j], sem.at[1, slot]
                ).wait()

        pages_loop(one)

    # The decode branch holds one query slot's heads: a tile of 16 rows
    # wherever the group fits one (every GQA cell), whole tiles above it
    # (MLA's 20 absorbed heads over the one latent head: 32).
    small = min(_round_up(group, ROWS_SMALL), TM)

    def run(rows: int):
        """The streaming softmax over the block's first ``rows`` rows."""
        m_ref[:, :rows] = jnp.full((K, rows, 1), NEG_INF, jnp.float32)
        l_ref[:, :rows] = jnp.zeros((K, rows, 1), jnp.float32)
        acc_ref[:, :rows] = jnp.zeros((K, rows, D), jnp.float32)
        fetch(0, 0)

        def step(i, masked: bool):
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_blocks)
            def _prefetch():
                fetch(i + 1, 1 - slot)

            arrive(slot)
            if masked:
                t = i * kb + jax.lax.broadcasted_iota(jnp.int32, (rows, kb), 1)
                qpos = q0 + slot_ref[:rows]                     # [rows, 1]
                visible = (t <= qpos) & (t < kv_end)
            def head(k, _):
                lanes = pl.ds(pl.multiple_of(k * D, D), D)
                keys = k_buf[slot, :, :, lanes].reshape(kb, D)
                vals = keys if shared else (
                    v_buf[slot, :, :, lanes].reshape(kb, D))
                s = jax.lax.dot_general(
                    q_ref[0, k, :rows], keys,
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale                                       # [rows, kb]
                if masked:
                    s = jnp.where(visible, s, NEG_INF)
                m_prev = m_ref[k, :rows]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                l_ref[k, :rows] = alpha * l_ref[k, :rows] + jnp.sum(
                    p, axis=1, keepdims=True
                )
                acc_ref[k, :rows] = alpha * acc_ref[k, :rows] + jnp.dot(
                    p.astype(vals.dtype), vals,
                    preferred_element_type=jnp.float32,
                )
                m_ref[k, :rows] = m_new

            # Heads traced once and written out when the kernel is
            # lowered, each lane slice then a constant: a rolled loop over
            # dynamic slices read 15-28 % slower (PERF.md section 6, PR 29).
            jax.lax.fori_loop(0, K, head, None, unroll=True)

        # A decode row's query rows mask for nothing; only the wide
        # branch is worth a second, unmasked copy of the loop.
        first_masked = 0
        if rows > small:
            first_masked = n_open
            jax.lax.fori_loop(
                0, n_open, lambda i, _: step(i, masked=False), None
            )
        jax.lax.fori_loop(
            first_masked, n_blocks, lambda i, _: step(i, masked=True), None
        )
        o_ref[0, :, :rows] = (
            acc_ref[:, :rows] / l_ref[:, :rows]
        ).astype(o_ref.dtype)
        if rows < TM:
            o_ref[0, :, rows:] = jnp.zeros((K, TM - rows, D), o_ref.dtype)

    @pl.when(live == 0)
    def _inactive():
        o_ref[...] = jnp.zeros_like(o_ref)

    if small < TM:
        @pl.when((live > 0) & (live * group <= small))
        def _few_rows():
            run(small)

    @pl.when(live * group > (small if small < TM else 0))
    def _all_rows():
        run(TM)


def _kernel_shared(
    table_ref, start_ref, qlens_ref, base_ref, q_ref, slot_ref, k_hbm, o_ref,
    k_buf, *scratch, **static,
):
    """``_kernel`` for pages that are keys and values alike: no value
    pages among the operands, no second page buffer in the scratch."""
    _kernel(
        table_ref, start_ref, qlens_ref, base_ref, q_ref, slot_ref, k_hbm,
        None, o_ref, k_buf, None, *scratch, **static,
    )


def _largest_divisor(n: int, cap: int) -> int:
    return next(d for d in range(min(n, cap), 0, -1) if n % d == 0)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pallas_call(
    *, B, nQ, K, TM, D, TS, G, MaxP, bp, P, pages, page_dtype, q_dtype,
    interpret, shared=False,
):
    """The ``pallas_call`` of one kernel shape: ``(table, start, q_lens,
    base, queries [B, K, nQ*TM, D], slot_of_row, k_pages, v_pages
    [pages, P, K*D]) -> [B, K, nQ*TM, D]``; ``shared`` (the values are the
    keys' pages) takes no ``v_pages`` and holds one page buffer."""
    KD = K * D
    sides = 1 if shared else 2
    q_spec = pl.BlockSpec(
        (1, K, TM, D), lambda b, i, *_: (b, 0, i, 0),
        memory_space=pltpu.VMEM,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, nQ),
        in_specs=[
            q_spec,
            pl.BlockSpec(
                (TM, 1), lambda b, i, *_: (0, 0), memory_space=pltpu.VMEM
            ),
            *[pl.BlockSpec(memory_space=pl.ANY)] * sides,
        ],
        out_specs=q_spec,
        scratch_shapes=[
            *[pltpu.VMEM((2, bp, P, KD), page_dtype)] * sides,
            pltpu.SemaphoreType.DMA((sides, 2)),
            pltpu.VMEM((K, TM, 1), jnp.float32),
            pltpu.VMEM((K, TM, 1), jnp.float32),
            pltpu.VMEM((K, TM, D), jnp.float32),
        ],
    )
    live_tokens = B * MaxP * P // 2     # an estimate for XLA's scheduler
    page_bytes, q_bytes = (
        jnp.dtype(page_dtype).itemsize, jnp.dtype(q_dtype).itemsize
    )
    return pl.pallas_call(
        functools.partial(
            _kernel_shared if shared else _kernel,
            block_tokens=TS, group=G, max_pages=MaxP,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, nQ * TM, D), q_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * 2 * nQ * TM * K * D * live_tokens,
            bytes_accessed=(
                sides * nQ * live_tokens * KD * page_bytes
                + 2 * B * K * nQ * TM * D * q_bytes
            ),
            transcendentals=nQ * TM * K * live_tokens,
        ),
        name="paged_attention_stream",
    )


@functools.lru_cache(maxsize=None)
def _kernel_call(*, inline: bool, **shape):
    """The kernel of one shape as something to call inside a step program:
    traced and lowered once, exported, and inlined as bytes by every
    program after that (``pallas_export``: what that saves, and where the
    bytes live). Interpreted (the CPU tests) and inside a shard_map
    (``inline``) the kernel is called as it is."""
    call = _pallas_call(**shape)
    if inline:
        return call
    B, nQ, K, TM, D = (shape[k] for k in ("B", "nQ", "K", "TM", "D"))
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    pages = jax.ShapeDtypeStruct(
        (shape["pages"], shape["P"], K * D), shape["page_dtype"]
    )
    args = (
        i32((B, shape["MaxP"])), i32((B,)), i32((B,)), i32((1,)),
        jax.ShapeDtypeStruct((B, K, nQ * TM, D), shape["q_dtype"]),
        i32((TM, 1)), *[pages] * (1 if shape["shared"] else 2),
    )
    return exported_call(
        call, args, name="paged_attention_stream", source=__file__,
        shape=shape, scope="attn_core",
    )


def paged_ragged_attention_stream(
    q: jax.Array,           # [B, S, H, D] right-padded ragged queries
    k_pages: jax.Array,     # [(L,) N, P, K*D] merged, or [(L,) N, P, 1, D]
    v_pages: jax.Array,     # like k_pages, or ``k_pages`` itself
    page_table: jax.Array,  # [B, MaxP] int32
    start: jax.Array,       # [B] int32 tokens already in cache per row
    q_lens: jax.Array,      # [B] int32 valid query slots (0 = inactive)
    interpret: bool = False,
    layer: jax.Array | None = None,  # [] int32 with the layer-axis form
    block_pages: int | None = None,  # pages a compute step (None: by shape)
) -> jax.Array:
    """Streaming ragged paged attention (module header). Same contract as
    ``ops.attention.paged_ragged_attention``; rows of a block past its
    live queries come back as zeros. Handed ONE array as keys and values
    (``v_pages is k_pages``: MLA's latent), it fetches a page once."""
    from .attention import QuantizedPages

    if isinstance(k_pages, QuantizedPages):
        raise ValueError(
            "pallas-stream reads bf16/f32 pages; int8 QuantizedPages go "
            "through the xla gather (ops.kernels.pallas_refusal)"
        )
    # Seen here, outside the jit: inside it two arguments are two tracers.
    return _stream(
        q, k_pages, None if v_pages is k_pages else v_pages, page_table,
        start, q_lens, layer, interpret=interpret, block_pages=block_pages,
    )


@functools.partial(jax.jit, static_argnames=("interpret", "block_pages"))
def _stream(
    q, k_pages, v_pages, page_table, start, q_lens, layer, *, interpret,
    block_pages,
):
    """``paged_ragged_attention_stream`` with ``v_pages`` None where the
    values are the keys' pages."""
    held = [k_pages] if v_pages is None else [k_pages, v_pages]
    B, S, H, D = q.shape
    if k_pages.shape[-1] == D and k_pages.shape[-2] == 1:
        # One kv head a shard: [.., P, 1, D] is the merged row's bytes.
        held = [p.reshape(*p.shape[:-2], D) for p in held]
    lead = held[0].shape[:-2]
    P, KD = held[0].shape[-2:]
    if len(lead) not in (1, 2) or KD % D or D % 128 and not interpret:
        raise ValueError(
            f"pallas-stream wants merged pages [(L,) N, P, K*D] and a head "
            f"dim on the 128-lane tiling; got pages {tuple(k_pages.shape)} "
            f"for head dim {D}"
        )
    K = KD // D
    G = H // K
    base = 0
    if len(lead) == 2:
        held = [p.reshape(-1, P, KD) for p in held]
        base = (layer if layer is not None else 0) * lead[1]
    MaxP = page_table.shape[1]

    TS = min(S, QUERY_BLOCK_TOKENS)
    nQ = -(-S // TS)
    TM = _round_up(TS * G, 16)
    bp = block_pages or key_block_pages(
        P, KD * k_pages.dtype.itemsize, MaxP, TM
    )

    # [B, S, K, G, D] -> [B, K, nQ, TS*G (padded to TM), D]: a kv head's
    # queries are rows s*G + g of its own block.
    qs = jnp.pad(q, ((0, 0), (0, nQ * TS - S), (0, 0), (0, 0)))
    qs = qs.reshape(B, nQ, TS, K, G, D).transpose(0, 3, 1, 2, 4, 5)
    qs = qs.reshape(B, K, nQ, TS * G, D)
    qs = jnp.pad(qs, ((0, 0), (0, 0), (0, 0), (0, TM - TS * G), (0, 0)))
    qs = qs.reshape(B, K, nQ * TM, D)
    slot_of_row = (jnp.arange(TM, dtype=jnp.int32) // G).reshape(TM, 1)

    call = _kernel_call(
        B=B, nQ=nQ, K=K, TM=TM, D=D, TS=TS, G=G, MaxP=MaxP, bp=bp, P=P,
        pages=held[0].shape[0], page_dtype=k_pages.dtype.name,
        q_dtype=q.dtype.name, interpret=interpret, shared=len(held) == 1,
        # Inside a shard_map (tp > 1) the kernel is one shard's, and an
        # export made there would be lowered for the whole mesh.
        inline=interpret or bool(
            jax.sharding.get_abstract_mesh().manual_axes
        ),
    )
    out = call(
        page_table.astype(jnp.int32), start.astype(jnp.int32),
        q_lens.astype(jnp.int32), jnp.full((1,), base, jnp.int32),
        qs, slot_of_row, *held,
    )
    out = out.reshape(B, K, nQ, TM, D)[:, :, :, : TS * G]
    out = out.reshape(B, K, nQ, TS, G, D).transpose(0, 2, 3, 1, 4, 5)
    return out.reshape(B, nQ * TS, H, D)[:, :S]


def paged_decode_attention_stream(
    q: jax.Array,           # [B, H, D] (one new token per sequence)
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,  # [B, MaxP] int32
    lengths: jax.Array,     # [B] int32 (incl. the token being decoded)
    interpret: bool = False,
    layer: jax.Array | None = None,
) -> jax.Array:
    """Decode attention is the ragged op at one query slot: the query sits
    at position ``lengths - 1`` and sees every key below ``lengths``; a
    row of length 0 streams nothing and comes back as zeros."""
    lengths = lengths.astype(jnp.int32)
    live = (lengths > 0).astype(jnp.int32)
    return paged_ragged_attention_stream(
        q[:, None], k_pages, v_pages, page_table, lengths - live, live,
        interpret=interpret, layer=layer,
    )[:, 0]
