"""Attention ops: prefill (causal GQA) and paged-KV decode.

These are the XLA reference implementations — correct on any backend and the
ground truth for the Pallas TPU kernel in ``paged_attention_stream.py``.
Softmax accumulates in float32 regardless of the activation dtype (bf16 on
TPU) for numerical parity with the fused kernel.

The paged layout: KV lives in fixed-size pages; a sequence owns a row of
the page table ``[max_pages_per_seq]`` holding page indices. This is the
structure the continuous-batching scheduler allocates against (SURVEY.md
section 7 step 5 / the Ragged-Paged-Attention design in PAPERS.md).

A page array is HELD in one of two forms (``page_form`` chooses, where the
cache is made): *split* ``[num_pages, page_size, kv_heads, head_dim]`` or
*merged* ``[num_pages, page_size, kv_heads * head_dim]``, the same bytes
in the same order. The TPU tiles an array's two minor axes into (8, 128)
tiles. Split at 8 kv heads fills a tile with (kv_heads, head_dim), and
both the page write's scatter and the page gather run in it. Split at 4
(or 2) gets a half-height ``T(4,128)`` tile that the scatter takes but
the gather does not: it asks for full tiles with the page slots under the
head dim, and the compiler re-tiled ALL of K and of V for it in every
layer (56 copies of 1.17 GB a step at the 7B's 28 layers x 2560 pages,
200 ms of a 511 ms step, PERF.md PR 25). Merged, the 16 page slots fill
the tile's rows at any head count and write and gather share the tiling.
``write_pages`` and ``_gather_kv`` take either form and tell them apart
by the trailing axis (``pages_merged``). ONE kv head that every query head
shares (MLA's latent) is held merged too, ``[layers, num_pages, page_size,
page_dim]``, its row padded to whole lane tiles (``MLAConfig.page_dim``):
576 wide, with or without a unit kv-head axis, the TPU holds the array
pages-innermost, and the chip's compiler copied the whole cache to row-major
at the entry of every step program and back at its exit (compile, PRs 30
and 40). The streaming kernel
(``paged_attention_stream``, "pallas-stream") reads merged pages at any
head count, a kv head being a slice of whole 128-lane tiles of the page
row; the latent is its one head of ``page_dim`` lanes, handed once as
keys and values alike.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..utils.profiling import scoped
from . import kernels
from .paged_attention_stream import (
    paged_decode_attention_stream,
    paged_ragged_attention_stream,
)

NEG_INF = -1e30

# Upper bound on one block's f32 score matrix in ``paged_ragged_attention``
# (its softmax output doubles it).
SCORE_BLOCK_BYTES = 512 * 1024 * 1024


@jax.tree_util.register_pytree_node_class
class QuantizedPages:
    """int8 KV pages + per-(slot, token, head) float32 scales.

    At the 8B bench shape KV reads (~4 GB/step at 4k context, B=32) rival
    the int4 weight stream (PERF.md), so halving them is the next decode
    lever after weight quantization. ``q`` keeps the page layout
    [L, N, P, K, D] (or [N, P, K, D]) in int8; ``scale`` drops the D axis:
    one symmetric absmax scale per written token per kv head — 4 bytes per
    D-row, ~3 % traffic overhead at D=128, and near-lossless for attention
    (per-token scaling keeps rounding error local, the same locality
    argument as group-wise int4 weights).

    A registered pytree node, so it flows through lax.scan carries,
    shard_params, donation, and engine restart plumbing exactly like a
    plain page array. Readers dequantize AFTER their page gather — XLA
    fuses the convert+multiply into the attention matmul's operand read,
    so HBM sees int8 pages + small scales, never a dequantized copy."""

    def __init__(self, q: jax.Array, scale: jax.Array):
        self.q = q
        self.scale = scale

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def dtype(self):
        return self.q.dtype


PAGE_FORMS = ("split", "merged")
TILE_ROWS = 8  # rows of the TPU's (rows, 128 lanes) tile, at 1, 2 and 4 bytes


def page_form(kv_heads_per_shard: int, attn_impl: str = "xla") -> str:
    """The form a shard's KV pages are held in on the device (module
    header): "merged" ``[.., P, K*D]`` or "split" ``[.., P, K, D]``, by
    the reader. Under the xla gather: merged where split pages would get
    a part-empty tile (K neither 1 nor a multiple of 8). By the compiler,
    for a described v5e (tests/test_tpu_compile_steps.py): split at K = 2 and 4
    re-tiles the whole cache between write and gather in every layer,
    bf16 and int8 alike (both get 8-row tiles); at K = 8 split has no
    such copy and merged would add one of each gathered block. The
    streaming kernel gathers nothing and slices a kv head out of the
    merged row's lanes, so under it every K above 1 is merged. A tp shard
    of one head keeps its unit axis (the kv-head axis is what is sharded);
    MLA's latent, one head by construction, is made without one
    (``llama.make_cache``)."""
    if kv_heads_per_shard == 1:
        return "split"
    if attn_impl == "pallas-stream":
        return "merged"
    return "split" if kv_heads_per_shard % TILE_ROWS == 0 else "merged"


def pages_merged(pages, head_dim: int, layer=None) -> bool:
    """Whether ``pages`` (an array or ``QuantizedPages``) are held merged:
    a trailing axis wider than the head dim says so. One head held merged
    (MLA's latent, ``[L, N, P, D]``) has the head dim there, so its rank
    says so, which takes knowing that the pages carry a layer axis: they
    do wherever a caller names a ``layer``."""
    if pages.shape[-1] != head_dim:
        return True
    return layer is not None and pages.ndim == 4


def page_view(pages: jax.Array, page_shape: tuple[int, ...]) -> jax.Array:
    """``[L, n, <a page in either form>]`` -> ``[L, n, *page_shape]``: how
    code outside the step programs (the host tier, and through it
    snapshots and the fleet's wire) reads held pages as ``[P, K, D]`` and
    writes them back. Merging trailing axes does not reorder a page's
    bytes, so the stored format is the split one whatever is held."""
    return pages.reshape(*pages.shape[:2], *page_shape)


def quantize_kv_rows(new: jax.Array) -> tuple[jax.Array, jax.Array]:
    """[B, S, K, D] fresh K/V -> (int8 values, [B, S, K] f32 scales):
    symmetric absmax over the head dim, the write-side half of
    ``QuantizedPages``."""
    absmax = jnp.max(jnp.abs(new.astype(jnp.float32)), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.round(new.astype(jnp.float32) / scale[..., None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def _dequantize_gathered(seq: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """Gathered int8 [..., K, D] + scales [..., K] -> compute dtype."""
    return (seq.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _tp(mesh: Mesh | None) -> int:
    return 1 if mesh is None else mesh.shape.get("tp", 1)


def _require_reader(impl: str) -> None:
    """A name that is no reader's (a deleted kernel's, a typo) is an
    error where the dispatch would otherwise run the gather under it."""
    if impl not in kernels.PAGED_BACKENDS:
        raise ValueError(
            f"paged backend {impl!r}: "
            f"expected one of {kernels.PAGED_BACKENDS}"
        )


def _require_form(pages, head_dim: int, tp: int = 1, layer=None) -> None:
    """The streaming kernel takes the form ``page_form`` gives it at the
    kv heads a shard holds (``tp`` shards of the pages' kv-head axis):
    merged float pages (one head held merged among them: MLA's latent,
    told by ``layer`` as ``pages_merged`` tells it), or one head a shard
    with its unit axis."""
    if isinstance(pages, QuantizedPages):
        raise ValueError(kernels.pallas_refusal(
            "pallas-stream", head_dim=head_dim, kv_heads_per_shard=1,
            page_itemsize=1,
        ))
    merged = pages_merged(pages, head_dim, layer)
    if not merged and pages.shape[-2] != tp:
        raise ValueError(
            f"paged backend 'pallas-stream' was given split pages "
            f"{tuple(pages.shape)}: make the cache with "
            "page_form(kv_heads, attn_impl)"
        )


def _shard_map(fn, mesh: Mesh, in_specs, out_specs):
    # check_vma off: pallas_call does not annotate its outputs'
    # varying-mesh-axes metadata, and the head axis is fully data-parallel
    # here (no cross-shard reduction to validate anyway).
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def _tp_page_spec(pages: jax.Array, head_dim: int) -> P:
    """PartitionSpec that shards held pages by kv head over ``tp``: the
    kv-head axis of split pages, the trailing ``K*D`` axis of merged ones
    (a shard's heads are contiguous lanes)."""
    if pages_merged(pages, head_dim):
        return P(*(None,) * (pages.ndim - 1), "tp")
    return P(*(None,) * (pages.ndim - 2), "tp", None)


def paged_decode_attention_pallas_tp(
    q: jax.Array,           # [B, H, D] — H sharded over tp
    k_pages: jax.Array,     # [N, P, K, D] or [L, N, P, K, D] — K over tp
    v_pages: jax.Array,     # like k_pages
    page_table: jax.Array,  # [B, MaxP] replicated
    lengths: jax.Array,     # [B] replicated
    mesh: Mesh,
    layer: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """The streaming kernel's decode form under tensor parallelism.

    A bare pallas_call is opaque to the pjit partitioner, so it is wrapped
    in shard_map over the ``tp`` mesh axis: q's heads and the KV pages' kv
    heads are both tp-sharded (models.llama param/cache specs), every
    device runs the kernel on its own H/tp query heads against its own
    K/tp kv heads — the GQA group structure is preserved per shard and NO
    collective is needed (the head axis is fully data-parallel here; the
    all-reduce happens later at the wo row-parallel matmul)."""
    spec_q = P(None, "tp", None)
    spec_kv = _tp_page_spec(k_pages, q.shape[-1])
    if layer is None:
        layer = jnp.int32(0)

    def local(q, kp, vp, table, ln, ly):
        return paged_decode_attention_stream(
            q, kp, vp, table, ln, interpret=interpret, layer=ly
        )

    mapped = _shard_map(
        local, mesh,
        in_specs=(spec_q, spec_kv, spec_kv, P(None, None), P(None), P()),
        out_specs=spec_q,
    )
    return mapped(q, k_pages, v_pages, page_table, lengths, layer)


@scoped("attn_core")
def paged_decode_attention_auto(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    impl: str = "xla",
    layer: jax.Array | None = None,
    mesh: Mesh | None = None,
) -> jax.Array:
    """Impl-dispatched paged decode attention (impl from
    ``paged_attention_backend``, resolved at trace time by the caller).
    With a mesh whose tp axis is >1, the kernel runs shard_mapped over
    tp (see ``paged_decode_attention_pallas_tp``). int8+scale
    ``QuantizedPages`` flow through the XLA gather; the streaming kernel
    refuses them by name (``pallas_refusal``)."""
    if impl == "pallas-stream":
        _require_form(k_pages, q.shape[-1], _tp(mesh), layer)
        interpret = kernels.pallas_interpret()
        if _tp(mesh) > 1:
            return paged_decode_attention_pallas_tp(
                q, k_pages, v_pages, page_table, lengths, mesh, layer=layer,
                interpret=interpret,
            )
        return paged_decode_attention_stream(
            q, k_pages, v_pages, page_table, lengths, layer=layer,
            interpret=interpret,
        )
    _require_reader(impl)
    return paged_decode_attention(
        q, k_pages, v_pages, page_table, lengths, layer=layer
    )


@scoped("attn_core")
def causal_prefill_attention(
    q: jax.Array,        # [B, S, H, D]
    k: jax.Array,        # [B, S, K, D]
    v: jax.Array,        # [B, S, K, D]
    lengths: jax.Array | None = None,  # [B] valid lengths (right padding)
) -> jax.Array:
    """Causal grouped-query attention over the in-flight (fresh) K/V."""
    B, S, H, D = q.shape
    K = k.shape[2]
    G = H // K
    scale = 1.0 / (D ** 0.5)
    qg = q.reshape(B, S, K, G, D)
    # MXU-native matmul in the input dtype, f32 accumulation.
    scores = jnp.einsum(
        "bskgd,btkd->bkgst", qg, k, preferred_element_type=jnp.float32
    )
    scores = scores * scale
    pos_q = jnp.arange(S)[:, None]
    pos_t = jnp.arange(S)[None, :]
    mask = pos_t <= pos_q  # [S, S]
    mask = mask[None, None, None, :, :]
    if lengths is not None:
        tvalid = (jnp.arange(S)[None, :] < lengths[:, None])[:, None, None, None, :]
        mask = jnp.logical_and(mask, tvalid)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgst,btkd->bskgd",
        probs.astype(v.dtype),
        v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, S, H, D).astype(q.dtype)


@scoped("kv_write")
def write_kv_pages(
    k_pages: jax.Array,     # [N, P, K, D] or merged [N, P, K*D] — with a
    v_pages: jax.Array,     # leading L axis when ``layer`` is given
    k_new: jax.Array,       # [B, S, K, D]
    v_new: jax.Array,       # [B, S, K, D]
    page_table: jax.Array,  # [B, MaxP] int32 page indices (-1 = unassigned)
    start: jax.Array,       # [B] int32 write offset (tokens already in cache)
    valid_len: jax.Array | None = None,  # [B] number of valid new tokens
    layer: jax.Array | None = None,  # [] int32 when pages carry a layer axis
) -> tuple[jax.Array, jax.Array]:
    """Scatter freshly-computed K/V into their sequences' pages.

    Token t of sequence b lands at flat slot ``page_table[b, (start[b]+t)//P]
    * P + (start[b]+t) % P`` (offset by ``layer * N * P`` when the pages
    carry a leading layer axis). Out-of-range/padded tokens get an
    out-of-bounds index and are dropped by the scatter (negative indices
    would WRAP under JAX indexing semantics, so the sentinel is past-the-end).

    The whole-cache-with-layer form exists so the layer stack can thread ONE
    cache array through ``lax.scan`` as a loop carry: the scatter then
    updates the carry in place, where per-layer stacked scan outputs would
    copy the entire cache every step (~GBs/step at serving shapes).
    """
    k_pages = write_pages(
        k_pages, k_new, page_table, start, valid_len=valid_len, layer=layer
    )
    v_pages = write_pages(
        v_pages, v_new, page_table, start, valid_len=valid_len, layer=layer
    )
    return k_pages, v_pages


@scoped("kv_write")
def write_pages(
    pages: jax.Array,       # [(L,) N, P, K, D] or merged [(L,) N, P, K*D]
    new: jax.Array,         # [B, S, K, D]
    page_table: jax.Array,  # [B, MaxP] int32 page indices (-1 = unassigned)
    start: jax.Array,       # [B] int32 write offset (tokens already in cache)
    valid_len: jax.Array | None = None,  # [B] number of valid new tokens
    layer: jax.Array | None = None,  # [] int32 when pages carry a layer axis
) -> jax.Array:
    """Single-array page scatter (``write_kv_pages`` for one side; the MLA
    latent cache writes only one array per token): every slot of the rows,
    ``B * S`` of them, goes to the scatter, the padded ones with the
    past-the-end index (``_write`` has the page forms)."""
    S = new.shape[1]
    return _write(
        pages, new, layer,
        lambda P, base, total: _flat_slot_indices(
            page_table, start, S, P, base, total, valid_len).reshape(-1))


def token_slots(
    page_table: jax.Array,  # [B, MaxP] int32 page indices (-1 = unassigned)
    start: jax.Array,       # [B] int32 write offset (tokens already in cache)
    row: jax.Array,         # [T] the row of each packed token (B: none)
    at: jax.Array,          # [T] its place among the row's new tokens
    page_size: int,
) -> jax.Array:
    """[T] the slot of each packed token in ONE layer's pages, ``page_table[
    row, pos // P] * P + pos % P`` at ``pos = start[row] + at``; -1 for a
    token of no row and for an unassigned page. A program computes it once,
    outside its layer loop; ``write_kv_tokens`` adds a layer's offset."""
    B, MaxP = page_table.shape
    r = jnp.minimum(row, B - 1)
    pos = start[r] + at
    page = page_table[r, jnp.clip(pos // page_size, 0, MaxP - 1)]
    return jnp.where(
        (row < B) & (page >= 0), page * page_size + pos % page_size, -1)


@scoped("kv_write")
def write_kv_tokens(
    k_pages: jax.Array,     # as ``write_kv_pages``
    v_pages: jax.Array,
    k_new: jax.Array,       # [1, T, K, D] packed tokens
    v_new: jax.Array,       # [1, T, K, D]
    slots: jax.Array,       # [T] ``token_slots``
    layer: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """``write_kv_pages`` for a packed stream (``llama.Pack``): the scatter
    is handed the tick's ``T`` tokens, each with its own slot, not the
    rows' ``B x S`` slots. A scatter on the chip walks its indices one
    after another, written or dropped (PERF.md section 6, PR 39), so a
    write costs the rows it is handed and not the bytes it moves. One
    scatter of ``T`` rows and no conditional around the cache: tokens past
    the tick's last are dropped by index (-1 in ``slots``)."""
    def flat(P, base, total):
        return jnp.where(slots >= 0, slots + base * P, total * P)

    return (_write(k_pages, k_new, layer, flat),
            _write(v_pages, v_new, layer, flat))


def _write(pages, new: jax.Array, layer, flat_of) -> jax.Array:
    """``new`` ``[..., K, D]``, a row of it a cache slot, scattered to the
    flat slots ``flat_of(P, base, total)`` names (``base`` the layer's
    first flat page, ``total`` the flat pages in all; ``total * P``, one
    past the end, drops a row: a negative index would WRAP).

    The scatter runs in the form the pages are held in (module header):
    rows of ``[K, D]`` into the flat ``[slots, K, D]`` view of split pages,
    rows of ``[K*D]`` into the ``[slots, K*D]`` view of merged ones, in
    place in the array's own tiling either way.

    ``QuantizedPages`` targets quantize the fresh rows on write (absmax
    over the head dim) and scatter values and scales with the same flat
    indices, so the drop-sentinel/validity logic is shared."""
    K, D = new.shape[-2:]
    merged = pages_merged(pages, D, layer)
    if isinstance(pages, QuantizedPages):
        q_new, s_new = quantize_kv_rows(new)
        # the scales' row: a number a kv head, or just the number where one
        # head is held merged (a unit axis would pad each to a tile's lanes)
        s_row = pages.scale.shape[pages.q.ndim - (1 if merged else 2):]
        return QuantizedPages(
            _write(pages.q, q_new, layer, flat_of),
            _scatter(pages.scale, s_new, s_row, layer, flat_of),
        )
    row = pages.shape[-1:] if merged else (K, D)
    return _scatter(pages, new, row, layer, flat_of)


def _scatter(pages: jax.Array, new: jax.Array, row: tuple, layer, flat_of):
    """Rows ``row`` of ``new`` into ``pages`` ``[(L,) N, P, *row]`` (values
    in either form, or the scale planes ``[(L,) N, P, K]``)."""
    lead = pages.shape[: pages.ndim - len(row) - 1]    # (L, N) or (N,)
    P = pages.shape[len(lead)]
    if len(lead) == 2:
        total = lead[0] * lead[1]
        base = (layer if layer is not None else 0) * lead[1]
    else:
        total, base = lead[0], 0
    pf = pages.reshape(total * P, *row)
    pf = pf.at[flat_of(P, base, total)].set(
        new.reshape(-1, *row), mode="drop")
    return pf.reshape(pages.shape)


def _flat_slot_indices(
    page_table: jax.Array,  # [B, MaxP] int32 page indices (-1 = unassigned)
    start: jax.Array,       # [B] int32 write offsets
    S: int,                 # tokens per row being written
    P: int,                 # page size
    base,                   # layer * N flat-page offset (0 without layers)
    total: int,             # total flat pages
    valid_len: jax.Array | None,
) -> jax.Array:
    """[B, S] flat cache-slot index per written token, shared by the value
    and scale planes so the drop-sentinel/validity logic cannot diverge.
    Token t of row b lands at ``(page_table[b, (start+t)//P] + base) * P +
    (start+t) % P``; unassigned (-1) pages and tokens past ``valid_len``
    get ``total * P`` — one past the end, dropped by the scatter (negative
    indices would WRAP under JAX indexing semantics, so the sentinel is
    past-the-end)."""
    oob = total * P
    pos = start[:, None] + jnp.arange(S)[None, :]          # [B, S]
    page_idx = jnp.take_along_axis(
        page_table, jnp.clip(pos // P, 0, page_table.shape[1] - 1), axis=1
    )                                                       # [B, S]
    flat = (page_idx + base) * P + pos % P                  # [B, S]
    if valid_len is not None:
        ok = jnp.arange(S)[None, :] < valid_len[:, None]
        return jnp.where(ok & (page_idx >= 0), flat, oob)
    return jnp.where(page_idx >= 0, flat, oob)


@scoped("kv_gather")
def _gather_kv(
    k_pages, v_pages, page_table: jax.Array, layer, dtype, head_dim: int
) -> tuple[jax.Array, jax.Array]:
    """Shared page gather for the XLA readers: [B, MaxP] table ->
    contiguous ([B, L, K, D], [B, L, K, D]) sequence views, L = MaxP * P.
    Whole pages are gathered in the form they are held in (``head_dim``,
    the queries', tells merged ``[.., P, K*D]`` from split) and only the
    gathered block is given its kv-head axis, so no reader asks the
    compiler for another tiling of the cache. Handles the optional
    leading layer axis (flatten + ``layer * N`` offset) and
    ``QuantizedPages`` (gather int8 values + scales, then dequantize —
    XLA fuses the convert/multiply into the consuming einsum's operand
    read)."""
    k_scale = v_scale = None
    if isinstance(k_pages, QuantizedPages):
        k_pages, k_scale = k_pages.q, k_pages.scale
        v_pages, v_scale = v_pages.q, v_pages.scale
    shared = v_pages is k_pages     # MLA's latent: keys and values alike
    lead = k_pages.ndim - (
        2 if pages_merged(k_pages, head_dim, layer) else 3)
    N, P = k_pages.shape[lead - 1 : lead + 1]
    base, nmax = 0, N - 1
    if lead == 2:
        Lr = k_pages.shape[0]
        base = (layer if layer is not None else 0) * N
        nmax = Lr * N - 1
        k_pages = k_pages.reshape(Lr * N, *k_pages.shape[2:])
        v_pages = v_pages.reshape(Lr * N, *v_pages.shape[2:])
        if k_scale is not None:
            k_scale = k_scale.reshape(Lr * N, *k_scale.shape[2:])
            v_scale = v_scale.reshape(Lr * N, *v_scale.shape[2:])
    B = page_table.shape[0]
    L = page_table.shape[1] * P
    safe_table = jnp.clip(page_table + base, 0, nmax)
    k_seq = k_pages[safe_table].reshape(B, L, -1, head_dim)
    if k_scale is not None:
        ks = k_scale[safe_table].reshape(B, L, -1)
        k_seq = _dequantize_gathered(k_seq, ks, dtype)
    if shared:
        return k_seq, k_seq
    v_seq = v_pages[safe_table].reshape(B, L, k_seq.shape[2], -1)
    if v_scale is not None:
        vs = v_scale[safe_table].reshape(B, L, -1)
        v_seq = _dequantize_gathered(v_seq, vs, dtype)
    return k_seq, v_seq


@scoped("attn_core")
def paged_ragged_attention(
    q: jax.Array,           # [B, S, H, D] queries (right-padded per row)
    k_pages: jax.Array,     # [(L,) N, P, K, D] or merged [(L,) N, P, K*D]
    v_pages: jax.Array,     # like k_pages
    page_table: jax.Array,  # [B, MaxP]
    start: jax.Array,       # [B] tokens already in cache (queries begin here)
    q_lens: jax.Array,      # [B] valid query rows per sequence (0 = inactive)
    layer: jax.Array | None = None,  # [] int32 with the layer-axis form
) -> jax.Array:
    """Ragged-query paged attention: every batch row carries its own query
    length, so q_len=1 decode rows and q_len=chunk prefill rows run in ONE
    program (PAPERS.md: Ragged Paged Attention, arxiv 2604.15464) — the op
    under the engine's mixed prefill+decode step, where chunked prefill
    rides the decode dispatch's weight stream instead of buying its own.

    Row b's fresh K/V has already been written into pages at offset
    ``start[b]``; query s attends causally to every cached position
    t <= start[b] + s (causal masking INSIDE the chunk) and nothing past
    ``start[b] + q_lens[b]``. Rows with q_lens == 0 produce garbage output
    (finite — all-masked softmax degrades to uniform) that callers
    discard. Gather-based XLA reference; the Pallas page-streaming variant
    is ``paged_ragged_attention_stream`` behind
    ``paged_ragged_attention_auto``."""
    k_seq, v_seq = _gather_kv(
        k_pages, v_pages, page_table, layer, q.dtype, q.shape[-1]
    )
    B, S, H, _ = q.shape
    L = k_seq.shape[1]
    # The f32 score matrix is [B, H, S, L]: at a 4096-token admission
    # chunk over a 5120-slot table it alone outgrows the chip beside the
    # weights. Walk it in (batch, query) blocks of bounded size; every
    # query row still sees all L keys at once, so a row's softmax and
    # its reductions are unchanged.
    rows = max(1, SCORE_BLOCK_BYTES // (4 * H * L))
    s_blk = _largest_divisor(S, rows)
    b_blk = _largest_divisor(B, max(1, rows // s_blk))
    if s_blk == S and b_blk == B:
        return _ragged_attention_block(q, k_seq, v_seq, start, q_lens, 0)

    def batch_block(xs):
        qb, kb, vb, st, ql = xs
        out = jax.lax.map(
            lambda ys: _ragged_attention_block(
                ys[0], kb, vb, st, ql, ys[1]
            ),
            (
                jnp.moveaxis(
                    qb.reshape(b_blk, S // s_blk, s_blk, *qb.shape[2:]),
                    1, 0,
                ),
                jnp.arange(S // s_blk) * s_blk,
            ),
        )                                       # [S/s_blk, b_blk, s_blk, H, D]
        return jnp.moveaxis(out, 0, 1).reshape(qb.shape)

    def blocks(x):
        return x.reshape(B // b_blk, b_blk, *x.shape[1:])

    # XLA folds this blocking into the gather's own reshape and moves the
    # gathered keys once for both (kv heads above positions, for the dot):
    # a re-tiling of what was read from the cache, so it is named as one.
    with jax.named_scope("kv_gather"):
        k_seq, v_seq = blocks(k_seq), blocks(v_seq)
    out = jax.lax.map(
        batch_block,
        (blocks(q), k_seq, v_seq, blocks(start), blocks(q_lens)),
    )
    return out.reshape(q.shape)


def _largest_divisor(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is <= ``cap`` (at least 1)."""
    for d in range(min(n, cap), 0, -1):
        if n % d == 0:
            return d
    return 1


def _ragged_attention_block(
    q: jax.Array,        # [B, S, H, D] queries at row offsets q_off..q_off+S
    k_seq: jax.Array,    # [B, L, K, D] gathered keys
    v_seq: jax.Array,    # [B, L, K, D] gathered values
    start: jax.Array,    # [B]
    q_lens: jax.Array,   # [B]
    q_off,               # [] offset of q's first row inside the chunk
) -> jax.Array:
    """Masked softmax attention of one (batch, query) block against every
    gathered key position — the body of ``paged_ragged_attention``."""
    B, S, H, _ = q.shape
    K, D = k_seq.shape[-2:]
    G = H // K
    L = k_seq.shape[1]
    scale = 1.0 / (D ** 0.5)
    qg = q.reshape(B, S, K, G, D)
    scores = jnp.einsum(
        "bskgd,btkd->bkgst", qg, k_seq, preferred_element_type=jnp.float32
    ) * scale
    pos_t = jnp.arange(L)[None, None, :]                   # [1, 1, L]
    pos_q = (
        start[:, None] + q_off + jnp.arange(S)[None, :]
    )[:, :, None]                                          # [B, S, 1]
    mask = (pos_t <= pos_q) & (pos_t < (start + q_lens)[:, None, None])
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgst,btkd->bskgd",
        probs.astype(v_seq.dtype),
        v_seq,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, S, H, D).astype(q.dtype)


def paged_ragged_attention_pallas_tp(
    q: jax.Array,           # [B, S, H, D] — H sharded over tp
    k_pages: jax.Array,     # [N, P, K, D] or [L, N, P, K, D] — K over tp
    v_pages: jax.Array,     # like k_pages
    page_table: jax.Array,  # [B, MaxP] replicated
    start: jax.Array,       # [B] replicated
    q_lens: jax.Array,      # [B] replicated
    mesh: Mesh,
    layer: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """The streaming kernel under tensor parallelism: shard_mapped over
    ``tp`` exactly like ``paged_decode_attention_pallas_tp`` — query
    heads and kv heads are both tp-sharded, the GQA group structure is
    preserved per shard, and no collective is needed (the all-reduce
    happens later at the wo row-parallel matmul)."""
    spec_q = P(None, None, "tp", None)
    spec_kv = _tp_page_spec(k_pages, q.shape[-1])
    if layer is None:
        layer = jnp.int32(0)

    def local(q, kp, vp, table, st, ql, ly):
        return paged_ragged_attention_stream(
            q, kp, vp, table, st, ql, interpret=interpret, layer=ly
        )

    mapped = _shard_map(
        local, mesh,
        in_specs=(
            spec_q, spec_kv, spec_kv, P(None, None), P(None), P(None), P()
        ),
        out_specs=spec_q,
    )
    return mapped(q, k_pages, v_pages, page_table, start, q_lens, layer)


@scoped("attn_core")
def paged_ragged_attention_auto(
    q: jax.Array,           # [B, S, H, D]
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,  # [B, MaxP]
    start: jax.Array,       # [B]
    q_lens: jax.Array,      # [B]
    impl: str = "xla",
    layer: jax.Array | None = None,
    mesh: Mesh | None = None,
) -> jax.Array:
    """Impl-dispatched ragged paged attention (the mixed-step analogue of
    ``paged_decode_attention_auto``, and the tail prefill's op over a
    cached prefix: per-row write offset + per-row valid tail length):
    "pallas-stream" is the streaming kernel over merged pages
    (``paged_attention_stream``), which refuses int8 ``QuantizedPages``
    by name; an engine with int8 pages resolves to the gather."""
    if impl == "pallas-stream":
        _require_form(k_pages, q.shape[-1], _tp(mesh), layer)
        interpret = kernels.pallas_interpret()
        if _tp(mesh) > 1:
            return paged_ragged_attention_pallas_tp(
                q, k_pages, v_pages, page_table, start, q_lens, mesh,
                layer=layer, interpret=interpret,
            )
        return paged_ragged_attention_stream(
            q, k_pages, v_pages, page_table, start, q_lens, layer=layer,
            interpret=interpret,
        )
    _require_reader(impl)
    return paged_ragged_attention(
        q, k_pages, v_pages, page_table, start, q_lens, layer=layer
    )


@scoped("attn_core")
def paged_decode_attention(
    q: jax.Array,           # [B, H, D] (one new token per sequence)
    k_pages: jax.Array,     # [(L,) N, P, K, D] or merged [(L,) N, P, K*D]
    v_pages: jax.Array,     # like k_pages
    page_table: jax.Array,  # [B, MaxP]
    lengths: jax.Array,     # [B] total tokens in cache (incl. the new one)
    layer: jax.Array | None = None,  # [] int32 with the layer-axis form
) -> jax.Array:
    """Decode-step attention over paged KV (gather-based XLA reference).

    Gathers each sequence's pages into a contiguous [B, MaxP*P] view and
    masks positions >= length. The Pallas kernel avoids this materialized
    gather; results must match to ~1e-2 in bf16 / 1e-5 in f32.
    """
    k_seq, v_seq = _gather_kv(
        k_pages, v_pages, page_table, layer, q.dtype, q.shape[-1]
    )
    B, H, _ = q.shape
    K, D = k_seq.shape[-2:]
    G = H // K
    L = k_seq.shape[1]
    scale = 1.0 / (D ** 0.5)
    qg = q.reshape(B, K, G, D)
    scores = jnp.einsum(
        "bkgd,blkd->bkgl", qg, k_seq, preferred_element_type=jnp.float32
    ) * scale
    valid = (jnp.arange(L)[None, :] < lengths[:, None])[:, None, None, :]
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgl,blkd->bkgd",
        probs.astype(v_seq.dtype),
        v_seq,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, H, D).astype(q.dtype)
