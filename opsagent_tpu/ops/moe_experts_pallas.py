"""The expert share's blocks as ONE grouped kernel, Pallas TPU
("pallas-grouped").

``llama._moe_share`` sorts a pass's assignments by expert into a buffer
``xs [rows, d]`` in which each expert's rows are padded to whole blocks of
``bm`` rows, and names every block's expert (``block_expert``). The XLA
form runs a ``while`` over the blocks in use, two or three fusion calls a
block, each of which starts and drains a pipeline of its own over 3-6 MB
of int8 (PERF.md section 5, PR 43: 8.6 of a block's 20.1 us in cell 5 and
12-13 of 32.8 us in cell 3 are not the weight stream). This kernel is the
same arithmetic as one call a layer:

- **One pipeline over every block.** Grid ``(blocks in use, f / tf)``; the
  first bound is the plan's own count (a dynamic grid bound), so blocks
  past the last in use cost nothing. The weight tiles' index maps read the
  block's expert from SMEM (scalar prefetch), so the pipeline has block
  ``b + 1``'s first tiles in flight while block ``b`` multiplies. Where an
  expert's matrices fit whole (``f_tile``: both cells), consecutive blocks
  of one expert read its weights once.
- **The stacks are read where they lie.** The whole leaf ``[.., E, d, f]``
  goes in with its leading axes flattened (no data moves) and the layer's
  offset added to the block's expert; nothing takes a layer's experts out.
- **int8 is widened in VMEM and nowhere else.** A tile of int8 is exact in
  the activations' dtype; the per-output-channel float32 scale multiplies
  the float32 PRODUCT (one multiply an output, not one a weight). For a
  block: ``silu((x @ g) * sg) * ((x @ u) * su)`` a tile of ``f`` at a time,
  cast once to the activations' dtype, ``@ d`` accumulated in float32 in
  VMEM over the tiles, ``* sd`` and one write of ``ys``'s block: int8
  weights, bfloat16 activations, float32 accumulation.

Rows of ``ys`` in blocks past the last in use are never written (and never
read: no assignment's destination lies there).

Correctness oracle: ``llama._moe_share``'s XLA loop (interpret mode on the
CPU, tests/test_moe_experts_kernel.py); the chip's compiler is asked at
both expert cells' shapes in tests/test_tpu_compile_experts.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_export import exported_call

IMPL = "pallas-grouped"
MIN_BLOCK_ROWS = 16       # a bfloat16 operand's tile
# int8 bytes of one weight tile [d, tf] at most. Three matrices, two VMEM
# slots each: 36 MB of VMEM_LIMIT_BYTES. Both expert cells' matrices fit
# whole (3.1 and 5.2 MB), so a block is ONE grid step there, and a block
# of the expert the last block had finds its tiles' indices unchanged: the
# pipeline fetches nothing for it.
TILE_BYTES = 6 << 20
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def f_tile(d: int, f: int) -> int:
    """Columns of the intermediate width a grid step: the widest whole
    number of 128-lane tiles that divides ``f`` with ``d x tf`` int8 within
    ``TILE_BYTES``; the whole of a width off the lanes (the CPU tests)."""
    fits = [t for t in range(128, f + 1, 128)
            if f % t == 0 and d * t <= TILE_BYTES]
    return max(fits, default=128 if f % 128 == 0 else f)


def _kernel(
    # scalar prefetch
    expert_ref,    # [blocks] int32 flat expert of each block (layer's offset in)
    # blocks
    x_ref,         # [bm, d] the block's rows
    g_ref,         # [d, tf] int8: the gate matrix's tile
    u_ref,         # [d, tf] int8: the up matrix's
    d_ref,         # [tf, d] int8: the down matrix's
    gs_ref,        # [1, tf] float32 scales of the tile's outputs
    us_ref,        # [1, tf]
    ds_ref,        # [1, d]
    o_ref,         # [bm, d]
    # scratch
    acc_ref,       # [bm, d] float32
    *,
    tiles: int,    # f / tf
):
    del expert_ref          # the index maps' alone
    j = pl.program_id(1)
    x = x_ref[...]

    def widened(w_ref, s_ref):
        return jnp.dot(
            x, w_ref[...].astype(x.dtype), preferred_element_type=jnp.float32
        ) * s_ref[...]

    h = jax.nn.silu(widened(g_ref, gs_ref)) * widened(u_ref, us_ref)
    y = jnp.dot(
        h.astype(x.dtype), d_ref[...].astype(x.dtype),
        preferred_element_type=jnp.float32)
    if tiles == 1:
        o_ref[...] = (y * ds_ref[...]).astype(o_ref.dtype)
        return

    @pl.when(j == 0)
    def _first():
        acc_ref[...] = y

    @pl.when(j > 0)
    def _more():
        acc_ref[...] += y

    @pl.when(j == tiles - 1)
    def _last():
        o_ref[...] = (acc_ref[...] * ds_ref[...]).astype(o_ref.dtype)


def _pallas_call(*, rows, bm, d, f, tf, experts, x_dtype, interpret):
    """The ``pallas_call`` of one shape: ``(blocks in use [1], expert of
    each block [rows / bm], xs [rows, d], gate / up int8 [experts, d, f],
    down [experts, f, d], their scales [experts, 1, f | d]) -> ys``."""
    tiles = f // tf
    rows_of = pl.BlockSpec((bm, d), lambda b, j, e: (b, 0))

    def columns(height):    # tile j of the block's expert's [height, f]
        return pl.BlockSpec((None, height, tf), lambda b, j, e: (e[b], 0, j))

    down = pl.BlockSpec((None, tf, d), lambda b, j, e: (e[b], j, 0))
    down_scale = pl.BlockSpec((None, 1, d), lambda b, j, e: (e[b], 0, 0))
    weights = 3 * d * f

    def call(used, expert, xs, g, u, dn, gs, us, ds):
        return pl.pallas_call(
            functools.partial(_kernel, tiles=tiles),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(used[0], tiles),
                in_specs=[rows_of, columns(d), columns(d), down,
                          columns(1), columns(1), down_scale],
                out_specs=rows_of,
                scratch_shapes=[pltpu.VMEM((bm, d), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((rows, d), x_dtype),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                # the accumulator carries over a block's tiles
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES,
            ),
            cost_estimate=pl.CostEstimate(     # half the blocks, for XLA
                flops=2 * bm * weights * (rows // bm // 2),
                bytes_accessed=weights * (rows // bm // 2),
                transcendentals=bm * f * (rows // bm // 2),
            ),
            name="moe_experts",
        )(expert, xs, g, u, dn, gs, us, ds)

    return call


@functools.lru_cache(maxsize=None)
def _kernel_call(*, inline: bool, **shape):
    """The kernel of one shape as something to call inside a step program:
    traced and lowered once, then inlined as bytes (``pallas_export``)."""
    call = _pallas_call(**shape)
    if inline:
        return call
    rows, bm, d, f, n = (shape[k] for k in ("rows", "bm", "d", "f", "experts"))
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    i8 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int8)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    args = (
        i32((1,)), i32((rows // bm,)),
        jax.ShapeDtypeStruct((rows, d), shape["x_dtype"]),
        i8((n, d, f)), i8((n, d, f)), i8((n, f, d)),
        f32((n, 1, f)), f32((n, 1, f)), f32((n, 1, d)),
    )
    return exported_call(
        call, args, name="moe_experts", source=__file__, shape=shape,
        scope="moe_experts")


def moe_expert_blocks(
    xs: jax.Array,            # [rows, d] sorted, padded to blocks of bm rows
    block_expert: jax.Array,  # [rows / bm] int32 each block's expert (E: none)
    blocks_used: jax.Array,   # [] int32 blocks that hold assignments
    stacks,                   # gate, up, down: QuantizedLinear [*lead, E, ..]
    idx: tuple = (),          # the layer's index in ``lead``
    *,
    bm: int,
    interpret: bool = False,
) -> jax.Array:
    """``ys [rows, d]``: every block in use through its expert's gated MLP
    (module header). Rows of blocks past ``blocks_used`` are undefined."""
    from ..models.quant import QuantizedLinear

    rows, d = xs.shape
    gate, up, down = stacks
    if not all(type(w) is QuantizedLinear and w.q.dtype == jnp.int8
               for w in stacks):
        raise ValueError(
            f"{IMPL} reads int8 QuantizedLinear stacks; got "
            f"{[type(w).__name__ for w in stacks]} "
            "(ops.kernels.moe_experts_backend sends others to the loop)")
    *lead, E, _, f = gate.q.shape
    if len(idx) != len(lead):
        raise ValueError(
            f"{IMPL} wants an index for each of the stack's leading axes "
            f"{tuple(lead)}; got {len(idx)}")
    base = 0
    for i, n in zip(idx, lead):
        base = base * n + i
    flat = lambda a: a.reshape(-1, *a.shape[-2:])    # noqa: E731
    # A block past the last in use is no grid step; the clamp keeps a
    # block's expert a held one whatever the map says of it.
    expert = base * E + jnp.minimum(block_expert, E - 1)
    call = _kernel_call(
        rows=rows, bm=bm, d=d, f=f, tf=f_tile(d, f),
        experts=flat(gate.q).shape[0], x_dtype=xs.dtype.name,
        interpret=interpret,
        inline=interpret or bool(jax.sharding.get_abstract_mesh().manual_axes),
    )
    return call(
        jnp.reshape(blocks_used, (1,)).astype(jnp.int32),
        expert.astype(jnp.int32), xs,
        flat(gate.q), flat(up.q), flat(down.q),
        flat(gate.scale), flat(up.scale), flat(down.scale),
    )
