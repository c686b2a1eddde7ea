"""Pallas quantized matmul with double-buffered weight-tile streaming.

The decode/mixed hot path is weight-streaming-bound (PERF.md roofline:
~9.8 ms/step of weight bytes at 8B int8) and the XLA path serializes that
stream with compute: every ``x @ w.dequantize()`` waits for its operand
tiles. This kernel applies the same manual ``make_async_copy`` DMA
discipline the paged-attention kernel (ops/paged_attention_stream.py)
uses for KV pages to the WEIGHTS: int8 / self-packed-int4 tiles stream
HBM->VMEM through two double-buffered slots, so tile i+1's DMA runs under
tile i's MXU dot and the stream hides behind compute instead of adding to
it. Group-wise scales (models/quant.py layouts) are applied in-register
per tile — no dequantized HBM copy ever materializes.

Numerics mirror the XLA oracle (``llama._mm``) tile-by-tile: each weight
tile is dequantized to f32, cast to the activation dtype, and fed to an
f32-accumulating dot — elementwise identical math, only the contraction's
reduction ORDER differs (tiled partial sums vs one long sum), which is
the same fidelity class as the paged Pallas kernels vs the XLA gather.

Interpret mode (``interpret=True`` or ``OPSAGENT_PALLAS_INTERPRET=1``)
runs the identical kernel body on CPU so tiny test models exercise the
path end-to-end; compiled mode is the opt-in
``EngineConfig.weight_stream="pallas-dma"`` backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Contraction-axis tile for int8 weights (int4 tiles are one scale group
# each). 256 int8 rows x a 512-lane out tile = 128 KB per slot — two
# slots plus the x block fit VMEM with room for the accumulator.
IN_TILE = 256
OUT_TILE = 512
# Activation rows resident per grid step. The x block spans the whole
# contraction axis, so its VMEM footprint is T_TILE x In x itemsize,
# double-buffered by the pipeline: 128 rows of an 18944-wide bf16
# contraction are 2 x 4.6 MB, inside the 16 MB scoped-VMEM limit that a
# whole (T, In) block overruns from T = 1024 (the chip's compiler, at the
# engine's largest mixed bucket). Decode batches fit one tile, so the
# weights still stream exactly once per matmul there; wider batches
# re-stream them once per row tile.
T_TILE = 128


def _out_tile(out: int) -> int:
    """Largest 128-multiple divisor of ``out`` up to OUT_TILE; falls back
    to the whole axis for tiny (CPU-test) widths."""
    for t in range(min(OUT_TILE, out), 127, -128):
        if out % t == 0:
            return t
    return out


def _kernel_int8(
    x_ref,      # [T_T, In] VMEM (one row tile, full contraction axis)
    s_ref,      # [1, OUT_T] VMEM (per-output-channel scale tile)
    q_hbm,      # [In, Out] int8, HBM-resident (memory_space=ANY)
    o_ref,      # [T_T, OUT_T] VMEM
    q_buf,      # [2, IN_T, OUT_T] int8 VMEM scratch (the two DMA slots)
    sem,        # DMA semaphores (2,)
    *,
    in_tile: int,
    n_in: int,
    In: int,
):
    """Per-output-tile int8 quant matmul, contraction streamed through two
    DMA slots. The last tile CLAMPS its start (like the grid attention
    kernels clamp page indices) so a ragged contraction axis re-reads a
    few rows instead of reading out of bounds; the re-read rows are zeroed
    in the x slice, so their products vanish."""
    j = pl.program_id(1)
    out_t = o_ref.shape[1]

    def start(i):
        return jnp.minimum(i * in_tile, In - in_tile)

    def dma(slot, i):
        return pltpu.make_async_copy(
            q_hbm.at[pl.ds(start(i), in_tile), pl.ds(j * out_t, out_t)],
            q_buf.at[slot],
            sem.at[slot],
        )

    dma(0, 0).start()
    scale = s_ref[0, :][None, :].astype(jnp.float32)        # [1, OUT_T]

    def body(i, acc):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_in)
        def _prefetch():
            dma(1 - slot, i + 1).start()

        dma(slot, i).wait()
        st = start(i)
        xs = x_ref[:, pl.ds(st, in_tile)]                   # [T, IN_T]
        # Ragged tail: columns the previous tile already covered
        # (global col < i*in_tile) are zeroed so the clamped re-read
        # contributes nothing.
        col = st + jax.lax.broadcasted_iota(
            jnp.int32, (1, in_tile), 1
        )
        xs = jnp.where(col >= i * in_tile, xs, jnp.zeros_like(xs))
        # Mirror the oracle's elementwise math: dequantize to f32,
        # cast to the activation dtype, f32-accumulating dot.
        wt = (q_buf[slot].astype(jnp.float32) * scale).astype(xs.dtype)
        return acc + jax.lax.dot_general(
            xs, wt,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    acc = jax.lax.fori_loop(
        0, n_in, body,
        jnp.zeros((x_ref.shape[0], out_t), jnp.float32),
    )
    o_ref[:] = acc.astype(o_ref.dtype)


def _kernel_int4(
    x_ref,      # [T_T, In] VMEM
    s_ref,      # [G, 1, OUT_T] VMEM (group scales for this out tile)
    q_hbm,      # [In//2, Out] packed int8, HBM-resident
    o_ref,      # [T_T, OUT_T] VMEM
    q_buf,      # [2, g//2, OUT_T] int8 VMEM scratch
    sem,
    *,
    group: int,
    n_groups: int,
):
    """Per-output-tile int4 quant matmul: one scale GROUP per DMA step, so
    each streamed tile owns exactly one scale row — the group-wise scale
    applies as a broadcast multiply with no cross-group bookkeeping.
    ``group`` always divides the contraction axis (quantize_weight4
    derives it as a divisor), so there is no ragged tail here."""
    j = pl.program_id(1)
    out_t = o_ref.shape[1]
    half = group // 2

    def dma(slot, i):
        return pltpu.make_async_copy(
            q_hbm.at[pl.ds(i * half, half), pl.ds(j * out_t, out_t)],
            q_buf.at[slot],
            sem.at[slot],
        )

    dma(0, 0).start()

    def body(i, acc):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_groups)
        def _prefetch():
            dma(1 - slot, i + 1).start()

        dma(slot, i).wait()
        packed = q_buf[slot]                                # [g/2, OUT_T]
        # Nibble unpack, exactly quant.QuantizedLinear4.dequantize:
        # arithmetic shifts sign-extend; stack on -2 interleaves
        # (even, odd) rows back into contraction order. The shifts run
        # on int32 lanes: Mosaic does not legalize arith.shli on int8
        # vectors.
        p32 = packed.astype(jnp.int32)
        low = jax.lax.shift_right_arithmetic(
            jax.lax.shift_left(p32, jnp.int32(28)), jnp.int32(28)
        )
        high = jax.lax.shift_right_arithmetic(p32, jnp.int32(4))
        w = jnp.stack([low, high], axis=-2)                 # [g/2, 2, OUT_T]
        w = w.astype(jnp.float32).reshape(group, out_t)
        xs = x_ref[:, pl.ds(i * group, group)]              # [T, g]
        wt = (w * s_ref[i, 0, :][None, :]).astype(xs.dtype)
        return acc + jax.lax.dot_general(
            xs, wt,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    acc = jax.lax.fori_loop(
        0, n_groups, body,
        jnp.zeros((x_ref.shape[0], out_t), jnp.float32),
    )
    o_ref[:] = acc.astype(o_ref.dtype)


def supports(w) -> bool:
    """Whether ``w`` is a quantized leaf this kernel family can stream:
    a 2D QuantizedLinear, or a 2D QuantizedLinear4 whose scale group is
    even (the packed layout pairs rows, so an odd group would split a
    byte across two scale groups). Stacked/MoE 3D leaves and anything
    else stay on the XLA dequant path of ``llama._mm`` / ``_ein`` (an
    expert share's whole int8 stacks have a kernel of their own on a TPU,
    ``ops.moe_experts_pallas``, and do not come through here)."""
    from ..models.quant import QuantizedLinear, QuantizedLinear4

    if isinstance(w, QuantizedLinear4):
        if w.q.ndim != 2:
            return False
        In = 2 * w.q.shape[0]
        return (In // w.scale.shape[-3]) % 2 == 0
    if isinstance(w, QuantizedLinear):
        return w.q.ndim == 2
    return False


@functools.partial(jax.jit, static_argnames=("interpret",))
def quant_matmul_pallas(
    x: jax.Array,   # [T, In] activations (any float dtype)
    w,              # models.quant.QuantizedLinear | QuantizedLinear4 (2D)
    interpret: bool = False,
) -> jax.Array:
    """``x @ w.dequantize().astype(x.dtype)`` with the weight stream
    double-buffered HBM->VMEM instead of serialized with the dot.

    Grid is (row tiles of T_TILE, output tiles); within a step the
    contraction axis streams through two DMA slots (int8: IN_TILE rows per slot; int4: one
    scale group per slot, packed two-per-byte). Returns [T, Out] in
    ``x.dtype``.
    """
    from ..models.quant import QuantizedLinear, QuantizedLinear4

    if x.ndim != 2:
        raise ValueError(f"x must be [T, In], got {x.shape}")
    if w.q.ndim != 2:
        raise ValueError(
            f"quant_matmul_pallas needs a 2D weight, got q{w.q.shape} "
            f"(stacked/MoE leaves stay on the XLA dequant path)"
        )
    T = x.shape[0]
    t_tile = min(T, T_TILE)

    if isinstance(w, QuantizedLinear4):
        half, Out = w.q.shape
        In = 2 * half
        G = w.scale.shape[-3]
        group = In // G
        if x.shape[1] != In:
            raise ValueError(f"x In={x.shape[1]} != weight In={In}")
        out_t = _out_tile(Out)
        kernel = functools.partial(
            _kernel_int4, group=group, n_groups=G
        )
        in_specs = [
            pl.BlockSpec(
                (t_tile, In), lambda t, j: (t, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (G, 1, out_t), lambda t, j: (0, 0, j),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(memory_space=pl.ANY),
        ]
        scratch = [
            pltpu.VMEM((2, group // 2, out_t), jnp.int8),
            pltpu.SemaphoreType.DMA((2,)),
        ]
        weight_bytes = half * Out + 4 * G * Out
    elif isinstance(w, QuantizedLinear):
        In, Out = w.q.shape
        if x.shape[1] != In:
            raise ValueError(f"x In={x.shape[1]} != weight In={In}")
        in_tile = min(IN_TILE, In)
        n_in = pl.cdiv(In, in_tile)
        out_t = _out_tile(Out)
        kernel = functools.partial(
            _kernel_int8, in_tile=in_tile, n_in=n_in, In=In
        )
        in_specs = [
            pl.BlockSpec(
                (t_tile, In), lambda t, j: (t, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, out_t), lambda t, j: (0, j), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(memory_space=pl.ANY),
        ]
        scratch = [
            pltpu.VMEM((2, in_tile, out_t), jnp.int8),
            pltpu.SemaphoreType.DMA((2,)),
        ]
        weight_bytes = In * Out + 4 * Out
    else:
        raise TypeError(f"unsupported quantized weight: {type(w)!r}")

    return pl.pallas_call(
        kernel,
        # Row tiles outermost: a row tile's x block stays resident while
        # every output tile streams its weight columns past it. A ragged
        # last row tile reads padding and drops the out-of-range rows on
        # write; matmul rows are independent, so valid rows are exact.
        grid=(pl.cdiv(T, t_tile), Out // out_t),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (t_tile, out_t), lambda t, j: (t, j), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((T, Out), x.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * T * In * Out,
            bytes_accessed=(
                weight_bytes * pl.cdiv(T, t_tile)
                + T * (In + Out) * x.dtype.itemsize
            ),
            transcendentals=0,
        ),
    )(x, w.scale, w.q)


def quant_matmul_pallas_tp(
    x: jax.Array,
    w,
    mesh,
    interpret: bool = False,
) -> jax.Array:
    """Column-parallel TP form: ``w`` sharded on its OUTPUT axis over the
    mesh's tp axis, ``x`` replicated — each shard streams only its own
    weight columns and emits its own output columns; no collective. The
    engine refuses weight_stream=pallas-dma at tp > 1 (row-parallel
    projections would need a psum epilogue); this form exists so the
    sharded kernel stays covered ahead of that wiring."""
    from jax.sharding import PartitionSpec as Pspec

    from ..models.quant import QuantizedLinear4
    from .attention import _shard_map

    if isinstance(w, QuantizedLinear4):
        w_spec = type(w)(
            Pspec(None, "tp"), Pspec(None, None, "tp")
        )
    else:
        w_spec = type(w)(Pspec(None, "tp"), Pspec(None, "tp"))

    def shard_fn(xs, ws):
        return quant_matmul_pallas(xs, ws, interpret=interpret)

    return _shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(Pspec(), w_spec),
        out_specs=Pspec(None, "tp"),
    )(x, w)
